"""Time to the first token, 95th percentile over the requests whose
first token fell in the window: the scheduler's own stamps, submit
(``Request.enqueue_mono``) to first token (``first_token_mono``). With
callers = slots nothing queues, so this is admission plus chunked
prefill among live decodes. Recorded, not bounded: a window holds some
fifty first tokens, and their tail is two or three requests. A caller
waiting for its first token emits nothing: moves serve_tokens_per_s."""


def read(run):
    return run["counters"].get("ttft_p95_ms")
