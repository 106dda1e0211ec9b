"""Share of the traced window in which the device sat idle while the
host was inside one of the engine's hand-overs (``engine.admit``,
``engine.prefill``, ``engine.activate``, ``engine.retire``):
``idle_by_layer.share`` of the layer ``engine``. With
``sched_idle_share.serve`` it splits ``device_idle_share.serve`` by
layer. None where the program names no engine span. Moves
serve_tokens_per_s."""

from benchmark import idle_by_layer


def read(run):
    return idle_by_layer.share(run, "engine")
