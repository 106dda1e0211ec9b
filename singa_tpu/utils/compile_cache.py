"""Persistent XLA compilation cache: warm-start repeat runs.

Most of a run's fixed start-up cost is XLA recompilation of programs
that are bit-identical across runs (the train step, the eval chunks,
the engine's decode/prefill/verify programs). jax ships a persistent
compilation cache keyed on the lowered computation — and on the cache
directory's own path, so a directory that moves never hits.

The directory is placed from OUTSIDE the program: when
``JAX_COMPILATION_CACHE_DIR`` is set jax has already taken it from the
environment and nothing here (or anywhere in the repo) sets another.
When it is not, every entry point — training jobs, serving hosts,
``benchmark/run.py``, ``chip_smoke.py`` — shares ONE fixed, git-ignored
directory inside the checkout (``DEFAULT_CACHE_DIR``), so two
consecutive runs of the same checkout hit each other's entries.
``JAX_ENABLE_COMPILATION_CACHE=false`` (jax's own switch) turns it off.
"""

from __future__ import annotations

import os

#: <checkout>/.compile_cache — fixed, so the path-keyed cache hits
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".compile_cache")

#: hashed into every key (jax's own hook for it). jax leaves a program's
#: names (``jax.named_scope`` paths, source lines) out of the key, so an
#: executable cached by code that named its operations otherwise, or not
#: at all, would be served with ITS names — and a trace is read by them
#: (benchmark/program_trace.py). The names themselves stay out of the
#: key: programs that differ only in them (one helper jitted at two
#: call sites) keep sharing an entry, as jax intends. Count this up in
#: a change that renames a scope and leaves the operations as they are.
NAMES = "singa-names-1"


def setup_compile_cache(log=print) -> str:
    """Turn the persistent cache on for this process and return its
    directory. The min-time/min-size gates are zeroed: singa-tpu jobs
    compile a handful of large programs, so every entry is worth
    keeping."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    from jax._src import cache_key

    cache_key.custom_hook = lambda: NAMES
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    log(f"persistent compile cache: {path}")
    return path


class CacheCounter:
    """Counts this process's persistent-cache hits and misses, and the
    seconds spent obtaining executables (compiled or read back), from
    jax's own monitoring events while the ``with`` block is open — how
    a run says whether its compiles were served from the cache."""

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        self.compile_s = 0.0

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def __enter__(self) -> "CacheCounter":
        import jax

        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_listener(self._on_event)
        jax.monitoring.unregister_event_duration_listener(self._on_duration)


def disable_compile_cache(log=print) -> None:
    """Turn the persistent cache off for the rest of this process.

    The supervisor calls this before an in-process restart attempt
    rebuilds the trainer: re-jitting the same programs in the process
    that just wrote their cache entries can crash jaxlib's executable
    deserialization (segfault observed on the CPU backend after a
    mid-run crash). Restarts are the rare path — losing the cache there
    costs one recompile; the cross-process warm start (the actual win)
    is untouched."""
    import jax

    if jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", None)
        log(
            "persistent compile cache: disabled for restart attempts "
            "(in-process re-read of fresh entries is not crash-safe)"
        )
