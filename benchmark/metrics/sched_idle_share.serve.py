"""Share of the traced window in which the device sat idle while the
host was in the scheduler's own work and in none of the engine's
hand-overs (the tick, admission and a chunk outside the engine's calls,
the dispatch, the pull, the fan-out): ``idle_by_layer.share`` of the
layer ``sched``. With ``engine_idle_share.serve`` it splits
``device_idle_share.serve`` by layer. None where the program names no
scheduler span. Moves serve_tokens_per_s."""

from benchmark import idle_by_layer


def read(run):
    return idle_by_layer.share(run, "sched")
