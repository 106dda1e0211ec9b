"""Measure the update-phase stall: replicated vs ZeRO-sharded update.

The zero_update claim (parallel/shardings.py zero_update_shardings) is
that reduce-scattering gradients, updating each rank's shard only, and
allgathering fresh params shrinks per-device optimizer state by the
data-parallel degree WITHOUT slowing the step down: the collectives
move the same bytes as the replicated update's all-reduce, and the
update math itself shrinks per device. This tool — the sibling of
ckpt_stall / input_stall — measures it by timing the same small MLP job
on an ``ndata``-wide virtual data mesh both ways:

  replicated  every rank applies the full update (the reference's
              ParamSync semantics)
  zero        reduce-scatter grads -> shard-local optimizer ->
              allgather params (update_mode "zero")

and printing one JSON line::

  {"replicated_step_ms": .., "zero_step_ms": .., "ratio": ..,
   "replicated_update_ms": .., "zero_update_ms": ..,
   "opt_bytes_replicated": .., "opt_bytes_zero": .., "opt_bytes_ratio": ..,
   "threshold": .., "pass": ..}

Exit status 0 iff zero/replicated step time <= ``threshold`` (default
1.05: the sharded update may cost at most 5% on the CPU host, where
emulated collectives are memcpys and the shard-local math win cannot
show) AND per-device opt-state bytes actually shrank. On a real
accelerator the zero update should win outright once optimizer state
stops fitting replicated.

``measure_update_ms`` is importable (bench.py and the MULTICHIP dryrun
reuse it): it slope-fits the update phase in isolation — one jitted
program running N chained updater applications — so the reported ms is
the marginal per-update cost, free of dispatch latency.

Usage::

  python -m singa_tpu.tools.update_stall [--steps N] [--warmup N]
      [--trials N] [--batch N] [--hidden N] [--ndata N] [--threshold R]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time


def measure_update_ms(trainer, i1: int = 4, i2: int = 20,
                      trials: int = 3) -> float:
    """Slope-fit the update phase in isolation: jit a program running N
    chained ``_constrain_grads`` + ``_apply_update`` rounds (zeros
    grads — the same dense elementwise math) on non-donated copies of
    the live state, time two window sizes, and return the marginal
    per-update cost in ms (bench.py's two-window methodology)."""
    import jax
    import jax.numpy as jnp

    grads = jax.tree.map(jnp.zeros_like, trainer.params)

    def make(n):
        def prog(params, state, grads):
            def body(carry, i):
                p, s = carry
                g = trainer._constrain_grads(grads)
                return trainer._apply_update(i, p, g, s), jnp.float32(0)

            (p, s), _ = jax.lax.scan(
                body, (params, state), jnp.arange(n)
            )
            return p, s

        # inputs are the LIVE params/state — never donate them
        return jax.jit(prog)  # netlint: disable=JAX003

    fns = {n: make(n) for n in (i1, i2)}

    def run(n) -> float:
        t0 = time.perf_counter()
        p, _ = fns[n](trainer.params, trainer.state, grads)
        # close the window on a host pull of a reduction over the
        # result: it cannot return before the work is done
        float(jnp.sum(jnp.abs(next(iter(p.values())))))
        return time.perf_counter() - t0

    for n in fns:  # compile
        run(n)
    best = {n: float("inf") for n in fns}
    for _ in range(trials):
        for n in fns:
            best[n] = min(best[n], run(n))
    # floor at 0: on a contended host a tiny update's window delta can
    # sink under dispatch jitter — a negative marginal ms must never
    # poison bench rows or the stall JSON
    return max(0.0, (best[i2] - best[i1]) / (i2 - i1) * 1e3)


def _make_runner(shard: str, batch: int, hidden: int, warmup: int,
                 zero: bool, ndata: int):
    """-> (trainer, window(steps) -> (seconds, steps)) for one mode.

    Both modes run the identical per-step sync loop on the same
    ndata-wide data mesh (device_cache off so the step is the honest
    assemble + step path, like input_stall's sync baseline); only the
    update layout differs."""
    import jax
    import jax.numpy as jnp

    from ..config import parse_model_config
    from ..parallel import build_mesh
    from ..trainer import Trainer
    from .input_stall import _CONF

    cfg = parse_model_config(_CONF.format(shard=shard, batch=batch,
                                          hidden=hidden, head=10))
    cfg.zero_update = zero
    mesh = build_mesh(ndata, 1, jax.devices()[:ndata])
    trainer = Trainer(
        cfg, seed=0, log=lambda s: None, mesh=mesh,
        prefetch=False, device_cache=False,
    )
    assert trainer.update_mode == ("zero" if zero else "replicated")

    def sync() -> float:
        return float(jnp.sum(jnp.abs(next(iter(trainer.params.values())))))

    state = {"step": 0}

    def run(steps: int) -> None:
        step0 = state["step"]
        for s in range(step0, step0 + steps):
            trainer.train_one_batch(s)
        state["step"] = step0 + steps

    run(warmup)  # compile
    sync()

    def window(steps: int) -> float:
        t0 = time.perf_counter()
        run(steps)
        sync()
        return time.perf_counter() - t0

    return trainer, window


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="update_stall", description=__doc__)
    ap.add_argument("--steps", type=int, default=12, help="timed steps")
    ap.add_argument("--warmup", type=int, default=4, help="untimed steps")
    ap.add_argument(
        "--trials", type=int, default=3,
        help="windows per mode; the best (least-contended) one counts",
    )
    # the probe regime: a compute-representative step (~85 ms at batch
    # 8192 on the 2-core host) against which the zero update's fixed
    # per-step collective cost (an emulated reduce-scatter + param
    # allgather, ~1 ms of memcpys here) is the honest small share it is
    # on real models — measured ratio 0.92-1.01. A tiny-step probe
    # (batch 512, ~8 ms steps) measures the emulation overhead instead
    # of the update sharding (~1.12 there), the same host-steals-from-
    # itself artifact input_stall documents for its per-step feeder.
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--records", type=int, default=8192,
                    help="synthetic dataset size")
    ap.add_argument("--ndata", type=int, default=2,
                    help="data-axis width (virtual CPU devices)")
    ap.add_argument(
        "--threshold", type=float, default=1.05,
        help="max allowed zero/replicated step-time ratio",
    )
    args = ap.parse_args(argv)

    # the device count and platform must land before jax is imported
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={args.ndata}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

    from ..data.loader import synthetic_arrays, write_records

    root = tempfile.mkdtemp(prefix="singa_tpu_update_stall_")
    shard = os.path.join(root, "shard")
    write_records(shard, *synthetic_arrays(args.records, seed=0))
    runners = {
        mode: _make_runner(shard, args.batch, args.hidden, args.warmup,
                           mode == "zero", args.ndata)
        for mode in ("replicated", "zero")
    }
    # INTERLEAVED best-of-trials (ckpt/input_stall's methodology): one
    # window per mode per round so host-load bursts land on both modes
    best = {mode: float("inf") for mode in runners}
    for _ in range(args.trials):
        for mode, (_, window) in runners.items():
            best[mode] = min(best[mode], window(args.steps) / args.steps)
    repl_ms = best["replicated"] * 1e3
    zero_ms = best["zero"] * 1e3
    t_repl, _ = runners["replicated"]
    t_zero, _ = runners["zero"]
    ob_repl = t_repl.opt_state_bytes_per_device()
    ob_zero = t_zero.opt_state_bytes_per_device()
    shrank = args.ndata == 1 or ob_zero < ob_repl
    ok = zero_ms <= repl_ms * args.threshold and shrank
    out = {
        "replicated_step_ms": round(repl_ms, 3),
        "zero_step_ms": round(zero_ms, 3),
        "ratio": round(zero_ms / repl_ms, 3),
        "replicated_update_ms": round(measure_update_ms(t_repl), 3),
        "zero_update_ms": round(measure_update_ms(t_zero), 3),
        "opt_bytes_replicated": ob_repl,
        "opt_bytes_zero": ob_zero,
        "opt_bytes_ratio": round(ob_zero / ob_repl, 3) if ob_repl else None,
        "ndata": args.ndata,
        "threshold": args.threshold,
        "pass": ok,
    }
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
