"""Chip smoke: the standing proof that singa-tpu starts on the attached TPU.

    python chip_smoke.py            # one chip: resnet50, lm kernels, serve
    python chip_smoke.py --chips 4  # four chips: the cross-chip phase only

One process, one import of JAX. The script fails at once unless
``jax.devices()[0].platform == "tpu"``, then drives the program through
the entry points a user calls — ``singa_tpu.main.main`` for the trainer,
``Engine`` + ``Scheduler`` for serving — at the full width of the models
the repo ships, with seed-made records and weights, and checks what
comes out by the repo's own means (the flight recorder's step events,
the dense/gather oracles, single-device runs of the same seed). Any
failed check raises: nothing is caught and downgraded to a warning.

Everything it writes goes under ``chiprun_out/chip_smoke/`` (bulky
shards and checkpoints are removed again before it exits); the compile
cache is the program's own (utils/compile_cache.py). The LAST stdout
line is ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}`` with the device as JAX reports it.

Sizes are arguments of the phase functions, real by default; the CPU
rehearsal (tests/test_chip_smoke.py) calls the same functions at tiny
sizes with the device gate steered, so a later change cannot break the
script unnoticed.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import re
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(REPO, "chiprun_out", "chip_smoke")

#: |loss(flash kernels) - loss(dense attention)| on the same steps, bf16
#: compute: both sides round activations to bf16 (eps 2^-8) and differ
#: only in where the softmax is accumulated; the losses sit near ln(256)
LM_LOSS_TOL = 2e-2
#: |paged kernel - cache_attend(gathered pool)| on unit-variance inputs:
#: both sides run their f32 matmuls as bf16 passes on the MXU (each sat
#: within 7e-3 of an exact oracle on the chip)
KERNEL_TOL = 1e-2
#: top-two logit gap under which two compiled programs may pick
#: different greedy tokens on the chip (bf16-pass matmuls; the logits of
#: a seed-made model span a few tenths)
LOGIT_TIE_TOL = 5e-3
#: sharded vs single-device loss, relative to max(1, |ref|) — the bar
#: __graft_entry__.dryrun_multichip holds its virtual meshes to
MESH_TOL = 1e-4
#: int8 ring vs exact collective, per-step loss — the bar
#: tests/test_quantized_collective.py holds the ring to
RING_TOL = 2e-2


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What each phase runs at. The defaults are the real sizes; only
    the CPU rehearsal passes others."""

    resnet_conf: str = "examples/imagenet/resnet50.conf"
    resnet_batch: int = 128       # one chip's batch
    resnet_image: int = 256       # stored record edge (the conf crops)
    lm_conf: str = "examples/lm/tinylm_d128.conf"
    lm_seq: int = 8192            # the standing long-context shape
    lm_samples: int = 32
    serve_d_model: int = 256      # lm_d128_serve: 2 heads of 128
    serve_heads: int = 2
    serve_d_ff: int = 1024
    serve_max_len: int = 512      # 32-block tables at kv_block_len 16
    serve_new_tokens: int = 32
    mlp_conf: str = "examples/mnist/mlp.conf"
    mlp_batch: int = 1000         # the flagship's own batch
    ring_seq: int = 128
    ring_samples: int = 256


def require_tpu(chips: int):
    """The device gate: -> jax.devices(), or exit non-zero with one
    line saying why. Nothing else in the script decides whether it may
    run."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found platform "
            f"{devices[0].platform!r} ({len(devices)} device(s))"
        )
    if len(devices) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} TPU devices, JAX "
            f"found {len(devices)}"
        )
    return devices


# ---------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _conf_copy(src: str, dst: str, subs, tail: str) -> str:
    """A copy of a shipped conf with ``subs`` ((regex, replacement)
    pairs, each of which must match) applied and ``tail`` appended —
    text-format scalars take their LAST occurrence, so the tail
    overrides top-level cadences without touching the net."""
    with open(os.path.join(REPO, src)) as f:
        text = f.read()
    for pattern, repl in subs:
        text, n = re.subn(pattern, repl, text)
        _check(n > 0, f"{src}: nothing matched {pattern!r}")
    with open(dst, "w") as f:
        f.write(text + "\n" + tail + "\n")
    return dst


def _cluster_conf(path: str, workspace: str) -> str:
    """One worker on one device, with a workspace for the flight
    recorder and the final snapshot."""
    with open(path, "w") as f:
        f.write(
            f'nworkers: 1\nnprocs_per_group: 1\nworkspace: "{workspace}"\n'
        )
    return path


def _run_job(model_conf: str, cluster_conf: str):
    """One training job through the CLI entry, in this process. -> the
    Trainer it built (captured at the factory seam, as
    tests/mp_worker.py does) after ``main`` returned 0."""
    import singa_tpu.main as cli
    import singa_tpu.trainer as trainer_mod

    captured = []
    real_make = trainer_mod.make_trainer

    def capturing_make(*args, **kwargs):
        captured.append(real_make(*args, **kwargs))
        return captured[-1]

    trainer_mod.make_trainer = capturing_make
    try:
        rc = cli.main(
            ["-model_conf", model_conf, "-cluster_conf", cluster_conf]
        )
    finally:
        trainer_mod.make_trainer = real_make
    _check(rc == 0, f"singa_tpu.main exited {rc} on {model_conf}")
    (trainer,) = captured
    return trainer


def _displayed_losses(workspace: str) -> list[tuple[int, float]]:
    """(step, loss) of every display, from the flight recorder's step
    events — the program's own record of what it showed."""
    out = []
    with open(os.path.join(workspace, "events", "rank_0.jsonl")) as f:
        for line in f:
            ev = json.loads(line)
            if ev["kind"] == "step":
                (metrics,) = ev["data"]["metrics"].values()
                out.append((int(ev["step"]), float(metrics["loss"])))
    return out


def _check_job(trainer, workspace: str, steps: int, devices) -> list:
    """The checks every training phase shares: the step count is
    reached, the final snapshot landed, every displayed loss is finite,
    and every parameter lives on the mesh's devices — a subset of the
    gate's."""
    import jax

    _check(
        trainer.completed_steps == steps,
        f"ran {trainer.completed_steps} of {steps} steps",
    )
    ckpts = os.listdir(os.path.join(workspace, "checkpoints"))
    _check(
        any(c.startswith(f"step_{steps}") for c in ckpts),
        f"no step_{steps} snapshot among {ckpts}",
    )
    shown = _displayed_losses(workspace)
    _check(bool(shown), "no display reached the flight recorder")
    for step, loss in shown:
        _check(math.isfinite(loss), f"loss {loss} at step {step}")
    mesh_devices = set(trainer.mesh.devices.flat)
    _check(mesh_devices <= set(devices), "mesh left the gate's devices")
    for name, p in trainer.params.items():
        _check(
            isinstance(p, jax.Array) and p.sharding.device_set == mesh_devices,
            f"param {name} on {p.sharding.device_set}, mesh {mesh_devices}",
        )
    return shown


def _lower_step(trainer):
    """The job's train step, lowered on its own arguments. Lowering
    alone already shows a Mosaic kernel (``tpu_custom_call`` in the
    StableHLO text); ``.compile()`` adds the collectives."""
    import jax
    import jax.numpy as jnp

    batch = trainer._assemble_host_batch(trainer.train_net)
    rng = jax.random.fold_in(trainer._step_key, 0)
    return trainer._train_step.lower(
        trainer.params, trainer.state, trainer.buffers,
        jnp.int32(0), batch, rng,
    )


def _per_step_losses(trainer, nsteps: int) -> list[float]:
    out = []
    for s in range(nsteps):
        trainer.perf.reset()
        trainer.train_one_batch(s)
        (m,) = trainer.perf.avg().values()
        out.append(float(m["loss"]))
    return out


@contextlib.contextmanager
def _phase(name: str):
    """Times one phase and splits it into compile and run seconds;
    yields the cache counter."""
    from singa_tpu.utils.compile_cache import CacheCounter

    print(f"[{name}] start", flush=True)
    t0 = time.perf_counter()
    with CacheCounter() as c:
        yield c
    total = time.perf_counter() - t0
    print(
        f"[{name}] ok in {total:.1f}s: compile {c.compile_s:.1f}s (cache "
        f"{c.hits} hit / {c.misses} miss), run {total - c.compile_s:.1f}s",
        flush=True,
    )


# ---------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------


def train_resnet50(devices, work: str, seed: int, sizes: Sizes) -> dict:
    """The shipped ResNet conf at its own widths and compute dtype, six
    steps through the CLI: displays at steps 1, 3, 5 cut the run into
    three two-step ``lax.scan`` chunks (one compiled program), so two
    chunk boundaries and three displays are crossed."""
    from singa_tpu.data.loader import synthetic_arrays, write_records

    steps = 6
    ws = os.path.join(work, "resnet_ws")
    shard = os.path.join(work, "resnet_shard")
    write_records(shard, *synthetic_arrays(
        sizes.resnet_batch, size=sizes.resnet_image, channels=3, seed=seed
    ))
    conf = _conf_copy(
        sizes.resnet_conf, os.path.join(work, "resnet.conf"),
        [
            (r'path: "[^"]*"', f'path: "{shard}"'),
            (r"batchsize: \d+", f"batchsize: {sizes.resnet_batch}"),
            (r"random_skip: \d+", "random_skip: 0"),
        ],
        f"train_steps: {steps}\ntest_steps: 0\ntest_frequency: 0\n"
        "display_frequency: 2\ndisplay_after_steps: 1\n"
        "checkpoint_frequency: 0",
    )
    trainer = _run_job(
        conf, _cluster_conf(os.path.join(work, "resnet_cluster.conf"), ws)
    )
    shown = _check_job(trainer, ws, steps, devices[:1])
    _check(trainer.feeder_mode == "cached", f"feeder {trainer.feeder_mode}")
    _check([s for s, _ in shown] == [1, 3, 5], f"displays at {shown}")
    return {"displayed": shown, "batch": trainer.train_net.batchsize}


def train_lm_kernel(devices, work: str, seed: int, sizes: Sizes) -> dict:
    """tinylm_d128 at the long-context shape, five steps through the
    CLI with displays at steps 0, 2, 4 (the single step, then two-step
    chunks). The kernel must be IN the program, and the displayed
    losses — step 0's forward, then steps 1-2 after two backward passes
    through the kernels — must agree with a dense-attention run of the
    same conf, seed and records."""
    from singa_tpu.config import load_model_config
    from singa_tpu.data.loader import synthetic_token_arrays, write_records
    from singa_tpu.trainer import Trainer

    steps = 5
    ws = os.path.join(work, "lm_ws")
    shard = os.path.join(work, "lm_shard")
    write_records(shard, *synthetic_token_arrays(
        sizes.lm_samples, seq_len=sizes.lm_seq, vocab=256, seed=seed
    ))
    subs = [
        (r'path: "[^"]*"', f'path: "{shard}"'),
        (r"batchsize: \d+", "batchsize: 1"),
    ]
    tail = (
        f"train_steps: {steps}\ndisplay_frequency: 2\n"
        "display_after_steps: 0\ncheckpoint_frequency: 0"
    )
    conf = _conf_copy(
        sizes.lm_conf, os.path.join(work, "lm.conf"), subs, tail
    )
    trainer = _run_job(
        conf, _cluster_conf(os.path.join(work, "lm_cluster.conf"), ws)
    )
    shown = _check_job(trainer, ws, steps, devices[:1])
    _check([s for s, _ in shown] == [0, 2, 4], f"displays at {shown}")
    on_chip = devices[0].platform == "tpu"
    if on_chip:
        _check(
            "tpu_custom_call" in _lower_step(trainer).as_text(),
            "the LM step holds no Mosaic kernel: dense attention ran",
        )
    # the same conf with the attention mode repointed at the dense
    # reference, driven step by step on the same seed and records
    dense_conf = _conf_copy(
        sizes.lm_conf, os.path.join(work, "lm_dense.conf"),
        subs + [(r'mode: "flash"', 'mode: "dense"')], tail,
    )
    dense = Trainer(
        load_model_config(dense_conf), seed=0, log=lambda s: None,
        prefetch=False,
    )
    dense_losses = _per_step_losses(dense, 3)
    want = [dense_losses[0], (dense_losses[1] + dense_losses[2]) / 2]
    got = [loss for _, loss in shown[:2]]
    for g, w, label in zip(got, want, ("step 0", "steps 1-2")):
        _check(
            abs(g - w) <= LM_LOSS_TOL,
            f"{label}: kernel loss {g:.5f} vs dense {w:.5f} "
            f"(tol {LM_LOSS_TOL})",
        )
    return {
        "displayed": shown, "dense": want,
        "max_abs_diff": max(abs(g - w) for g, w in zip(got, want)),
    }


def _serve_requests(seed: int, vocab: int, new_tokens: int):
    """A handful of requests with mixed prompt lengths."""
    import numpy as np

    rs = np.random.RandomState(seed)
    lengths = [3, 9, 17, 40, 5, 26]
    return [
        (rs.randint(0, vocab, size=(n,)).astype(np.int32), new_tokens)
        for n in lengths
    ]


def _serve_streams(params, cfg, impl: str, spec_k: int, requests):
    """Serve ``requests`` through Engine + Scheduler. -> (engine,
    {rid: tokens})."""
    from singa_tpu.serve import Engine, EngineConfig, Request, Scheduler

    engine = Engine(params, cfg, EngineConfig(
        slots=8, kv_block_len=16, max_prefill_chunk=16,
        spec_k=spec_k, attend_impl=impl,
    ))
    sched = Scheduler(engine)
    for rid, (prompt, budget) in enumerate(requests):
        sched.submit(Request(rid=rid, prompt=prompt, max_new_tokens=budget))
    sched.serve()
    _check(
        len(sched.finished) == len(requests),
        f"{impl}/spec_k={spec_k}: {len(sched.finished)} of "
        f"{len(requests)} requests finished",
    )
    for r in sched.finished:
        _check(
            len(r.tokens) == requests[r.rid][1],
            f"{impl}: request {r.rid} emitted {len(r.tokens)} tokens",
        )
    return engine, {r.rid: list(r.tokens) for r in sched.finished}


def _kernel_vs_gather(seed: int, h: int, d: int, max_len: int) -> float:
    """The paged kernel against ``cache_attend`` over the gathered pool
    on the same seed-made inputs, ``h`` heads of ``d``, at the engine's
    three call shapes (decode, prefill chunk, verify overlay).
    -> max |difference|."""
    import jax.numpy as jnp
    import numpy as np

    from singa_tpu.models.transformer import cache_attend
    from singa_tpu.ops.paged_attention import (
        paged_attention,
        paged_attention_overlay,
    )

    rs = np.random.RandomState(seed)
    bl = 16
    mb = max_len // bl
    worst = 0.0

    def gather(pool, tables):
        g = jnp.moveaxis(pool[tables], 2, 1)
        return g.reshape(g.shape[0], h, mb * bl, d)

    def stored(pool):
        # the oracle reads (NB, H, BL, D); the engine stores, and the
        # kernel takes, (NB, BL, H * D)
        return jnp.moveaxis(pool, 1, 2).reshape(pool.shape[0], bl, h * d)

    for s, q_len, overlay in ((8, 1, False), (1, 16, False), (8, 5, True)):
        nb = s * mb + 1
        kp = jnp.asarray(rs.randn(nb, h, bl, d), jnp.float32)
        vp = jnp.asarray(rs.randn(nb, h, bl, d), jnp.float32)
        q = jnp.asarray(rs.randn(s, h, q_len, d), jnp.float32)
        tables = jnp.asarray(1 + np.arange(s * mb).reshape(s, mb), jnp.int32)
        start = rs.randint(0, mb * bl - q_len, size=(s, 1))
        pos = jnp.asarray(start + np.arange(q_len)[None, :], jnp.int32)
        if overlay:
            # verify form: the chunk's own K/V ride beside the pool; the
            # oracle writes them into the gathered view at their
            # positions, which is what the reference path does
            ck = jnp.asarray(rs.randn(s, h, q_len, d), jnp.float32)
            cv = jnp.asarray(rs.randn(s, h, q_len, d), jnp.float32)
            got = paged_attention_overlay(
                q, stored(kp), stored(vp), tables, pos, ck, cv,
                jnp.ones((s, q_len), jnp.int32),
            )
            rows = jnp.arange(s)[:, None]
            gk = gather(kp, tables).at[rows, :, pos].set(
                jnp.moveaxis(ck, 1, 2)
            )
            gv = gather(vp, tables).at[rows, :, pos].set(
                jnp.moveaxis(cv, 1, 2)
            )
            want = cache_attend(q, gk, gv, pos)
        else:
            got = paged_attention(q, stored(kp), stored(vp), tables, pos)
            want = cache_attend(
                q, gather(kp, tables), gather(vp, tables), pos
            )
        _check(bool(jnp.all(jnp.isfinite(got))), "paged kernel non-finite")
        worst = max(worst, float(jnp.max(jnp.abs(got - want))))
    _check(
        worst <= KERNEL_TOL,
        f"paged kernel off cache_attend by {worst} (tol {KERNEL_TOL})",
    )
    return worst


def _top2_gap(params, cfg, prompt, tokens) -> float:
    """The reference's top-two logit gap for the token after ``prompt +
    tokens``, from the dense full forward."""
    import jax.numpy as jnp
    import numpy as np

    from singa_tpu.models.transformer import lm_apply

    seq = np.concatenate([prompt, np.asarray(tokens, np.int32)])
    logits = lm_apply(params, jnp.asarray(seq)[None], cfg)[0, -1]
    top = jnp.sort(logits)[-2:]
    return float(top[1] - top[0])


def serve(devices, work: str, seed: int, sizes: Sizes) -> dict:
    """The lm_d128_serve shape answers six requests four times: with the
    ``reference`` attend and with ``fused`` — which on a TPU compiles
    through Mosaic with no conf change, and is what the engine picks
    there when nothing is pinned — each as plain decode and as
    speculative verify (spec_k 4)."""
    import jax
    import jax.numpy as jnp

    from singa_tpu.models.transformer import TransformerConfig, init_lm

    del work
    cfg = TransformerConfig(
        vocab=256, d_model=sizes.serve_d_model, n_heads=sizes.serve_heads,
        n_layers=2, d_ff=sizes.serve_d_ff, max_len=sizes.serve_max_len,
    )
    params = init_lm(jax.random.PRNGKey(seed), cfg)
    requests = _serve_requests(seed, cfg.vocab, sizes.serve_new_tokens)
    on_chip = devices[0].platform == "tpu"
    streams = {}
    for impl in ("reference", "fused"):
        for spec_k in (0, 4):
            engine, streams[impl, spec_k] = _serve_streams(
                params, cfg, impl, spec_k, requests
            )
            if impl == "fused" and on_chip:
                # the programs this engine ran, lowered on its own state
                i32 = jnp.int32
                programs = {"prefill": engine._prefill_jit.lower(
                    engine.params, engine.state, i32(0),
                    jnp.zeros((16,), i32), i32(0), i32(16),
                )}
                if spec_k:
                    programs["verify"] = engine._verify_jit.lower(
                        engine.params, engine.state,
                        jnp.zeros((8, spec_k), i32), jnp.zeros((8,), i32),
                    )
                else:
                    programs["decode"] = engine._decode_jit.lower(
                        engine.params, engine.state
                    )
                for name, lowered in programs.items():
                    # the decode tick and the verify pass run the
                    # kernel; a prefill chunk keeps the one-slot gather
                    # whatever the engine runs (serve/engine.py)
                    _check(
                        ("tpu_custom_call" in lowered.as_text())
                        == (name != "prefill"),
                        f"fused {name} program: Mosaic kernel "
                        f"{'missing' if name != 'prefill' else 'present'}",
                    )
    # left to itself the engine picks the kernel on the chip for this
    # model (causal, as many K/V heads as query heads, no mesh)
    from singa_tpu.serve.engine import choose_attend, EngineConfig

    chosen = choose_attend(
        cfg, EngineConfig(kv_block_len=16), None, devices[0].platform
    )
    _check(
        chosen == ("fused" if on_chip else f"reference: platform = "
                   f"{devices[0].platform}"),
        f"the engine chose {chosen!r} on {devices[0].platform}",
    )
    # the model's own heads, and GPT-2's 16 of 64: the kernel walks the
    # heads as column slices of a pool row, and a 64-wide head's slice
    # starts off the 128-lane boundary
    worst = max(
        _kernel_vs_gather(seed, h, d, cfg.max_len)
        for h, d in ((cfg.n_heads, cfg.head_dim), (16, 64))
    )
    # greedy streams against the reference's plain decode. Every other
    # run is a different compiled program (another attend, or the
    # verify shape), and on the chip their f32 matmuls run as bf16
    # passes, so identity is not owed where the logits nearly tie: a
    # stream may leave the baseline only at a token whose top-two logit
    # gap, by the dense full forward, is inside LOGIT_TIE_TOL. Seed
    # weights give near-flat logits, so the verdict says which it was.
    base = streams["reference", 0]
    ties = []
    for variant, got in streams.items():
        for rid, (prompt, _) in enumerate(requests):
            if got[rid] == base[rid]:
                continue
            at = next(
                i for i, (a, b) in enumerate(zip(base[rid], got[rid]))
                if a != b
            )
            ties.append({
                "impl": variant[0], "spec_k": variant[1], "request": rid,
                "token": at,
                "top2_gap": _top2_gap(params, cfg, prompt, base[rid][:at]),
            })
    _check(
        all(t["top2_gap"] <= LOGIT_TIE_TOL for t in ties),
        f"a stream left the reference's plain decode where its top-two "
        f"logit gap exceeds {LOGIT_TIE_TOL}: {ties}",
    )
    return {
        "streams": (
            "first differences at near-ties only" if ties else "identical"
        ),
        "near_ties": ties,
        "kernel_max_abs_diff": worst,
    }


# ---------------------------------------------------------------------
# the four-chip phase
# ---------------------------------------------------------------------


def _check_spread(trainer, devices, collectives, split_params=False) -> None:
    """The work really is spread: every parameter and the batch sit on
    exactly the mesh's devices, all four of them (``split_params``:
    and some weight is genuinely partitioned, not replicated), and the
    compiled step holds the collectives the layout implies."""
    import jax

    mesh_devices = set(trainer.mesh.devices.flat)
    _check(mesh_devices == set(devices), f"mesh covers {mesh_devices}")
    if split_params:
        _check(
            any(
                not p.sharding.is_fully_replicated
                for p in trainer.params.values()
            ),
            "kLayerPartition left every parameter replicated",
        )
    trees = {"param": trainer.params, "batch": trainer._last_batch}
    for kind, tree in trees.items():
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            _check(
                leaf.sharding.device_set == mesh_devices,
                f"{kind} {jax.tree_util.keystr(path)} on "
                f"{len(leaf.sharding.device_set)} of {len(mesh_devices)} "
                "devices",
            )
    hlo = _lower_step(trainer).compile().as_text()
    for op in collectives:
        _check(op in hlo, f"compiled step holds no {op}")


def cross_chip(devices, work: str, seed: int, sizes: Sizes) -> dict:
    """Data parallelism across worker groups and partitioning inside a
    group, on real chips: the flagship MLP on a data=4 mesh and on a
    data=2 x model=2 mesh (kLayerPartition), each against the same seed
    on ONE device of the four; then tinylm_d128 with the int8 ring on
    data=4 against the exact collective."""
    from singa_tpu.config import load_model_config
    from singa_tpu.data.loader import (
        synthetic_arrays,
        synthetic_token_arrays,
        write_records,
    )
    from singa_tpu.parallel import build_mesh
    from singa_tpu.trainer import Trainer

    devices = devices[:4]
    nsteps = 3

    def trainer_for(conf, ndata, nmodel, devs):
        return Trainer(
            load_model_config(conf), mesh=build_mesh(ndata, nmodel, devs),
            seed=0, log=lambda s: None, prefetch=False, device_cache=False,
        )

    # (i) the flagship MLP
    shard = os.path.join(work, "mlp_shard")
    write_records(shard, *synthetic_arrays(sizes.mlp_batch, seed=seed))
    subs = [
        (r'path: "[^"]*"', f'path: "{shard}"'),
        (r"batchsize: \d+", f"batchsize: {sizes.mlp_batch}"),
        (r"random_skip: \d+", "random_skip: 0"),
    ]
    tail = f"train_steps: {nsteps}\ntest_steps: 0\ntest_frequency: 0"
    plain = _conf_copy(
        sizes.mlp_conf, os.path.join(work, "mlp.conf"), subs, tail
    )
    layered = _conf_copy(
        sizes.mlp_conf, os.path.join(work, "mlp_layer.conf"),
        subs + [(r"neuralnet \{", "neuralnet {\n  partition_type: "
                 "kLayerPartition")],
        tail,
    )
    out = {}
    for label, conf, ndata, nmodel in (
        ("data=4", plain, 4, 1),
        ("data=2 x model=2", layered, 2, 2),
    ):
        ref = _per_step_losses(
            trainer_for(conf, 1, 1, devices[:1]), nsteps
        )
        sharded = trainer_for(conf, ndata, nmodel, devices)
        got = _per_step_losses(sharded, nsteps)
        for s, (g, r) in enumerate(zip(got, ref)):
            _check(
                math.isfinite(g)
                and abs(g - r) <= MESH_TOL * max(1.0, abs(r)),
                f"MLP {label} step {s}: loss {g:.6f} vs one device "
                f"{r:.6f} (tol {MESH_TOL})",
            )
        _check_spread(
            sharded, devices, ("all-reduce",), split_params=nmodel > 1
        )
        out[f"mlp {label}"] = {"sharded": got, "one_device": ref}

    # (ii) the int8 ring against the exact collective
    tokens = os.path.join(work, "ring_shard")
    write_records(tokens, *synthetic_token_arrays(
        sizes.ring_samples, seq_len=sizes.ring_seq, vocab=256, seed=seed
    ))
    lm_subs = [(r'path: "[^"]*"', f'path: "{tokens}"')]
    lm_tail = f"train_steps: {nsteps}"
    exact = _conf_copy(
        sizes.lm_conf, os.path.join(work, "ring_exact.conf"),
        lm_subs, lm_tail,
    )
    ring = _conf_copy(
        sizes.lm_conf, os.path.join(work, "ring_q8.conf"), lm_subs,
        lm_tail + "\ngrad_comm { mode: quantized dtype: int8 }\n"
        "kernels { grad_allreduce: quantized_ring }",
    )
    want = _per_step_losses(trainer_for(exact, 4, 1, devices), nsteps)
    ringed = trainer_for(ring, 4, 1, devices)
    _check(ringed.grad_wire_impl == "quantized_ring", ringed.grad_wire_impl)
    got = _per_step_losses(ringed, nsteps)
    for s, (g, w) in enumerate(zip(got, want)):
        _check(
            math.isfinite(g) and abs(g - w) <= RING_TOL,
            f"ring step {s}: loss {g:.5f} vs exact {w:.5f} (tol {RING_TOL})",
        )
    _check_spread(ringed, devices, ("collective-permute",))
    out["ring data=4"] = {"ring": got, "exact": want}

    if devices[0].platform == "tpu":
        in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
        _check(all(b > 0 for b in in_use), f"bytes in use per chip: {in_use}")
        out["bytes_in_use"] = in_use
    return out


# ---------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------

ONE_CHIP_PHASES = (train_resnet50, train_lm_kernel, serve)
FOUR_CHIP_PHASES = (cross_chip,)


def run(devices, phases, seed: int, sizes: Sizes, out: str = OUT) -> dict:
    """Run ``phases`` in this process; -> the summary that also lands in
    ``<out>/summary.json``. Shared by ``main`` and the CPU rehearsal."""
    work = os.path.join(out, "work")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(work)
    summary = {"seed": seed}
    try:
        for phase in phases:
            with _phase(phase.__name__) as c:
                summary[phase.__name__] = phase(devices, work, seed, sizes)
            summary[phase.__name__]["cache"] = {
                "hits": c.hits, "misses": c.misses,
                "compile_s": round(c.compile_s, 2),
            }
    finally:
        # shards and snapshots are hundreds of MB: never left behind
        shutil.rmtree(work, ignore_errors=True)
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    return summary


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="chip_smoke")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the cross-chip phase and nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)

    from singa_tpu import native
    from singa_tpu.utils.compile_cache import setup_compile_cache

    # nothing prebuilt is trusted: the record codec is rebuilt from the
    # tracked sources, and the line says which one serves
    codec = "native (g++ build of the tracked .cc)" if native.rebuild() \
        else "pure Python (native build unavailable)"
    print(f"record codec: {codec}", flush=True)
    setup_compile_cache()
    phases = FOUR_CHIP_PHASES if args.chips == 4 else ONE_CHIP_PHASES
    run(devices, phases, args.seed, Sizes())
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
