"""Benchmark: the framework's headline workloads on the real chip.

Workloads (BASELINE.json targets; all on the production hot path — the
device-cached, bf16-compute, lax.scan-chunked training engine):

  mnist_mlp     the reference's headline job (examples/mnist/mlp.conf:
                six FC layers 2500-2000-1500-1000-500-10, batch 1000) —
                the model its batch.sh scaling sweep measures
                (examples/mnist/batch.sh:3-17)
  cifar_alexnet examples/cifar10/alexnet.conf (BASELINE config 3), the
                conv path
  tinylm        examples/lm/tinylm.conf, byte-level transformer LM with
                the Pallas flash-attention kernel (tokens/sec)
  resnet50      examples/imagenet/resnet50.conf train step (BASELINE
                stretch config 5), 224x224, BatchNorm buffers threaded

Each workload reports {samples_per_sec, step_ms, model_flops, mfu,
phase_ms}: model_flops is the analytic per-step matmul count
(singa_tpu/utils/flops.py, 3x forward; causal attention at half
density), mfu divides achieved FLOP/s by the chip's bf16 peak
(utils/flops.py device_kind table; a chip not in it is an error), and
phase_ms are the per-phase host timers — TimerInfo parity with the
reference (include/worker/worker.h:91-114).

Output contract: the lossless JSON object prints first (and lands in
BENCH.json), and the LAST stdout line is a compact machine-parseable
summary — {metric, value, unit, vs_baseline, workloads:
[{name, value, unit, mfu}], warm_start_saved_ms} — sized to survive
a tail capture. "compile_warm_start" in the lossless object reports
the persistent-compilation-cache delta (first step, then the same
first step again from the persistent cache; utils/compile_cache.py).
A selected workload that raises is recorded as an error row AND makes
the run exit non-zero.

Timing methodology: each workload times TWO window sizes and reports
the SLOPE (T(n2) - T(n1)) / (n2 - n1) — the marginal per-step cost,
free of whatever fixed cost a dispatch-and-sync carries. The fixed
intercept is reported as fixed_overhead_ms. A window closes on a host
pull of a reduction over the params, which cannot return before the
work is done.

vs_baseline is None: no number has been measured on this code on the
chip (the reference repo publishes none either); ``chip_smoke.py`` is
the standing proof that the program runs there.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

BASELINE_NOTE = (
    "not measured on this code on the chip; the reference publishes no "
    "numbers"
)


def _bench_trainer(trainer, n1: int, n2: int, trials: int = 2):
    """Slope-fit the per-step cost: time n1-step and n2-step windows
    (best of `trials` each) and return (slope_sec_per_step,
    fixed_overhead_sec, total_timed_steps).

    Uses the chunked engine when available (one dispatch per chunk cap),
    otherwise the per-step loop. Sync = a host pull of a reduction over
    the params.
    """
    import jax.numpy as jnp

    def sync() -> float:
        return float(jnp.sum(jnp.abs(next(iter(trainer.params.values())))))

    if trainer._can_chunk():
        cap = trainer._chunk_cap()

        def run(step0, n):
            s = step0
            while s < step0 + n:
                # _chunk_len keeps cadence semantics (the replica
                # trainer bounds windows at its sync cadence so protocol
                # rounds run inside the timed region)
                take = min(cap, trainer._chunk_len(s), step0 + n - s)
                if take > 1:
                    trainer.train_chunk(s, take)
                else:
                    trainer.train_one_batch(s)
                s += take
    else:
        def run(step0, n):
            for s in range(step0, step0 + n):
                trainer.train_one_batch(s)

    # warm: compile every chunk length both windows will use
    run(0, n1)
    run(n1, n2)
    sync()
    trainer.timers.reset()
    step = n1 + n2
    best = {}
    for n in (n1, n2):
        best[n] = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            run(step, n)
            sync()
            best[n] = min(best[n], time.perf_counter() - t0)
            step += n
    slope = (best[n2] - best[n1]) / (n2 - n1)
    overhead = best[n1] - slope * n1
    return slope, overhead, trials * (n1 + n2)


def _workload_result(name, trainer, slope, overhead, timed_steps,
                     unit="samples/sec", tokens_per_sample=None,
                     flops=None):
    from singa_tpu.utils.flops import device_peak_flops, train_step_flops

    # records per step: the replica trainer consumes one batch per
    # replica, so use the trainer's own accounting, not net.batchsize
    batch = trainer._batch_size
    sps = batch / slope
    # `flops` overrides the backprop 3x-forward convention (the CD
    # engine has no backward pass — utils/flops.py cd_step_flops)
    if flops is None:
        flops = train_step_flops(trainer.train_net)
    flops *= getattr(trainer, "_batches_per_step", 1)
    peak = device_peak_flops()
    mfu = (flops / slope) / peak if peak else None
    value = sps * tokens_per_sample if tokens_per_sample else sps
    # host-side phase timers over every timed step (dispatch cost under
    # the chunked engine; full host loop otherwise). The data phase is
    # ALWAYS reported — a 0.0 row proves input stalls were measured and
    # absent, instead of hiding them (a `train`-only row makes an
    # input-bound regression invisible).
    t = trainer.timers
    phase_ms = {
        ph: round(t.total(ph) / timed_steps * 1e3, 4) for ph in t.phases()
    }
    phase_ms.setdefault("data", 0.0)
    # update-phase ms measured in isolation (tools/update_stall.py's
    # slope fit over chained updater applications): the number the
    # zero_update sharding is allowed to move, reported per row so a
    # regression stays attributable.
    from singa_tpu.tools.update_stall import measure_update_ms

    update_ms = round(measure_update_ms(trainer), 4)
    # gradient-collective machinery ms measured in isolation
    # (tools/collective_stall.py's chained-reduce slope fit): the number
    # the grad_comm quantize/overlap path is allowed to move, reported
    # per row so a regression stays attributable.
    from singa_tpu.tools.collective_stall import measure_comm_ms

    comm_ms = round(measure_comm_ms(trainer), 4)
    return {
        "name": name,
        "value": round(value, 1),
        "unit": unit,
        "samples_per_sec": round(sps, 1),
        "step_ms": round(slope * 1e3, 4),
        "fixed_overhead_ms": round(overhead * 1e3, 1),
        "batch": batch,
        "model_flops": flops,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "phase_ms": phase_ms,
        # which input path fed the row (cached / stream / prefetch /
        # sync) — regressions stay attributable to the feeder mode
        "feeder": trainer.feeder_mode,
        # how the weight update is laid out (replicated / zero) plus
        # the bytes the zero mode exists to shrink and the phase it is
        # allowed to move — the ZeRO win, measured per row
        "update_mode": trainer.update_mode,
        "opt_state_bytes_per_device": trainer.opt_state_bytes_per_device(),
        "update_ms": update_ms,
        # how gradients cross the data axis (exact / quantized + wire
        # dtype) and the isolated cost of that machinery — the
        # grad_comm analog of update_mode/update_ms
        "comm_mode": trainer.comm_mode,
        "comm_dtype": trainer.comm_dtype,
        "comm_ms": comm_ms,
        **_wire_fields(trainer),
        "method": "two-window slope fit (marginal per-step cost)",
    }


def _wire_fields(trainer, nominal_ndata: int = 8) -> dict:
    """The int8-on-the-wire ring's deterministic numbers ({} unless the
    row runs `kernels { grad_allreduce: quantized_ring }`): modeled
    per-device data-axis bytes per step, reference fp32 collective over
    the quantized ring — tools/collective_stall.py's gated arm. The
    bench host's own data axis may be 1-wide (an empty wire), so the
    model is priced at a nominal `wire_ndata`-wide axis (halved by
    `wire_bytes_model` until the chunking actually divides — the
    reported `wire_ndata` is the validated width); the RATIO is what
    the row pins, and it is width-stable (both costs scale with
    (n-1)/n)."""
    comm = getattr(trainer, "_comm", None)
    if comm is None or not comm.ring:
        return {}
    model = trainer.wire_bytes_model(
        ndata=max(nominal_ndata, trainer._ring_ndata())
    )
    ref, ring = model["reference"], model["quantized_ring"]
    fields = {
        "wire_ndata": model["ndata"],
        "wire_ref_bytes": ref,
        "wire_ring_bytes": ring,
        "wire_bytes_ratio": round(ref / ring, 3) if ring else None,
    }
    if "inter" in model:
        # the hierarchical row's per-level split: the scarce inter-slice
        # bytes x intra_degree must stay at or under the flat same-n
        # ring (K(M-1) <= KM-1) — `wire_inter_vs_flat` <= 1.0 pins it
        flat = model.get("flat_ring")
        fields["wire_intra_bytes"] = model["intra"]
        fields["wire_inter_bytes"] = model["inter"]
        fields["wire_intra_degree"] = model["intra_degree"]
        fields["wire_inter_vs_flat"] = (
            round(model["inter"] * model["intra_degree"] / flat, 3)
            if flat else None
        )
    return fields


def _tmpdir() -> str:
    return tempfile.mkdtemp(prefix="singa_tpu_bench_")


def _prep_cfg(cfg, nsteps: int, bf16: bool = False):
    """Silence cadences and size train_steps for a slope-fit run."""
    cfg.train_steps = nsteps
    cfg.test_steps = 0
    cfg.display_frequency = 0
    cfg.checkpoint_frequency = 0
    if bf16:
        cfg.compute_dtype = "bfloat16"
    return cfg


def _run_workload(name, cfg, n1, n2, unit="samples/sec",
                  tokens_per_sample=None):
    from singa_tpu.trainer import Trainer

    trainer = Trainer(cfg, seed=0, log=lambda s: None, prefetch=False)
    slope, ovh, ts = _bench_trainer(trainer, n1, n2)
    return _workload_result(
        name, trainer, slope, ovh, ts,
        unit=unit, tokens_per_sample=tokens_per_sample,
    )


def bench_mnist_mlp(n1=256, n2=1280):
    from __graft_entry__ import _flagship_cfg

    cfg = _prep_cfg(_flagship_cfg(batchsize=1000), 4 * (n1 + n2), bf16=True)
    return _run_workload("mnist_mlp", cfg, n1, n2)


def bench_cifar_alexnet(n1=256, n2=1280, batch=256):
    import numpy as np

    from singa_tpu.config import load_model_config
    from singa_tpu.data.loader import synthetic_arrays, write_records

    cfg = load_model_config(
        os.path.join(REPO, "examples", "cifar10", "alexnet.conf")
    )
    tmp = _tmpdir()
    shard = os.path.join(tmp, "shard")
    write_records(shard, *synthetic_arrays(512, size=32, channels=3, seed=0))
    mean = os.path.join(tmp, "mean.npy")
    np.save(mean, np.zeros((3, 32, 32), dtype=np.float32))
    for layer in cfg.neuralnet.layer:
        if layer.type == "kShardData":
            layer.data_param.path = shard
            layer.data_param.batchsize = batch
            layer.data_param.random_skip = 0
        if layer.rgbimage_param is not None and layer.rgbimage_param.meanfile:
            layer.rgbimage_param.meanfile = mean
    _prep_cfg(cfg, 4 * (n1 + n2), bf16=True)
    return _run_workload("cifar_alexnet", cfg, n1, n2)


def bench_tinylm(n1=256, n2=1280, seq_len=128, batch=0, n_samples=256,
                 name="tinylm", conf="tinylm.conf", zero=False,
                 grad_comm="", comm_buckets=0):
    from singa_tpu.config import load_model_config
    from singa_tpu.data.loader import synthetic_token_arrays, write_records
    from singa_tpu.parallel import apply_grad_comm_tag

    cfg = load_model_config(os.path.join(REPO, "examples", "lm", conf))
    tmp = _tmpdir()
    shard = os.path.join(tmp, "shard")
    write_records(
        shard, *synthetic_token_arrays(n_samples, seq_len=seq_len, vocab=256)
    )
    for layer in cfg.neuralnet.layer:
        if layer.type == "kSequenceData":
            layer.data_param.path = shard
            if batch:
                layer.data_param.batchsize = batch
    cfg.zero_update = zero
    apply_grad_comm_tag(cfg, grad_comm)
    if comm_buckets and cfg.grad_comm is not None:
        cfg.grad_comm.buckets = comm_buckets
    _prep_cfg(cfg, 4 * (n1 + n2))  # conf already sets bfloat16
    return _run_workload(
        name, cfg, n1, n2, unit="tokens/sec", tokens_per_sample=seq_len
    )


def bench_resnet50(n1=20, n2=60, batch=128, stats_stride=0,
                   name="resnet50"):
    # window sizes: short (6/18-step) windows leave the slope exposed
    # to per-window jitter; 20/60 steadies it
    from singa_tpu.config import load_model_config
    from singa_tpu.data.loader import synthetic_arrays, write_records

    cfg = load_model_config(
        os.path.join(REPO, "examples", "imagenet", "resnet50.conf")
    )
    tmp = _tmpdir()
    shard = os.path.join(tmp, "shard")
    write_records(
        shard, *synthetic_arrays(batch, size=256, channels=3, seed=0)
    )
    for layer in cfg.neuralnet.layer:
        if layer.type == "kShardData":
            layer.data_param.path = shard
            layer.data_param.batchsize = batch
            layer.data_param.random_skip = 0
        if stats_stride and layer.type == "kBatchNorm":
            layer.batchnorm_param.stats_sample_stride = stats_stride
    _prep_cfg(cfg, 4 * (n1 + n2))  # conf already sets bfloat16
    return _run_workload(name, cfg, n1, n2)


def bench_resnet50_fastbn(n1=20, n2=60, batch=128):
    """ResNet-50 with the OPT-IN subsample-stats BN knob (stride 4:
    stats from 32 of 128 samples, straight-through backward —
    batchnorm_param.stats_sample_stride, different math, default off).
    Exists because, with the same math, the stats read is the only
    fusion-recoverable term (bench/ablations/bn_roofline.py)."""
    return bench_resnet50(
        n1, n2, batch, stats_stride=4, name="resnet50_fastbn"
    )


def bench_lm_longctx(n1=64, n2=256):
    """tinylm at S=8192 (batch 1): the long-context regime where the
    S x S score tensor exceeds the dense budget and the staged-K/V
    Pallas flash kernel carries the attention."""
    return bench_tinylm(
        n1, n2, seq_len=8192, batch=1, n_samples=32, name="lm_longctx"
    )


def bench_lm_32k(n1=16, n2=48):
    """tinylm at S=32768 (batch 1): K/V exceed the VMEM staging budget,
    so the HBM-streaming flash kernels carry the attention."""
    return bench_tinylm(
        n1, n2, seq_len=32768, batch=1, n_samples=8, name="lm_32k"
    )


def bench_lm_longctx_d128(n1=64, n2=256):
    """lm_longctx on the d_head=128 shape (tinylm_d128.conf): the flash
    kernels are MXU-shape-bound at d=64 (half of a 128-wide MXU pass),
    so the wider head is the long-context shape. A standing row, so it
    is regression-guarded."""
    return bench_tinylm(
        n1, n2, seq_len=8192, batch=1, n_samples=32,
        name="lm_longctx_d128", conf="tinylm_d128.conf",
    )


def bench_lm_32k_d128(n1=16, n2=48):
    """lm_32k on the d_head=128 shape."""
    return bench_tinylm(
        n1, n2, seq_len=32768, batch=1, n_samples=8,
        name="lm_32k_d128", conf="tinylm_d128.conf",
    )


def bench_lm_d128_zero(n1=256, n2=1280):
    """tinylm_d128 under the ZeRO update sharding (zero_update: true) —
    the standing regression row for the sharded update path. On the
    bench chip's data axis the row must hold the tinylm_d128 number
    (the update is the same elementwise math; only its layout changes)
    while `opt_state_bytes_per_device` shrinks by the data width —
    both visible in the row, so a zero regression is attributable to
    either throughput or footprint, never silent."""
    return bench_tinylm(
        n1, n2, name="lm_d128_zero", conf="tinylm_d128.conf", zero=True
    )


def bench_lm_d128_q8(n1=256, n2=1280):
    """tinylm_d128 under the quantized + bucketized gradient collective
    (grad_comm: quantized int8, error feedback, 4 reverse-topo buckets)
    — the standing regression row for the grad_comm path. On the bench
    chip the row must hold the tinylm_d128 number (the quantize math is
    cheap elementwise work; the wire value the data-axis collective
    moves is a quarter the bytes) while `comm_mode`/`comm_dtype`/
    `comm_ms` make any regression attributable to the collective
    machinery rather than the model."""
    return bench_tinylm(
        n1, n2, name="lm_d128_q8", conf="tinylm_d128.conf",
        grad_comm="q8", comm_buckets=4,
    )


def bench_lm_d128_q8wire(n1=256, n2=1280):
    """`lm_d128_q8` with `kernels { grad_allreduce: quantized_ring }` —
    the same quantized numerics, but the data-axis reduction is the
    explicit int8-on-the-wire ppermute ring
    (ops/quantized_collective.py) instead of the quantize-around-the-
    psum reference seam. `wire_bytes_ratio` is the deterministic number
    the row exists to pin — modeled per-device data-axis bytes,
    reference fp32 collective over the ring's ppermute payloads (~3.9x
    at int8; a regression in the chunking, the scale plumbing, or the
    allgather skip moves it). On this CPU host the ring is a per-shard
    shard_map emulation, so `value` (tokens/sec) trails `lm_d128_q8` by
    construction — the bytes model and ring-vs-reference parity are
    what regress-guard here, exactly collective_stall's or-gate in
    CI."""
    return bench_tinylm(
        n1, n2, name="lm_d128_q8wire", conf="tinylm_d128.conf",
        grad_comm="q8wire", comm_buckets=4,
    )


def bench_lm_d128_q8hier(n1=256, n2=1280):
    """`lm_d128_q8wire` with `kernels { grad_allreduce: q8_hier }` and
    `ring { intra_degree: 2 }` — the two-level hierarchical ring:
    intra-slice reduce-scatter/allgather on the f32 fast wire, ONE int8
    ring over group leaders on the scarce inter-slice hop. On the
    1-wide bench host the runtime geometry degenerates (no hops), so
    the row's numbers come from the nominal-width pricing in
    `wire_bytes_model`: `wire_intra_bytes`/`wire_inter_bytes` are the
    per-level model at the configured intra_degree, and
    `wire_inter_vs_flat` (inter x K over the flat same-n ring, <= 1.0
    by the K(M-1) <= KM-1 identity) is the deterministic number the
    row exists to pin — the hierarchy must never pay more on the slow
    wire than the flat ring it replaces."""
    return bench_tinylm(
        n1, n2, name="lm_d128_q8hier", conf="tinylm_d128.conf",
        grad_comm="q8hier", comm_buckets=4,
    )


def bench_rbm(n1=128, n2=640, batch=100):
    """The CD engine (BASELINE config 4) on examples/mnist/rbm.conf:
    greedy layerwise CD-1 over the 784-1000-500-250-30 stack, one jitted
    step for the whole stack. MFU uses the CD-specific FLOPs walk
    (utils/flops.py cd_step_flops — CD has no backward pass, so the
    backprop 3x-forward convention would overstate the model FLOPs).
    Runs fp32 (the CD step does not thread compute_dtype), so on-chip
    MFU vs the bf16 peak is conservative."""
    from singa_tpu.config import load_model_config
    from singa_tpu.data.loader import synthetic_arrays, write_records
    from singa_tpu.trainer import CDTrainer
    from singa_tpu.utils.flops import cd_step_flops

    cfg = load_model_config(
        os.path.join(REPO, "examples", "mnist", "rbm.conf")
    )
    tmp = _tmpdir()
    shard = os.path.join(tmp, "shard")
    write_records(shard, *synthetic_arrays(512, seed=0))
    for layer in cfg.neuralnet.layer:
        if layer.type == "kShardData":
            layer.data_param.path = shard
            layer.data_param.batchsize = batch
            layer.data_param.random_skip = 0
    _prep_cfg(cfg, 4 * (n1 + n2))
    trainer = CDTrainer(cfg, seed=0, log=lambda s: None, prefetch=False)
    slope, ovh, ts = _bench_trainer(trainer, n1, n2)
    return _workload_result(
        "rbm", trainer, slope, ovh, ts,
        flops=cd_step_flops(trainer.train_net),
    )


def bench_mnist_mlp_replica(n1=256, n2=1280):
    """The async-protocol engine (ReplicaTrainer, Elastic) on the same
    flagship MLP: on one chip this runs a single replica with a protocol
    round every sync_frequency steps — the engine-overhead comparison
    against the sync trainer's mnist_mlp row."""
    from __graft_entry__ import _flagship_cfg
    from singa_tpu.trainer import ReplicaTrainer

    cfg = _prep_cfg(_flagship_cfg(batchsize=1000), 4 * (n1 + n2), bf16=True)
    cfg.updater.param_type = "Elastic"
    cfg.updater.moving_rate = 0.9
    cfg.updater.sync_frequency = 8
    cfg.updater.warmup_steps = 8
    trainer = ReplicaTrainer(
        cfg, seed=0, log=lambda s: None, prefetch=False
    )
    # _bench_trainer's untimed warm pass single-steps the warmup (the
    # replica _chunk_len returns 1 pre-bootstrap) and bootstraps before
    # the timed windows — no extra priming needed
    slope, ovh, ts = _bench_trainer(trainer, n1, n2)
    return _workload_result("mnist_mlp_replica", trainer, slope, ovh, ts)


def bench_lm_d128_serve():
    """The serving tier (singa_tpu/serve/) on the d_head=128 LM shape:
    continuous batching at concurrency 8 with the paged KV cache vs the
    same engine one stream at a time. The standing regression row for
    the serving path — `tokens_per_s` is the row value, `p50_ms` /
    `p99_ms` are request latency percentiles, `kv_blocks_used` the pool
    high-water mark, `speedup` the continuous/sequential ratio the CI
    serve-smoke job gates at >= 2x. Unlike the training rows this is a
    request-level wall-clock measurement (tools/serve_bench.py), not a
    two-window slope — serving latency IS the metric, there is no
    fixed-overhead term to subtract."""
    import io
    from contextlib import redirect_stdout

    from singa_tpu.tools import serve_bench

    buf = io.StringIO()
    with redirect_stdout(buf):
        serve_bench.main([
            "--d_model", "256", "--n_heads", "2", "--d_ff", "1024",
            "--requests", "12", "--max_new", "32", "--no_gate",
        ])
    r = json.loads(buf.getvalue().strip().splitlines()[-1])
    return {
        "name": "lm_d128_serve",
        "value": r["tokens_per_s"],
        "unit": "tokens/sec",
        "tokens_per_s": r["tokens_per_s"],
        "p50_ms": r["p50_ms"],
        "p99_ms": r["p99_ms"],
        "kv_blocks_used": r["kv_blocks_peak"],
        "slot_occupancy": r["slot_occupancy"],
        "speedup": r.get("speedup"),
        "steady_speedup": r.get("steady_speedup"),
        "seq_tokens_per_s": r.get("seq_tokens_per_s"),
        "concurrency": r["concurrency"],
        "token_mismatches": r.get("token_mismatches"),
        "method": "serve_bench open-loop workload (request wall clock)",
    }


def bench_lm_d128_spec():
    """Speculative decode on the serving shape: the same engine as
    `lm_d128_serve` with n-gram drafting at k=4 on the
    drafting-friendly repeat workload vs its own one-token tick
    (`base_tokens_per_s`). `tokens_per_s` is the row value;
    `acceptance_rate` and `tokens_per_tick` are the amortization
    numbers a regression in either the drafter or the verify program
    would move; `spec_machinery_ratio` is the compiled-cost ratio of
    the zero-draft verify tick over the decode tick (the
    speculation-when-it-buys-nothing overhead, ~1.0 by construction).
    On this CPU host decode is compute-bound so `spec_speedup` < 1 is
    expected (the (k+1)-wide verify pays real FLOPs a
    weight-streaming-bound accelerator would not) — the row exists to
    pin acceptance, identity (token_mismatches == 0), and machinery,
    which is exactly what serve_bench's or-gate enforces in CI."""
    import io
    from contextlib import redirect_stdout

    from singa_tpu.tools import serve_bench

    buf = io.StringIO()
    with redirect_stdout(buf):
        serve_bench.main([
            "--d_model", "256", "--n_heads", "2", "--d_ff", "1024",
            "--requests", "12", "--max_new", "32", "--no_gate",
            "--speculate_k", "4", "--workload", "repeat",
        ])
    r = json.loads(buf.getvalue().strip().splitlines()[-1])
    return {
        "name": "lm_d128_spec",
        "value": r["tokens_per_s"],
        "unit": "tokens/sec",
        "tokens_per_s": r["tokens_per_s"],
        "base_tokens_per_s": r.get("base_tokens_per_s"),
        "spec_speedup": r.get("spec_speedup"),
        "acceptance_rate": r.get("acceptance_rate"),
        "tokens_per_tick": r.get("tokens_per_tick"),
        "spec_machinery_ratio": r.get("spec_machinery_ratio"),
        "spec_k": r.get("spec_k"),
        "p50_ms": r["p50_ms"],
        "p99_ms": r["p99_ms"],
        "token_mismatches": r.get("token_mismatches"),
        "method": "serve_bench speculative workload (request wall clock)",
    }


def bench_lm_d128_prefix():
    """Prefix caching on the serving shape: the shared_prefix workload
    (one long common system-prompt prefix, short unique tails) with
    the content-addressed refcounted block cache warm vs the same
    engine cold (cache disabled). `tokens_per_s` (warm) is the row
    value; `prefix_speedup` the warm/cold end-to-end ratio;
    `hit_rate`, `blocks_shared`, and `prefill_chunks_saved` are the
    deterministic numbers a regression in matching, sharing, or the
    admission seeding would move (`prefill_chunk_ratio` is the
    host-independent or-gate arm CI enforces); `cow_copies` pins that
    the whole-prompt-hit copy-on-write path actually ran. Identity
    (token_mismatches == 0) is the hard bar — a hit may only skip
    prefill work, never move a token."""
    import io
    from contextlib import redirect_stdout

    from singa_tpu.tools import serve_bench

    buf = io.StringIO()
    with redirect_stdout(buf):
        serve_bench.main([
            "--d_model", "256", "--n_heads", "2", "--d_ff", "1024",
            "--requests", "12", "--max_new", "16", "--no_gate",
            "--workload", "shared_prefix", "--prompt_len", "48",
            "--block_len", "8", "--prefill_chunk", "8",
        ])
    r = json.loads(buf.getvalue().strip().splitlines()[-1])
    return {
        "name": "lm_d128_prefix",
        "value": r["tokens_per_s"],
        "unit": "tokens/sec",
        "tokens_per_s": r["tokens_per_s"],
        "cold_tokens_per_s": r.get("cold_tokens_per_s"),
        "prefix_speedup": r.get("prefix_speedup"),
        "hit_rate": r.get("prefix_hit_rate"),
        "blocks_shared": r.get("blocks_shared"),
        "prefill_chunks_saved": r.get("prefill_chunks_saved"),
        "prefill_chunk_ratio": r.get("prefill_chunk_ratio"),
        "cow_copies": r.get("cow_copies"),
        "lru_reclaims": r.get("lru_reclaims"),
        "p50_ms": r["p50_ms"],
        "p99_ms": r["p99_ms"],
        "token_mismatches": r.get("token_mismatches"),
        "method": "serve_bench shared_prefix workload (request wall clock)",
    }


def bench_lm_d128_fleetprefix():
    """The FLEET prefix cache on the serving shape: the shared_prefix
    workload across two unified fleet hosts, where the measured host
    has never seen the prompts — its only path to warm KV is a
    cross-host cache_fetch -> cache_ship bulk frame from its peer
    (serve/fleet/host.py). `tokens_per_s` (warm) is the row value;
    `hit_rate`, `blocks_shipped`, `ship_bytes`, and
    `prefill_chunk_ratio` are the deterministic numbers a regression
    in fetch targeting, the ship codec, or slot-free install would
    move (the chunk ratio is the host-independent or-gate arm CI
    enforces). Identity (token_mismatches == 0 vs the cache-off cold
    fleet) is the hard bar — shipped bytes may only skip prefill
    work, never move a token."""
    import io
    from contextlib import redirect_stdout

    from singa_tpu.tools import serve_bench

    buf = io.StringIO()
    with redirect_stdout(buf):
        serve_bench.main([
            "--d_model", "256", "--n_heads", "2", "--d_ff", "1024",
            "--requests", "12", "--max_new", "16", "--no_gate",
            "--fleet", "--workload", "shared_prefix",
            "--prompt_len", "48", "--block_len", "8",
            "--prefill_chunk", "8",
        ])
    r = json.loads(buf.getvalue().strip().splitlines()[-1])
    return {
        "name": "lm_d128_fleetprefix",
        "value": r["tokens_per_s"],
        "unit": "tokens/sec",
        "tokens_per_s": r["tokens_per_s"],
        "cold_tokens_per_s": r.get("cold_tokens_per_s"),
        "fleet_speedup": r.get("fleet_speedup"),
        "hit_rate": r.get("hit_rate"),
        "cache_fetches": r.get("cache_fetches"),
        "blocks_shipped": r.get("blocks_shipped"),
        "ship_bytes": r.get("ship_bytes"),
        "prefill_chunk_ratio": r.get("prefill_chunk_ratio"),
        "pass_mode": r.get("pass_mode"),
        "token_mismatches": r.get("token_mismatches"),
        "method": "serve_bench --fleet shared_prefix workload "
        "(cross-host cache_ship vs cold fleet, request wall clock)",
    }


def bench_lm_d128_rollout():
    """Live weight rollout under load on the serving shape: two
    unified fleet hosts serve the workload while the rollout controller
    (serve/rollout.py) hot-swaps a new weight version mid-bench —
    canary one host, parity-probe it against a reference engine on the
    staged weights, promote the fleet. `tokens_per_s` is the row value
    (throughput of the run that absorbed the swap); `pre_flip_streams`
    / `pre_flip_mismatches` pin flip identity (streams retired before
    the flip are bitwise the no-rollout oracle), `verdict` must be
    `promoted` and every host must land on v1 with zero hung streams —
    the numbers a regression in staging, the tick-boundary flip, the
    cache purge, or the parity gate would move."""
    import io
    import time
    from contextlib import redirect_stdout

    from singa_tpu.tools import serve_bench

    buf = io.StringIO()
    t0 = time.perf_counter()
    with redirect_stdout(buf):
        serve_bench.main([
            "--d_model", "256", "--n_heads", "2", "--d_ff", "1024",
            "--requests", "12", "--max_new", "16", "--no_gate",
            "--rollout", "promote", "--fleet_hosts", "unified,unified",
            "--rollout_at_tick", "12", "--prompt_len", "8",
            "--block_len", "8", "--prefill_chunk", "8",
        ])
    wall_s = time.perf_counter() - t0
    r = json.loads(buf.getvalue().strip().splitlines()[-1])
    return {
        "name": "lm_d128_rollout",
        # the drill JSON reports identity/verdict fields, not a
        # throughput — the row value is workload tokens over the
        # whole drill's wall clock (oracle + swap run + probes)
        "value": round(r["requests"] * 16 / wall_s, 1),
        "unit": "tokens/sec",
        "verdict": r.get("verdict"),
        "versions": r.get("versions"),
        "finished": r.get("finished"),
        "hung": r.get("hung"),
        "pre_flip_streams": r.get("pre_flip_streams"),
        "pre_flip_mismatches": r.get("pre_flip_mismatches"),
        "rollbacks": r.get("rollbacks"),
        "torn_ships": r.get("torn_ships"),
        "gate_pass": r.get("pass"),
        "method": "serve_bench --rollout promote (mid-bench hot-swap "
        "vs no-rollout oracle, drill wall clock)",
    }


def bench_lm_d128_fusedattn():
    """Fused paged attention on the serving shape: the same engine as
    `lm_d128_serve` with `kernels { paged_attention: fused }` — the
    Pallas kernel reading K/V blocks in place through the block table
    (Mosaic-compiled on a TPU, interpreted elsewhere — the platform
    decides, ops/paged_attention._call). `tokens_per_s` is the row value;
    `attn_bytes_ratio` is the deterministic number the row exists to
    pin — modeled attention bytes accessed, reference dense-gather
    path over fused block-tile reads (tools/attend_stall.py's gated
    arm; a regression in the kernel's fetch clamping or the reference
    gather moves it). On a CPU host the kernel runs interpreted, so
    wall-clock `tokens_per_s` trails `lm_d128_serve` by construction —
    identity (token_mismatches == 0 vs the reference-path baselines)
    and the bytes model are what regress-guard there, which is exactly
    what attend_stall's or-gate enforces in CI."""
    import io
    from contextlib import redirect_stdout

    import jax

    from singa_tpu.models.transformer import TransformerConfig, init_lm
    from singa_tpu.tools import serve_bench
    from singa_tpu.tools.attend_stall import (
        build_argparser as as_parser,
        measure_attend_bytes,
    )

    buf = io.StringIO()
    with redirect_stdout(buf):
        serve_bench.main([
            "--d_model", "256", "--n_heads", "2", "--d_ff", "1024",
            "--requests", "8", "--max_new", "16", "--no_gate",
            "--kernels", "fused",
        ])
    r = json.loads(buf.getvalue().strip().splitlines()[-1])
    st = as_parser().parse_args([
        "--d_model", "256", "--n_heads", "2", "--d_ff", "1024",
        "--max_new", "16",
    ])
    cfg = TransformerConfig(
        vocab=st.vocab, d_model=st.d_model, n_heads=st.n_heads,
        n_layers=st.n_layers, d_ff=st.d_ff, max_len=st.max_len,
    )
    by = measure_attend_bytes(
        init_lm(jax.random.PRNGKey(st.seed), cfg), cfg, st
    )
    return {
        "name": "lm_d128_fusedattn",
        "value": r["tokens_per_s"],
        "unit": "tokens/sec",
        "tokens_per_s": r["tokens_per_s"],
        "kernels": r.get("kernels"),
        "attn_bytes_ratio": by["bytes_ratio"],
        "attn_ref_bytes": by["ref_bytes"],
        "attn_fused_bytes": by["fused_bytes"],
        "p50_ms": r["p50_ms"],
        "p99_ms": r["p99_ms"],
        "speedup": r.get("speedup"),
        "token_mismatches": r.get("token_mismatches"),
        "method": "serve_bench --kernels fused (request wall clock) + "
        "attend_stall modeled-bytes probe",
    }


BENCHES = (
    ("mnist_mlp", bench_mnist_mlp),
    ("cifar_alexnet", bench_cifar_alexnet),
    ("tinylm", bench_tinylm),
    ("lm_longctx", bench_lm_longctx),
    ("lm_32k", bench_lm_32k),
    ("lm_longctx_d128", bench_lm_longctx_d128),
    ("lm_32k_d128", bench_lm_32k_d128),
    ("lm_d128_zero", bench_lm_d128_zero),
    ("lm_d128_q8", bench_lm_d128_q8),
    ("lm_d128_q8wire", bench_lm_d128_q8wire),
    ("lm_d128_q8hier", bench_lm_d128_q8hier),
    ("lm_d128_serve", bench_lm_d128_serve),
    ("lm_d128_spec", bench_lm_d128_spec),
    ("lm_d128_prefix", bench_lm_d128_prefix),
    ("lm_d128_fleetprefix", bench_lm_d128_fleetprefix),
    ("lm_d128_rollout", bench_lm_d128_rollout),
    ("lm_d128_fusedattn", bench_lm_d128_fusedattn),
    ("resnet50", bench_resnet50),
    ("resnet50_fastbn", bench_resnet50_fastbn),
    ("mnist_mlp_replica", bench_mnist_mlp_replica),
    ("rbm", bench_rbm),
)


def bench_warm_start():
    """Measure the persistent-compile-cache warm start: the first step
    of the flagship MLP program, then the same first step again after
    ``jax.clear_caches()`` drops the in-memory executable, so the second
    compile is served from the persistent cache (utils/compile_cache.py)
    — the delta is the fixed per-run overhead a repeat run skips.

    The cache is the process's own (JAX_COMPILATION_CACHE_DIR, or the
    fixed directory inside the checkout), never a fresh one: the first
    reading is cold only where ``cold_was_cache_hit`` says false."""
    import jax

    from __graft_entry__ import _flagship_cfg
    from singa_tpu.trainer import Trainer
    from singa_tpu.utils.compile_cache import CacheCounter

    def first_step_ms() -> float:
        cfg = _prep_cfg(
            _flagship_cfg(batchsize=128, hidden_scale=0.25), 8, bf16=True
        )
        trainer = Trainer(
            cfg, seed=0, log=lambda s: None, prefetch=False,
            device_cache=False,
        )
        import jax.numpy as jnp

        t0 = time.perf_counter()
        trainer.train_one_batch(0)
        float(jnp.sum(jnp.abs(next(iter(trainer.params.values())))))
        return (time.perf_counter() - t0) * 1e3

    with CacheCounter() as counter:
        cold = first_step_ms()
        cold_misses = counter.misses
        jax.clear_caches()  # drop in-memory executables; disk cache remains
        warm = first_step_ms()
    return {
        "cold_first_step_ms": round(cold, 1),
        "warm_first_step_ms": round(warm, 1),
        "saved_ms": round(cold - warm, 1),
        "cold_was_cache_hit": cold_misses == 0,
        "method": (
            "flagship-MLP first step, then the same step from the "
            "persistent cache after jax.clear_caches()"
        ),
    }


#: set by main(): a partial (workload-selected) run writes its JSON to
#: the .partial sidecar so it cannot clobber the canonical full-suite
#: BENCH.json record
_PARTIAL_RUN = False


def main() -> int:
    global _PARTIAL_RUN
    only = set(sys.argv[1:])
    _PARTIAL_RUN = bool(only)
    unknown = only - {name for name, _ in BENCHES} - {"warm_start"}
    if unknown:
        print(f"unknown workload(s): {sorted(unknown)}; "
              f"choose from {[n for n, _ in BENCHES] + ['warm_start']}",
              file=sys.stderr)
        return 2
    from singa_tpu.utils.compile_cache import setup_compile_cache

    setup_compile_cache(log=lambda s: print(s, file=sys.stderr))
    workloads = []
    for name, fn in BENCHES:
        if only and name not in only:
            continue
        try:
            workloads.append(fn())
        except Exception:
            # the rest still run, but the run exits non-zero (below)
            print(f"bench {name} FAILED:", file=sys.stderr)
            traceback.print_exc()
            workloads.append({"name": name, "error": "failed (see stderr)"})
    failed = any("error" in w for w in workloads)
    head = next(
        (w for w in workloads if w.get("name") == "mnist_mlp" and "value" in w),
        None,
    )
    # persistent-compile warm start: measured after every workload (it
    # flips global cache config). The probe's same-process cache re-read
    # pattern can in principle hard-crash jaxlib (the reason
    # utils/compile_cache.py disables the cache for supervisor
    # restarts), and a segfault is not catchable — so the measured
    # workloads are persisted to the BENCH file FIRST, in the full
    # contract shape; a probe crash costs the warm-start number, never
    # the suite.
    warm_start = None
    if not only or "warm_start" in only:
        _write_bench_file(json.dumps({
            "metric": "mnist_mlp_train_throughput",
            "value": head["value"] if head else None,
            "unit": "samples/sec",
            "vs_baseline": None,
            "baseline_note": BASELINE_NOTE,
            "compile_warm_start": None,
            "workloads": workloads,
        }))
        try:
            warm_start = bench_warm_start()
        except Exception:
            print("bench warm_start FAILED:", file=sys.stderr)
            traceback.print_exc()
            warm_start = {"error": "failed (see stderr)"}
            failed = True
    if head is None and only and "mnist_mlp" not in only:
        # headline workload deliberately not selected: promote the first
        # measured workload instead of reporting a misreadable 0.0
        promoted = next((w for w in workloads if "value" in w), None)
        out = {
            "metric": (
                f"{promoted['name']}_train_throughput" if promoted
                else "mnist_mlp_train_throughput"
            ),
            "value": promoted["value"] if promoted else None,
            "unit": promoted["unit"] if promoted else "samples/sec",
            "vs_baseline": None,
            "baseline_note": BASELINE_NOTE,
            "compile_warm_start": warm_start,
            "workloads": workloads,
        }
        _emit(out)
        return 1 if failed else 0
    out = {
        "metric": "mnist_mlp_train_throughput",
        "value": head["value"] if head else None,
        "unit": "samples/sec",
        "vs_baseline": None,
        "baseline_note": BASELINE_NOTE,
        "compile_warm_start": warm_start,
        "workloads": workloads,
    }
    _emit(out)
    return 1 if failed else 0


def _write_bench_file(line: str) -> None:
    default = os.path.join(
        REPO, "BENCH.partial.json" if _PARTIAL_RUN else "BENCH.json"
    )
    path = os.environ.get("SINGA_TPU_BENCH_OUT", default)
    try:
        # tmp + atomic rename: a crash mid-dump (the warm-start probe
        # can hard-crash jaxlib in-process) must leave either the
        # previous complete record or the new one — never a torn,
        # unparseable BENCH.json that poisons trajectory tooling
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(line + "\n")
        os.replace(tmp, path)
    except OSError as e:
        print(f"bench: could not write {path}: {e}", file=sys.stderr)


def _emit(out: dict) -> None:
    """Write the lossless record, then end stdout with ONE compact
    machine-parseable JSON line.

    A tail capture of stdout is defeated by the ~5 KB lossless line —
    so the lossless object goes to BENCH.json (SINGA_TPU_BENCH_OUT to
    relocate) and is printed first for humans, and the LAST stdout line
    is a compact summary (headline + per-workload name/value/mfu +
    warm-start delta) sized to survive tail capture."""
    line = json.dumps(out)
    print(line)
    _write_bench_file(line)
    compact = {
        "metric": out["metric"],
        "value": out["value"],
        "unit": out["unit"],
        "vs_baseline": out["vs_baseline"],
        "workloads": [
            (
                {"name": w["name"], "error": w["error"]}
                if "error" in w
                else {
                    "name": w["name"],
                    "value": w.get("value"),
                    "unit": w.get("unit"),
                    "mfu": w.get("mfu"),
                }
            )
            for w in out.get("workloads", [])
        ],
    }
    ws = out.get("compile_warm_start")
    if ws is not None:
        compact["warm_start_saved_ms"] = ws.get("saved_ms")
    print(json.dumps(compact))


if __name__ == "__main__":
    sys.exit(main())
