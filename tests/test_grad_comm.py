"""Quantized + overlapped gradient collectives (``grad_comm``).

The block's whole contract (PAPERS.md arxiv 2506.17615, ISSUE 8):
``mode: exact`` (or no block) traces the IDENTICAL program today's main
traces — bitwise, at the jaxpr level; ``mode: quantized`` casts each
bucket's gradients to a scaled int8/bf16 wire value around the
data-axis reduction (composing with ``zero_update``'s reduce-scatter
layout) with persistent error-feedback residuals in the buffer pytree,
so convergence matches fp32; ``buckets: N`` chains reverse-topo
reduction groups without changing any value; and the guard, the chunk
engine, checkpoints, and the CD engine all ride the same seam.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.config import parse_model_config
from singa_tpu.config.schema import ClusterConfig, ConfigError
from singa_tpu.data.loader import synthetic_arrays, write_records
from singa_tpu.parallel import build_mesh
from singa_tpu.parallel.collectives import (
    GradCommSpec,
    is_residual_key,
    residual_key,
    reverse_topo_buckets,
)
from singa_tpu.resilience import FaultPlan, ResilienceContext
from singa_tpu.trainer import Trainer

MLP_CONF = """
name: "gc-mlp"
train_steps: {train_steps}
checkpoint_frequency: {checkpoint_frequency}
checkpoint_format: "{checkpoint_format}"
zero_update: {zero}
updater {{
  base_learning_rate: 0.05
  learning_rate_change_method: kFixed
  momentum: 0.9
  type: kSGD
}}
neuralnet {{
  layer {{ name: "data" type: "kShardData"
    data_param {{ path: "{shard}" batchsize: 32 }} }}
  layer {{ name: "mnist" type: "kMnistImage" srclayers: "data"
    mnist_param {{ norm_a: 127.5 norm_b: 1 }} }}
  layer {{ name: "label" type: "kLabel" srclayers: "data" }}
  layer {{ name: "fc1" type: "kInnerProduct" srclayers: "mnist"
    inner_product_param {{ num_output: 32 }}
    param {{ name: "weight" init_method: kUniform low: -0.05 high: 0.05 }}
    param {{ name: "bias" init_method: kConstant value: 0 }} }}
  layer {{ name: "tanh1" type: "kTanh" srclayers: "fc1" }}
  layer {{ name: "fc2" type: "kInnerProduct" srclayers: "tanh1"
    inner_product_param {{ num_output: 10 }}
    param {{ name: "weight" init_method: kUniform low: -0.05 high: 0.05 }}
    param {{ name: "bias" init_method: kConstant value: 0 }} }}
  layer {{ name: "loss" type: "kSoftmaxLoss" srclayers: "fc2"
    srclayers: "label" softmaxloss_param {{ topk: 1 }} }}
}}
{extra}
"""

Q8 = "grad_comm { mode: quantized dtype: int8 }"
Q8_BUCKETS = "grad_comm { mode: quantized dtype: int8 buckets: 2 }"


@pytest.fixture
def shard(tmp_path):
    path = str(tmp_path / "shard")
    write_records(path, *synthetic_arrays(96, seed=4))
    return path


def _cfg(shard, *, extra="", zero=False, train_steps=12,
         checkpoint_frequency=0, checkpoint_format="npz"):
    return parse_model_config(MLP_CONF.format(
        shard=shard, zero="true" if zero else "false",
        train_steps=train_steps, checkpoint_frequency=checkpoint_frequency,
        checkpoint_format=checkpoint_format, extra=extra,
    ))


def _mk(cfg, *, ndata=2, cl=None, seed=3, **kw):
    mesh = build_mesh(ndata, 1, jax.devices()[:ndata])
    kw.setdefault("prefetch", False)
    kw.setdefault("device_cache", False)
    return Trainer(cfg, cl, mesh=mesh, seed=seed, log=lambda s: None, **kw)


def _loss_trace(t, nsteps):
    out = []
    for s in range(nsteps):
        t.perf.reset()
        t.train_one_batch(s)
        (m,) = t.perf.avg().values()
        out.append(float(m["loss"]))
    return out


def _residuals(t):
    return {
        k: np.asarray(v) for k, v in t.buffers.items() if is_residual_key(k)
    }


def _jaxpr(t):
    """Trace the full jitted step entry on a real batch (the trace-level
    exactness oracle: two trainers whose jaxprs match run the same
    program)."""
    batch = t._assemble_host_batch(t.train_net)
    rng = jax.random.fold_in(t._step_key, 0)
    return str(jax.make_jaxpr(t._train_step_entry)(
        t.params, t.state, t.buffers, jnp.int32(0), batch, rng,
    ))


# ---------------------------------------------------------------------------
# exact mode: bitwise-identical to pre-grad_comm main
# ---------------------------------------------------------------------------


def test_exact_mode_traces_bitwise_identical(shard):
    """The acceptance bar: ``grad_comm { mode: exact }`` is structurally
    inert — the step's jaxpr is CHARACTER-IDENTICAL to a config with no
    block, no residual buffers exist, and a run matches bitwise."""
    t_none = _mk(_cfg(shard))
    t_exact = _mk(_cfg(shard, extra="grad_comm { mode: exact }"))
    assert t_exact._comm is None  # the spec is inert, not merely similar
    assert not _residuals(t_exact)
    assert _jaxpr(t_none) == _jaxpr(t_exact)
    assert _loss_trace(t_none, 8) == _loss_trace(t_exact, 8)
    for name in t_none.params:
        np.testing.assert_array_equal(
            np.asarray(t_none.params[name]),
            np.asarray(t_exact.params[name]), err_msg=name,
        )


def test_spec_inert_and_active_forms():
    from singa_tpu.config.schema import GradCommConfig

    assert GradCommSpec.from_config(None) is None
    assert GradCommSpec.from_config(GradCommConfig()) is None
    gc = GradCommConfig()
    gc.mode = "quantized"
    spec = GradCommSpec.from_config(gc)
    assert spec is not None and spec.quantized and spec.wants_residuals
    gc2 = GradCommConfig()
    gc2.buckets = 3
    spec2 = GradCommSpec.from_config(gc2)
    assert spec2 is not None and spec2.overlapped and not spec2.quantized


def test_overlap_buckets_leave_values_bitwise(shard):
    """``buckets: N`` with mode exact only chains the reductions in
    reverse-topo order (optimization_barrier is a value identity): the
    reduced GRADIENTS stay bitwise-identical to the unbucketized run.

    The trajectory after the optimizer update is pinned to 2 ulps of
    each tensor's magnitude, not bitwise: the update ``0.9*h + lr*g``
    holds two multiplies feeding one add, XLA:CPU lets LLVM contract
    one of them into an fma, and which one depends on the emission
    order inside the fused update — which the barrier shifts (the
    gradient's transpose lands before the lr multiply instead of after
    it). Same all-reduce, same operands, one rounding moved."""
    t_none = _mk(_cfg(shard))
    t_ovl = _mk(_cfg(shard, extra="grad_comm { mode: exact buckets: 3 }"))
    assert t_ovl._comm is not None and t_ovl._comm.overlapped
    ln, lo = _loss_trace(t_none, 10), _loss_trace(t_ovl, 10)
    eps = float(np.finfo(np.float32).eps)
    for a, b in zip(ln, lo):
        assert abs(a - b) <= 2 * eps * abs(a), (ln, lo)
    for name in t_none.params:
        want = np.asarray(t_none.params[name])
        np.testing.assert_allclose(
            np.asarray(t_ovl.params[name]), want, rtol=0,
            atol=2 * eps * float(np.abs(want).max()), err_msg=name,
        )
    # the value identity itself, bitwise: one step whose "update" hands
    # the reduced gradients straight back
    g_none = _mk(_cfg(shard))
    g_ovl = _mk(_cfg(shard, extra="grad_comm { mode: exact buckets: 3 }"))
    for t in (g_none, g_ovl):
        t._apply_update = lambda step, params, grads, state: (grads, state)
        t.train_one_batch(0)
    for name in g_none.params:
        np.testing.assert_array_equal(
            np.asarray(g_none.params[name]),
            np.asarray(g_ovl.params[name]), err_msg=name,
        )


# ---------------------------------------------------------------------------
# quantized mode: error feedback + convergence
# ---------------------------------------------------------------------------


def test_quantized_int8_tracks_fp32_with_error_feedback(shard):
    """q8 with error feedback stays glued to the fp32 trajectory across
    a whole run (per-step loss within 5e-3; the residuals carry the
    compression error forward and stay finite)."""
    t_fp = _mk(_cfg(shard))
    t_q8 = _mk(_cfg(shard, extra=Q8))
    lf, lq = _loss_trace(t_fp, 12), _loss_trace(t_q8, 12)
    assert lf[0] == lq[0]  # step 0 quantizes but starts identical params
    for a, b in zip(lf, lq):
        assert abs(a - b) < 5e-3, (lf, lq)
    res = _residuals(t_q8)
    assert set(res) == {residual_key(n) for n in t_q8.params}
    for k, v in res.items():
        assert np.isfinite(v).all(), k
    assert any(np.abs(v).max() > 0 for v in res.values())


def test_quantized_bf16_tracks_fp32(shard):
    t_fp = _mk(_cfg(shard))
    t_bf = _mk(_cfg(shard, extra="grad_comm { mode: quantized dtype: bf16 }"))
    lf, lb = _loss_trace(t_fp, 12), _loss_trace(t_bf, 12)
    for a, b in zip(lf, lb):
        assert abs(a - b) < 5e-3, (lf, lb)
    # bf16's residual is the truncation error: tiny relative to grads
    for k, v in _residuals(t_bf).items():
        assert np.isfinite(v).all(), k


def test_error_feedback_converges_end_to_end(shard):
    """The convergence claim in miniature (CI's full gate runs
    tools/convergence.py --grad_comm q8 on the mlp workload): after a
    full 40-step run the q8 loss has moved well off its start and lands
    within 1e-2 of fp32 — compression error is re-injected, not
    accumulated."""
    t_fp = _mk(_cfg(shard, train_steps=40))
    t_q8 = _mk(_cfg(shard, extra=Q8, train_steps=40))
    lf, lq = _loss_trace(t_fp, 40), _loss_trace(t_q8, 40)
    assert lf[0] - lf[-1] > 0.5  # training actually converged
    assert abs(lf[-1] - lq[-1]) < 1e-2


def test_quantized_without_error_feedback_carries_no_residuals(shard):
    t = _mk(_cfg(
        shard,
        extra="grad_comm { mode: quantized dtype: int8 "
              "error_feedback: false }",
    ))
    _loss_trace(t, 6)
    assert not _residuals(t)
    for name, v in t.params.items():
        assert np.isfinite(np.asarray(v)).all(), name


# ---------------------------------------------------------------------------
# composition: zero_update, chunk engine, guard, CD
# ---------------------------------------------------------------------------


def test_quantized_composes_with_zero_update(shard):
    """q8 over the ZeRO update layout (the quantized wire tensor is what
    the reduce-scatter constraint pins) is LOSS-IDENTICAL (tolerance 0)
    to q8 over the replicated update — the same bar zero_update itself
    holds — and the slots still live sharded."""
    tz = _mk(_cfg(shard, extra=Q8_BUCKETS, zero=True))
    tr = _mk(_cfg(shard, extra=Q8_BUCKETS, zero=False))
    assert tz.update_mode == "zero" and tz.comm_mode == "quantized"
    assert _loss_trace(tz, 12) == _loss_trace(tr, 12)
    for name in tz.params:
        np.testing.assert_allclose(
            np.asarray(tz.params[name]), np.asarray(tr.params[name]),
            rtol=0, atol=1e-6, err_msg=name,
        )
    for n, slots in tz.state.items():
        for s, v in slots.items():
            assert v.sharding.is_equivalent_to(
                tz.state_sh[n][s], v.ndim
            ), (n, s)


def test_quantized_chunked_matches_per_step(shard):
    """q8 under the chunk engine (lax.scan, device-cached): the
    residuals thread the scan carry with the other buffers, and the
    chunked run matches the per-step q8 run bitwise."""
    chunked = _mk(_cfg(shard, extra=Q8), device_cache=True)
    assert chunked._can_chunk()
    chunked.run()
    stepwise = _mk(_cfg(shard, extra=Q8), device_cache=False,
                   stream_chunks=False)
    assert not stepwise._can_chunk()
    stepwise.run()
    for name in chunked.params:
        np.testing.assert_array_equal(
            np.asarray(chunked.params[name]),
            np.asarray(stepwise.params[name]), err_msg=name,
        )
    a, b = _residuals(chunked), _residuals(stepwise)
    assert set(a) == set(b) and a
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_guard_skip_fires_same_step_as_fp32(shard):
    """nanloss@5 under kSkip: a NaN gradient poisons its bucket's scale
    and survives dequantization, so the guard's verdict over the
    DEQUANTIZED grads fires on exactly the same step with the same
    counters — and a skipped step keeps the old residuals (no NaN ever
    lands in the error-feedback state)."""
    extra_fp = "resilience { max_restarts: 0 guard_policy: kSkip }"
    extra_q8 = Q8 + "\n" + extra_fp

    def run(extra):
        cfg = _cfg(shard, extra=extra, train_steps=10)
        ctx = ResilienceContext(
            cfg.resilience, FaultPlan.parse("nanloss@5"), log=lambda s: None
        )
        t = _mk(cfg)
        ctx.bind(t)
        try:
            t.run()
        finally:
            ctx.stop()
        return t

    tq, tf = run(extra_q8), run(extra_fp)
    assert tq.guard_counters() == tf.guard_counters() == {
        "consecutive_bad": 0, "bad_steps": 1, "lr_scale": 1.0,
    }
    for name, v in tq.params.items():
        assert np.isfinite(np.asarray(v)).all(), name
    for k, v in _residuals(tq).items():
        assert np.isfinite(v).all(), k


def test_cd_engine_rides_the_same_seam(tmp_path):
    """The CD engine's greedy layerwise grads quantize through the same
    _reduce_grads seam: q8 CD training stays glued to fp32 CD and the
    RBM params carry residuals."""
    from singa_tpu.trainer import CDTrainer

    shard = str(tmp_path / "shard")
    write_records(shard, *synthetic_arrays(64, seed=6))

    def conf(extra: str) -> str:
        return f"""
name: "gc-rbm"
train_steps: 8
alg: kContrastiveDivergence
updater {{ base_learning_rate: 0.1 momentum: 0.8 type: kSGD }}
neuralnet {{
  layer {{ name: "data" type: "kShardData"
    data_param {{ path: "{shard}" batchsize: 32 }} }}
  layer {{ name: "mnist" type: "kMnistImage" srclayers: "data"
    mnist_param {{ norm_a: 255 norm_b: 0 }} }}
  layer {{ name: "rbm1" type: "kRBM" srclayers: "mnist"
    rbm_param {{ num_hidden: 16 cd_k: 1 }}
    param {{ name: "weight" init_method: kGaussain mean: 0 std: 0.1 }}
    param {{ name: "vbias" init_method: kConstant value: 0 }}
    param {{ name: "hbias" init_method: kConstant value: 0 }} }}
}}
{extra}
"""

    def mk(extra):
        cfg = parse_model_config(conf(extra))
        return CDTrainer(cfg, None, mesh=build_mesh(2, 1), seed=3,
                         log=lambda s: None, prefetch=False,
                         device_cache=False)

    tq, tf = mk(Q8), mk("")
    lq, lf = _loss_trace(tq, 8), _loss_trace(tf, 8)
    for a, b in zip(lq, lf):
        assert abs(a - b) < 5e-2, (lq, lf)
    res = _residuals(tq)
    assert any(k.endswith("rbm1/weight") for k in res)
    for k, v in res.items():
        assert np.isfinite(v).all(), k


# ---------------------------------------------------------------------------
# checkpoints: residuals persist
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["npz", "sharded"])
def test_checkpoint_roundtrip_carries_residuals(shard, tmp_path, fmt):
    """A q8 run's checkpoint (either format) carries the error-feedback
    residuals; the resumed run matches the uninterrupted q8 run bitwise
    — compression error survives a restart instead of silently
    resetting."""
    cl = ClusterConfig()
    cl.workspace = str(tmp_path / "ws")

    def run(steps, checkpoint=None):
        cfg = _cfg(shard, extra=Q8, train_steps=steps,
                   checkpoint_frequency=4, checkpoint_format=fmt)
        if checkpoint:
            cfg.checkpoint = checkpoint
        t = _mk(cfg, cl=cl)
        t.run()
        return t

    full = run(12)
    ext = "ckpt" if fmt == "sharded" else "npz"
    ck = os.path.join(str(tmp_path / "ws"), "checkpoints", f"step_8.{ext}")
    resumed = run(12, checkpoint=ck)
    assert resumed.start_step == 8
    for name in full.params:
        np.testing.assert_array_equal(
            np.asarray(full.params[name]),
            np.asarray(resumed.params[name]), err_msg=name,
        )
    a, b = _residuals(full), _residuals(resumed)
    assert set(a) == set(b) and a
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


# ---------------------------------------------------------------------------
# engines + knob surface + lint
# ---------------------------------------------------------------------------


def test_replica_engine_rejects_grad_comm(shard):
    from singa_tpu.trainer import ReplicaTrainer

    cfg = _cfg(shard, extra=Q8)
    cfg.updater.param_type = "Elastic"
    cfg.updater.moving_rate = 0.9
    with pytest.raises(ConfigError, match="grad_comm"):
        ReplicaTrainer(cfg, None, mesh=build_mesh(2, 1),
                       seed=3, log=lambda s: None, prefetch=False)


def test_knob_lint_did_you_mean(shard):
    """netlint's raw-config walk covers the block: each of the four
    knobs typo'd gets CFG001 with the did-you-mean, and a typo'd block
    name points at grad_comm."""
    from singa_tpu.lint import Collector, lint_model_text

    base = MLP_CONF.format(
        shard=shard, zero="false", train_steps=4, checkpoint_frequency=0,
        checkpoint_format="npz",
        extra="grad_comm { mode: quantized dtype: int8 "
              "error_feedback: true buckets: 2 }",
    )
    for typo, want in [
        ("mode:", "mode"),
        ("dtype:", "dtype"),
        ("error_feedback:", "error_feedback"),
        ("buckets:", "buckets"),
        ("grad_comm {", "grad_comm"),
    ]:
        text = base.replace(typo, typo[:-2] + "x" + typo[-2:], 1)
        col = Collector()
        lint_model_text(text, "job.conf", col)
        assert any(
            d.code == "CFG001" and want in (d.fix_hint or "")
            for d in col.sorted()
        ), (typo, [str(d) for d in col.sorted()])


def test_lint_engine_rule_rejects_replica_combo(shard):
    """CMM001: an active grad_comm block with an async nservers>0
    cluster (the replica engine) is a lint ERROR — the static mirror of
    the constructor rejection; a synchronous cluster is fine."""
    from singa_tpu.lint import Collector, engine_rules

    cfg = _cfg(shard, extra=Q8)
    async_cl = ClusterConfig()
    async_cl.workspace = "ws"
    async_cl.nservers = 1
    async_cl.synchronous = False
    col = Collector()
    engine_rules(cfg, async_cl, "job.conf", col)
    assert any(d.code == "CMM001" for d in col.sorted())

    sync_cl = ClusterConfig()
    sync_cl.workspace = "ws"
    sync_cl.synchronous = True
    col2 = Collector()
    engine_rules(cfg, sync_cl, "job.conf", col2)
    assert not col2.sorted()
    # an inert block never trips the rule
    col3 = Collector()
    engine_rules(
        _cfg(shard, extra="grad_comm { mode: exact }"), async_cl,
        "job.conf", col3,
    )
    assert not col3.sorted()


def test_reverse_topo_bucket_partition(shard):
    """Buckets come out in reverse topological order (fc2 before fc1 —
    the order backward produces the grads), cover every name exactly
    once, and balance by element count."""
    t = _mk(_cfg(shard, extra=Q8))
    names = frozenset(t.params)
    buckets = reverse_topo_buckets(t.train_net, names, 2, t.specs)
    flat = [n for b in buckets for n in b]
    assert sorted(flat) == sorted(names) and len(flat) == len(set(flat))
    assert len(buckets) == 2
    assert flat.index("fc2/weight") < flat.index("fc1/weight")
    # per-param granularity when unbucketized
    singles = reverse_topo_buckets(t.train_net, names, 0, t.specs)
    assert all(len(b) == 1 for b in singles)
    assert [b[0] for b in singles] == flat or len(singles) == len(flat)


def test_ordering_chain_only_when_bucketized(shard):
    """The documented contract: buckets <= 1 (per-param granularity)
    traces NO optimization_barrier — the scheduler stays free — while
    buckets: N > 1 chains the N groups (N-1 barriers)."""
    t_flat = _mk(_cfg(shard, extra=Q8))
    t_b2 = _mk(_cfg(shard, extra=Q8_BUCKETS))
    assert _jaxpr(t_flat).count("optimization_barrier") == 0
    assert _jaxpr(t_b2).count("optimization_barrier") == 1


# ---------------------------------------------------------------------------
# probes + telemetry
# ---------------------------------------------------------------------------


def test_comm_probe_records_span_and_summarize(shard, tmp_path):
    """The flight-recorder satellite: a grad_comm run with telemetry
    attached records ONE comm calibration span + comm_probe event at
    run start, and tools/trace.py --summarize reports the comm share
    next to input/ckpt."""
    from singa_tpu.obs import FlightRecorder
    from singa_tpu.tools.trace import load_events, summarize

    events = str(tmp_path / "events")
    rec = FlightRecorder(events, rank=0, run_id="t")
    t = _mk(_cfg(shard, extra=Q8, train_steps=6))
    t.attach_telemetry(rec)
    t.run()
    rec.close()
    records, skipped = load_events(events)
    assert skipped == 0
    comm_spans = [
        r for r in records
        if r.get("kind") == "span" and r.get("name") == "comm"
    ]
    assert len(comm_spans) == 1 and comm_spans[0]["steps"] > 0
    probes = [r for r in records if r.get("kind") == "comm_probe"]
    assert len(probes) == 1
    assert probes[0]["data"]["mode"] == "quantized"
    assert probes[0]["data"]["dtype"] == "int8"
    assert probes[0]["data"]["comm_ms"] >= 0.0
    report = summarize(records)
    assert report["comm_ms_per_step"] is not None
    assert report["stall_shares"]["comm"] >= 0.0
    # a run with no grad_comm block records no comm span and reports
    # a zero share
    events2 = str(tmp_path / "events2")
    rec2 = FlightRecorder(events2, rank=0, run_id="t2")
    t2 = _mk(_cfg(shard, train_steps=6))
    t2.attach_telemetry(rec2)
    t2.run()
    rec2.close()
    records2, _ = load_events(events2)
    assert not [
        r for r in records2
        if r.get("kind") == "span" and r.get("name") == "comm"
    ]
    report2 = summarize(records2)
    assert report2["stall_shares"]["comm"] == 0.0
    assert report2["comm_ms_per_step"] is None
