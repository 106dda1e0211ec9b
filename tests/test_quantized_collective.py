"""True int8-on-the-wire gradient collectives (ISSUE 13).

The ``kernels { grad_allreduce }`` contract: ``reference`` (or no
block) traces the IDENTICAL program PR 8's quantized path traces — the
knob is inert until selected; ``quantized_ring`` swaps the data-axis
reduction onto the explicit shard_map'd ring
(ops/quantized_collective.py) whose ppermute'd wire value is genuinely
int8 — asserted here at the jaxpr level, with the modeled per-device
wire bytes pinned against the bytes the traced program actually moves
and gated >= 3.5x under the reference fp32 collective. Composition
rides the PR 8 machinery: error-feedback residuals
checkpoint/resume bitwise, zero_update skips the allgather (the
scatter output IS the update layout), bucket chaining keeps its
barrier, NaN gradients poison the scale mid-ring so the guard fires on
the same step, and the CD/replica engines reject the knob loudly
(netlint KRN002 is the static mirror).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.config import parse_model_config
from singa_tpu.config.schema import ClusterConfig, ConfigError
from singa_tpu.data.loader import synthetic_arrays, write_records
from singa_tpu.ops.quantized_collective import (
    dequantize_int8,
    hier_ring_geometry,
    modeled_wire_bytes,
    modeled_wire_bytes_levels,
    ppermute_wire_bytes,
    ppermute_wire_bytes_levels,
    quant_acc,
    quantize_int8,
    reference_wire_bytes,
    ring_fusable,
    ring_reducible,
    symmetric_scale,
)
from singa_tpu.parallel import build_mesh
from singa_tpu.parallel.collectives import (
    GradCommSpec,
    is_residual_key,
    residual_key,
)
from singa_tpu.resilience import FaultPlan, ResilienceContext
from singa_tpu.trainer import Trainer

from test_grad_comm import MLP_CONF

Q8 = "grad_comm { mode: quantized dtype: int8 }"
RING = "kernels { grad_allreduce: quantized_ring }"
Q8_RING = Q8 + "\n" + RING
Q8B_RING = (
    "grad_comm { mode: quantized dtype: int8 buckets: 2 }\n" + RING
)


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


def _step_jaxpr(t):
    batch = t._assemble_host_batch(t.train_net)
    rng = jax.random.fold_in(t._step_key, 0)
    return jax.make_jaxpr(t._train_step_entry)(
        t.params, t.state, t.buffers, jnp.int32(0), batch, rng,
    )


def _wire_bytes(t) -> dict:
    """``t.wire_bytes_model()`` plus the same bytes re-counted from the
    step jaxpr's actual ppermute operand bytes x trip counts
    (``ring_jaxpr``; per level as ``ring_jaxpr_intra`` /
    ``ring_jaxpr_inter`` for a ``q8_hier`` trainer): the audit that the
    model prices what the program sends — counts, no clocks."""
    assert t._comm is not None and t._comm.ring
    out = t.wire_bytes_model()
    jaxpr = _step_jaxpr(t)
    if t._comm.hier and t._ring_hier is not None:
        intra_ax, inter_ax, k, _ = t._ring_hier
        levels = ppermute_wire_bytes_levels(
            jaxpr, intra_axis=intra_ax, inter_axis=inter_ax,
            intra_degree=k,
        )
        out["ring_jaxpr_intra"] = int(levels["intra"])
        out["ring_jaxpr_inter"] = int(levels["inter"])
        out["ring_jaxpr"] = int(levels["intra"] + levels["inter"])
    else:
        out["ring_jaxpr"] = int(ppermute_wire_bytes(jaxpr))
    return out


def _ppermute_dtypes(jaxpr):
    """Every dtype a ppermute anywhere in the program moves, with the
    operand's element count — the wire inventory."""
    from jax.extend import core as jcore

    out = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "ppermute":
                for v in eqn.invars:
                    out.append((str(v.aval.dtype), int(v.aval.size)))
            for val in eqn.params.values():
                vals = val if isinstance(val, (list, tuple)) else (val,)
                for v in vals:
                    if isinstance(v, jcore.ClosedJaxpr):
                        walk(v.jaxpr)
                    elif isinstance(v, jcore.Jaxpr):
                        walk(v)

    walk(jaxpr.jaxpr)
    return out


# ---------------------------------------------------------------------------
# shared quantize/dequantize helpers (the dedupe satellite's unit tests)
# ---------------------------------------------------------------------------


def test_symmetric_scale_maxabs_over_bucket():
    a = jnp.array([1.0, -3.0])
    b = jnp.array([[2.0, 0.5]])
    s = symmetric_scale([a, b])
    np.testing.assert_allclose(float(s), 3.0 / 127.0)
    # layout/order independent (max is exactly associative)
    assert float(symmetric_scale([b, a])) == float(s)


def test_symmetric_scale_zero_bucket_floored():
    s = symmetric_scale([jnp.zeros((4,))])
    assert float(s) > 0.0  # never a divide-by-zero downstream
    q = quantize_int8(jnp.zeros((4,)), s)
    np.testing.assert_array_equal(np.asarray(q), np.zeros((4,), np.int8))


def test_symmetric_scale_nan_poisons():
    """The guard contract: a NaN/Inf element drives the bucket scale to
    NaN, and dequantization propagates it — detection cannot be masked
    by the wire format."""
    s = symmetric_scale([jnp.array([1.0, float("nan")])])
    assert np.isnan(float(s))
    deq = dequantize_int8(jnp.array([1], np.int8), s)
    assert np.isnan(np.asarray(deq)).all()
    s_inf = symmetric_scale([jnp.array([1.0, float("inf")])])
    assert np.isinf(float(s_inf))


def test_quantize_roundtrip_within_scale():
    g = jnp.array([0.5, -1.0, 0.25, 1.0])
    s = symmetric_scale([g])
    back = dequantize_int8(quantize_int8(g, s), s)
    np.testing.assert_allclose(np.asarray(back), np.asarray(g),
                               atol=float(s) / 2 + 1e-9)
    # clipping: values at +-max land on +-127 exactly
    assert int(quantize_int8(g, s)[3]) == 127


def test_reference_path_uses_shared_helpers(shard):
    """The dedupe is real, not cosmetic: collectives._bucket_scale IS
    symmetric_scale (one formula for the oracle and the ring)."""
    from singa_tpu.parallel.collectives import _bucket_scale

    es = {"a": jnp.array([2.0, -4.0]), "b": jnp.array([1.0])}
    np.testing.assert_array_equal(
        np.asarray(_bucket_scale(es)),
        np.asarray(symmetric_scale(es.values())),
    )


# ---------------------------------------------------------------------------
# geometry predicates + the fused per-hop kernel
# ---------------------------------------------------------------------------


def test_ring_reducible_divisibility():
    ok = {"w": (8, 3), "b": (4,)}
    assert ring_reducible(ok, 4) is None
    assert ring_reducible(ok, 1) is None  # 1-wide axis: trivially fine
    bad = ring_reducible({"b": (10,)}, 4)
    assert bad is not None and "not divisible" in bad
    scalar = ring_reducible({"s": ()}, 2)
    assert scalar is not None and "scalar" in scalar
    # chunk_dims overrides: dim 1 divisible even though dim 0 is not
    assert ring_reducible({"w": (3, 8)}, 4, {"w": 1}) is None


def test_ring_fusable_tile_floor():
    # interpret mode tiles anything reducible
    assert ring_fusable({"w": (4, 3)}, 2, interpret=True) is None
    # compiled: per-shard chunk elements must align to the (8,128) tile
    good = {"w": (16, 512)}  # chunk = 8*512 = 4096 = 4 tiles
    assert ring_fusable(good, 2, interpret=False) is None
    bad = ring_fusable({"w": (4, 3)}, 2, interpret=False)
    assert bad is not None and "tile" in bad


def test_quant_acc_interpret_matches_jnp():
    """The fused per-hop kernel in interpret mode computes the same
    dequantize+accumulate it replaces (to 1 ulp: the interpreter may
    contract the multiply-add into an fma, a tolerance-level
    reassociation like the PR 9 cross-shape caveat)."""
    rng = np.random.default_rng(0)
    local = jnp.asarray(rng.normal(size=(8, 128)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=(8, 128)).astype(np.float32))
    s = symmetric_scale([g])
    q = quantize_int8(g, s)
    np.testing.assert_allclose(
        np.asarray(quant_acc(q, s, local, interpret=True)),
        np.asarray(dequantize_int8(q, s) + local),
        rtol=1e-5, atol=1e-6,
    )
    # non-lane-aligned sizes fall back to a single row
    local3 = jnp.asarray(rng.normal(size=(3,)).astype(np.float32))
    q3 = quantize_int8(local3, s)
    np.testing.assert_allclose(
        np.asarray(quant_acc(q3, s, local3, interpret=True)),
        np.asarray(dequantize_int8(q3, s) + local3),
        rtol=1e-5, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# spec + knob surface
# ---------------------------------------------------------------------------


def test_spec_ring_requires_quantized_block():
    from singa_tpu.config.schema import GradCommConfig, KernelsConfig

    kern = KernelsConfig()
    kern.grad_allreduce = "quantized_ring"
    with pytest.raises(ConfigError, match="quantized_ring"):
        GradCommSpec.from_config(None, kern)
    inert = GradCommConfig()  # mode exact
    with pytest.raises(ConfigError, match="quantized_ring"):
        GradCommSpec.from_config(inert, kern)
    gc = GradCommConfig()
    gc.mode = "quantized"
    spec = GradCommSpec.from_config(gc, kern)
    assert spec is not None and spec.ring and spec.interpret
    # reference knob (or no kernels block) leaves the spec untouched
    ref = GradCommSpec.from_config(gc, KernelsConfig())
    assert ref == GradCommSpec.from_config(gc, None)
    assert not ref.ring


def test_q8wire_cli_tag():
    """apply_grad_comm_tag's q8wire shorthand = q8 + the ring knob (the
    sweep/convergence/bench surface)."""
    from singa_tpu.config.schema import ModelConfig
    from singa_tpu.parallel import apply_grad_comm_tag

    cfg = apply_grad_comm_tag(ModelConfig(), "q8wire")
    assert cfg.grad_comm.mode == "quantized"
    assert cfg.grad_comm.dtype == "int8"
    assert cfg.kernels.grad_allreduce == "quantized_ring"
    plain = apply_grad_comm_tag(ModelConfig(), "q8")
    assert plain.kernels is None


# ---------------------------------------------------------------------------
# the acceptance bar: reference inert, ring wire genuinely int8
# ---------------------------------------------------------------------------


def test_reference_knob_is_jaxpr_inert(shard):
    """`grad_allreduce: reference` traces the CHARACTER-IDENTICAL
    program a q8 config with no kernels block traces — the pre-PR
    path is untouched until the ring is selected."""
    t_plain = _mk(_cfg(shard, extra=Q8))
    t_ref = _mk(_cfg(
        shard, extra=Q8 + "\nkernels { grad_allreduce: reference }"
    ))
    assert t_ref._comm is not None and not t_ref._comm.ring
    assert str(_step_jaxpr(t_plain)) == str(_step_jaxpr(t_ref))


def test_ring_wire_value_is_int8(shard):
    """THE tentpole assertion: every gradient chunk the ring ppermutes
    is int8 bytes — the only f32 riding the wire is the per-bucket
    scalar scale."""
    t = _mk(_cfg(shard, extra=Q8_RING))
    assert t._comm.ring and t.grad_wire_impl == "quantized_ring"
    wires = _ppermute_dtypes(_step_jaxpr(t))
    assert wires, "ring step traced no ppermutes"
    int8_elems = sum(n for d, n in wires if d == "int8")
    other = [(d, n) for d, n in wires if d != "int8"]
    assert int8_elems > 0
    # non-int8 wire operands are exactly the scalar scales
    assert all(d == "float32" and n == 1 for d, n in other), wires
    # and the reference program moves NO ppermutes at all (GSPMD psum)
    t_ref = _mk(_cfg(shard, extra=Q8))
    assert not _ppermute_dtypes(_step_jaxpr(t_ref))


def test_wire_bytes_model_matches_jaxpr_and_gates(shard):
    """The analytic ppermute-payload model equals the bytes the traced
    program actually moves (scan trip counts included), and the int8
    drop vs the reference fp32 collective is >= 3.5x (~3.9x
    modeled)."""
    t = _mk(_cfg(shard, extra=Q8B_RING))
    wire = _wire_bytes(t)
    assert wire["quantized_ring"] == wire["ring_jaxpr"] > 0
    assert wire["reference"] / wire["quantized_ring"] >= 3.5
    # the trainer-facing model agrees (what kernel_select reports)
    assert t.modeled_wire_bytes_per_step() == wire["quantized_ring"]
    # reference-mode trainer models the fp32 ring-allreduce equivalent
    t_ref = _mk(_cfg(shard, extra=Q8))
    sizes = {
        n: int(np.prod(s.shape, dtype=np.int64))
        for n, s in t_ref.specs.items()
    }
    assert t_ref.modeled_wire_bytes_per_step() == reference_wire_bytes(
        sizes, 2
    )
    # a nominal width the chunking can't divide (fc2 bias is (10,):
    # 10 % 8, 10 % 4) falls back to a validated width instead of
    # pricing floor-divided phantom geometry
    model = t.wire_bytes_model(ndata=8)
    assert model["ndata"] == 2
    assert model == t.wire_bytes_model()


def test_modeled_wire_bytes_formula():
    sizes = {"w": 1024, "b": 64}
    buckets = (("w",), ("b",))
    n = 4
    got = modeled_wire_bytes(sizes, buckets, n, dtype="int8")
    # per bucket: (n-1) * (chunk*1 + 4) for each of the two phases
    want = sum(
        2 * (n - 1) * (sizes[b[0]] // n + 4) for b in buckets
    )
    assert got == want
    # zero_update skips the allgather for scatter-layout params
    gather = {"w": False, "b": True}
    got_z = modeled_wire_bytes(sizes, buckets, n, dtype="int8",
                               gather=gather)
    assert got_z == want - (n - 1) * (sizes["w"] // n + 4)
    assert modeled_wire_bytes(sizes, buckets, 1) == 0


# ---------------------------------------------------------------------------
# numerics: the ring tracks the reference quantized path
# ---------------------------------------------------------------------------


def test_ring_tracks_reference_q8(shard):
    """q8 through the ring stays glued to q8 through the reference seam
    across a run: the per-hop re-quantization (the documented
    un-fed-back caveat) moves nothing beyond tolerance at this scale,
    and the residuals stay finite."""
    t_ref = _mk(_cfg(shard, extra=Q8))
    t_ring = _mk(_cfg(shard, extra=Q8_RING))
    lr, lg = _loss_trace(t_ref, 12), _loss_trace(t_ring, 12)
    assert lr[0] == pytest.approx(lg[0], abs=1e-5)
    for a, b in zip(lr, lg):
        assert abs(a - b) < 2e-2, (lr, lg)
    res = _residuals(t_ring)
    assert set(res) == {residual_key(n) for n in t_ring.params}
    for k, v in res.items():
        assert np.isfinite(v).all(), k


def test_ring_converges_end_to_end(shard):
    t_fp = _mk(_cfg(shard, train_steps=40))
    t_ring = _mk(_cfg(shard, extra=Q8_RING, train_steps=40))
    lf, lg = _loss_trace(t_fp, 40), _loss_trace(t_ring, 40)
    assert lf[0] - lf[-1] > 0.5  # fp32 actually converged
    assert abs(lf[-1] - lg[-1]) < 2e-2


def test_ring_bucketized_keeps_barrier_chain(shard):
    """Bucket chaining survives the seam swap: the bucketized ring
    traces its optimization_barrier (reverse-topo issue order) ON TOP
    of the one barrier per param that materializes every ring's reduced
    gradient, and stays glued to the unbucketized ring."""
    t_flat = _mk(_cfg(shard, extra=Q8_RING))
    t_b2 = _mk(_cfg(shard, extra=Q8B_RING))
    n_flat = str(_step_jaxpr(t_flat)).count("optimization_barrier")
    assert n_flat == len(t_flat.params)
    assert str(_step_jaxpr(t_b2)).count("optimization_barrier") > n_flat
    lf, lb = _loss_trace(t_flat, 8), _loss_trace(t_b2, 8)
    for a, b in zip(lf, lb):
        assert abs(a - b) < 2e-2, (lf, lb)


def test_ring_probe_reduces_correctly(shard):
    """The ring reduction in isolation (`_ring_reduce_probe`, the comm
    probe's seam): replicated input g on every shard -> the reduced
    value is g back within one quantization step, and the banked
    residual is EXACTLY the owner-side quantization error (acc - deq),
    which re-injection would cancel."""
    t = _mk(_cfg(shard, extra=Q8_RING))
    rng = np.random.default_rng(7)
    grads = {
        n: jnp.asarray(
            rng.normal(size=t.specs[n].shape).astype(np.float32) * 0.1
        )
        for n in t.params
    }
    res = {
        residual_key(n): jnp.zeros(t.specs[n].shape, jnp.float32)
        for n in t.params
    }
    out, new_res = t._ring_reduce_probe(grads, res)
    for n, g in grads.items():
        scale = np.abs(np.asarray(g)).max() / 127.0
        np.testing.assert_allclose(
            np.asarray(out[n]), np.asarray(g),
            atol=3.5 * scale + 1e-9, err_msg=n,
        )
        assert np.abs(np.asarray(new_res[residual_key(n)])).max() <= (
            np.abs(np.asarray(g)).max() / 127.0 + 1e-9
        ), n


def test_ring_chunk_dim_nonzero_with_error_feedback():
    """Regression: a param whose ring chunk dim is NOT 0 (zero_update
    picks the first data-divisible free dim) must add and bank its
    error-feedback residual in the residual's ORIGINAL dim order — the
    chunk-front accumulator layout differs, and a non-square chunk
    (here (4, 3)) crashes outright if either side forgets the
    moveaxis, while a square one would silently transpose."""
    from jax.sharding import PartitionSpec as P

    from singa_tpu.ops.quantized_collective import ring_reduce_gradients

    n = 2
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:n]), ("data",))
    rng = np.random.default_rng(3)
    g = jnp.asarray(rng.normal(size=(4, 6)).astype(np.float32))
    res0 = jnp.zeros((4, 6), jnp.float32)
    chunk_dims = {"w": 1}
    rkey = lambda nm: f"res/{nm}"  # noqa: E731

    def body(g, res):
        out, new_res = ring_reduce_gradients(
            {"w": g / n}, {"res/w": res}, (("w",),),
            axis_name="data", nshards=n, chunk_dims=chunk_dims,
            gather={"w": False}, dtype="int8",
            error_feedback=True, residual_key=rkey,
        )
        return out["w"], new_res["res/w"]

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, "data")),
        out_specs=(P(None, "data"), P(None, "data")),
        check_vma=False,
    )
    out, new_res = fn(g, res0)
    # per-shard chunk (4, 3), assembled back to the original (4, 6)
    assert out.shape == (4, 6) and new_res.shape == (4, 6)
    scale = float(np.abs(np.asarray(g)).max()) / 127.0
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(g), atol=3.5 * scale + 1e-9
    )
    # the banked residual is the owner-side quantization error in the
    # original orientation: re-adding it must cancel the rounding
    np.testing.assert_allclose(
        np.asarray(out) + np.asarray(new_res), np.asarray(g),
        atol=scale * 0.51 + 1e-9,
    )


# ---------------------------------------------------------------------------
# composition: zero_update, guard, checkpoints, engines
# ---------------------------------------------------------------------------


def test_ring_composes_with_zero_update(shard):
    """Under zero_update the ring's scatter output IS the update layout:
    the allgather phase never traces (fewer wire bytes, pinned against
    the jaxpr), and the run is LOSS-IDENTICAL to the ring over the
    replicated update — the same bar zero_update itself holds."""
    tz = _mk(_cfg(shard, extra=Q8_RING, zero=True))
    tr = _mk(_cfg(shard, extra=Q8_RING, zero=False))
    assert tz.update_mode == "zero" and tz._comm.ring
    assert any(not g for g in tz._ring_gather.values())
    wz, wr = _wire_bytes(tz), _wire_bytes(tr)
    assert wz["quantized_ring"] == wz["ring_jaxpr"]
    assert wz["quantized_ring"] < wr["quantized_ring"]
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


def test_guard_skip_fires_same_step_under_ring(shard):
    """nanloss@5 under kSkip: a NaN partial poisons its bucket's scale
    inside the ring (NaN survives every hop's dequantize+accumulate),
    so the guard verdict fires on the same step as fp32 and no NaN
    lands in params or residuals."""
    extra_fp = "resilience { max_restarts: 0 guard_policy: kSkip }"
    extra_ring = Q8_RING + "\n" + extra_fp

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

    tq, tf = run(extra_ring), run(extra_fp)
    assert tq.guard_counters() == tf.guard_counters() == {
        "consecutive_bad": 0, "bad_steps": 1, "lr_scale": 1.0,
    }
    for name, v in tq.params.items():
        assert np.isfinite(np.asarray(v)).all(), name
    for k, v in _residuals(tq).items():
        assert np.isfinite(v).all(), k


def test_guard_rollback_restores_ring_residuals(shard, tmp_path):
    """nanloss@6 under kRollback(after=1) on the ring step: the guard
    restores step_4 — including the chunk-sharded error-feedback
    residuals — backs the LR off, and the run completes finite."""
    logs = []
    cl = ClusterConfig()
    cl.workspace = str(tmp_path / "ws")
    cfg = _cfg(
        shard,
        extra=Q8_RING + "\nresilience { guard_policy: kRollback "
        "guard_rollback_after: 1 guard_lr_backoff: 0.5 }",
        train_steps=12, checkpoint_frequency=4,
    )
    ctx = ResilienceContext(
        cfg.resilience, FaultPlan.parse("nanloss@6"), log=logs.append
    )
    t = _mk(cfg, cl=cl)
    ctx.bind(t)
    try:
        t.run()
    finally:
        ctx.stop()
    assert any("rolling back" in l and "step_4" in l for l in logs), logs
    assert t.guard_counters()["lr_scale"] == 0.5
    for name, v in t.params.items():
        assert np.isfinite(np.asarray(v)).all(), name
    res = _residuals(t)
    assert res
    for k, v in res.items():
        assert np.isfinite(v).all(), k


@pytest.mark.parametrize("fmt", ["npz", "sharded"])
def test_ring_checkpoint_roundtrip_bitwise(shard, tmp_path, fmt):
    """The acceptance criterion: a ring run's error-feedback residuals
    (owner-chunk banked) checkpoint and the resumed run matches the
    uninterrupted one bitwise, both formats."""
    cl = ClusterConfig()
    cl.workspace = str(tmp_path / "ws")

    def run(steps, checkpoint=None):
        cfg = _cfg(shard, extra=Q8_RING, train_steps=steps,
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


def test_cd_engine_rejects_ring(tmp_path):
    from singa_tpu.trainer import CDTrainer

    shard = str(tmp_path / "shard")
    write_records(shard, *synthetic_arrays(64, seed=6))
    cfg = parse_model_config(f"""
name: "ring-rbm"
train_steps: 4
alg: kContrastiveDivergence
updater {{ base_learning_rate: 0.1 type: kSGD }}
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
{Q8_RING}
""")
    with pytest.raises(ConfigError, match="quantized_ring"):
        CDTrainer(cfg, None, mesh=build_mesh(2, 1), seed=3,
                  log=lambda s: None, prefetch=False, device_cache=False)


def test_ring_rejects_batch_stat_buffers(tmp_path):
    """A kBatchNorm net under the ring would silently lose its sync-BN
    semantics: the layer's global batch moments come from GSPMD's
    implicit psums (layers/norm.py), and inside the ring's per-shard
    shard_map the forward sees only its local shard — a biased
    variance, not the documented tolerance caveat. The trainer rejects
    the combination at construction (netlint KRN002 mirrors it)."""
    shard = str(tmp_path / "shard")
    write_records(shard, *synthetic_arrays(64, seed=6))
    cfg = parse_model_config(f"""
name: "ring-bn"
train_steps: 4
updater {{ base_learning_rate: 0.1 type: kSGD }}
neuralnet {{
  layer {{ name: "data" type: "kShardData"
    data_param {{ path: "{shard}" batchsize: 16 }} }}
  layer {{ name: "mnist" type: "kMnistImage" srclayers: "data"
    mnist_param {{ norm_a: 255 norm_b: 0 }} }}
  layer {{ name: "label" type: "kLabel" srclayers: "data" }}
  layer {{ name: "fc1" type: "kInnerProduct" srclayers: "mnist"
    inner_product_param {{ num_output: 32 }}
    param {{ name: "w" init_method: kUniform low: -0.05 high: 0.05 }}
    param {{ name: "b" init_method: kConstant value: 0 }} }}
  layer {{ name: "bn" type: "kBatchNorm" srclayers: "fc1"
    param {{ name: "gamma" init_method: kConstant value: 1 }}
    param {{ name: "beta" init_method: kConstant value: 0 }} }}
  layer {{ name: "relu" type: "kReLU" srclayers: "bn" }}
  layer {{ name: "fc2" type: "kInnerProduct" srclayers: "relu"
    inner_product_param {{ num_output: 10 }}
    param {{ name: "w" init_method: kUniform low: -0.05 high: 0.05 }}
    param {{ name: "b" init_method: kConstant value: 0 }} }}
  layer {{ name: "loss" type: "kSoftmaxLoss" srclayers: "fc2"
    srclayers: "label" softmaxloss_param {{ topk: 1 }} }}
}}
{Q8_RING}
""")
    with pytest.raises(ConfigError, match="batch-statistics buffers"):
        _mk(cfg)


def test_ring_rejects_model_axis_and_bad_geometry(shard):
    """Construction-time rejections the lint mirrors: a >1-wide
    non-data axis (hierarchical rings are a ROADMAP carry-over) and a
    data width the chunking can't divide both fail loudly."""
    cfg = _cfg(shard, extra=Q8_RING)
    mesh = build_mesh(2, 2, jax.devices()[:4])
    with pytest.raises(ConfigError, match="data axis only"):
        Trainer(cfg, None, mesh=mesh, seed=3, log=lambda s: None,
                prefetch=False, device_cache=False)
    # fc2 bias is (10,): a 4-wide axis cannot chunk it
    with pytest.raises(ConfigError, match="not divisible"):
        _mk(_cfg(shard, extra=Q8_RING), ndata=4)
    # interpret off additionally demands (8,128)-tileable chunks for
    # the compiled quant_acc kernel (the mlp's bias chunks are not)
    with pytest.raises(ConfigError, match="interpret off"):
        _mk(_cfg(
            shard,
            extra=Q8 + "\nkernels { grad_allreduce: quantized_ring "
            "interpret: false }",
        ))


# ---------------------------------------------------------------------------
# lint: KRN002 + schema did-you-mean
# ---------------------------------------------------------------------------


def _lint(text, code=None):
    from singa_tpu.lint import Collector, lint_model_text

    col = Collector()
    lint_model_text(text, "job.conf", col)
    return [d for d in col.sorted() if code is None or d.code == code]


def _base_conf(shard, extra):
    return MLP_CONF.format(
        shard=shard, zero="false", train_steps=4, checkpoint_frequency=0,
        checkpoint_format="npz", extra=extra,
    )


def test_kernels_grad_allreduce_did_you_mean(shard):
    """CFG001/CFG002 cover the new knob: a typo'd field name and a
    typo'd impl value both get did-you-means."""
    base = _base_conf(shard, Q8_RING)
    assert not _lint(base, "CFG001"), _lint(base)
    typo = base.replace("grad_allreduce:", "grad_allreducex:", 1)
    assert any(
        "grad_allreduce" in (d.fix_hint or "")
        for d in _lint(typo, "CFG001")
    ), _lint(typo)
    bad_enum = base.replace("quantized_ring", "quantized_rng", 1)
    assert any(
        "quantized_ring" in (d.fix_hint or "")
        for d in _lint(bad_enum, "CFG002")
    ), _lint(bad_enum)


def test_krn002_arms(shard):
    from singa_tpu.lint import Collector, ring_rules

    def diags(extra, cl=None, widths=None):
        cfg = _cfg(shard, extra=extra)
        col = Collector()
        ring_rules(cfg, cl, widths, "job.conf", col)
        return [d for d in col.sorted() if d.code == "KRN002"]

    # arm 1: ring without an active quantized grad_comm block
    assert diags(RING)
    assert diags("grad_comm { mode: exact }\n" + RING)
    assert not diags(Q8_RING)
    # arm 2: the replica (async PS) engine, threaded through --cluster
    async_cl = ClusterConfig()
    async_cl.workspace = "ws"
    async_cl.nservers = 1
    async_cl.synchronous = False
    assert diags(Q8_RING, cl=async_cl)
    sync_cl = ClusterConfig()
    sync_cl.workspace = "ws"
    sync_cl.synchronous = True
    assert not diags(Q8_RING, cl=sync_cl)
    # arm 3: the CD engine (CDTrainer rejects the ring's shard_map
    # shape at construction; the same conf lints instead of crashing)
    cd_cfg = _cfg(shard, extra=Q8_RING)
    cd_cfg.alg = "kContrastiveDivergence"
    col = Collector()
    ring_rules(cd_cfg, None, {"data": 2}, "job.conf", col)
    hits = [d for d in col.sorted() if d.code == "KRN002"]
    assert hits and "kContrastiveDivergence" in hits[0].msg
    # arm 4: a batch-stat (kBatchNorm) net — the static mirror of the
    # trainer's local-shard-BN rejection, naming the layer
    from singa_tpu.config.schema import LayerConfig

    bn_cfg = _cfg(shard, extra=Q8_RING)
    bn_cfg.neuralnet.layer.append(
        LayerConfig(name="bn", type="kBatchNorm")
    )
    col = Collector()
    ring_rules(bn_cfg, None, {"data": 2}, "job.conf", col)
    hits = [d for d in col.sorted() if d.code == "KRN002"]
    assert hits and "bn" in hits[0].msg and "BatchNorm" in hits[0].msg
    # arm 5: a >1-wide non-data mesh axis (the trainer's flat-ring
    # rejection; hierarchical rings are a ROADMAP carry-over)
    hits = diags(Q8_RING, widths={"data": 2, "model": 2})
    assert hits and "data axis only" in hits[0].msg
    assert not diags(Q8_RING, widths={"data": 2, "model": 1})
    # arm 6: a train batchsize the data axis can't divide (the conf's
    # batch is 32; a 3-wide axis also trips the chunk arm — both
    # report independently)
    hits = diags(Q8_RING, widths={"data": 3})
    assert any("batchsize 32" in d.msg for d in hits), hits
    assert not any(
        "batchsize" in d.msg for d in diags(Q8_RING, widths={"data": 2})
    )
    # arm 7: a data-axis width the bucket chunking can't divide (fc2's
    # bias is (10,): 10 % 4 != 0), reported with the width in the text
    hits = diags(Q8_RING, widths={"data": 4})
    assert hits and "not divisible" in hits[0].msg
    assert not diags(Q8_RING, widths={"data": 2})
    # reference impl never fires any arm
    assert not diags(Q8, widths={"data": 4})


def test_krn002_through_cli(shard, tmp_path, capsys):
    """The whole tool path (`netlint job.conf --cluster c.conf`): the
    ring-without-quantized-block arm reaches the CLI output, and a
    clean q8wire conf lints clean — the wiring, not just the rule."""
    from singa_tpu.tools import lint as lint_cli

    bad = tmp_path / "bad.conf"
    bad.write_text(_base_conf(shard, RING))
    cl = tmp_path / "cluster.conf"
    cl.write_text('workspace: "ws"\nnworkers: 2\n')
    rc = lint_cli.main([str(bad), "--cluster", str(cl)])
    out = capsys.readouterr().out
    assert rc == 1 and "KRN002" in out
    good = tmp_path / "good.conf"
    good.write_text(_base_conf(shard, Q8_RING))
    assert lint_cli.main([str(good), "--cluster", str(cl)]) == 0


# ---------------------------------------------------------------------------
# observability: kernel_select event + trace --summarize
# ---------------------------------------------------------------------------


def test_kernel_select_event_and_summarize(shard, tmp_path):
    """A ring run with telemetry records ONE train.grad_allreduce
    kernel_select event at run start, and trace.py --summarize reports
    grad_wire_impl + wire_bytes_per_step next to comm_ms_per_step; a
    reference-impl run reports its fp32 equivalent."""
    from singa_tpu.obs import FlightRecorder
    from singa_tpu.tools.trace import load_events, summarize

    def run(extra, tag):
        events = str(tmp_path / f"events_{tag}")
        rec = FlightRecorder(events, rank=0, run_id=tag)
        t = _mk(_cfg(shard, extra=extra, train_steps=6))
        t.attach_telemetry(rec)
        t.run()
        rec.close()
        records, skipped = load_events(events)
        assert skipped == 0
        return t, records

    t, records = run(Q8_RING, "ring")
    selects = [
        r for r in records
        if r.get("kind") == "kernel_select"
        and r["data"].get("site") == "train.grad_allreduce"
    ]
    assert len(selects) == 1
    assert selects[0]["data"]["impl"] == "quantized_ring"
    assert selects[0]["data"]["wire_dtype"] == "int8"
    assert selects[0]["data"]["wire_bytes_per_step"] == (
        t.modeled_wire_bytes_per_step()
    )
    report = summarize(records)
    assert report["grad_wire_impl"] == "quantized_ring"
    assert report["wire_bytes_per_step"] == t.modeled_wire_bytes_per_step()
    assert report["comm_ms_per_step"] is not None

    t2, records2 = run(Q8, "ref")
    report2 = summarize(records2)
    assert report2["grad_wire_impl"] == "reference"
    assert report2["wire_bytes_per_step"] == (
        t2.modeled_wire_bytes_per_step()
    ) > 0
    # no grad_comm machinery -> no event, None fields
    _, records3 = run("", "off")
    assert not [
        r for r in records3 if r.get("kind") == "kernel_select"
    ]
    assert summarize(records3)["grad_wire_impl"] is None


def test_ppermute_wire_bytes_counts_scans():
    """The jaxpr byte counter multiplies by scan trip counts — the ring
    hides its hops inside lax.scan."""

    def prog(x):
        def hop(c, _):
            return jax.lax.ppermute(c, "i", [(0, 1), (1, 0)]), None

        y, _ = jax.lax.scan(hop, x, jnp.arange(3))
        return y

    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("i",))
    fn = jax.shard_map(prog, mesh=mesh, in_specs=P("i"), out_specs=P("i"),
                       check_vma=False)
    jaxpr = jax.make_jaxpr(fn)(jnp.zeros((8, 4), jnp.int8))
    # per shard: (4, 4) int8 = 16 bytes x 3 trips
    assert ppermute_wire_bytes(jaxpr) == 48


# ---------------------------------------------------------------------------
# the hierarchical two-level ring (q8_hier): intra-slice x inter-slice
# ---------------------------------------------------------------------------

# fc2's 12-wide head keeps every param chunkable by a 4-wide reduction
# (the stock conf's (10,) bias is not — that indivisibility is itself a
# pinned rejection arm above)
MLP12_CONF = MLP_CONF.replace("num_output: 10", "num_output: 12")
Q8B = "grad_comm { mode: quantized dtype: int8 buckets: 2 }"
HIER = "kernels { grad_allreduce: q8_hier }\nring { intra_degree: 2 }"
Q8B_HIER = Q8B + "\n" + HIER
NAMED = (
    Q8B + "\nkernels { grad_allreduce: q8_hier }\n"
    'ring { intra_axis: "model" inter_axis: "data" }'
)


def _cfg12(shard, *, extra="", zero=False, train_steps=12,
           checkpoint_frequency=0, checkpoint_format="npz"):
    return parse_model_config(MLP12_CONF.format(
        shard=shard, zero="true" if zero else "false",
        train_steps=train_steps, checkpoint_frequency=checkpoint_frequency,
        checkpoint_format=checkpoint_format, extra=extra,
    ))


def test_hier_geometry_predicate():
    """The pure geometry gate, every arm: factored, named, degenerate,
    and each reason string the trainer/KRN002 surface."""
    # factored: intra_degree splits the data axis
    from singa_tpu.config.schema import RingConfig

    ring = RingConfig(intra_degree=2)
    assert hier_ring_geometry({"data": 4}, ring) == ("data", "data", 2, 2)
    assert hier_ring_geometry({"data": 8}, ring) == ("data", "data", 2, 4)
    # degenerate n<=1: accepted as the 1x1 no-hop ring (bench hosts)
    assert hier_ring_geometry({"data": 1}, ring) == ("data", "data", 1, 1)
    # named: two distinct mesh axes, inter-major
    named = RingConfig(intra_axis="model", inter_axis="data")
    assert hier_ring_geometry({"data": 2, "model": 2}, named) == (
        "model", "data", 2, 2
    )
    # reasons, not tuples
    assert "needs a ring {}" in hier_ring_geometry({"data": 4}, None)
    assert "does not divide" in hier_ring_geometry(
        {"data": 4}, RingConfig(intra_degree=3)
    )
    assert "factors the 'data' axis only" in hier_ring_geometry(
        {"data": 4, "model": 2}, ring
    )
    assert "mutually exclusive" in hier_ring_geometry(
        {"data": 4}, RingConfig(intra_degree=2, intra_axis="data",
                                inter_axis="data")
    )
    assert "BOTH axes" in hier_ring_geometry(
        {"data": 4}, RingConfig(intra_axis="data")
    )
    assert "same mesh axis" in hier_ring_geometry(
        {"data": 4}, RingConfig(intra_axis="data", inter_axis="data")
    )
    assert "names no mesh axis" in hier_ring_geometry(
        {"data": 2, "model": 2},
        RingConfig(intra_axis="modle", inter_axis="data"),
    )
    assert "not covered" in hier_ring_geometry(
        {"data": 2, "model": 2, "expert": 2},
        RingConfig(intra_axis="model", inter_axis="expert"),
    )
    assert "outside the" in hier_ring_geometry(
        {"data": 2, "model": 2, "expert": 2},
        RingConfig(intra_axis="model", inter_axis="data"),
    )


def test_q8hier_cli_tag():
    """apply_grad_comm_tag's q8hier shorthand = q8 + the hierarchical
    knob + a default factored ring { intra_degree: 2 } block."""
    from singa_tpu.config.schema import ModelConfig
    from singa_tpu.parallel import apply_grad_comm_tag

    cfg = apply_grad_comm_tag(ModelConfig(), "q8hier")
    assert cfg.grad_comm.mode == "quantized"
    assert cfg.grad_comm.dtype == "int8"
    assert cfg.kernels.grad_allreduce == "q8_hier"
    assert cfg.ring is not None and cfg.ring.intra_degree == 2
    with pytest.raises(ValueError, match="q8hier"):
        apply_grad_comm_tag(ModelConfig(), "q8_heir")


def test_hier_requires_quantized_block(shard):
    """Same seam as the flat ring: q8_hier without an active quantized
    grad_comm block is a construction-time ConfigError."""
    from singa_tpu.parallel.collectives import GradCommSpec

    with pytest.raises(ConfigError, match="q8_hier"):
        GradCommSpec.from_config(
            None, kernels=type("K", (), {"grad_allreduce": "q8_hier",
                                         "interpret": True})(),
        )


def test_hier_factored_matches_flat_ring_convergence(shard):
    """THE acceptance bar: the 2x2 factored hierarchical ring converges
    with the flat 4-wide q8 ring — per-step losses track within float
    noise (the intra level accumulates in f32, so the trajectories are
    close, not bitwise) and the runs end at the same loss."""
    th = _mk(_cfg12(shard, extra=Q8B_HIER), ndata=4)
    assert th._comm.hier and th.grad_wire_impl == "q8_hier"
    assert th._ring_hier == ("data", "data", 2, 2)
    tf = _mk(_cfg12(shard, extra=Q8B_RING), ndata=4)
    lh, lf = _loss_trace(th, 10), _loss_trace(tf, 10)
    assert all(np.isfinite(lh)), lh
    np.testing.assert_allclose(lh, lf, rtol=0, atol=5e-3)
    assert lh[-1] < lh[0] * 0.75  # it actually trains


def test_hier_named_axes_bitwise_matches_factored(shard):
    """The named form on a REAL 2x2 composed mesh (data=2 x model=2,
    the reduction riding both axes) produces the bitwise-identical
    trajectory the factored 4x1 form produces — the two spellings are
    the same algorithm over the same 4-wide reduction."""
    tn = _mk_named(_cfg12(shard, extra=NAMED))
    assert tn._ring_hier == ("model", "data", 2, 2)
    tfac = _mk(_cfg12(shard, extra=Q8B_HIER), ndata=4)
    ln, lfac = _loss_trace(tn, 6), _loss_trace(tfac, 6)
    assert ln == lfac, (ln, lfac)


def _mk_named(cfg, *, cl=None, seed=3, **kw):
    mesh = build_mesh(2, 2, jax.devices()[:4])
    kw.setdefault("prefetch", False)
    kw.setdefault("device_cache", False)
    return Trainer(cfg, cl, mesh=mesh, seed=seed, log=lambda s: None, **kw)


def test_hier_wire_bytes_per_level_parity_and_gate(shard):
    """Per level: the analytic intra/inter
    split equals the jaxpr-counted ppermute attribution EXACTLY (an
    inter level that shipped f32 chunks would count 4x the model and
    fail loudly), and the scarce-hop gate holds — inter bytes x
    intra_degree <= the flat same-n ring's bytes (K(M-1) <= KM-1,
    exact integers)."""
    t = _mk(_cfg12(shard, extra=Q8B_HIER), ndata=4)
    wire = _wire_bytes(t)
    assert wire["intra"] == wire["ring_jaxpr_intra"] > 0
    assert wire["inter"] == wire["ring_jaxpr_inter"] > 0
    assert wire["ring_jaxpr"] == wire["quantized_ring"] == (
        wire["intra"] + wire["inter"]
    )
    assert wire["intra_degree"] == 2
    assert wire["inter"] * 2 <= wire["flat_ring"]
    # the wire inventory is int8 + f32 only (chunks, planes, scales)
    wires = _ppermute_dtypes(_step_jaxpr(t))
    assert {d for d, _ in wires} == {"int8", "float32"}
    # trainer-facing total (what kernel_select reports) is the hier sum
    assert t.modeled_wire_bytes_per_step() == wire["quantized_ring"]


def test_modeled_wire_bytes_levels_formula():
    sizes = {"w": 1024, "b": 64}
    buckets = (("w",), ("b",))
    n, K = 4, 2
    M = n // K
    got = modeled_wire_bytes_levels(sizes, buckets, n, intra_degree=K)
    intra = inter = 0
    for (nm,) in buckets:
        chunk = sizes[nm] // n
        intra += (K - 1) * M * chunk * 4  # f32 reduce planes
        intra += (K - 1) * (M * chunk * 1 + M * 4)  # int8 gather planes
        inter += (M - 1) * (chunk * 1 + 4) * 2  # reduce + gather hops
    assert got == {"intra": intra, "inter": inter,
                   "total": intra + inter}
    # zero_update's gather map skips the allgather phases per param
    gz = modeled_wire_bytes_levels(
        sizes, buckets, n, intra_degree=K,
        gather={"w": False, "b": True},
    )
    wchunk = sizes["w"] // n
    assert gz["intra"] == intra - (K - 1) * (M * wchunk + M * 4)
    assert gz["inter"] == inter - (M - 1) * (wchunk + 4)
    # the scarce-hop identity vs the flat ring, same sizes/buckets
    flat = modeled_wire_bytes(sizes, buckets, n, dtype="int8")
    assert got["inter"] * K <= flat
    # degenerate + indivisible arms
    assert modeled_wire_bytes_levels(
        sizes, buckets, 1, intra_degree=2
    ) == {"intra": 0, "inter": 0, "total": 0}
    with pytest.raises(ValueError, match="does not divide"):
        modeled_wire_bytes_levels(sizes, buckets, 4, intra_degree=3)


def test_ppermute_levels_rejects_flat_ring_perm(shard):
    """Feeding a FLAT ring's program to the per-level classifier raises
    (a 4-wide flat perm matches neither level's structure) —
    misattribution is loud, never silent. (A 2-wide flat ring IS a
    valid 2x1 intra ring, so the flat trainer runs at ndata=4.)"""
    t = _mk(_cfg12(shard, extra=Q8B_RING), ndata=4)
    with pytest.raises(ValueError, match="neither ring level"):
        ppermute_wire_bytes_levels(_step_jaxpr(t), intra_degree=2)


def test_hier_zero_update_composes(shard):
    """zero_update + the factored hierarchical ring: the chunk layout
    IS the update layout (same n-way chunking as the flat ring), the
    run trains, and the allgather skip shows in the per-level model."""
    t = _mk(_cfg12(shard, extra=Q8B_HIER, zero=True), ndata=4)
    assert t._comm.hier and t._zero_sh is not None
    losses = _loss_trace(t, 8)
    assert np.isfinite(losses[-1]) and losses[-1] < losses[0]
    full = _mk(_cfg12(shard, extra=Q8B_HIER), ndata=4)
    zm, fm = t.wire_bytes_model(), full.wire_bytes_model()
    assert zm["inter"] < fm["inter"] and zm["intra"] < fm["intra"]


@pytest.mark.parametrize("fmt", ["npz", "sharded"])
def test_hier_checkpoint_roundtrip_bitwise(shard, tmp_path, fmt):
    """Error-feedback residuals under the hierarchical ring keep the
    flat ring's chunk-sharded geometry, so a mid-run checkpoint resumes
    bitwise — both formats, on the 2x2 factored mesh."""
    cl = ClusterConfig()
    cl.workspace = str(tmp_path / "ws")

    def run(steps, checkpoint=None):
        cfg = _cfg12(
            shard,
            extra=Q8B_HIER.replace("buckets: 2",
                                   "buckets: 2 error_feedback: true"),
            train_steps=steps, checkpoint_frequency=4,
            checkpoint_format=fmt,
        )
        if checkpoint:
            cfg.checkpoint = checkpoint
        t = _mk(cfg, ndata=4, cl=cl)
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


def test_hier_trainer_rejections(shard):
    """Construction-time rejections KRN002 mirrors: broken geometry
    carries the predicate's reason; the named form refuses
    zero_update; the flat ring still rejects composed meshes with its
    pinned message."""
    with pytest.raises(ConfigError, match="does not divide"):
        _mk(_cfg12(shard, extra=Q8B_HIER.replace(
            "intra_degree: 2", "intra_degree: 3")), ndata=4)
    with pytest.raises(ConfigError, match="does not compose with "
                                          "zero_update"):
        _mk_named(_cfg12(shard, extra=NAMED, zero=True))
    # the un-factorable stock conf: fc2's (10,) bias can't chunk by 4
    with pytest.raises(ConfigError, match="not divisible"):
        _mk(_cfg(shard, extra=Q8B_HIER), ndata=4)
    mesh = build_mesh(2, 2, jax.devices()[:4])
    with pytest.raises(ConfigError, match="data axis only"):
        Trainer(_cfg(shard, extra=Q8_RING), None, mesh=mesh, seed=3,
                log=lambda s: None, prefetch=False, device_cache=False)


def test_krn002_hier_arms(shard):
    """The static mirror of every hierarchical rejection, with
    did-you-means for near-miss axis names — threaded like the flat
    arms (ring_rules directly; the CLI threading test rides
    --cluster)."""
    from singa_tpu.lint import Collector, ring_rules

    def diags(extra, widths=None, conf=None, zero=False):
        cfg = (_cfg12 if conf is None else conf)(shard, extra=extra)
        if zero:
            cfg.zero_update = True
        col = Collector()
        ring_rules(cfg, None, widths, "job.conf", col)
        return [d for d in col.sorted() if d.code == "KRN002"]

    q8h = Q8B + "\nkernels { grad_allreduce: q8_hier }\n"
    # clean factored conf on a 4-wide axis: silent
    assert not diags(Q8B_HIER, {"data": 4})
    # >1-wide non-data axis is ACCEPTED when the named form covers it
    # (the flat ring's pinned arm-5 rejection, relaxed under q8_hier)
    assert not diags(
        q8h + 'ring { intra_axis: "model" inter_axis: "data" }',
        {"data": 2, "model": 2},
    )
    # no ring block
    hits = diags(q8h, {"data": 4})
    assert hits and "needs a ring {}" in hits[0].msg
    # absent axis name -> did-you-mean ERROR arm
    hits = diags(
        q8h + 'ring { intra_axis: "modle" inter_axis: "data" }',
        {"data": 2, "model": 2},
    )
    assert hits and "names no mesh axis" in hits[0].msg
    assert "did you mean intra_axis: model?" in (hits[0].fix_hint or "")
    # indivisible intra_degree
    hits = diags(q8h + "ring { intra_degree: 3 }", {"data": 4})
    assert hits and "does not divide" in hits[0].msg
    # factored form leaves a >1-wide axis uncovered
    hits = diags(Q8B_HIER, {"data": 4, "model": 2})
    assert hits and "factors the 'data' axis only" in hits[0].msg
    # named + zero_update
    hits = diags(
        q8h + 'ring { intra_axis: "model" inter_axis: "data" }',
        {"data": 2, "model": 2}, zero=True,
    )
    assert hits and "zero_update" in hits[0].msg
    # widths unknown (no --cluster): form-only pass stays silent on a
    # well-formed block, loud on a malformed one
    assert not diags(Q8B_HIER, None)
    assert diags(q8h + 'ring { intra_axis: "x" }', None)
    # batch arm prices the EFFECTIVE reduction width (2x2 named = 4)
    hits = diags(
        q8h + 'ring { intra_axis: "model" inter_axis: "data" }',
        {"data": 3, "model": 2},
    )
    assert hits, "3x2 reduction cannot divide batchsize 32"


def test_krn002_hier_through_cli(shard, tmp_path, capsys):
    """The whole tool path for a hierarchical conf: --cluster supplies
    the widths, the indivisible-degree arm reaches the CLI output, and
    the clean q8_hier conf lints clean."""
    from singa_tpu.tools import lint as lint_cli

    base = MLP12_CONF.format(
        shard=shard, zero="false", train_steps=4, checkpoint_frequency=0,
        checkpoint_format="npz",
        extra=Q8B + "\nkernels { grad_allreduce: q8_hier }\n"
        "ring { intra_degree: 3 }",
    )
    bad = tmp_path / "bad.conf"
    bad.write_text(base)
    cl = tmp_path / "cluster.conf"
    cl.write_text('workspace: "ws"\nnworkers: 4\n')
    rc = lint_cli.main([str(bad), "--cluster", str(cl)])
    out = capsys.readouterr().out
    assert rc == 1 and "KRN002" in out and "does not divide" in out
    good = tmp_path / "good.conf"
    good.write_text(base.replace("intra_degree: 3", "intra_degree: 2"))
    assert lint_cli.main([str(good), "--cluster", str(cl)]) == 0
