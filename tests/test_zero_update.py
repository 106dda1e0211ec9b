"""ZeRO-style cross-replica update sharding (``zero_update``).

The mode's whole contract (PAPERS.md arxiv 2004.13336, ISSUE 7):
reduce-scatter grads over the data axis, run the optimizer on each
rank's shard only (slots LIVE sharded — per-device opt-state bytes
shrink by the data width), allgather fresh params — and NOTHING about
training is allowed to change: the loss trace is identical (tolerance
0) to the replicated update, the divergence guard's verdict (now
computed over sharded grads) fires on the same step, rollback restores
the sharded opt-state exactly, and sharded/npz checkpoints round-trip
the sharded slots.
"""

import os

import jax
import numpy as np
import pytest

from singa_tpu.config import parse_model_config
from singa_tpu.config.schema import ClusterConfig, ConfigError
from singa_tpu.data.loader import synthetic_arrays, write_records
from singa_tpu.parallel import build_mesh
from singa_tpu.resilience import FaultPlan, ResilienceContext, retention
from singa_tpu.resilience import supervisor
from singa_tpu.trainer import Trainer

MLP_CONF = """
name: "zero-mlp"
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


@pytest.fixture
def shard(tmp_path):
    path = str(tmp_path / "shard")
    write_records(path, *synthetic_arrays(96, seed=4))
    return path


def _cfg(shard, *, zero, train_steps=12, checkpoint_frequency=0,
         checkpoint_format="npz", extra=""):
    return parse_model_config(MLP_CONF.format(
        shard=shard, zero="true" if zero else "false",
        train_steps=train_steps, checkpoint_frequency=checkpoint_frequency,
        checkpoint_format=checkpoint_format, extra=extra,
    ))


def _mk(cfg, *, ndata=2, cl=None, seed=3, **kw):
    mesh = build_mesh(ndata, 1, jax.devices()[:ndata])
    kw.setdefault("prefetch", False)
    return Trainer(cfg, cl, mesh=mesh, seed=seed, log=lambda s: None, **kw)


def _loss_trace(t, nsteps):
    out = []
    for s in range(nsteps):
        t.perf.reset()
        t.train_one_batch(s)
        (m,) = t.perf.avg().values()
        out.append(float(m["loss"]))
    return out


def _state_arrays(t):
    return {
        (n, s): np.asarray(v)
        for n, slots in t.state.items()
        for s, v in slots.items()
    }


# ---------------------------------------------------------------------------
# layout
# ---------------------------------------------------------------------------


def test_zero_layout_adds_data_axis_and_composes_with_model(shard):
    """Every param's update sharding = forward sharding + the data axis
    on the first free evenly-divisible dim; kLayerPartition params keep
    their model axis and gain the data axis on dim 0."""
    from singa_tpu.graph.builder import build_net
    from singa_tpu.parallel.shardings import (
        param_shardings,
        zero_update_shardings,
    )

    cfg = _cfg(shard, zero=True)
    cfg.neuralnet.partition_type = "kLayerPartition"
    net = build_net(cfg, "kTrain")
    mesh = build_mesh(2, 2, jax.devices()[:4])
    net.bind_mesh(mesh)
    psh = param_shardings(mesh, net)
    zsh = zero_update_shardings(mesh, net, psh)
    # weights: dim 1 already model-sharded, dim 0 gains the data axis
    assert tuple(psh["fc1/weight"].spec) == (None, "model")
    assert tuple(zsh["fc1/weight"].spec) == ("data", "model")
    # biases are model-sharded on their only dim under kLayerPartition:
    # no free dim left -> the replicate fallback keeps the forward spec
    assert tuple(zsh["fc1/bias"].spec) == tuple(psh["fc1/bias"].spec)


def test_zero_layout_indivisible_dim_falls_back_with_warning(shard):
    """A param with no evenly divisible free dim keeps its forward
    sharding (the replicate fallback) and says so."""
    from singa_tpu.graph.builder import build_net
    from singa_tpu.parallel.shardings import (
        param_shardings,
        zero_update_shardings,
    )

    net = build_net(_cfg(shard, zero=True), "kTrain")
    mesh = build_mesh(8, 1, jax.devices()[:8])
    net.bind_mesh(mesh)
    psh = param_shardings(mesh, net)
    with pytest.warns(UserWarning, match="stays replicated"):
        zsh = zero_update_shardings(mesh, net, psh, warn=True)
    # (10,) head bias: 10 % 8 != 0 -> replicated update
    assert tuple(zsh["fc2/bias"].spec) == tuple(psh["fc2/bias"].spec)
    # (784, 32) weight: dim 0 shards over the 8-wide data axis
    assert tuple(zsh["fc1/weight"].spec) == ("data", None)


# ---------------------------------------------------------------------------
# the tentpole contract: loss-identical, opt bytes shrink
# ---------------------------------------------------------------------------


def test_zero_matches_replicated_update(shard):
    """The acceptance bar: zero vs replicated on the same data mesh is
    LOSS-IDENTICAL (tolerance 0) across the run, params agree to
    reduction-order ulps, and per-device opt-state bytes halve on the
    2-wide mesh (every param dim here divides evenly)."""
    tz = _mk(_cfg(shard, zero=True), device_cache=False)
    tr = _mk(_cfg(shard, zero=False), device_cache=False)
    assert tz.update_mode == "zero" and tr.update_mode == "replicated"
    lz, lr = _loss_trace(tz, 12), _loss_trace(tr, 12)
    assert lz == lr  # tolerance 0
    for name in tz.params:
        np.testing.assert_allclose(
            np.asarray(tz.params[name]), np.asarray(tr.params[name]),
            rtol=0, atol=1e-6, err_msg=name,
        )
    assert tz.opt_state_bytes_per_device() * 2 == (
        tr.opt_state_bytes_per_device()
    )
    # the slots really live in the update layout
    for n, slots in tz.state.items():
        for s, v in slots.items():
            assert v.sharding.is_equivalent_to(
                tz.state_sh[n][s], v.ndim
            ), (n, s)


def test_zero_chunked_matches_per_step(shard):
    """zero_update under the chunk engine (lax.scan, device-cached):
    the sharding constraints sit inside the scan body, and the chunked
    run matches the per-step zero run bitwise (within-mode XLA
    determinism, like the replicated chunk oracle in test_chunk)."""
    chunked = _mk(_cfg(shard, zero=True), device_cache=True)
    assert chunked._can_chunk()
    chunked.run()
    stepwise = _mk(_cfg(shard, zero=True), device_cache=False,
                   stream_chunks=False)
    assert not stepwise._can_chunk()
    stepwise.run()
    for name in chunked.params:
        np.testing.assert_array_equal(
            np.asarray(chunked.params[name]),
            np.asarray(stepwise.params[name]), err_msg=name,
        )
    for k, v in _state_arrays(chunked).items():
        np.testing.assert_array_equal(v, _state_arrays(stepwise)[k],
                                      err_msg=str(k))


def test_zero_stream_blocks_stage_data_sharded(shard):
    """The staged-block satellite: stream mode on a data mesh stages
    blocks to the data-axis batch shardings (each device holds only its
    slice) and stays bitwise-identical to the sync path."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    # inspect a LIVE staged block (a dedicated trainer, so the bitwise
    # run below keeps its unbroken window schedule): the arrays the put
    # closure committed must actually BE data-sharded on the device —
    # not merely intended to be by batch_sh
    probe = _mk(_cfg(shard, zero=True), device_cache=False, prefetch=True)
    assert probe.feeder_mode == "stream"
    block, _ = probe._chunk_stager().take(0, probe._chunk_len(0))
    for kind in ("image", "label"):
        sh = block["data"][kind].sharding
        assert isinstance(sh, NamedSharding)
        assert sh.spec == P("data"), (kind, sh.spec)
    probe._reset_feeders()

    stream = _mk(_cfg(shard, zero=True), device_cache=False, prefetch=True)
    assert stream.feeder_mode == "stream"
    stream.run()
    sync = _mk(_cfg(shard, zero=True), device_cache=False, prefetch=False)
    sync.run()
    for name in stream.params:
        np.testing.assert_array_equal(
            np.asarray(stream.params[name]),
            np.asarray(sync.params[name]), err_msg=name,
        )


# ---------------------------------------------------------------------------
# guard: verdict over sharded grads (satellite 3)
# ---------------------------------------------------------------------------


def _run_guarded(cfg, cl=None, faults="nanloss@5", **kw):
    ctx = ResilienceContext(
        cfg.resilience, FaultPlan.parse(faults), log=lambda s: None
    )
    t = _mk(cfg, cl=cl, device_cache=False, **kw)
    ctx.bind(t)
    try:
        t.run()
    finally:
        ctx.stop()
    return t, ctx


def test_zero_guard_skip_fires_same_step_as_replicated(shard):
    """nanloss@5 under kSkip: the verdict — now shard-local partial
    norms psum'd to one scalar — must fire on exactly the same step as
    the replicated update's global-norm verdict: same counters, same
    finite outcome."""
    extra = "resilience { max_restarts: 0 guard_policy: kSkip }"
    tz, _ = _run_guarded(
        _cfg(shard, zero=True, train_steps=10, extra=extra)
    )
    tr, _ = _run_guarded(
        _cfg(shard, zero=False, train_steps=10, extra=extra)
    )
    assert tz.guard_counters() == tr.guard_counters() == {
        "consecutive_bad": 0, "bad_steps": 1, "lr_scale": 1.0,
    }
    for name, v in tz.params.items():
        assert np.isfinite(np.asarray(v)).all(), name


def test_zero_guard_rollback_restores_sharded_opt_state(shard, tmp_path):
    """nanloss@6 under kRollback with sharded checkpoints: the guard
    rolls back to step_4 and the restored opt-state is EXACTLY the
    sharded slots the checkpoint holds — bit for bit, in the zero
    layout — and the run completes finite with the LR backoff."""
    extra = (
        "resilience { max_restarts: 0 backoff_base: 0 "
        "guard_policy: kRollback guard_rollback_after: 1 "
        "guard_lr_backoff: 0.5 }"
    )
    cfg = _cfg(shard, zero=True, train_steps=12, checkpoint_frequency=4,
               checkpoint_format="sharded", extra=extra)
    cl = ClusterConfig()
    cl.workspace = str(tmp_path / "ws")
    logs = []
    ctx = ResilienceContext(
        cfg.resilience, FaultPlan.parse("nanloss@6"), log=logs.append
    )
    t = _mk(cfg, cl=cl, device_cache=False)
    ctx.bind(t)
    try:
        t.run()
    finally:
        ctx.stop()
    assert ctx.rollbacks == 1
    assert any("rolling back" in l and "step_4" in l for l in logs)
    assert t.guard_counters()["lr_scale"] == 0.5
    for name, v in t.params.items():
        assert np.isfinite(np.asarray(v)).all(), name
    # replay: an identical zero run up to the SAME rollback point must
    # agree bitwise with the slots the rollback restored — prove it by
    # restoring the step_4 save into a fresh trainer and comparing the
    # layouts it places
    ck = os.path.join(str(tmp_path / "ws"), "checkpoints", "step_4.ckpt")
    assert retention.validate_checkpoint(ck)
    cfg2 = _cfg(shard, zero=True, train_steps=12,
                checkpoint_format="sharded", extra=extra)
    cfg2.checkpoint = ck
    t2 = _mk(cfg2, device_cache=False)
    assert t2.start_step == 4
    for n, slots in t2.state.items():
        for s, v in slots.items():
            assert v.sharding.is_equivalent_to(
                t2.state_sh[n][s], v.ndim
            ), (n, s)
    # and a direct mid-run rollback restores those exact arrays
    t3 = _mk(_cfg(shard, zero=True, train_steps=12,
                  checkpoint_format="sharded", extra=extra),
             device_cache=False)
    _loss_trace(t3, 8)
    assert t3.rollback_to(ck) == 4
    a, b = _state_arrays(t3), _state_arrays(t2)
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=str(k))


# ---------------------------------------------------------------------------
# checkpoints: sharded slots round-trip (npz + sharded)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", ["npz", "sharded"])
def test_zero_checkpoint_roundtrip(shard, tmp_path, fmt):
    """A zero run's checkpoint (either format) resumes into the zero
    layout with bitwise-equal params AND opt-state; the resumed run
    matches the uninterrupted zero run bitwise."""
    cl = ClusterConfig()
    cl.workspace = str(tmp_path / "ws")

    def run(steps, checkpoint=None):
        cfg = _cfg(shard, zero=True, train_steps=steps,
                   checkpoint_frequency=4, checkpoint_format=fmt)
        if checkpoint:
            cfg.checkpoint = checkpoint
        t = _mk(cfg, cl=cl, device_cache=False)
        t.run()
        return t

    full = run(12)
    ext = "ckpt" if fmt == "sharded" else "npz"
    resumed = run(
        12, checkpoint=os.path.join(
            str(tmp_path / "ws"), "checkpoints", f"step_8.{ext}"
        )
    )
    assert resumed.start_step == 8
    for name in full.params:
        np.testing.assert_array_equal(
            np.asarray(full.params[name]),
            np.asarray(resumed.params[name]), err_msg=name,
        )
    a, b = _state_arrays(full), _state_arrays(resumed)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=str(k))


# ---------------------------------------------------------------------------
# engines + knob surface
# ---------------------------------------------------------------------------


def test_zero_rejected_on_replica_engine(shard):
    from singa_tpu.trainer import ReplicaTrainer

    cfg = _cfg(shard, zero=True)
    cfg.updater.param_type = "Elastic"
    cfg.updater.moving_rate = 0.9
    with pytest.raises(ConfigError, match="zero_update"):
        ReplicaTrainer(cfg, None, mesh=build_mesh(2, 1),
                       seed=3, log=lambda s: None, prefetch=False)


def test_cd_zero_matches_replicated(tmp_path):
    """The CD engine rides the same seam: zero CD training on a data
    mesh is loss-identical to replicated CD and its slots live in the
    update layout."""
    from singa_tpu.trainer import CDTrainer

    shard = str(tmp_path / "shard")
    write_records(shard, *synthetic_arrays(64, seed=6))

    def conf(zero: bool) -> str:
        return f"""
name: "zero-rbm"
train_steps: 8
alg: kContrastiveDivergence
zero_update: {"true" if zero else "false"}
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
"""

    def mk(zero):
        cfg = parse_model_config(conf(zero))
        return CDTrainer(cfg, None, mesh=build_mesh(2, 1), seed=3,
                         log=lambda s: None, prefetch=False,
                         device_cache=False)

    tz, tr = mk(True), mk(False)
    assert tz.update_mode == "zero"
    lz = _loss_trace(tz, 8)
    lr = _loss_trace(tr, 8)
    assert lz == lr
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


def test_zero_supervised_resume(shard, tmp_path):
    """crash@7 under the supervisor with zero_update: auto-resume
    completes and matches the uninterrupted zero run bitwise."""
    def job(sub, faults=None):
        cfg = _cfg(
            shard, zero=True, train_steps=12, checkpoint_frequency=5,
            extra="resilience { max_restarts: 3 backoff_base: 0 }",
        )
        cl = ClusterConfig()
        cl.workspace = str(tmp_path / sub)
        logs = []
        rc = supervisor.run(cfg, cl, seed=3, faults=faults,
                            log=logs.append, prefetch=False)
        assert rc == 0
        ck = retention.resolve_latest(
            os.path.join(str(tmp_path / sub), "checkpoints")
        )
        from singa_tpu.trainer.checkpoint import load_checkpoint

        step, params, state, _ = load_checkpoint(ck)
        return step, params, logs

    step_a, params_a, _ = job("clean")
    step_b, params_b, logs = job("faulted", faults="crash@7")
    assert any("resumed from" in l and "step_5" in l for l in logs)
    assert step_a == step_b == 12
    for name in params_a:
        np.testing.assert_array_equal(
            params_a[name], params_b[name], err_msg=name
        )


def test_zero_knob_lint_did_you_mean(shard):
    """netlint's raw-config walk covers the new knob: a typo'd
    ``zero_updat`` gets CFG001 with the did-you-mean."""
    from singa_tpu.lint import Collector, lint_model_text

    text = MLP_CONF.format(
        shard=shard, zero="true", train_steps=4, checkpoint_frequency=0,
        checkpoint_format="npz", extra="",
    ).replace("zero_update: true", "zero_updat: true")
    col = Collector()
    lint_model_text(text, "job.conf", col)
    assert any(
        d.code == "CFG001" and "zero_update" in (d.fix_hint or "")
        for d in col.sorted()
    )
