"""BatchNorm/Add/GlobalPooling layers, buffer plumbing, and the ResNet
config generator."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.config import parse_model_config
from singa_tpu.data.loader import synthetic_arrays, write_records
from singa_tpu.graph.builder import build_net
from singa_tpu.models.resnet import resnet_conf
from singa_tpu.params import init_params
from singa_tpu.trainer import Trainer, load_checkpoint


# ---------------------------- BN layer numerics ----------------------------


def _bn_net(shard, batch=16, extra_bn=""):
    return parse_model_config(f"""
name: "bn-test"
train_steps: 8
updater {{ base_learning_rate: 0.1 param_type: "Param" }}
neuralnet {{
  layer {{ name: "data" type: "kShardData"
          data_param {{ path: "{shard}" batchsize: {batch} }} }}
  layer {{ name: "mnist" type: "kMnistImage" srclayers: "data"
          mnist_param {{ norm_a: 255 norm_b: 0 }} }}
  layer {{ name: "label" type: "kLabel" srclayers: "data" }}
  layer {{ name: "fc1" type: "kInnerProduct" srclayers: "mnist"
          inner_product_param {{ num_output: 32 }}
          param {{ name: "w" init_method: "kUniformSqrtFanIn" }}
          param {{ name: "b" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "bn" type: "kBatchNorm" srclayers: "fc1" {extra_bn}
          param {{ name: "gamma" init_method: "kConstant" value: 1 }}
          param {{ name: "beta" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "relu" type: "kReLU" srclayers: "bn" }}
  layer {{ name: "fc2" type: "kInnerProduct" srclayers: "relu"
          inner_product_param {{ num_output: 10 }}
          param {{ name: "w" init_method: "kUniformSqrtFanIn" }}
          param {{ name: "b" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "loss" type: "kSoftmaxLoss" srclayers: "fc2" srclayers: "label"
          softmaxloss_param {{ topk: 1 }} }}
}}
""")


@pytest.fixture
def shard(tmp_path):
    path = str(tmp_path / "shard")
    write_records(path, *synthetic_arrays(64, seed=4))
    return path


@pytest.mark.parametrize("shape", [(8, 16), (4, 8, 5, 5)])
def test_fused_bn_matches_naive_formula(shape):
    """ops.batch_norm_train (custom VJP, one-pass moments) must agree
    with the textbook two-pass formula in values AND grads."""
    from singa_tpu import ops

    key = jax.random.PRNGKey(0)
    kx, kg, kb, kd = jax.random.split(key, 4)
    c = shape[1]
    x = jax.random.normal(kx, shape, jnp.float32) * 3.0 + 1.0
    gamma = jax.random.normal(kg, (c,)) * 0.5 + 1.0
    beta = jax.random.normal(kb, (c,))
    dy = jax.random.normal(kd, shape)
    eps = 1e-5
    axes = (0,) if len(shape) == 2 else (0, 2, 3)
    bshape = (1, -1) if len(shape) == 2 else (1, -1, 1, 1)

    def naive(x, gamma, beta):
        mean = jnp.mean(x, axes)
        var = jnp.var(x, axes)
        inv = 1.0 / jnp.sqrt(var + eps)
        y = (x - mean.reshape(bshape)) * inv.reshape(bshape)
        return y * gamma.reshape(bshape) + beta.reshape(bshape), mean, var

    y_f, m_f, v_f = ops.batch_norm_train(x, gamma, beta, eps)
    y_n, m_n, v_n = naive(x, gamma, beta)
    np.testing.assert_allclose(y_f, y_n, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(m_f, m_n, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(v_f, v_n, rtol=1e-4, atol=1e-4)

    def loss_fused(x, gamma, beta):
        y, m, v = ops.batch_norm_train(x, gamma, beta, eps)
        # stats detached, like the layer's running-stat update
        return jnp.sum(y * dy) + 0.0 * jnp.sum(
            jax.lax.stop_gradient(m) + jax.lax.stop_gradient(v)
        )

    def loss_naive(x, gamma, beta):
        y, _, _ = naive(x, gamma, beta)
        return jnp.sum(y * dy)

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x, gamma, beta)
    gn = jax.grad(loss_naive, argnums=(0, 1, 2))(x, gamma, beta)
    for a, b in zip(gf, gn):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("shape", [(16, 8), (8, 4, 5, 5)])
def test_sampled_bn_semantics(shape):
    """batch_norm_train_sampled (the OPT-IN subsample-stats knob,
    r5): stats come from the first batch/stride rows, dx is
    straight-through gamma*inv*dy, and dgamma/dbeta stay exact for
    those stats."""
    from singa_tpu import ops

    key = jax.random.PRNGKey(3)
    kx, kg, kb, kd = jax.random.split(key, 4)
    c = shape[1]
    x = jax.random.normal(kx, shape, jnp.float32) * 2.0 + 0.5
    gamma = jax.random.normal(kg, (c,)) * 0.5 + 1.0
    beta = jax.random.normal(kb, (c,))
    dy = jax.random.normal(kd, shape)
    eps = 1e-5
    axes = (0,) if len(shape) == 2 else (0, 2, 3)
    bshape = (1, -1) if len(shape) == 2 else (1, -1, 1, 1)
    stride = 2

    y, mean, var = ops.batch_norm_train_sampled(
        x, gamma, beta, eps, stride
    )
    # PREFIX subsample: the op reads the first N/stride rows (a strided
    # slice lowers to a gather on TPU — measured 9 ms/step slower)
    xs = np.asarray(x)[: shape[0] // stride]
    np.testing.assert_allclose(
        mean, np.mean(xs, axis=tuple(axes)), rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(
        var, np.var(xs, axis=tuple(axes)), rtol=1e-4, atol=1e-4
    )
    # the FULL batch normalizes by the sampled stats
    inv = 1.0 / np.sqrt(np.asarray(var) + eps)
    want_y = (
        (np.asarray(x) - np.asarray(mean).reshape(bshape))
        * inv.reshape(bshape)
        * np.asarray(gamma).reshape(bshape)
        + np.asarray(beta).reshape(bshape)
    )
    np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)

    def loss(x, gamma, beta):
        y, m, v = ops.batch_norm_train_sampled(x, gamma, beta, eps, stride)
        return jnp.sum(y * dy)

    dx, dgamma, dbeta = jax.grad(loss, argnums=(0, 1, 2))(x, gamma, beta)
    # straight-through dx: gamma * inv * dy exactly (no reduction terms)
    want_dx = (
        np.asarray(dy)
        * (np.asarray(gamma) * inv).reshape(bshape)
    )
    np.testing.assert_allclose(dx, want_dx, rtol=1e-4, atol=1e-4)
    xhat = (np.asarray(x) - np.asarray(mean).reshape(bshape)) * inv.reshape(bshape)
    np.testing.assert_allclose(
        dbeta, np.sum(np.asarray(dy), axis=tuple(axes)), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        dgamma,
        np.sum(np.asarray(dy) * xhat, axis=tuple(axes)),
        rtol=1e-3, atol=1e-3,
    )
    # stride 1 forward == the exact op's forward
    y1, m1, v1 = ops.batch_norm_train_sampled(x, gamma, beta, eps, 1)
    ye, me, ve = ops.batch_norm_train(x, gamma, beta, eps)
    np.testing.assert_allclose(y1, ye, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(v1, ve, rtol=1e-5, atol=1e-5)


def test_bn_layer_stats_stride_knob_trains(shard):
    """The config knob reaches the layer: a kBatchNorm with
    stats_sample_stride 2 trains, moves its running stats, and the
    EVAL path (batch_norm_infer over running stats fed by sampled
    moments) produces finite metrics."""
    cfg = _bn_net(
        shard, extra_bn="batchnorm_param { stats_sample_stride: 2 }"
    )
    tr = Trainer(cfg, seed=0, log=lambda s: None, prefetch=False)
    tr.run()
    for name, buf in tr.buffers.items():
        arr = np.asarray(buf)
        assert np.isfinite(arr).all(), name
    moved = [
        np.abs(np.asarray(b) - b0).max()
        for (n, b), b0 in zip(
            sorted(tr.buffers.items()),
            [v for _, v in sorted(tr.train_net.init_buffers().items())],
        )
    ]
    assert max(moved) > 0
    # _bn_net has no test phase: drive the infer path directly
    rng = jax.random.fold_in(tr._step_key, 99)
    batch = tr._resolve_batch(
        tr.train_net, tr._next_batch(tr.train_net), constrain=False
    )
    loss, metrics = tr.train_net.forward(
        tr.params, batch, training=False, rng=rng, buffers=tr.buffers
    )
    assert np.isfinite(float(loss))


def test_bn_layer_stats_stride_rejects_tiny_subsample(shard):
    from singa_tpu.config.schema import ConfigError

    cfg = _bn_net(
        shard, extra_bn="batchnorm_param { stats_sample_stride: 16 }"
    )  # batch 16 -> 1 row of stats
    with pytest.raises(ConfigError, match="stats_sample_stride"):
        Trainer(cfg, seed=0, log=lambda s: None, prefetch=False)


@pytest.mark.parametrize("shape", [(64, 4), (16, 4, 6, 6)])
def test_fused_bn_one_pass_variance_is_anchored(shape):
    """A channel with |mean|/std ~ 1e5 cancels catastrophically in a raw
    one-pass E[x^2]-E[x]^2 (fp32 holds ~7 digits). Unanchored, the
    lax.cond rescue pass must recover the exact variance (the step-0 /
    cold-anchor path); with an explicit shift anchor the one-pass result
    is already exact."""
    from singa_tpu import ops

    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, shape, jnp.float32) * 1e-2 + 1e3
    c = shape[1]
    gamma = jnp.ones((c,))
    beta = jnp.zeros((c,))
    axes = (0,) if len(shape) == 2 else (0, 2, 3)
    true_var = jnp.var(x, axis=axes)

    _, _, var_default = ops.batch_norm_train(x, gamma, beta, 1e-5)
    np.testing.assert_allclose(var_default, true_var, rtol=1e-2)

    # explicit anchor path
    _, _, var_explicit = ops.batch_norm_train(
        x, gamma, beta, 1e-5, shift=jnp.full((c,), 1e3)
    )
    np.testing.assert_allclose(var_explicit, true_var, rtol=1e-2)


def test_fused_bn_mean_var_cotangents():
    """Differentiating through the mean/var outputs (no stop_gradient)
    must match autodiff of the naive formula — the VJP's dmean/dvar
    terms are real, not dropped."""
    from singa_tpu import ops

    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (32, 3), jnp.float32)
    gamma = jnp.ones((3,))
    beta = jnp.zeros((3,))

    def loss_fused(x):
        y, m, v = ops.batch_norm_train(x, gamma, beta, 1e-5)
        return jnp.sum(y**2) + jnp.sum(m * 3.0) + jnp.sum(v * 0.5)

    def loss_naive(x):
        m = jnp.mean(x, 0)
        v = jnp.var(x, 0)
        y = (x - m) / jnp.sqrt(v + 1e-5)
        return jnp.sum(y**2) + jnp.sum(m * 3.0) + jnp.sum(v * 0.5)

    np.testing.assert_allclose(
        jax.grad(loss_fused)(x), jax.grad(loss_naive)(x),
        rtol=1e-3, atol=1e-4,
    )


def test_bn_normalizes_batch(shard):
    """Training-mode BN output has ~zero mean / unit variance per feature."""
    net = build_net(_bn_net(shard), "kTrain")
    params = init_params(jax.random.PRNGKey(0), net.param_specs())
    (dl,) = net.datalayers
    batch = {"data": {"image": jnp.asarray(dl.images[:16]),
                      "label": jnp.asarray(dl.labels[:16])}}
    _, _, acts = net.forward(
        params, batch, training=True, rng=jax.random.PRNGKey(1),
        return_acts=True,
    )
    bn = np.asarray(acts["bn"])
    np.testing.assert_allclose(bn.mean(axis=0), 0.0, atol=1e-4)
    # the normalizer divides by sqrt(var + eps), so a channel whose
    # activation variance is within a couple orders of magnitude of
    # eps=1e-5 lands measurably BELOW unit std (var 2e-4 -> std 0.977
    # — exactly what this net's smallest fc1 channels produce; the old
    # flat `std == 1 +- 1e-2` assert flickered with jax/thread-count
    # reduction details shifting those tiny variances). Assert the
    # exact eps-aware expectation per channel, plus a loose sanity
    # band that the output is still ~unit scale.
    fc1 = np.asarray(acts["fc1"])
    want_std = fc1.std(axis=0) / np.sqrt(fc1.var(axis=0) + 1e-5)
    np.testing.assert_allclose(bn.std(axis=0), want_std, atol=1e-3)
    np.testing.assert_allclose(bn.std(axis=0), 1.0, atol=5e-2)


def test_bn_buffers_track_running_stats(shard):
    tr = Trainer(_bn_net(shard), seed=0, log=lambda s: None, prefetch=False)
    assert set(tr.buffers) == {"bn/running_mean", "bn/running_var"}
    m0 = np.asarray(tr.buffers["bn/running_mean"]).copy()
    assert np.all(m0 == 0.0)
    for step in range(6):
        tr.train_one_batch(step)
    m6 = np.asarray(tr.buffers["bn/running_mean"])
    v6 = np.asarray(tr.buffers["bn/running_var"])
    assert np.abs(m6).max() > 0  # stats moved
    assert np.all(v6 > 0)


def test_bn_eval_uses_running_stats(shard):
    tr = Trainer(_bn_net(shard), seed=0, log=lambda s: None, prefetch=False)
    for step in range(4):
        tr.train_one_batch(step)
    net = tr.train_net
    (dl,) = net.datalayers
    batch = {"data": {"image": jnp.asarray(dl.images[:16]),
                      "label": jnp.asarray(dl.labels[:16])}}
    # eval with trained running stats vs eval with init stats must differ
    _, _, a = net.forward(tr.params, batch, training=False,
                          buffers=tr.buffers, return_acts=True)
    _, _, b = net.forward(tr.params, batch, training=False,
                          return_acts=True)  # init buffers
    assert float(jnp.max(jnp.abs(a["bn"] - b["bn"]))) > 1e-3


def test_bn_chunk_equals_stepwise(shard):
    a = Trainer(_bn_net(shard), seed=3, log=lambda s: None, prefetch=False)
    b = Trainer(_bn_net(shard), seed=3, log=lambda s: None, prefetch=False)
    for step in range(6):
        a.train_one_batch(step)
    b.train_chunk(0, 6)
    for name in a.params:
        np.testing.assert_allclose(
            np.asarray(a.params[name]), np.asarray(b.params[name]),
            rtol=1e-5, atol=1e-6, err_msg=name,
        )
    for name in a.buffers:
        np.testing.assert_allclose(
            np.asarray(a.buffers[name]), np.asarray(b.buffers[name]),
            rtol=1e-5, atol=1e-6, err_msg=name,
        )


def test_bn_buffers_checkpoint_roundtrip(shard, tmp_path):
    from singa_tpu.config import parse_cluster_config

    cluster = parse_cluster_config(f'nworkers: 1 workspace: "{tmp_path}/ws"')
    tr = Trainer(_bn_net(shard), cluster, seed=0, log=lambda s: None,
                 prefetch=False)
    for step in range(5):
        tr.train_one_batch(step)
    path = tr.save(5)
    _, _, _, buffers = load_checkpoint(path)
    assert set(buffers) == {"bn/running_mean", "bn/running_var"}
    np.testing.assert_allclose(
        buffers["bn/running_mean"], np.asarray(tr.buffers["bn/running_mean"])
    )
    # resume: restored trainer carries the stats onward
    cfg2 = _bn_net(shard)
    cfg2.checkpoint = path
    tr2 = Trainer(cfg2, seed=0, log=lambda s: None, prefetch=False)
    np.testing.assert_allclose(
        np.asarray(tr2.buffers["bn/running_var"]),
        np.asarray(tr.buffers["bn/running_var"]),
    )


# (the former rejects-buffers test is gone: ReplicaTrainer supports
# stateful layers since the round-3 promotion — positively covered by
# test_consistency.py::TestReplicaProductionEngine)


# ---------------------------- resnet generator ----------------------------


def test_resnet50_conf_builds(tmp_path):
    """The generated ResNet-50 parses and shape-infers end to end."""
    shard = str(tmp_path / "shard")
    write_records(
        shard, *synthetic_arrays(8, classes=4, size=32, channels=3)
    )
    text = resnet_conf(
        depth=50, classes=4, batchsize=4, size=32,
        train_shard=shard, test_shard=shard,
    )
    cfg = parse_model_config(text)
    net = build_net(cfg, "kTrain")
    # 1 stem + 16 bottlenecks x 3 + 4 projections = 53 convs
    convs = [l for l in net.layers if l.TYPE == "kConvolution"]
    assert len(convs) == 53
    bns = [l for l in net.layers if l.TYPE == "kBatchNorm"]
    assert len(bns) == 53
    assert net.name2layer["gap"].out_shape == (4, 2048)
    assert net.name2layer["fc"].out_shape == (4, 4)
    assert len(net.buffer_specs()) == 106


@pytest.mark.parametrize("depth,nconv", [(18, 20), (34, 36)])
def test_resnet_basic_depths(tmp_path, depth, nconv):
    shard = str(tmp_path / "shard")
    write_records(
        shard, *synthetic_arrays(8, classes=4, size=32, channels=3)
    )
    text = resnet_conf(
        depth=depth, classes=4, batchsize=4, size=32,
        train_shard=shard, test_shard=shard,
    )
    net = build_net(parse_model_config(text), "kTrain")
    convs = [l for l in net.layers if l.TYPE == "kConvolution"]
    assert len(convs) == nconv


def test_small_resnet_trains(tmp_path):
    """A ResNet-18 at 32x32 learns synthetic RGB classes through the
    chunked engine (buffers in the scan carry)."""
    shard = str(tmp_path / "shard")
    write_records(
        shard, *synthetic_arrays(96, classes=4, size=32, channels=3, seed=1)
    )
    # batch 16 (r5, was 32): steps dominate at ~2.9 s/step on this
    # 1-core host; halving the batch reads 0.802 vs the 0.6 bar
    # (batch 32 read 0.849) — same oracle, smaller geometry
    text = resnet_conf(
        depth=18, classes=4, batchsize=16, size=32,
        train_shard=shard, test_shard=shard, train_steps=20,
        compute_dtype="",
    )
    cfg = parse_model_config(text)
    cfg.test_steps = 0
    cfg.display_frequency = 0
    cfg.checkpoint_frequency = 0
    # 1-device mesh: this test pins training/buffer mechanics, not
    # sharding (test_parallel covers that); 8 virtual devices on this
    # 1-core host only serialize the same math with 8x dispatch overhead
    from singa_tpu.parallel import build_mesh

    tr = Trainer(
        cfg, mesh=build_mesh(1, 1, jax.devices()[:1]),
        seed=0, log=lambda s: None, prefetch=False,
    )
    tr.train_chunk(0, 8)
    tr.perf.reset()
    tr.train_chunk(8, 12)
    (m,) = tr.perf.avg().values()
    # measured 0.849 at this geometry — same oracle, fewer steps
    assert m["precision"] > 0.6  # random = 0.25
