"""Operator tooling tests: graph dot export, log plotting, record
partitioning (script/load_data.py semantics), hostfile bootstrap."""

import json
import os

import pytest

from singa_tpu.parallel.launch import (
    coordinator_address,
    init_distributed,
    read_hostfile,
)
from singa_tpu.tools.draw import parse_log
from singa_tpu.tools.graph import net_json_to_dot
from singa_tpu.tools.partition import partition_records


# ---------------------------- graph ----------------------------


def test_net_json_to_dot():
    doc = {
        "phase": "kTrain",
        "nodes": [
            {"id": "data", "type": "kShardData", "shape": [32, 28, 28]},
            {"id": "fc", "type": "kInnerProduct", "shape": [32, 10]},
            {"id": "loss", "type": "kSoftmaxLoss", "shape": []},
        ],
        "links": [
            {"source": "data", "target": "fc"},
            {"source": "fc", "target": "loss"},
        ],
    }
    dot = net_json_to_dot(doc)
    assert dot.startswith("digraph net {")
    assert '"data" -> "fc";' in dot
    assert '"fc" -> "loss";' in dot
    assert "cylinder" in dot  # data layer shape
    assert "doubleoctagon" in dot  # loss layer shape


def test_graph_cli_end_to_end(tmp_path):
    """Dump a real net and render it."""
    from singa_tpu.config import load_model_config
    from singa_tpu.data.loader import synthetic_arrays, write_records
    from singa_tpu.graph.builder import build_net
    from singa_tpu.tools.graph import main as graph_main
    from singa_tpu.utils import dump_net_json

    shard = str(tmp_path / "shard")
    write_records(shard, *synthetic_arrays(64, seed=0))
    cfg = load_model_config("examples/mnist/mlp.conf")
    for layer in cfg.neuralnet.layer:
        if layer.type == "kShardData":
            layer.data_param.path = shard
            layer.data_param.batchsize = 16
    net = build_net(cfg, "kTrain")
    path = dump_net_json(net, str(tmp_path))
    out = str(tmp_path / "net.dot")
    assert graph_main(["--input", path, "--output", out]) == 0
    dot = open(out).read()
    assert dot.count("->") == sum(len(l.srclayers) for l in net.layers)


# ---------------------------- draw ----------------------------


LOG = """\
step 0: train loss : 2.30, precision : 0.10 [data 1ms/it]
step 10: train loss : 1.50, precision : 0.55 [data 1ms/it]
step 10: test loss : 1.60, precision : 0.50
step 20: train loss : 0.90, precision : 0.80 [data 1ms/it]
"""


def test_parse_log():
    curves = parse_log(LOG)
    assert curves["loss"]["train"] == [(0, 2.30), (10, 1.50), (20, 0.90)]
    assert curves["loss"]["test"] == [(10, 1.60)]
    assert curves["precision"]["train"][-1] == (20, 0.80)


def test_draw_writes_png(tmp_path):
    from singa_tpu.tools.draw import draw

    out = str(tmp_path / "curves.png")
    draw(parse_log(LOG), out)
    assert os.path.getsize(out) > 1000
    assert open(out, "rb").read(8)[1:4] == b"PNG"


# ---------------------------- partition ----------------------------


def test_partition_split():
    recs = list(range(12))
    shares = partition_records(recs, nworkers=4, group_size=2)
    # 2 groups x 6 records, split 3/3 inside each group
    assert shares == [[0, 1, 2], [3, 4, 5], [6, 7, 8], [9, 10, 11]]


def test_partition_replicate():
    recs = list(range(8))
    shares = partition_records(recs, nworkers=4, group_size=2, replicate=True)
    assert shares == [[0, 1, 2, 3], [0, 1, 2, 3], [4, 5, 6, 7], [4, 5, 6, 7]]


def test_partition_truncates_like_reference():
    # 10 records over 3 groups -> 3 per group, remainder dropped
    shares = partition_records(list(range(10)), nworkers=3, group_size=1)
    assert [len(s) for s in shares] == [3, 3, 3]


def test_partition_bad_geometry():
    with pytest.raises(ValueError):
        partition_records([1], nworkers=3, group_size=2)


def test_partition_cli_shard(tmp_path):
    from singa_tpu.data.loader import synthetic_arrays, write_records
    from singa_tpu.data.pipeline import load_shard_arrays
    from singa_tpu.tools.partition import main as part_main

    shard = str(tmp_path / "shard")
    write_records(shard, *synthetic_arrays(16, seed=0))
    prefix = str(tmp_path / "part")
    assert part_main([
        "--input", shard, "--output-prefix", prefix, "--nworkers", "2",
    ]) == 0
    a, _ = load_shard_arrays(f"{prefix}-w0")
    b, _ = load_shard_arrays(f"{prefix}-w1")
    assert len(a) == len(b) == 8


# ---------------------------- launch ----------------------------


def test_read_hostfile(tmp_path):
    p = tmp_path / "hosts"
    p.write_text("# cluster\nnode-a\n\nnode-b:1234  # head\nnode-c\n")
    assert read_hostfile(str(p)) == ["node-a", "node-b:1234", "node-c"]


def test_coordinator_address():
    assert coordinator_address(["h1", "h2"]) == "h1:9999"
    assert coordinator_address(["h1:42"]) == "h1:42"
    with pytest.raises(ValueError):
        coordinator_address([])


def test_init_distributed_single_host_noop(tmp_path, monkeypatch):
    import jax

    def never(*a, **k):
        raise AssertionError("one host must not go looking for a rendezvous")

    monkeypatch.setattr(jax.distributed, "initialize", never)
    for var in ("COORDINATOR_ADDRESS", "MEGASCALE_COORDINATOR_ADDRESS",
                "TPU_WORKER_HOSTNAMES"):
        monkeypatch.delenv(var, raising=False)
    # no hostfile, no pod env -> no-op
    assert init_distributed(0, None) is False
    # a single-host TPU VM names itself alone: still one process
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    assert init_distributed(0, None) is False
    # one-line hostfile -> still single process
    p = tmp_path / "hosts"
    p.write_text("localhost\n")
    assert init_distributed(0, str(p)) is False


def test_init_distributed_failed_pod_rendezvous_raises(monkeypatch):
    """A pod-shaped environment whose rendezvous fails must not carry
    on as N independent same-seed trainers."""
    import jax

    def refuse(*a, **k):
        raise RuntimeError("coordinator unreachable")

    monkeypatch.setattr(jax.distributed, "initialize", refuse)
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "host-a,host-b")
    with pytest.raises(RuntimeError, match="coordinator unreachable"):
        init_distributed(0, None)


def test_local_gangs_are_a_cpu_rehearsal(monkeypatch):
    """One process per chip: the launchers refuse several LOCAL ranks
    unless the platform is pinned to the CPU."""
    from singa_tpu.parallel.launch import refuse_local_ranks_on_a_chip

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    refuse_local_ranks_on_a_chip(4)
    monkeypatch.delenv("JAX_PLATFORMS")
    refuse_local_ranks_on_a_chip(1)
    with pytest.raises(SystemExit, match="one process per chip"):
        refuse_local_ranks_on_a_chip(2)


def test_compile_cache_is_placed_from_outside(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: no directory is set in code.
    Unset: one fixed directory inside the checkout."""
    import jax

    from singa_tpu.utils import compile_cache

    updates = {}
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: updates.__setitem__(k, v)
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.setup_compile_cache(
        log=lambda s: None
    ) == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in updates
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.setup_compile_cache(log=lambda s: None)
    assert updates["jax_compilation_cache_dir"] == path
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(repo, ".compile_cache")


def test_init_distributed_bad_rank(tmp_path):
    p = tmp_path / "hosts"
    p.write_text("a\nb\n")
    with pytest.raises(ValueError):
        init_distributed(5, str(p))


# ---------------------------- sweep ----------------------------


def test_sweep_two_points(tmp_path):
    """Real subprocess sweep on 1- and 2-device virtual meshes."""
    from singa_tpu.data.loader import synthetic_arrays, write_records
    from singa_tpu.tools.sweep import run_sweep

    shard = str(tmp_path / "shard")
    write_records(shard, *synthetic_arrays(64, seed=0))
    conf = tmp_path / "job.conf"
    conf.write_text(f"""
name: "sweep-smoke"
train_steps: 6
updater {{ base_learning_rate: 0.1 param_type: "Param" }}
neuralnet {{
  layer {{ name: "data" type: "kShardData"
          data_param {{ path: "{shard}" batchsize: 16 }} }}
  layer {{ name: "mnist" type: "kMnistImage" srclayers: "data"
          mnist_param {{ norm_a: 255 norm_b: 0 }} }}
  layer {{ name: "label" type: "kLabel" srclayers: "data" }}
  layer {{ name: "fc" type: "kInnerProduct" srclayers: "mnist"
          inner_product_param {{ num_output: 10 }}
          param {{ name: "w" init_method: "kUniformSqrtFanIn" }}
          param {{ name: "b" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "loss" type: "kSoftmaxLoss" srclayers: "fc" srclayers: "label"
          softmaxloss_param {{ topk: 1 }} }}
}}
""")
    results = run_sweep(str(conf), [1, 2], steps=6, virtual=True)
    assert [r["nworkers"] for r in results] == [1, 2]
    assert results[0]["efficiency"] == 1.0
    assert all(r["samples_per_sec"] > 0 for r in results)


# ---------------------------------------------------------------------
# cluster launch/admin tool (run.sh / node.sh analog)
# ---------------------------------------------------------------------


@pytest.mark.slow
def test_cluster_tool_start_ps_stop_local(tmp_path, monkeypatch):
    """`cluster start` launches one CLI process per hostfile line
    (localhost -> subprocess), `ps` reads the pid files, the job trains
    to completion, and `stop` clears the records — the run.sh lifecycle
    executed for real, locally."""
    import socket
    import time

    from singa_tpu.data.loader import synthetic_arrays, write_records
    from singa_tpu.tools import cluster

    shard = str(tmp_path / "shard")
    write_records(shard, *synthetic_arrays(64, seed=7))
    conf = tmp_path / "job.conf"
    conf.write_text(f"""
name: "cluster-tool-test"
train_steps: 4
updater {{ base_learning_rate: 0.1 param_type: "Param" }}
neuralnet {{
  layer {{ name: "data" type: "kShardData"
          data_param {{ path: "{shard}" batchsize: 16 }} }}
  layer {{ name: "mnist" type: "kMnistImage" srclayers: "data"
          mnist_param {{ norm_a: 255 norm_b: 0 }} }}
  layer {{ name: "label" type: "kLabel" srclayers: "data" }}
  layer {{ name: "fc" type: "kInnerProduct" srclayers: "mnist"
          inner_product_param {{ num_output: 10 }}
          param {{ name: "w" init_method: "kUniformSqrtFanIn" }}
          param {{ name: "b" init_method: "kConstant" value: 0 }} }}
  layer {{ name: "loss" type: "kSoftmaxLoss" srclayers: "fc" srclayers: "label"
          softmaxloss_param {{ topk: 1 }} }}
}}
""")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    hostfile = tmp_path / "hostfile"
    hostfile.write_text(f"127.0.0.1:{port}\n127.0.0.1\n")
    ws = tmp_path / "ws"
    monkeypatch.chdir(tmp_path)
    # children must stay on CPU (test processes may not grab the TPU)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)

    rc = cluster.main([
        "start", "-n", "2", "-hostfile", str(hostfile),
        "-model_conf", str(conf), "-workspace", str(ws),
    ])
    try:
        assert rc == 0
        pids = cluster._pids(str(ws))
        assert sorted(pids) == [0, 1]
        # wait for both ranks to finish training (short job; exited
        # children are zombies of THIS process — _alive counts them dead)
        deadline = time.time() + 120
        while time.time() < deadline and any(
            cluster._alive(pid) for _, pid in pids.values()
        ):
            time.sleep(1)
        for rank in (0, 1):
            log = (ws / "procs" / f"rank{rank}.log").read_text()
            assert "training 'cluster-tool-test'" in log, log
            assert "mesh {'data': 2" in log, log
        assert cluster.main(["ps", "-hostfile", str(hostfile),
                             "-workspace", str(ws)]) == 0
    finally:
        # a hung rendezvous must not leave CPU-bound children behind on
        # this 1-core host (they'd trip later tests' collective timeouts)
        cluster.main(["stop", "-hostfile", str(hostfile),
                      "-workspace", str(ws)])
    assert cluster._pids(str(ws)) == {}
