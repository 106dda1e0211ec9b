"""Replica-protocol engine ablation on the 8-virtual-device geometry.

The engine-comparison methodology, with the
RandomSync ratios the protocol actually exists for (the reference's
bandwidth throttle SUBSAMPLES coordinates, param_manager.cc:85-93;
ratio 1.0 is the degenerate case its fast path special-cases away):

  sync Trainer           batch 512 over 8 devices
  Elastic                8 replicas x 64, sync_freq 8
  RandomSync ratio 1.0   dense-prefix fast path (no index tensors)
  RandomSync ratio 0.5   sampled path
  RandomSync ratio 0.1   sampled path

Both partial-coverage formulations are timed at each ratio: the dense
parallel prefix (O(R*n) transient) and the bounded-memory serial scan
(what production uses when R*n exceeds DENSE_PREFIX_MAX_ELEMS —
singa_tpu/parallel/consistency.py).

Run (takes ~2 min on the 1-core CI host):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
      python bench/ablations/replica_protocols.py
"""

import os
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np

CONF = """
name: "ablate-mlp"
train_steps: 4096
test_steps: 0
display_frequency: 0
updater {{
  base_learning_rate: 0.05
  momentum: 0.9
  type: kSGD
  param_type: "{param_type}"
  moving_rate: {moving_rate}
  sync_frequency: 8
  warmup_steps: 8
}}
neuralnet {{
  layer {{ name: "data" type: "kShardData"
    data_param {{ path: "{shard}" batchsize: {batch} }} }}
  layer {{ name: "mnist" type: "kMnistImage" srclayers: "data"
    mnist_param {{ norm_a: 127.5 norm_b: 1 }} }}
  layer {{ name: "label" type: "kLabel" srclayers: "data" }}
  layer {{ name: "fc1" type: "kInnerProduct" srclayers: "mnist"
    inner_product_param {{ num_output: 64 }}
    param {{ name: "weight" init_method: kUniform low: -0.05 high: 0.05 }}
    param {{ name: "bias" init_method: kConstant value: 0 }} }}
  layer {{ name: "tanh1" type: "kTanh" srclayers: "fc1" }}
  layer {{ name: "fc2" type: "kInnerProduct" srclayers: "tanh1"
    inner_product_param {{ num_output: 10 }}
    param {{ name: "weight" init_method: kUniform low: -0.05 high: 0.05 }}
    param {{ name: "bias" init_method: kConstant value: 0 }} }}
  layer {{ name: "loss" type: "kSoftmaxLoss" srclayers: "fc2" srclayers: "label"
    softmaxloss_param {{ topk: 1 }} }}
}}
"""


def _cfg(shard, param_type="Param", batch=512, moving_rate=0.3):
    from singa_tpu.config import parse_model_config

    return parse_model_config(
        CONF.format(
            shard=shard, param_type=param_type, batch=batch,
            moving_rate=moving_rate,
        )
    )


def _time_steps(trainer, n1=128, n2=512):
    """Two-window slope (bench.py methodology): marginal s/step."""
    import jax.numpy as jnp

    def sync():
        return float(jnp.sum(jnp.abs(next(iter(trainer.params.values())))))

    def run(s0, n):
        s = s0
        while s < s0 + n:
            take = min(
                trainer._chunk_cap(), trainer._chunk_len(s), s0 + n - s
            )
            if take > 1:
                trainer.train_chunk(s, take)
            else:
                trainer.train_one_batch(s)
            s += take

    run(0, n1)
    run(n1, n2)
    sync()
    best, step = {}, n1 + n2
    for n in (n1, n2):
        best[n] = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            run(step, n)
            sync()
            best[n] = min(best[n], time.perf_counter() - t0)
            step += n
    return (best[n2] - best[n1]) / (n2 - n1)


def bench_sync(shard):
    from singa_tpu.trainer import Trainer

    t = Trainer(
        _cfg(shard), seed=0, log=lambda s: None, prefetch=False
    )
    return _time_steps(t)


def bench_replica(shard, protocol, ratio=1.0):
    """ReplicaTrainer with the protocol; for RandomSync the ratio is
    FORCED after bootstrap (the bandwidth-adaptive SyncConfig would
    otherwise pick it from wall-clock noise)."""
    from singa_tpu.trainer import ReplicaTrainer

    moving = 0.3 if protocol == "Elastic" else 0.0
    t = ReplicaTrainer(
        _cfg(shard, param_type=protocol, batch=64, moving_rate=moving),
        seed=0, log=lambda s: None, prefetch=False,
    )
    # drive through warmup + bootstrap, then pin the ratio before the
    # lazily-built sync jit compiles
    for s in range(t.warmup_steps):
        t.train_one_batch(s)
    assert t._bootstrapped and t._sync_jit is None
    t.sample_ratio = ratio

    def run_from(s0, n):
        s = s0
        while s < s0 + n:
            take = min(t._chunk_cap(), t._chunk_len(s), s0 + n - s)
            if take > 1:
                t.train_chunk(s, take)
            else:
                t.train_one_batch(s)
            s += take

    import jax.numpy as jnp

    def sync():
        return float(jnp.sum(jnp.abs(next(iter(t.params.values())))))

    n1, n2 = 128, 512
    run_from(t.warmup_steps, n1 + n2)
    sync()
    best, step = {}, t.warmup_steps + n1 + n2
    for n in (n1, n2):
        best[n] = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            run_from(step, n)
            sync()
            best[n] = min(best[n], time.perf_counter() - t0)
            step += n
    return (best[n2] - best[n1]) / (n2 - n1)


ROWS = [
    # (label, kind, protocol, ratio, dense_budget or None=default)
    ("sync Trainer (batch 512 / 8 dev)", "sync", None, None, None),
    ("ReplicaTrainer, Elastic (sync_freq 8)", "rep", "Elastic", None, None),
    ("ReplicaTrainer, RandomSync ratio 1.0 (dense fast path)",
     "rep", "RandomSync", 1.0, None),
    ("ReplicaTrainer, RandomSync ratio 0.5 (dense prefix)",
     "rep", "RandomSync", 0.5, None),
    ("ReplicaTrainer, RandomSync ratio 0.5 (bounded scan)",
     "rep", "RandomSync", 0.5, 0),
    ("ReplicaTrainer, RandomSync ratio 0.1 (dense prefix)",
     "rep", "RandomSync", 0.1, None),
    ("ReplicaTrainer, RandomSync ratio 0.1 (bounded scan)",
     "rep", "RandomSync", 0.1, 0),
]


def run_row(shard, kind, protocol, ratio, budget):
    if budget is not None:
        from singa_tpu.parallel import consistency

        consistency.DENSE_PREFIX_MAX_ELEMS = budget
    if kind == "sync":
        return bench_sync(shard)
    return bench_replica(shard, protocol, ratio if ratio else 1.0)


def main():
    """Each row runs in its own subprocess: one long-lived process
    accumulating 7 jitted programs on this 1-core host starves the
    8 virtual device threads into XLA's collective-rendezvous timeout
    (observed: AllGather 'stuck' dumps after row 3)."""
    import json
    import subprocess

    from singa_tpu.data.loader import synthetic_arrays, write_records

    tmp = tempfile.mkdtemp(prefix="singa_ablate_")
    shard = os.path.join(tmp, "shard")
    write_records(shard, *synthetic_arrays(1024, seed=1))

    rows = []
    for label, kind, protocol, ratio, budget in ROWS:
        spec = json.dumps([shard, kind, protocol, ratio, budget])
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--row", spec],
            capture_output=True, text=True, timeout=600,
        )
        if out.returncode:
            print(f"{label}: FAILED\n{out.stderr}", file=sys.stderr)
            rows.append((label, None))
        else:
            rows.append((label, float(out.stdout.strip().splitlines()[-1])))

    s_sync = rows[0][1]
    print(f"{'engine':58s}  ms/step  vs sync")
    for name, s in rows:
        if s is None:
            print(f"{name:58s}   FAILED")
        else:
            ratio = f"{s / s_sync:5.2f}x" if s_sync else "  n/a"
            print(f"{name:58s}  {s * 1e3:7.2f}  {ratio}")


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--row":
        import json

        shard, kind, protocol, ratio, budget = json.loads(sys.argv[2])
        print(run_row(shard, kind, protocol, ratio, budget))
        sys.exit(0)
    main()
