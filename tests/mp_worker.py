"""Subprocess body for the multi-process integration test.

Drives the REAL CLI entry (singa_tpu.main.main) — the analog of the
reference actually launching ``build/singa -procsID=N -hostfile ...`` on
each host (examples/mnist/run.sh:19-37) — then dumps the trained params
and run metadata for the parent test to compare across ranks.

Usage: python mp_worker.py <procsid> <model_conf> <cluster_conf> \
           <hostfile> <out_npz> [faults]

A non-zero CLI exit (e.g. the resumable 75 from a coordinated drain or
a peer-death watchdog exit) propagates as this process's exit code; the
params/meta dump is only written for clean (rc 0) runs.
"""

import json
import os
import sys

# CPU platform, pinned BEFORE jax import (each process contributes its
# one CPU device to the 2-process global mesh)
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.pop("XLA_FLAGS", None)
# the elastic-reshard drills change the PROCESS count while keeping the
# device count (N hosts x 1 chip -> 1 host x N chips): SINGA_MP_DEVICES
# gives this rank that many virtual CPU devices
if os.environ.get("SINGA_MP_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["SINGA_MP_DEVICES"]
    )

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402


def run() -> int:
    procsid, model_conf, cluster_conf, hostfile, out = sys.argv[1:6]
    faults = sys.argv[6] if len(sys.argv) > 6 else None

    import numpy as np

    import singa_tpu.main as cli
    import singa_tpu.trainer as trainer_mod

    captured = {}
    real_make = trainer_mod.make_trainer

    def capturing_make(*args, **kwargs):
        t = real_make(*args, **kwargs)
        captured["trainer"] = t
        return t

    # the supervisor resolves make_trainer lazily from singa_tpu.trainer
    # (resilience/supervisor.py), so patch THAT module; the cli attr is
    # kept for any direct-main path
    trainer_mod.make_trainer = capturing_make
    cli.make_trainer = capturing_make
    argv = [
        "-model_conf", model_conf,
        "-cluster_conf", cluster_conf,
        "-procsID", procsid,
        "-hostfile", hostfile,
    ]
    if faults:
        argv += ["-faults", faults]
    rc = cli.main(argv)
    if rc != 0:
        return rc

    import jax

    t = captured["trainer"]
    # params may be SHARDED across processes (model axis spanning ranks —
    # the cross-process bridge analog): allgather to full numpy views.
    # np.asarray alone raises on non-addressable arrays.
    from jax.experimental import multihost_utils

    logical = t._unpad_stored(t.params)
    arrays = {
        n: np.asarray(multihost_utils.process_allgather(v, tiled=True))
        if jax.process_count() > 1 and not v.is_fully_addressable
        else np.asarray(v)
        for n, v in logical.items()
    }
    np.savez(out + ".tmp.npz", **arrays)
    os.replace(out + ".tmp.npz", out)
    meta = {
        "process_count": jax.process_count(),
        "process_index": jax.process_index(),
        "mesh": dict(t.mesh.shape),
        "global_devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
        "batch_shard_ok": _batch_sharded(t),
        "weight_spec": [
            None if ax is None else str(ax)
            for ax in t.params["fc1/w"].sharding.spec
        ] if "fc1/w" in t.params else None,
    }
    with open(out + ".json", "w") as f:
        json.dump(meta, f)
    return 0


def _batch_sharded(t) -> bool:
    """Per-process data sharding: the train batch's sharding must split
    dim 0 over the data axis (each rank computes its own half)."""
    sh = next(iter(t.batch_sh.values()))["image"]
    return tuple(sh.spec)[:1] == ("data",)


if __name__ == "__main__":
    sys.exit(run())
