"""CLI entry point: ``python -m singa_tpu.main -model_conf F -cluster_conf F``.

Mirrors the reference binary's gflags surface (src/main.cc:13-18:
-procsID, -hostfile, -cluster_conf, -model_conf) so reference job launch
lines work unchanged. The worker/server role dispatch (main.cc:49-55)
disappears for TRAINING: there is no parameter-server tier — every
process is a trainer and grad sync is an XLA collective.
-procsID/-hostfile feed jax.distributed.initialize (parallel/launch.py)
when a multi-host run is launched reference-style; on TPU pods the
runtime's own environment drives the rendezvous and both flags may be
omitted.

The rank-picks-role pattern returns at SERVING scale: a ``fleet { ... }``
config block dispatches this process to a serving-fleet host instead
(singa_tpu/serve/fleet/) — ``-procsID`` picks its prefill/decode/unified
role exactly as main.cc:49-55 picked Worker vs Server, hosts exchange
paged-KV block migrations through a shared filesystem mailbox (no
jax.distributed rendezvous), and a SIGTERM'd host drains its in-flight
sequences to a PEER and exits 75.

Jobs run under the resilience supervisor (singa_tpu/resilience/): a
``resilience { ... }`` config block enables supervised auto-resume from
the newest complete checkpoint, SIGTERM/SIGINT drain with a resumable
exit status (75), the divergence guard, and the step watchdog. The
``-faults`` flag (or SINGA_TPU_FAULTS) injects a deterministic fault
plan — ``crash@7,sigterm@12,nanloss@5`` — for recovery drills and CI.

Telemetry (singa_tpu/obs/) is always on for jobs with a workspace: each
rank appends structured lifecycle events and phase spans to
``<workspace>/events/rank_k.jsonl`` (flushed at display cadence — the
step path gains no syscalls or device syncs); ``python -m
singa_tpu.tools.trace <workspace>`` merges them into one
Perfetto-loadable trace.json. A ``profile@K:steps=N`` term in the fault
plan brackets steps K..K+N with a ``jax.profiler`` trace into
``<workspace>/xprof/``. The ``telemetry { ... }`` config block tunes or
disables all of it.
"""

from __future__ import annotations

import argparse
import os
import sys

from .config import load_cluster_config, load_model_config


def parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="singa_tpu", description=__doc__, add_help=True
    )
    ap.add_argument("-model_conf", required=True, help="ModelProto text file")
    ap.add_argument("-cluster_conf", default=None, help="ClusterProto text file")
    ap.add_argument("-procsID", type=int, default=0, help="process rank")
    ap.add_argument("-hostfile", default=None,
                    help="one host per line; line 0 hosts the rendezvous")
    ap.add_argument("-seed", type=int, default=0, help="init/dropout RNG seed")
    ap.add_argument(
        "-faults",
        default=os.environ.get("SINGA_TPU_FAULTS"),
        help="deterministic fault plan, e.g. 'crash@7,sigterm@12', or a "
        "'profile@20:steps=5' jax.profiler trigger "
        "(resilience/faults.py grammar; also via SINGA_TPU_FAULTS)",
    )
    return ap.parse_args(argv)


def main(argv: list[str] | None = None) -> int:
    from .parallel import init_distributed
    from .utils.compile_cache import setup_compile_cache

    args = parse_args(argv)
    model_cfg = load_model_config(args.model_conf)
    cluster_cfg = (
        load_cluster_config(args.cluster_conf) if args.cluster_conf else None
    )
    # persistent-compile warm start for trainers and serving hosts
    # alike: repeat runs skip XLA recompilation (JAX_COMPILATION_CACHE_DIR
    # places the cache; unset, one fixed directory inside the checkout —
    # utils/compile_cache.py)
    setup_compile_cache()
    if getattr(model_cfg, "fleet", None) is not None:
        # the reference's rank-picks-role dispatch (main.cc:49-55), at
        # serving scale: a ``fleet {}`` block makes this process a
        # serving-fleet host — -procsID picks prefill/decode/unified
        # (serve/fleet/host.role_for_rank) and hosts share nothing but
        # the mailbox, so no jax.distributed rendezvous is started
        from .serve.fleet.host import run_from_conf

        return run_from_conf(
            model_cfg, cluster_cfg, procs_id=args.procsID, seed=args.seed,
            faults=args.faults,
        )
    init_distributed(args.procsID, args.hostfile)
    # every job routes through the supervisor: configs without a
    # resilience block (and no fault plan) take its transparent
    # single-attempt path; configs with one get auto-resume, preemption
    # drain (exit 75 = resumable), divergence guard, and the watchdog
    from .resilience import supervisor

    rc = supervisor.run(
        model_cfg, cluster_cfg, seed=args.seed, faults=args.faults
    )
    from .resilience.coord import process_count

    if rc != 0 and process_count() > 1:
        # a non-zero exit in a multi-process job leaves peers
        # mid-collective (a crash) or exiting in parallel (a
        # coordinated drain). jax's atexit distributed shutdown would
        # block on them — or, when the coordination service dies first,
        # abort THIS process with SIGABRT, destroying the exit code the
        # launcher keys its restart decision on. Flush and leave with
        # the real status instead.
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(rc)
    return rc


if __name__ == "__main__":
    sys.exit(main())
