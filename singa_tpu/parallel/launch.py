"""Multi-host bootstrap: the reference's process-identity machinery on JAX.

The reference assigns roles from ``-procsID`` + a hostfile (one address
per line, comments allowed; src/utils/cluster.cc:18-24) and then
hand-shakes every process through Router PING/PONG barriers
(src/utils/router.cc:16-86). On TPU both jobs belong to
``jax.distributed.initialize``: the coordinator (hostfile line 0) runs
the rendezvous service, every process reports its rank, and the runtime
wires the global device mesh — after which cross-host traffic is XLA
collectives over ICI/DCN, not sockets we manage.

On TPU pods (GKE / gcloud-created slices) the runtime injects its own
coordinator environment and ``initialize()`` needs no arguments; the
hostfile path exists for parity with reference launch scripts and for
CPU/GPU clusters.
"""

from __future__ import annotations

import os

DEFAULT_PORT = 9999  # arbitrary; the reference's start_port plays this role


def read_hostfile(path: str) -> list[str]:
    """Hostfile -> ordered address list (cluster.cc:18-24 semantics:
    one host per line, blank lines and #-comments skipped, order is
    process rank order)."""
    hosts: list[str] = []
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if line:
                hosts.append(line)
    return hosts


def coordinator_address(hosts: list[str], port: int = DEFAULT_PORT) -> str:
    """Line 0 hosts the rendezvous, like the reference's server-0 router
    bind (router.cc:46-86). A host may carry its own ``:port``."""
    if not hosts:
        raise ValueError("empty hostfile")
    head = hosts[0]
    return head if ":" in head else f"{head}:{port}"


def init_distributed(
    procs_id: int | None = None,
    hostfile: str | None = None,
    *,
    port: int = DEFAULT_PORT,
) -> bool:
    """Initialize jax.distributed for a multi-host run; returns whether a
    multi-process rendezvous actually started.

    Resolution order matches how jobs launch in practice:
    1. No hostfile and no multi-host env (no coordinator address, at
       most one TPU worker hostname) -> single-process, no-op.
    2. TPU pod environment (runtime-injected coordinator, or several
       worker hostnames) -> ``jax.distributed.initialize()`` with no
       arguments; a failed rendezvous raises.
    3. Hostfile + procs_id -> explicit coordinator/num_processes/rank,
       the reference's ``-procsID``+hostfile contract (main.cc:13-18).
    """
    import jax

    if hostfile is None:
        explicit = any(
            os.environ.get(v)
            for v in ("COORDINATOR_ADDRESS", "MEGASCALE_COORDINATOR_ADDRESS")
        )
        workers = [
            w for w in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
            if w
        ]
        if not explicit and len(workers) <= 1:
            # one host (a single-host TPU VM names itself alone): there
            # is nobody to rendezvous with, and an argument-less
            # initialize() would go looking for a metadata server
            return False
        # a pod-shaped environment that fails to rendezvous must not
        # degrade to N independent same-seed trainers: let it raise
        jax.distributed.initialize()
        return True
    hosts = read_hostfile(hostfile)
    if len(hosts) <= 1:
        return False
    if procs_id is None or not 0 <= procs_id < len(hosts):
        raise ValueError(
            f"procs_id {procs_id!r} out of range for {len(hosts)} hosts"
        )
    _enable_cpu_collectives()
    jax.distributed.initialize(
        coordinator_address=coordinator_address(hosts, port),
        num_processes=len(hosts),
        process_id=procs_id,
    )
    return True


def refuse_local_ranks_on_a_chip(n_local: int) -> None:
    """One process per chip: a chip belongs to the first process that
    touches it, and a second local rank that needs it fails or hangs.
    The launchers that fork several ranks onto THIS host
    (tools/cluster.py, tools/elastic_launch.py) are therefore a
    ``JAX_PLATFORMS=cpu`` rehearsal; on a chip host one process drives
    every local chip. Refuse loudly instead of hanging."""
    if n_local > 1 and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"{n_local} local ranks would contend for this host's "
            "accelerator (one process per chip): set JAX_PLATFORMS=cpu "
            "to rehearse a multi-process gang here, or launch one "
            "process per host"
        )


def _enable_cpu_collectives() -> None:
    """Multi-process jobs on the CPU backend need jax's gloo collectives
    implementation — the default ('none') fails every cross-process
    computation with "Multiprocess computations aren't implemented on
    the CPU backend", which would take the whole coordination plane
    (resilience/coord.py preemption barriers, multihost_utils
    broadcasts) down with it. Must run BEFORE the backend initializes."""
    import jax

    if "cpu" in os.environ.get("JAX_PLATFORMS", ""):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
