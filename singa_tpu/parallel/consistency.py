"""Async consistency protocols (EASGD / RandomSync / hogwild), TPU-native.

The reference trains one model replica per worker group and reconciles the
replicas through a ZeroMQ parameter server running one of two protocols
(src/utils/param.cc:100-256), throttled by a bandwidth-adaptive sample
ratio (src/worker/param_manager.cc:85-93) on the SyncNow cadence
(param_manager.cc:155-159). Here the server tier dissolves: replicas live
on a leading array axis sharded over the mesh's data axis, and each
protocol becomes a pure, jit-compiled transform over that axis. The
server processed worker messages serially under a per-param lock
(src/server/server.cc:110-143), so the faithful equivalent is a
`lax.scan` over replicas with the server ("center") pytree as carry —
order-dependent exactly like the reference, but one XLA program instead
of a message storm.

Protocols (semantics pinned by tests/test_consistency.py):

- **Elastic (EASGD)** — worker ships its full vector w with moving rate
  alpha; the server computes diff = alpha*(w - s), absorbs it (s += diff)
  and returns diff; the worker subtracts it (w -= diff)
  (param.cc:216-256).
- **RandomSync** — the worker samples floor(ratio*n) coordinates without
  replacement (reservoir-style, param.cc:101-110; distributionally
  equivalent sampling here), ships delta = w[idx] - snapshot[idx]; the
  server adds each delta and returns its *pre-update* value old;
  the worker reconciles w[idx] = old + delta and refreshes the snapshot
  (param.cc:112-196).
- **hogwild** (UpdaterProto.hogwild, model.proto:316) was *intra-process*
  lock-free sharing among executor threads. It has no TPU counterpart by
  design: one XLA program already saturates a chip, so the
  `nthreads_per_procs` replicas collapse into the batch dimension (see
  singa_tpu/parallel/mesh.py). The flag is parsed and ignored.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

#: partial-coverage dense-prefix budget, in ELEMENTS of the (R, n) delta
#: field (fp32 => x4 bytes). Above this, random_sync uses the serial-scan
#: formulation whose peak transient is the (R, m) sampled field itself —
#: the dense field is never built. Read ONCE at import (a trace-time env
#: read would leave stale jit caches when the var changes mid-process).
DENSE_PREFIX_MAX_ELEMS = int(
    os.environ.get("SINGA_TPU_RS_DENSE_ELEMS", 64 * 1024 * 1024)
)


def sync_now(step: int, sync_frequency: int, warmup_steps: int) -> bool:
    """ParamManager::SyncNow (reference: param_manager.cc:155-159): every
    ``sync_frequency`` steps once past warmup. ``step`` is the step just
    completed."""
    return (
        sync_frequency > 0
        and (step + 1) % sync_frequency == 0
        and step > warmup_steps
    )


def sync_ratio(
    compute_time_s: float,
    model_mb: float,
    nworkers: int,
    nservers: int,
    bandwidth_mbps: float,
) -> float:
    """ParamManager::SyncConfig (reference: param_manager.cc:85-93): the
    bandwidth-adaptive RandomSync sample ratio. The cluster can absorb
    ``bandwidth * nservers`` MB/s of sync traffic; the workers produce
    ``model_mb * nworkers / compute_time`` MB/s; the ratio of the two is
    the fraction of coordinates each sync can afford, clamped to 1."""
    if compute_time_s <= 0 or model_mb <= 0:
        return 1.0
    produced = model_mb * nworkers / compute_time_s
    ratio = bandwidth_mbps * max(nservers, 1) / produced
    return float(min(ratio, 1.0))


def elastic_sync(replicas, center, alpha: float):
    """One EASGD round: every replica syncs with the center, serially.

    ``replicas`` is a pytree whose leaves carry a leading replica axis;
    ``center`` the matching server pytree. Returns (replicas, center).
    Matches ElasticParam::{GenSyncMsgFromWorker,HandleSyncMsg,
    ParseSyncMsgFromPS} (reference: src/utils/param.cc:216-256): for each
    replica in turn, diff = alpha*(w - s); s += diff; w -= diff.
    """

    def one(c, w):
        diff = jax.tree.map(lambda wi, ci: alpha * (wi - ci), w, c)
        c = jax.tree.map(jnp.add, c, diff)
        w = jax.tree.map(jnp.subtract, w, diff)
        return c, w

    center, replicas = jax.lax.scan(one, center, replicas)
    return replicas, center


def random_sync(replicas, snapshots, center, indices, full_coverage=False):
    """One RandomSync round over sampled coordinates.

    ``indices`` maps param name -> int32 (nreplicas, m) of flat coordinate
    indices (unique within each row). Per replica i and param (reference:
    src/utils/param.cc:112-196):

        delta = w[idx] - snapshot[idx]        (GenSyncMsgFromWorker)
        old   = s[idx];  s[idx] += delta      (HandleSyncMsg)
        w[idx] = old + delta;  snapshot[idx] = w[idx]   (ParseSyncMsgFromPS)

    so each replica absorbs exactly the other replicas' deltas that
    reached the server before its own message.

    **The serial server loop is a prefix sum in disguise**: at any coordinate x, replica i's
    new value is c0[x] + sum_{j<=i, x in idx_j} delta_j[x] and the final
    center is c0 + the full sum — an associative prefix over the replica
    axis. This computes it with one batched scatter + jnp.cumsum instead
    of a lax.scan of serial gather/scatter rounds, one per replica. The
    arrival order is fixed at 0..R-1 — the reference's order was
    whatever ZMQ delivered, so this is as valid an execution as any, and
    it matches the previous scan's order exactly (differences vs the
    serial form are only the summation tree's fp rounding).
    Transient memory is O(R * n) per param for the dense delta field.

    ``full_coverage=True`` is the ratio>=1.0 fast path: the CALLER
    asserts every replica syncs every coordinate (sample_sync_indices
    emits arange rows there), so the scatter/gather is skipped entirely
    and ``indices`` may be None. Passing partial indices with this flag
    would silently sync everything — it is a contract, not a checked
    argument (the only caller, trainer/replica.py, derives it from the
    static sample_ratio).

    **Memory bound (r5):** the partial-coverage dense path materializes
    an (R, n) delta field — at the flagship's 18.8M params x 8 replicas
    a ~600 MB fp32 transient. When R*n exceeds DENSE_PREFIX_MAX_ELEMS
    (default 64M elements = 256 MB fp32; SINGA_TPU_RS_DENSE_ELEMS, read
    once at import) the round instead runs the serial-scan formulation
    — the reference's own per-replica server loop — whose peak
    transient is the (R, m) sampled field plus one O(n) carry: the
    dense field is never built. Both compute identical values (scan ==
    prefix by associativity; the oracle test covers each). At the
    protocol's real operating point (small ratio, param.cc:148) the
    scan also does strictly less work: O(R*m) touched coordinates vs
    the prefix's O(R*n) cumsum.

    Returns (replicas, snapshots, center).
    """
    new_r, new_s, new_c = {}, {}, {}
    for name in center:
        shape = replicas[name].shape
        R = shape[0]
        n = center[name].size
        w = replicas[name].reshape(R, n)
        snap = snapshots[name].reshape(R, n)
        c0 = center[name].ravel()
        if full_coverage:
            dense = w - snap  # delta at every coordinate
            prefix = jnp.cumsum(dense, axis=0)
            new_vals = c0[None, :] + prefix
            new_r[name] = new_vals.reshape(shape)
            new_s[name] = new_vals.reshape(shape)
            new_c[name] = (c0 + prefix[-1]).reshape(center[name].shape)
        elif R * n <= DENSE_PREFIX_MAX_ELEMS:
            ix = indices[name]
            delta = (
                jnp.take_along_axis(w, ix, 1)
                - jnp.take_along_axis(snap, ix, 1)
            )
            dense = jax.vmap(
                lambda i, d: jnp.zeros((n,), w.dtype).at[i].add(d)
            )(ix, delta)
            prefix = jnp.cumsum(dense, axis=0)
            new_vals = c0[None, :] + prefix
            upd = jnp.take_along_axis(new_vals, ix, 1)
            new_r[name] = jax.vmap(
                lambda row, i, v: row.at[i].set(v)
            )(w, ix, upd).reshape(shape)
            new_s[name] = jax.vmap(
                lambda row, i, v: row.at[i].set(v)
            )(snap, ix, upd).reshape(shape)
            new_c[name] = (c0 + prefix[-1]).reshape(center[name].shape)
        else:
            wi, si, c = _scan_random_sync(w, snap, c0, indices[name])
            new_r[name] = wi.reshape(shape)
            new_s[name] = si.reshape(shape)
            new_c[name] = c.reshape(center[name].shape)
    return new_r, new_s, new_c


def _scan_random_sync(w, snap, c0, ix):
    """The serial server loop, verbatim: replica i's sampled deltas hit
    the center before replica i+1's message is handled (per-param lock,
    server.cc:110-143). Peak transient memory is the (R, m) gathered
    field — used by random_sync when the dense (R, n) prefix field
    would exceed DENSE_PREFIX_MAX_ELEMS."""

    def step(c, inp):
        wi, si, ixi = inp
        delta = wi[ixi] - si[ixi]
        new = c[ixi] + delta  # server's pre-update value + own delta
        c = c.at[ixi].add(delta)
        wi = wi.at[ixi].set(new)
        si = si.at[ixi].set(new)
        return c, (wi, si)

    c, (w2, s2) = jax.lax.scan(step, c0, (w, snap, ix))
    return w2, s2, c


def sample_sync_indices(
    rng: np.random.RandomState,
    shapes: dict[str, tuple],
    nreplicas: int,
    ratio: float,
) -> dict[str, np.ndarray]:
    """Host-side coordinate sampling for one RandomSync round.

    Each replica draws its own coordinates (the reference seeds per-worker
    from the wall clock, param.cc:146; parity is distributional). The
    sample count m = floor(ratio*n) — the reference's float-to-int
    truncation of data_.count()*sample_ratio (param.cc:148) — is static
    per param so the jitted sync retraces only when the ratio changes
    (it is fixed after warmup).
    """
    out: dict[str, np.ndarray] = {}
    for name, shape in shapes.items():
        n = int(np.prod(shape))
        if ratio >= 1.0:
            # every coordinate: the sorted sample IS arange — skip the
            # O(n) reject-sampling draw per replica (measured 5ms/round
            # on the MLP, pure overhead at full ratio)
            out[name] = np.broadcast_to(
                np.arange(n, dtype=np.int32), (nreplicas, n)
            )
            continue
        m = max(1, int(n * ratio))
        rows = [
            np.sort(rng.choice(n, size=m, replace=False))
            for _ in range(nreplicas)
        ]
        out[name] = np.stack(rows).astype(np.int32)
    return out
