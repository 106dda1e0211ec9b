"""ReplicaTrainer: worker-group replicas + async consistency protocols.

The reference's cluster runs ``ngroups`` model replicas, each training on
its own data and reconciling through the parameter-server protocols
selected by UpdaterProto.param_type ("Elastic" | "RandomSync",
src/worker/neuralnet.cc:35-44). This trainer reproduces that training
regime TPU-natively: replicas live on a leading param-array axis sharded
over the mesh's data axis, the per-replica step is ``vmap``-compiled (one
XLA program trains *all* replicas), and the protocol rounds are the pure
scan transforms in singa_tpu/parallel/consistency.py.

Lifecycle parity with Worker::Start (src/worker/worker.cc:14-57):

  1. every replica initializes its own params (different RNG folds —
     ParamManager::InitParams, distributional parity with time-seeded rand)
  2. ``warmup_steps`` local-only steps; their measured step time feeds
     SyncConfig's bandwidth-adaptive sample ratio (param_manager.cc:85-93)
  3. bootstrap: replica 0 publishes to the server, everyone else fetches
     (worker.cc:50-55) — here: center := replica 0, all replicas := center
  4. main loop: local update every step; protocol sync round every
     ``sync_frequency`` steps (SyncNow, param_manager.cc:155-159)

The driver for choosing this trainer mirrors the reference topology:
``nservers > 0`` and an asynchronous cluster (cluster.proto ``synchronous``
is false) mean PS-style training; otherwise singa_tpu uses the default
synchronous ParamSync Trainer (the north-star replacement).
"""

from __future__ import annotations

import os
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..config.schema import ClusterConfig, ConfigError, ModelConfig
from ..parallel.consistency import (
    elastic_sync,
    random_sync,
    sample_sync_indices,
    sync_now,
    sync_ratio,
)
from ..parallel.mesh import DATA_AXIS
from ..parallel.shardings import replicated
from ..params import init_params
from ..resilience.guard import GUARD_KEYS, grad_norm_sq, init_guard_buffers
from jax.sharding import NamedSharding, PartitionSpec as P

from .trainer import Trainer

PROTOCOLS = ("Elastic", "RandomSync")


class ReplicaTrainer(Trainer):
    """Trainer variant holding one param replica per data-axis mesh row.

    Production-engine parity with the sync Trainer (round-3 promotion):
    device-cached datasets (the vmapped step gathers a (replicas, batch)
    index grid on device), lax.scan chunking with chunk windows bounded
    by the sync cadence (one dispatch per window, then one sync
    dispatch), and stateful layers via per-replica buffer state.
    """

    _allow_device_cache = True
    _supports_buffers = True
    #: the replica protocol stacks params/slots (R, ...) under its own
    #: _rep_param_sh layout — zero_update's data-axis update sharding
    #: would fight it, so the knob is rejected loudly
    _supports_zero_update = False
    #: the EASGD/RandomSync protocol owns its own gradient-sync math
    #: (per-replica local steps + center pulls) — quantized/overlapped
    #: gradient collectives are rejected loudly, like zero_update
    _supports_grad_comm = False

    @property
    def _batches_per_step(self) -> int:  # one stream batch per replica
        return self.nreplicas

    def __init__(
        self,
        model_cfg: ModelConfig,
        cluster_cfg: ClusterConfig | None = None,
        *,
        mesh=None,
        seed: int = 0,
        log: Callable[[str], None] = print,
        prefetch: bool | None = None,
        device_cache: bool | None = None,
        stream_chunks: bool | None = None,
    ):
        ucfg = model_cfg.updater
        if ucfg is None:
            raise ConfigError("model config has no updater block")
        if ucfg.param_type not in PROTOCOLS:
            # the reference logs "Unkown parameter type" (neuralnet.cc:43)
            raise ConfigError(
                f"unknown param_type {ucfg.param_type!r} "
                f"(expected one of {PROTOCOLS})"
            )
        # protocol attrs before super(): _materialize_params (called from
        # the base ctor) and _resume consult them
        self.protocol = ucfg.param_type
        self.sync_frequency = ucfg.sync_frequency
        self.warmup_steps = ucfg.warmup_steps
        self.moving_rate = ucfg.moving_rate
        # The adaptive ratio from SyncConfig, set at bootstrap. RandomSync
        # uses it as the coordinate fraction; Elastic uses it as alpha when
        # moving_rate is 0 — the reference passes sample_ratio_ into
        # GenSyncMsgFromWorker whenever moving_rate_ is unset
        # (param_manager.cc:190-194), whatever the registered protocol.
        self.sample_ratio = 1.0
        self._warmup_time = 0.0
        self._warmup_timed = 0
        self._sync_rng = np.random.RandomState(seed ^ 0x5EED)
        self._sync_jit: Callable | None = None
        #: fused unpad+copy program for the async .server sidecar
        self._sidecar_snap_fn: Callable | None = None
        #: (nwindows, window_len) -> jitted multi-window program
        self._fused_chunk_fns: dict[tuple[int, int], Callable] = {}
        super().__init__(
            model_cfg,
            cluster_cfg,
            mesh=mesh,
            seed=seed,
            log=log,
            prefetch=prefetch,
            device_cache=device_cache,
            stream_chunks=stream_chunks,
        )
        # each step consumes one batch per replica
        self._batch_size = self.train_net.batchsize * self.nreplicas

    def _materialize_params(self) -> None:
        """Replica-axis params/state: leading axis over DATA_AXIS, any
        kLayerPartition axes shift right by one. Each replica initializes
        from its own RNG fold (ParamManager::InitParams — the reference
        seeds per-process from the wall clock, so parity is
        distributional)."""
        self.nreplicas = self.mesh.shape[DATA_AXIS]
        self._rep_param_sh = {
            n: NamedSharding(self.mesh, P(DATA_AXIS, *sh.spec))
            for n, sh in self.param_sh.items()
        }
        keys = jax.random.split(self._init_key, self.nreplicas)
        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[init_params(k, self.specs) for k in keys],
        )
        # uneven kLayerPartition dims: stored arrays pad-to-multiple
        # (trainer.py _pad_one pads trailing dims under the replica axis)
        stacked = {n: self._pad_one(n, v) for n, v in stacked.items()}
        self.params = {
            n: jax.device_put(v, self._rep_param_sh[n])
            for n, v in stacked.items()
        }
        # per-replica updater slots through the updater's own init contract
        # (fresh state per replica = the single-replica init, replicated)
        state0 = self.updater.init_state(
            {n: v[0] for n, v in stacked.items()}  # already padded
        )
        self.state = {
            n: {
                s: jax.device_put(
                    jnp.broadcast_to(v, (self.nreplicas,) + v.shape),
                    self._rep_param_sh[n],
                )
                for s, v in slots.items()
            }
            for n, slots in state0.items()
        }
        # per-replica stateful-layer buffers (each replica tracks its own
        # running stats, like each worker group's private batch-norm)
        self._rep_buf_sh = NamedSharding(self.mesh, P(DATA_AXIS))
        buffers0 = self.train_net.init_buffers()
        self.buffers = {
            n: jax.device_put(
                jnp.broadcast_to(v, (self.nreplicas,) + v.shape),
                self._rep_buf_sh,
            )
            for n, v in buffers0.items()
        }
        if self._guard is not None:
            # guard counters are SCALAR and replicated — the verdict is
            # global (any bad replica voids the step), so per-replica
            # counters would only ever disagree by a bug
            repl = replicated(self.mesh)
            for k, v in init_guard_buffers().items():
                self.buffers[k] = jax.device_put(v, repl)
        # server-side pytrees; materialized at bootstrap
        self.center: dict[str, jnp.ndarray] | None = None
        self.snapshot: dict[str, jnp.ndarray] | None = None
        # bootstrapped means the PS holds a published model (worker.cc:50-55)
        self._bootstrapped = False
        if self.cfg.checkpoint:
            self._resume(self.cfg.checkpoint)

    # ------------------------------------------------------------------
    # compiled steps
    # ------------------------------------------------------------------

    def _step_core(self, params, state, buffers, step, batch, rng, lr_scale):
        """vmap the per-replica forward/backward/update over the leading
        replica axis; metrics are averaged across replicas (each group
        reports its own Performance in the reference — one average is the
        honest aggregate). Buffers (batch-norm running stats) carry a
        replica axis too: each replica evolves its own state.

        Guard seam (resilience/guard.py): every replica computes its
        own loss + grad-norm finiteness verdict inside the vmap; the
        step's verdict is their conjunction — ANY bad replica voids the
        WHOLE step, because the shared counters (and a rollback, which
        restores every replica plus the ``.server`` sidecar) must stay
        consistent across replicas. ``lr_scale`` (a replicated scalar)
        broadcasts into each replica's grads."""
        rngs = jax.random.split(rng, self.nreplicas)
        guarded = lr_scale is not None

        def one(p, s, b, feed, r):
            def loss_fn(pp):
                loss, metrics, new_b = self.train_net.forward(
                    self._cast_compute(pp), self._cast_compute(feed),
                    training=True, rng=r,
                    buffers=b, return_buffers=True,
                )
                return loss, (metrics, new_b)

            (loss, (m, new_b)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(p)
            ok_r = jnp.bool_(True)
            if guarded:
                ok_r = jnp.isfinite(loss) & jnp.isfinite(
                    grad_norm_sq(grads)
                )
                grads = jax.tree.map(
                    lambda g: g * lr_scale.astype(g.dtype), grads
                )
            p2, s2 = self.updater.apply(step, p, grads, s, self.specs)
            return p2, s2, new_b, m, ok_r

        params, state, buffers, metrics, ok_r = jax.vmap(
            one, in_axes=(0, 0, 0, 0, 0)
        )(params, state, buffers, batch, rngs)
        metrics = jax.tree.map(lambda x: jnp.mean(x, axis=0), metrics)
        ok = jnp.all(ok_r) if guarded else None
        return params, state, buffers, metrics, ok

    def _build_sync(self):
        if self.protocol == "Elastic":
            # moving_rate if set, else the adaptive ratio — the reference's
            # GenSyncMsgFromWorker argument choice (param_manager.cc:190-194)
            alpha = self.moving_rate if self.moving_rate > 0 else self.sample_ratio

            def fn(replicas, center):
                return elastic_sync(replicas, center, alpha)

            # sync runs once per window, not per step; donation's saving
            # is negligible and CPU test runs warn on unused donations
            return jax.jit(fn)  # netlint: disable=JAX003

        # ratio is fixed once bootstrap ran (_build_sync is lazy), so
        # full coverage is a static property of the compiled sync
        full = self.sample_ratio >= 1.0

        def fn(replicas, snapshots, center, indices):
            return random_sync(
                replicas, snapshots, center, indices, full_coverage=full
            )

        # once-per-window protocol round, same tradeoff as elastic_sync
        return jax.jit(fn)  # netlint: disable=JAX003

    # ------------------------------------------------------------------
    # host-side loop hooks
    # ------------------------------------------------------------------

    def _next_batch(self, net) -> dict:
        """Train batches gain a leading replica axis: each replica consumes
        its own ``batchsize`` records, in stream order — replica i gets the
        i-th of ``nreplicas`` consecutive batches, like each worker group
        reading its own shard partition (script/load_data.py semantics).

        With the device-cached dataset only a (replicas, batch) index
        grid crosses to the device; the gather happens inside the jitted
        step (Trainer._resolve_batch handles the 2-D index). Non-cached
        routing (device feeder / host assembly) is the base class's —
        it lands in _assemble_host_batch below either way."""
        if net is not self.train_net or not self._cached:
            return super()._next_batch(net)
        out = {}
        for name, pipe in self._pipelines[id(net)].items():
            d = self._dev_data[id(net)][name]
            idx = np.stack(
                [pipe.next_indices() for _ in range(self.nreplicas)]
            )
            out[name] = {"__idx__": jnp.asarray(idx), **d}
        return out

    def _assemble_host_batch(self, net) -> dict:
        if net is not self.train_net:
            return super()._assemble_host_batch(net)
        out = {}
        leaf_sh = NamedSharding(self.mesh, P(DATA_AXIS))
        for name, pipe in self._pipelines[id(net)].items():
            imgs, labels = [], []
            for _ in range(self.nreplicas):
                i, l = pipe.next_batch()
                imgs.append(i)
                labels.append(l)
            out[name] = {
                "image": jax.device_put(np.stack(imgs), leaf_sh),
                "label": jax.device_put(np.stack(labels), leaf_sh),
            }
        return out

    def _step_via_chunk(self, step: int) -> bool:
        """Warmup steps must run through train_one_batch (their
        wall-clock feeds SyncConfig and the bootstrap fires between
        them); the streaming stager only starts once the schedule is
        stable — i.e. post-bootstrap."""
        return self._bootstrapped and step >= self.warmup_steps

    def _chunk_batch_indices(self, pos0, i, bs: int, n: int):
        """Scan-iteration i's (replicas, batch) index grid: replica r
        takes the (i*nreplicas + r)-th consecutive batch."""
        k = i * self.nreplicas + jnp.arange(self.nreplicas)[:, None]
        return (pos0 + k * bs + jnp.arange(bs)[None, :]) % n

    def _device_pure_sync(self) -> bool:
        """True when the protocol round is a pure function of device
        state — Elastic always, RandomSync at full coverage (the
        sampled path draws fresh host index tensors per round) — i.e.
        when rounds can compile INTO the chunk program."""
        return self.protocol == "Elastic" or self.sample_ratio >= 1.0

    def _chunk_len(self, step: int) -> int:
        """Warmup steps run singly (their wall-clock feeds SyncConfig and
        the bootstrap fires between them); afterwards chunks end at the
        sync cadence so a protocol round follows each window — EXCEPT
        when rounds are device-pure and the chunk starts window-aligned:
        then whole windows stack into one multi-window program (the
        rounds run between inner scans, one dispatch for many windows)."""
        if step < self.warmup_steps or not self._bootstrapped:
            return 1
        n = super()._chunk_len(step)
        freq = self.sync_frequency
        if freq > 0:
            # multi-window stacking needs every sub-window's fire to be
            # a REAL sync_now fire: sync_now requires step > warmup, so
            # freq == 1 starting exactly at the warmup boundary would
            # give the first window a spurious round (review-caught r5)
            aligned = (
                self._device_pure_sync()
                and step % freq == 0
                and n >= freq
                and (freq > 1 or step > self.warmup_steps)
            )
            if aligned:
                n = (n // freq) * freq  # whole windows, each ends at a fire
            else:
                # smallest s >= step with (s+1) % freq == 0 (sync_now)
                fire = step + (-(step + 1)) % freq
                n = min(n, fire - step + 1)
        return max(1, int(n))

    def train_chunk(self, step0: int, nsteps: int) -> None:
        freq = self.sync_frequency
        last = step0 + nsteps - 1
        fires = self._bootstrapped and sync_now(
            last, freq, self.warmup_steps
        )
        # FUSED sync windows (r5): when the window ends at a sync fire
        # and the round is device-pure, the round compiles INTO the
        # chunk program; window-aligned chunks additionally stack
        # MULTIPLE windows into one program (outer lax.scan over
        # windows, round between inner scans) — one dispatch where the
        # split engine paid 2 per window.
        fusable = fires and self._device_pure_sync()
        if not fusable:
            super().train_chunk(step0, nsteps)
            if fires:
                with self.timers.phase("sync"):
                    self._sync_round()
            return
        if (
            freq > 0
            and step0 % freq == 0
            and nsteps % freq == 0
            and (freq > 1 or step0 > self.warmup_steps)
        ):
            nwin, wlen = nsteps // freq, freq
        else:
            nwin, wlen = 1, nsteps
        key = (nwin, wlen)
        if key not in self._fused_chunk_fns:
            self._fused_chunk_fns[key] = self._make_fused_chunk_fn(nwin, wlen)
        extra_in = (
            (self.center,) if self.protocol == "Elastic"
            else (self.snapshot, self.center)
        )
        self._run_chunk(self._fused_chunk_fns[key], extra_in, step0, nsteps)

    def _store_chunk_extras(self, extra: tuple) -> None:
        if len(extra) == 1:
            (self.center,) = extra
        else:
            self.snapshot, self.center = extra

    def _make_fused_chunk_fn(self, nwindows: int, wlen: int):
        """jit(nwindows x (wlen-step inner scan + protocol round)): sync
        windows and their rounds reconcile in ONE compiled program.

        Meta spans the WHOLE multi-window range: device-cached, gathers
        wrap over the full dataset; streaming, each inner window indexes
        its slice of the one staged nwindows*wlen-step block."""
        meta = self._chunk_meta(nwindows * wlen)
        body = self._chunk_body(wlen, meta=meta)
        pipes = self._pipelines[id(self.train_net)]
        # per-stream position advance of one window
        adv = {
            name: wlen * self._batches_per_step * pipes[name].batchsize
            for name in meta
        }
        nrec = {name: meta[name][1] for name in meta}
        elastic = self.protocol == "Elastic"
        alpha = (
            self.moving_rate if self.moving_rate > 0 else self.sample_ratio
        )

        def one_window(carry, w, step0, pos0s, data):
            params, state, buffers, *proto = carry
            s0 = step0 + w * wlen
            p0s = {
                name: (pos0s[name] + w * adv[name]) % nrec[name]
                for name in pos0s
            }
            params, state, buffers, metrics = body(
                params, state, buffers, s0, p0s, data
            )
            if elastic:
                (center,) = proto
                params, center = elastic_sync(params, center, alpha)
                return (params, state, buffers, center), metrics
            snapshot, center = proto
            params, snapshot, center = random_sync(
                params, snapshot, center, None, full_coverage=True
            )
            return (params, state, buffers, snapshot, center), metrics

        def fused(params, state, buffers, *rest):
            *proto, step0, pos0s, data = rest
            carry, metrics = jax.lax.scan(
                lambda c, w: one_window(c, w, step0, pos0s, data),
                (params, state, buffers, *proto),
                jnp.arange(nwindows),
            )
            params, state, buffers, *proto = carry
            summed = jax.tree.map(lambda a: a.sum(axis=0), metrics)
            return (params, state, buffers, *proto, summed)

        donate = (0, 1, 2, 3) if elastic else (0, 1, 2, 3, 4)
        return jax.jit(fused, donate_argnums=donate)

    def train_one_batch(self, step: int) -> None:
        import time

        t0 = time.perf_counter()
        super().train_one_batch(step)
        if step < self.warmup_steps:
            # block: dispatch is async, and SyncConfig needs real per-step
            # compute time (the reference times the warmup loop wall-clock
            # around synchronous CPU math, worker.cc:42-48). The first step
            # of this process is excluded — it measures jit compilation.
            jax.block_until_ready(self.params)
            if step > self.start_step:
                self._warmup_time += time.perf_counter() - t0
                self._warmup_timed += 1
        if not self._bootstrapped and step + 1 >= self.warmup_steps:
            self._bootstrap()
        if self._bootstrapped and sync_now(
            step, self.sync_frequency, self.warmup_steps
        ):
            with self.timers.phase("sync"):
                self._sync_round()

    def _bootstrap(self) -> None:
        """Group 0 publishes, others fetch (worker.cc:50-55): center :=
        replica 0; every replica := center. Also runs SyncConfig with the
        measured warmup step time (worker.cc:42-48)."""
        self.center = jax.tree.map(lambda x: x[0], self.params)
        self.params = jax.tree.map(
            lambda c: jnp.broadcast_to(c, (self.nreplicas,) + c.shape),
            self.center,
        )
        self.params = {
            n: jax.device_put(v, self._rep_param_sh[n])
            for n, v in self.params.items()
        }
        if self.protocol == "RandomSync":
            # a genuine copy: the train step donates param buffers, so the
            # snapshot must own separate storage (Elastic ships the full
            # vector and keeps no snapshot, param.h:170-175)
            self.snapshot = {n: jnp.copy(v) for n, v in self.params.items()}
        needs_ratio = (
            self.protocol == "RandomSync" or self.moving_rate <= 0
        )
        if needs_ratio and self.cluster is not None:
            model_mb = sum(
                int(np.prod(s.shape)) for s in self.specs.values()
            ) * 4 / (1024 * 1024)
            steps = max(self._warmup_timed, 1)
            self.sample_ratio = sync_ratio(
                self._warmup_time / steps,
                model_mb,
                self.cluster.nworkers or self.nreplicas,
                self.cluster.nservers,
                self.cluster.bandwidth,
            )
            if jax.process_count() > 1:
                # every rank must agree on the ratio: it selects SPMD
                # programs (full vs sampled sync; fused vs split
                # windows) over jointly-sharded arrays, so rank-local
                # wall-clock noise would make ranks dispatch DIFFERENT
                # computations (the reference's per-worker ratio was
                # harmless — each worker's messages were its own,
                # param_manager.cc:85-93). Rank 0's measurement wins.
                from jax.experimental import multihost_utils

                self.sample_ratio = float(
                    multihost_utils.broadcast_one_to_all(
                        np.float32(self.sample_ratio)
                    )
                )
            self.log(f"Sample Ratio {self.sample_ratio}")
        self._bootstrapped = True

    def _sync_round(self) -> None:
        if self._sync_jit is None:
            self._sync_jit = self._build_sync()
        if self.protocol == "Elastic":
            self.params, self.center = self._sync_jit(
                self.params, self.center
            )
        elif self.sample_ratio >= 1.0:
            # full coverage: random_sync's dense path never reads the
            # indices — don't materialize/ship R*n int32 per param
            self.params, self.snapshot, self.center = self._sync_jit(
                self.params, self.snapshot, self.center, None
            )
        else:
            # STORED shapes, not spec shapes: padded params ravel with
            # different flat offsets, and sampling over the stored
            # coordinate space keeps the index<->value mapping exact
            # (tail coordinates carry zero deltas — harmless)
            shapes = {n: v.shape[1:] for n, v in self.params.items()}
            indices = sample_sync_indices(
                self._sync_rng, shapes, self.nreplicas, self.sample_ratio
            )
            self.params, self.snapshot, self.center = self._sync_jit(
                self.params, self.snapshot, self.center, indices
            )

    # ------------------------------------------------------------------
    # eval / checkpoint / debug over the replica axis
    # ------------------------------------------------------------------

    def _eval_params(self):
        """Evaluate replica 0's view (each reference group tests its own
        replica; group 0 is the one whose params seed the server)."""
        return {n: v[0] for n, v in self.params.items()}

    def _eval_buffers(self):
        # guard counters are scalars (no replica axis) and eval has no
        # use for them anyway
        return {
            n: v[0]
            for n, v in self.buffers.items()
            if n not in GUARD_KEYS
        }

    def _prepare_save(self, folder: str, step: int, snapshot: bool):
        """Extend the base save with the ``.server`` sidecar (center +
        protocol snapshot). Under the zero-stall path the sidecar trees
        are device-COPIED here too: the protocol round's fused program
        donates the live center/snapshot buffers, so the async writer
        must own separate storage. Cross-process allgathers (collective)
        always run here, on the main thread — never in the writer."""
        path, write = super()._prepare_save(folder, step, snapshot)
        if self.center is None:
            return path, write
        from .checkpoint import save_checkpoint

        # server-side trees store LOGICAL shapes like the base npz
        # format (resume re-pads for its mesh)
        if snapshot:
            # ONE compiled unpad+copy program over both trees (like the
            # base _snapshot_trees) — per-leaf eager copies would put a
            # dispatch round trip per param on exactly the step-boundary
            # path the zero-stall feature keeps clear
            if self._sidecar_snap_fn is None:

                def snap_fn(center, snap):
                    return (
                        {
                            n: self._unpad_one(n, jnp.copy(v))
                            for n, v in center.items()
                        },
                        {
                            n: self._unpad_one(n, jnp.copy(v))
                            for n, v in snap.items()
                        },
                    )

                # the sidecar snapshot copies the LIVE center/snapshot
                self._sidecar_snap_fn = jax.jit(snap_fn)  # netlint: disable=JAX003
            center_t, snap_t = self._sidecar_snap_fn(
                self.center, self.snapshot or {}
            )
        else:
            center_t = {
                n: self._unpad_one(n, v) for n, v in self.center.items()
            }
            snap_t = {
                n: self._unpad_one(n, v)
                for n, v in (self.snapshot or {}).items()
            }

        def host_view(v):
            """np-ready view; replica-axis arrays SPAN processes in
            multi-host jobs (e.g. the RandomSync snapshot on the
            2-process topology) — allgather them collectively.
            Every rank walks the same dict order, so the collective
            calls line up."""
            if (
                jax.process_count() > 1
                and not v.is_fully_addressable
                and not v.sharding.is_fully_replicated
            ):
                from jax.experimental import multihost_utils

                return multihost_utils.process_allgather(v, tiled=True)
            if snapshot and hasattr(v, "copy_to_host_async"):
                v.copy_to_host_async()
            return v

        server = {n: host_view(v) for n, v in center_t.items()}
        server["__sample_ratio__"] = jnp.float32(self.sample_ratio)
        snap = (
            {"__snapshot__": {n: host_view(v) for n, v in snap_t.items()}}
            if snap_t
            else None
        )

        def write_with_sidecar() -> None:
            write()
            # the sidecar is a host-global npz, identical on every rank
            # (host_view allgathered it above, on ALL ranks — that part
            # is collective and must stay on the main thread): one
            # writer, like the base npz path
            if jax.process_index() == 0:
                save_checkpoint(path + ".server", step, server, snap)
                if os.path.isdir(path):
                    # sharded save: vouch for the sidecar we just wrote
                    # (marker AFTER sidecar, the commit discipline) —
                    # retention rejects the save if either tears, so a
                    # committed shard save can never pair with a torn
                    # protocol sidecar
                    from ..resilience.coord import write_sidecar_commit

                    write_sidecar_commit(path)

        return path, write_with_sidecar

    def _manifest_extra(self) -> dict:
        """Promise the ``.server`` sidecar in sharded manifests: a save
        where rank 0 died between the shard commit and the sidecar (or
        its marker) must never validate as resumable."""
        if self.center is None:
            return {}
        return {**super()._manifest_extra(), "sidecar": True}

    def _resume(self, path: str) -> None:
        from .checkpoint import load_stream_positions, restore_into
        from .sharded_ckpt import is_sharded_checkpoint

        if is_sharded_checkpoint(path):
            # replica state is small (it must fit every replica on one
            # chip), so the host-assemble reader suffices here — the
            # placement still lands on the replica shardings
            from .sharded_ckpt import (
                ShardedCheckpoint,
                buffer_key,
                param_key,
                state_key,
            )

            with ShardedCheckpoint(path) as ck:
                have = set(ck.keys())
                step = ck.step

                def take(key, init_val):
                    """Assemble with the same loud shape check + model
                    dtype cast as restore_into / _restore_sharded."""
                    if key not in have:
                        return init_val
                    arr = ck.assemble(key)
                    if tuple(arr.shape) != tuple(init_val.shape):
                        raise ValueError(
                            f"checkpoint {path!r}: {key!r} shape "
                            f"{arr.shape} != model shape {init_val.shape}"
                            " (saved with a different replica count?)"
                        )
                    return arr.astype(init_val.dtype, copy=False)

                params = {
                    n: take(param_key(n), v)
                    for n, v in self.params.items()
                }
                state = {
                    n: {
                        s: take(state_key(n, s), v)
                        for s, v in slots.items()
                    }
                    for n, slots in self.state.items()
                }
                buffers = {
                    n: take(buffer_key(n), v)
                    for n, v in self.buffers.items()
                }
                self._resume_streams = dict(ck.streams)
        else:
            # npz checkpoints hold LOGICAL arrays: overlay against the
            # unpadded views, re-pad below at placement
            step, params, state, buffers = restore_into(
                path,
                self._unpad_stored(self.params),
                self._unpad_state(self.state),
                self.buffers,
            )
            params = self._pad_stored(params)
            state = self._pad_state(state)
            # stream positions: consumed by the base __init__ when it
            # builds the pipelines, same as the sync trainer's resume path
            self._resume_streams = load_stream_positions(path)
        self.start_step = max(self.start_step, step)
        # the readers return uncommitted host arrays — put them back on
        # the replica shardings or the donating jit compiles unsharded
        self.params = {
            n: jax.device_put(v, self._rep_param_sh[n])
            for n, v in params.items()
        }
        self.state = {
            n: {
                s: jax.device_put(v, self._rep_param_sh[n])
                for s, v in slots.items()
            }
            for n, slots in state.items()
        }
        self.buffers = {
            # guard counters are replicated scalars, never replica-axis
            n: jax.device_put(
                v,
                replicated(self.mesh) if n in GUARD_KEYS
                else self._rep_buf_sh,
            )
            for n, v in buffers.items()
        }
        server = path + ".server"
        if os.path.exists(server):
            from .checkpoint import load_checkpoint

            repl = replicated(self.mesh)
            _, sv_params, sv_state, _ = load_checkpoint(server)
            ratio = sv_params.pop("__sample_ratio__", None)
            if ratio is not None:
                self.sample_ratio = float(ratio)
            for n, v in sv_params.items():
                if n in self.specs and tuple(v.shape) != self.specs[n].shape:
                    raise ValueError(
                        f"{server}: center param {n!r} shape {v.shape} "
                        f"!= model shape {self.specs[n].shape}"
                    )
            self.center = {
                n: jax.device_put(self._pad_one(n, jnp.asarray(v)), repl)
                for n, v in sv_params.items()
            }
            snap = sv_state.get("__snapshot__")
            if self.protocol == "RandomSync":
                if snap:
                    self.snapshot = {
                        n: jax.device_put(
                            self._pad_one(n, jnp.asarray(v)),
                            self._rep_param_sh[n],
                        )
                        for n, v in snap.items()
                    }
                else:
                    # sidecar from an Elastic run (no snapshot): refresh
                    # snapshots from the restored replicas, like a fresh
                    # RandomSyncParam::Init (param.cc:203-207)
                    self.snapshot = {
                        n: jnp.copy(v) for n, v in self.params.items()
                    }
            self._bootstrapped = True
        self.log(f"resumed from {path} at step {self.start_step}")

    def debug_string(self, step: int) -> str:
        """Replica-0 view of the per-layer dump, plus the replica↔center
        spread (the quantity the protocols are supposed to bound)."""
        # resolve cached __idx__ feeds to real arrays FIRST (the base
        # does this inside the jit; here we're outside), then take
        # replica 0's slice of the (replicas, batch, ...) leaves
        resolved = self._resolve_batch(
            self.train_net, self._last_batch, constrain=False
        )
        batch = {
            name: {k: v[0] for k, v in feed.items()}
            for name, feed in resolved.items()
        }
        rng = jax.random.fold_in(self._step_key, step)
        p0 = self._eval_params()
        _, _, acts = self.train_net.forward(
            p0, batch, training=True, rng=rng,
            buffers=self._eval_buffers(), return_acts=True,
        )
        lines = [
            "debug: "
            + ", ".join(
                f"{name} {float(jnp.mean(jnp.abs(a))):.4g}"
                for name, a in acts.items()
                if hasattr(a, "dtype")
            )
        ]
        if self.center is not None:
            spread = {
                n: float(
                    jnp.max(jnp.abs(self.params[n] - self.center[n]))
                )
                for n in sorted(self.params)
            }
            lines.append(
                "replica spread: "
                + ", ".join(f"{n} {v:.4g}" for n, v in spread.items())
            )
        return "\n".join(lines)
