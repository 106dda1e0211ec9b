"""The training engine.

Replaces the reference's worker-side stack — Worker::Start/Run/RunOneBatch
(src/worker/worker.cc:14-106,187-213), Executor::TrainOneBatch (:304-316),
and ParamManager's init/update machinery (src/worker/param_manager.cc) —
with one `jit`-compiled, sharded XLA train step driven by a plain Python
cadence loop. The Forward/Backward hot loops (worker.cc:240-302), the
per-param WaitUpdate blocking, the bridge spins, and the PS sync sends all
dissolve into that single program; gradient sync across the data-parallel
mesh axis is the psum GSPMD inserts because the loss is a mean over the
sharded batch dim.

Cadence semantics match the reference's predicates exactly
(include/worker/worker.h:118-158): XNow(step) = freq > 0 and
step >= after and (step - after) % freq == 0; tests/validation run *before*
the train step of the step they trigger on (worker.cc:190-200).
"""

from __future__ import annotations

import os
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..config.schema import ClusterConfig, ConfigError, ModelConfig
from ..data.pipeline import BatchPipeline
from ..graph.builder import Net, active_phases, build_net
from ..optim import make_updater
from ..parallel import (
    batch_shardings,
    mesh_from_cluster,
    param_paddings,
    param_shardings,
    replicated,
    state_shardings,
    zero_update_shardings,
)
from ..params import init_params
from ..resilience.guard import (
    GUARD_BAD,
    GUARD_CONSEC,
    GUARD_LR,
    GuardSpec,
    grad_norm_sq,
    guarded_step,
    init_guard_buffers,
)
from ..utils import Performance, Timers, dump_net_json
from .checkpoint import (
    load_stream_positions,
    restore_into,
    save_checkpoint,
)


def _now(step: int, freq: int, after: int) -> bool:
    """The reference's {Display,Test,Validate}Now predicate (worker.h:118-158)."""
    return freq > 0 and step >= after and (step - after) % freq == 0


class Trainer:
    """Builds nets, owns params/updater state, runs the cadence loop."""

    #: subclasses whose step shape is incompatible with on-device batch
    #: gathering switch this off
    _allow_device_cache = True
    #: subclasses that do not thread buffer state (the CD trainer)
    #: reject nets with stateful layers instead of silently dropping them
    _supports_buffers = True
    #: stream batches consumed per train step (the replica trainer feeds
    #: one batch per replica)
    _batches_per_step = 1
    #: engines whose update layout is their own (the replica protocol's
    #: (R, ...)-stacked slots) reject zero_update instead of silently
    #: running it replicated
    _supports_zero_update = True
    #: engines whose gradient sync is their own protocol (the replica
    #: engine's EASGD rounds) reject an active grad_comm block instead
    #: of silently skipping the quantize/overlap machinery
    _supports_grad_comm = True
    #: engines whose step can run the backward per data shard inside the
    #: quantized-ring shard_map (kernels { grad_allreduce:
    #: quantized_ring }); the CD engine's layer-hooked Gibbs walk stays
    #: on the reference seam and rejects the ring instead of silently
    #: keeping fp32 bytes on the wire
    _supports_ring_collective = True

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
        self.cfg = model_cfg
        self.cluster = cluster_cfg
        self.log = log
        self.perf = Performance()
        self.timers = Timers()

        # --- nets (SetupNeuralNet x3, phase-filtered; worker.cc:16-27) ---
        # active_phases is the single source of truth for which nets a job
        # builds — netlint validates exactly the same set
        phases = active_phases(model_cfg)
        self.train_net = build_net(model_cfg, "kTrain")
        self.test_net: Net | None = (
            build_net(model_cfg, "kTest") if "kTest" in phases else None
        )
        self.val_net: Net | None = (
            build_net(model_cfg, "kValidation")
            if "kValidation" in phases
            else None
        )

        # --- params + updater (ParamManager ctor + InitParams) ---
        self.specs = self.train_net.param_specs()
        if model_cfg.updater is None:
            raise ConfigError("model config has no updater block")
        self.updater = make_updater(model_cfg.updater)

        # --- resilience seams (resilience/context.py): the supervisor
        # (or a test) attaches a ResilienceContext; None = inert ---
        self.resilience = None
        # --- telemetry (singa_tpu/obs/): the flight recorder the
        # supervisor attaches via attach_telemetry; None = inert. The
        # step path never writes or syncs for it — events buffer in
        # memory and flush at display cadence (_post_events) ---
        self.telemetry = None
        # every engine supports the guard through the shared _step_core
        # seam (resilience/guard.py guarded_step): each core reports
        # its own finiteness verdict, the wrapper applies the policy
        self._guard = GuardSpec.from_config(model_cfg.resilience)
        root = jax.random.PRNGKey(seed)
        self._init_key, self._step_key = jax.random.split(root)

        # --- mesh + shardings (replaces Cluster/PS/partitioner) ---
        self.mesh = mesh if mesh is not None else mesh_from_cluster(cluster_cfg)
        npipe = dict(self.mesh.shape).get("pipe", 1)
        for net in (self.train_net, self.test_net, self.val_net):
            if net is None:
                continue
            net.bind_mesh(self.mesh)
            if npipe > 1:
                from ..graph.pipeline_plan import plan_stages

                net.pipeline_plan = plan_stages(
                    net, npipe, model_cfg.pipeline_microbatches
                )
                net.pipeline_mesh = self.mesh
        self.param_sh = param_shardings(self.mesh, self.train_net)
        # --- ZeRO-style update sharding (zero_update: reduce-scatter
        # grads, shard-local optimizer, allgather params — arxiv
        # 2004.13336). The updater slots LIVE in the update layout, so
        # per-device opt-state bytes shrink by the data-parallel degree;
        # the step itself picks the layout up via _constrain_grads /
        # _apply_update. ---
        self._zero_sh = None
        if model_cfg.zero_update:
            if not self._supports_zero_update:
                raise ConfigError(
                    f"{type(self).__name__} does not support zero_update "
                    "(the replica protocol owns its own update layout)"
                )
            self._zero_sh = zero_update_shardings(
                self.mesh, self.train_net, self.param_sh, warn=True
            )
        # --- quantized + overlapped gradient collectives (grad_comm:
        # parallel/collectives.py — EQuARX-style scaled int8/bf16 wire
        # cast with error-feedback residuals in the buffer pytree, and
        # reverse-topo bucket chaining so bucket k's reduction overlaps
        # bucket k+1's backward segment). None = today's exact fp32
        # collective, traced bitwise-identically. ---
        from ..parallel.collectives import GradCommSpec

        self._comm = GradCommSpec.from_config(
            model_cfg.grad_comm, model_cfg.kernels, model_cfg.ring
        )
        if self._comm is not None and not self._supports_grad_comm:
            raise ConfigError(
                f"{type(self).__name__} does not support grad_comm mode "
                f"{self._comm.mode!r} (the replica protocol owns its own "
                "gradient sync math)"
            )
        #: grads-keyset -> reverse-topo bucket partition (cached: the CD
        #: engine's greedy layerwise grads cover a param subset)
        self._comm_bucket_cache: dict[frozenset, tuple] = {}
        #: one-shot comm-cost calibration flag (run() probes once)
        self._comm_probe_done = False
        self.state_sh = state_shardings(
            self.param_sh, self.updater.SLOTS, update_sh=self._zero_sh
        )
        #: pad-to-multiple storage for indivisible kLayerPartition dims
        #: (the reference's uneven-partition contract, neuralnet.cc:160-162
        #: — see parallel/shardings.py). Nets slice back to logical shapes
        #: inside forward.
        self.param_pad = param_paddings(self.mesh, self.train_net)
        if self.param_pad:
            logical = {n: self.specs[n].shape for n in self.param_pad}
            for net in (self.train_net, self.test_net, self.val_net):
                if net is not None:
                    net.param_logical = logical
        self.batch_sh = batch_shardings(self.mesh, self.train_net)
        self._repl = replicated(self.mesh)

        # --- quantized ring collective (kernels { grad_allreduce:
        # quantized_ring } — ops/quantized_collective.py): resolve each
        # param's ring chunk dim (zero_update's data dim when the update
        # is sharded — the ring's scatter output IS the update layout —
        # else dim 0) and reject un-runnable geometry at construction,
        # the same fail-early contract as the fused-attention kernel ---
        self._ring_chunk_dims: dict[str, int] | None = None
        self._ring_gather: dict[str, bool] | None = None
        #: hierarchical two-level geometry (intra_axis, inter_axis, K,
        #: M) from hier_ring_geometry — None for the flat ring
        self._ring_hier: tuple | None = None
        if self._comm is not None and self._comm.ring:
            self._setup_ring_collective()

        # --- buffers (stateful layers, e.g. batch-norm running stats) ---
        self._has_buffers = bool(self.train_net.buffer_specs())
        if self._has_buffers and not self._supports_buffers:
            raise ConfigError(
                f"{type(self).__name__} does not support stateful layers "
                f"(buffers: {sorted(self.train_net.buffer_specs())})"
            )

        # --- params + resume, placed on the mesh ---
        self.start_step = model_cfg.step
        #: stateful-layer state; base _materialize_params replaces it
        #: (subclass overrides without buffer support leave it empty)
        self.buffers: dict = {}
        self._materialize_params()

        # --- input pipelines (the Prefetching protocol's host half;
        # base_layer.h:510-537). Pipeline-level prefetch threads stay
        # OFF: with ``prefetch`` on, the DEVICE feeder / chunk stager
        # (data/device_prefetch.py) own the read-ahead thread — it does
        # the host gather AND starts the transfer, and keeping the
        # pipelines thread-free keeps them seek()-able for rollback ---
        if prefetch is None:
            prefetch = model_cfg.prefetch
        self._prefetch_input = bool(prefetch)
        self._pipelines: dict[int, dict[str, BatchPipeline]] = {}
        for net in (self.train_net, self.test_net, self.val_net):
            if net is None:
                continue
            self._pipelines[id(net)] = {
                l.name: BatchPipeline(
                    l.images,
                    l.labels,
                    l.batchsize,
                    random_skip=l.random_skip if net is self.train_net else 0,
                    seed=seed,
                )
                for l in net.datalayers
            }
        # resume: restore each stream to its checkpointed consumed
        # position (completing the Worker::Resume contract — a resumed
        # run continues the data stream, it doesn't replay from the
        # shard start)
        self._seek_resumed_streams()
        #: last step boundary reached (the supervisor's progress gauge)
        self.completed_steps = self.start_step

        # --- device-resident dataset fast path ---
        # When every data layer's decoded shard fits the budget, upload it
        # once and gather batches *inside* the jitted step (host work per
        # step drops to computing a batchsize-long index vector). The
        # reference's per-step shard read + prefetch copy has no useful
        # counterpart once the data already lives in HBM.
        self._dev_data: dict[int, dict[str, dict]] = {}
        #: (net id, layer) -> decoded dtype for uint8-compacted device
        #: data (cached datasets AND streaming staged blocks)
        self._cache_cast: dict[tuple[int, str], jnp.dtype] = {}
        self._cached = self._maybe_cache_datasets(device_cache)

        # --- zero-stall input (data/device_prefetch.py): with prefetch
        # on and no device cache, train batches arrive double-buffered —
        # per-step via the device feeder, or as staged scan-chunk blocks
        # (feeder_mode: cached / stream / prefetch / sync) ---
        if stream_chunks is None:
            stream_chunks = os.environ.get(
                "SINGA_TPU_STREAM_CHUNK", "1"
            ).lower() not in ("0", "off", "false")
        self._stream_chunks = bool(stream_chunks)
        self._feeder = None
        self._stager = None
        #: train-stream positions of batches the trainer actually
        #: consumed (the device feeder reads ahead; checkpoints must not
        #: skip what the step loop never saw)
        self._feeder_positions: dict[str, int] = {}
        if self.feeder_mode != "stream":
            # only the chunk stager consumes the over-budget compaction
            # stash; don't pin a dataset-sized copy for any other mode
            self.__dict__.pop("_compact_train", None)

        if model_cfg.checkpoint_frequency and self._checkpoint_dir() is None:
            self.log(
                "WARNING: checkpoint_frequency is set but no cluster "
                "workspace is configured — no snapshots will be written "
                "(pass -cluster_conf with a workspace field)"
            )

        # --- mixed precision (singa-tpu extension, ModelProto.compute_dtype)
        self._compute_dtype = None
        if model_cfg.compute_dtype:
            try:
                dt = jnp.dtype(model_cfg.compute_dtype)
            except TypeError:
                raise ConfigError(
                    f"unknown compute_dtype {model_cfg.compute_dtype!r}"
                ) from None
            if dt != jnp.float32:
                self._compute_dtype = dt

        # --- the one compiled program ---
        self._train_step = jax.jit(
            self._train_step_entry, donate_argnums=(0, 1, 2)
        )
        # multi-step chunks: scan over the same step body, one dispatch
        # per cadence window instead of per batch (cache keyed by length)
        self._chunk_fns: dict[int, Callable] = {}
        self._eval_steps: dict[int, Callable] = {}
        self._eval_chunk_fns: dict[tuple[int, int], Callable] = {}
        #: unpad? -> compiled snapshot program (zero-stall checkpointing)
        self._snapshot_fns: dict[bool, Callable] = {}
        self._batch_size = self.train_net.batchsize
        #: tokens consumed per train step (LM configs: kSequenceData
        #: feeds (B, S) token batches) — 0 for non-token workloads.
        #: Drives the display line's tok/s readout, straight from the
        #: existing Timers accumulators, no new host syncs.
        self._tokens_per_step = sum(
            l.batchsize * int(np.prod(l.sample_shape)) * self._batches_per_step
            for l in self.train_net.datalayers
            if getattr(l, "TYPE", "") == "kSequenceData"
        )

    # ------------------------------------------------------------------
    # telemetry (singa_tpu/obs/recorder.py)
    # ------------------------------------------------------------------

    def attach_telemetry(self, rec) -> None:
        """Wire the flight recorder in: lifecycle events from the
        cadence loop, and (span mode) every timed phase occurrence as a
        Chrome-trace span. Purely host-side buffer appends — the step
        path gains no write syscalls and no device syncs."""
        self.telemetry = rec
        if rec is not None:
            self.timers.span_sink = rec.phase_span

    # ------------------------------------------------------------------
    # param materialization (overridden by ReplicaTrainer)
    # ------------------------------------------------------------------

    def _materialize_params(self) -> None:
        """Initialize params + updater slots, overlay the resume
        checkpoint (fills Worker::Resume, worker.cc:65-67), and place
        everything onto the mesh shardings. Sharded checkpoints
        (directories) restore shard-to-device without any host gather."""
        from .sharded_ckpt import is_sharded_checkpoint

        params = init_params(self._init_key, self.specs)
        state = self.updater.init_state(params)
        buffers = self.train_net.init_buffers()
        if self._guard is not None:
            # guard counters ride the buffer pytree (reserved dunder
            # keys) so they thread the jitted step and checkpoint with
            # the rest of training state for free
            buffers.update(init_guard_buffers())
        if self._comm is not None and self._comm.wants_residuals:
            # error-feedback residuals ride the buffer pytree the same
            # way (STORED shapes — grads of padded params are padded):
            # they checkpoint, restore, and roll back with training
            # state, so compression error is never silently dropped
            # across a resume
            from ..parallel.collectives import init_residuals

            buffers.update(
                init_residuals(self._pad_stored(params), self._comm)
            )
        #: stream positions waiting to be applied once pipelines exist
        self._resume_streams: dict[str, int] = {}
        if self.cfg.checkpoint and is_sharded_checkpoint(self.cfg.checkpoint):
            # sharded checkpoints hold STORED (padded) arrays; pad the
            # fresh-init fallbacks so every entry matches its sharding
            self._restore_sharded(
                self._pad_stored(params), self._pad_state(state), buffers
            )
            return
        if self.cfg.checkpoint:
            # npz checkpoints hold LOGICAL arrays (save unpads): overlay
            # first, pad after
            ck_step, params, state, buffers = restore_into(
                self.cfg.checkpoint, params, state, buffers
            )
            self._resume_streams = load_stream_positions(self.cfg.checkpoint)
            self.start_step = max(self.start_step, ck_step)
            self.log(
                f"resumed from {self.cfg.checkpoint} at step {self.start_step}"
            )
        params = self._pad_stored(params)
        state = self._pad_state(state)
        self.params = {
            n: jax.device_put(v, self.param_sh[n]) for n, v in params.items()
        }
        self.state = {
            n: {
                s: jax.device_put(v, self.state_sh[n][s])
                for s, v in slots.items()
            }
            for n, slots in state.items()
        }
        self.buffers = {
            n: jax.device_put(v, self._buffer_sharding(n))
            for n, v in buffers.items()
        }

    def _seek_resumed_streams(self) -> None:
        """Apply ``_resume_streams`` to every pipeline (used at init and
        again after a guard rollback re-restores a checkpoint). Any
        input feeder's read-ahead is discarded FIRST — its thread must
        be parked before the streams it draws from are repositioned."""
        self._reset_feeders()
        for net in (self.train_net, self.test_net, self.val_net):
            if net is None:
                continue
            for name, pipe in self._pipelines.get(id(net), {}).items():
                pos = getattr(self, "_resume_streams", {}).get(
                    f"{net.phase}|{name}"
                )
                if pos is not None:
                    pipe.seek(pos)

    # ------------------------------------------------------------------
    # pad-to-multiple storage (uneven kLayerPartition dims)
    # ------------------------------------------------------------------

    def _pad_one(self, name: str, arr):
        """Logical -> stored array: zero-pad the dims param_paddings
        marked so every shard is even (the zero tail is invisible —
        Net.forward slices it off, its gradients are structurally zero,
        and save() strips it). Pad widths apply to the TRAILING dims, so
        replica-stacked (R, ...) arrays pad correctly too."""
        w = self.param_pad.get(name)
        if not w:
            return arr
        widths = ((0, 0),) * (arr.ndim - len(w)) + tuple(w)
        return jnp.pad(arr, widths)

    def _pad_stored(self, params: dict) -> dict:
        if not self.param_pad:
            return params
        return {n: self._pad_one(n, v) for n, v in params.items()}

    def _pad_state(self, state: dict) -> dict:
        if not self.param_pad:
            return state
        return {
            n: {s: self._pad_one(n, v) for s, v in slots.items()}
            for n, slots in state.items()
        }

    def _unpad_one(self, name: str, arr):
        """Stored -> logical (trailing-dims slice keeps any leading
        replica axis)."""
        if name not in self.param_pad:
            return arr
        logical = self.specs[name].shape
        return arr[(Ellipsis, *(slice(0, s) for s in logical))]

    def _unpad_stored(self, params: dict) -> dict:
        if not self.param_pad:
            return params
        return {n: self._unpad_one(n, v) for n, v in params.items()}

    def _unpad_state(self, state: dict) -> dict:
        if not self.param_pad:
            return state
        return {
            n: {s: self._unpad_one(n, v) for s, v in slots.items()}
            for n, slots in state.items()
        }

    def _restore_sharded(self, params, state, buffers) -> None:
        """Place a sharded checkpoint directly onto the mesh: every
        saved array goes shard-to-device (no host-global assembly when
        the topology matches); a checkpoint written by a DIFFERENT
        process count or mesh reshards — each target shard assembled
        from the intersecting saved boxes (resilience/reshard.py), so
        a drained N-rank job resumes on M ranks. Entries absent from
        the checkpoint keep their fresh init."""
        from ..resilience.reshard import Resharder
        from .sharded_ckpt import (
            ShardedCheckpoint,
            buffer_key,
            param_key,
            state_key,
        )

        with ShardedCheckpoint(self.cfg.checkpoint) as ck:
            # mesh admission first: a target that cannot host the
            # manifest's specs must reject loudly (ReshardError; the
            # static mirror is netlint ELA001), never half-restore
            resharder = Resharder(ck, dict(self.mesh.shape))
            have = set(ck.keys())

            def restore(key, init_val, sharding, pname=None):
                if key not in have:
                    return jax.device_put(init_val, sharding)
                saved = tuple(ck.manifest["arrays"][key]["shape"])
                expect = tuple(init_val.shape)
                if saved != expect:
                    # uneven-partition storage is mesh-dependent: a
                    # checkpoint written on a different model-axis width
                    # padded this param differently. Normalize through
                    # the logical shape (slice the saved tail, re-pad
                    # for THIS mesh) via host assembly.
                    logical = (
                        self.specs[pname].shape
                        if pname is not None and pname in self.specs
                        else None
                    )
                    lead = len(expect) - len(logical) if logical else 0
                    if (
                        logical is not None
                        and len(saved) == len(expect)
                        and saved[:lead] == expect[:lead]
                        and all(
                            s >= l for s, l in zip(saved[lead:], logical)
                        )
                    ):
                        arr = ck.assemble(key)[
                            (Ellipsis, *(slice(0, l) for l in logical))
                        ]
                        arr = self._pad_one(pname, jnp.asarray(arr))
                        return jax.device_put(
                            arr.astype(init_val.dtype), sharding
                        )
                    raise ValueError(
                        f"checkpoint {self.cfg.checkpoint!r}: {key!r} "
                        f"shape {saved} != model shape {init_val.shape}"
                    )
                # cast to the MODEL's dtype: a checkpoint written at a
                # different precision must not leak its dtype into the
                # donating jitted step
                return resharder.place(key, sharding, dtype=init_val.dtype)

            self.params = {
                n: restore(param_key(n), v, self.param_sh[n], pname=n)
                for n, v in params.items()
            }
            self.state = {
                n: {
                    s: restore(
                        state_key(n, s), v, self.state_sh[n][s], pname=n
                    )
                    for s, v in slots.items()
                }
                for n, slots in state.items()
            }
            self.buffers = {
                n: restore(buffer_key(n), v, self._buffer_sharding(n))
                for n, v in buffers.items()
            }
            # stream positions are CONSUMED-batch counts against the
            # GLOBAL stream (each rank advances the same cursor; the
            # batch shardings slice each batch, not the stream), so
            # they are world-size-invariant: restoring them verbatim on
            # M ranks replays and skips nothing
            self._resume_streams = dict(ck.streams)
            self.start_step = max(self.start_step, ck.step)
            from ..resilience.coord import process_count

            if resharder.saved_nprocs != process_count():
                self.log(
                    f"elastic restore: checkpoint written by "
                    f"{resharder.saved_nprocs} process(es), resuming on "
                    f"{process_count()}"
                )
            reshard_note = resharder.summary()
            if reshard_note is not None:
                self.log(f"elastic restore: {reshard_note}")
        self.log(
            f"resumed sharded from {self.cfg.checkpoint} at step "
            f"{self.start_step}"
        )

    # ------------------------------------------------------------------
    # device-resident dataset cache
    # ------------------------------------------------------------------

    @staticmethod
    def _compact_cache_array(images: np.ndarray):
        """-> (storage array, original dtype) for the device cache.

        Raw record pixels are byte-valued floats (uint8 widened at
        decode, data/pipeline.py); storing them as uint8 quarters the
        HBM the per-step gather reads — at ResNet scale the gather of a
        (B, 3, 256, 256) fp32 batch is ~100 MB of pure bandwidth before
        any compute. The round trip is exact: values are integers in
        [0, 255], and _resolve_batch casts back to the original dtype
        inside the jitted step (so every consumer sees identical
        arrays). Non-byte-valued data stays as-is."""
        if images.dtype == np.uint8 or images.size == 0:
            return images, images.dtype
        if (
            np.issubdtype(images.dtype, np.floating)
            or np.issubdtype(images.dtype, np.integer)
        ):
            lo, hi = images.min(), images.max()
            if 0 <= lo and hi <= 255 and np.all(images == np.trunc(images)):
                return images.astype(np.uint8), images.dtype
        return images, images.dtype

    def _maybe_cache_datasets(self, enabled: bool | None) -> bool:
        """Upload every net's dataset to the mesh (replicated) when it
        fits SINGA_TPU_DEVICE_CACHE_MB (default 512). Byte-valued data
        is stored uint8 (see _compact_cache_array). Explicit
        ``device_cache=False`` or a cache-incompatible subclass wins."""
        if not self._allow_device_cache or enabled is False:
            return False
        nets = [n for n in (self.train_net, self.test_net, self.val_net)
                if n is not None]
        compact: dict[tuple[int, str], tuple[np.ndarray, np.dtype]] = {}
        total = 0
        for net in nets:
            for l in net.datalayers:
                arr, orig = self._compact_cache_array(np.asarray(l.images))
                compact[(id(net), l.name)] = (arr, orig)
                total += arr.nbytes + l.labels.nbytes
        if enabled is None:
            limit = float(os.environ.get("SINGA_TPU_DEVICE_CACHE_MB", "512"))
            if total > limit * 1e6:
                # over budget -> the stream stager will want exactly the
                # train net's compacted arrays; hand them over instead of
                # re-scanning (and re-copying) a cache-sized dataset
                self._compact_train = {
                    name: compact[(nid, name)]
                    for nid, name in compact
                    if nid == id(self.train_net)
                }
                return False
        if total == 0:
            return False
        for net in nets:
            self._dev_data[id(net)] = {}
            for l in net.datalayers:
                arr, orig = compact[(id(net), l.name)]
                if arr.dtype != orig:
                    self._cache_cast[(id(net), l.name)] = jnp.dtype(orig)
                self._dev_data[id(net)][l.name] = {
                    "image": jax.device_put(jnp.asarray(arr), self._repl),
                    "label": jax.device_put(
                        jnp.asarray(l.labels), self._repl
                    ),
                }
        return True

    def _resolve_batch(self, net: Net, batch: dict, constrain: bool = True):
        """Turn ``__idx__``-tagged feeds (device-cached mode) into real
        per-batch arrays by gathering on device; host-assembled feeds pass
        through unchanged. Runs inside the jitted step, so the gather and
        everything downstream compile into one program."""
        out = {}
        for name, feed in batch.items():
            if "__idx__" not in feed:
                out[name] = feed
                continue
            idx = feed["__idx__"]
            img = jnp.take(feed["image"], idx, axis=0)
            lbl = jnp.take(feed["label"], idx, axis=0)
            # compact uint8 cache: restore the decoded dtype AFTER the
            # gather, so consumers see exactly the host-path arrays but
            # the HBM read was a quarter the size
            cast = getattr(self, "_cache_cast", {}).get((id(net), name))
            if cast is not None:
                img = img.astype(cast)
            if constrain and net is self.train_net:
                sh = self.batch_sh.get(name)
                if sh is not None:
                    img = jax.lax.with_sharding_constraint(img, sh["image"])
                    lbl = jax.lax.with_sharding_constraint(lbl, sh["label"])
            out[name] = {"image": img, "label": lbl}
        return out

    # ------------------------------------------------------------------
    # compiled step functions
    # ------------------------------------------------------------------

    def _train_step_entry(self, params, state, buffers, step, batch, rng):
        """Jit entry: resolve cached batches, then run the (possibly
        subclass-overridden) step body. Buffers always thread through —
        an empty dict for stateless nets costs nothing."""
        batch = self._resolve_batch(self.train_net, batch)
        return self._train_step_fn(params, state, buffers, step, batch, rng)

    def _cast_compute(self, tree):
        """Cast float leaves to the compute dtype (bf16 matmuls on the
        MXU); params keep fp32 masters — the cast sits inside loss_fn so
        its transpose upcasts the grads back to fp32 automatically."""
        if self._compute_dtype is None:
            return tree
        dt = self._compute_dtype
        return jax.tree.map(
            lambda x: x.astype(dt)
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
            else x,
            tree,
        )

    def _train_step_fn(self, params, state, buffers, step, batch, rng):
        """One train step: the engine's ``_step_core`` update, wrapped
        by the shared divergence guard when one is configured
        (resilience/guard.py guarded_step — the verdict folds into the
        step's existing outputs, zero per-step host syncs)."""
        if self._guard is None:
            params, state, buffers, metrics, _ = self._step_core(
                params, state, buffers, step, batch, rng, None
            )
            return params, state, buffers, metrics
        return guarded_step(
            self._step_core, params, state, buffers, step, batch, rng
        )

    def _step_core(self, params, state, buffers, step, batch, rng, lr_scale):
        """One forward+backward+update -> (params, state, buffers,
        metrics, ok). Stateful layers' buffer updates (batch-norm
        running stats) ride the has_aux output — plain forward values,
        outside any gradient path.

        The engine-specific half of the guard seam: ``lr_scale`` is
        None for unguarded runs (``ok`` is then unused); guarded, it is
        the accumulated rollback LR backoff — multiplying the grads
        inside the program (scale 1.0 is a bitwise no-op) means backing
        off needs no recompile and no host sync — and ``ok`` is this
        engine's finiteness verdict: loss + global grad-norm."""
        if self._comm is not None and self._comm.ring:
            # the int8-on-the-wire ring runs the backward per data
            # shard (shard_map) so the reduction sees local partials —
            # a different program shape, same seam contract
            return self._ring_step_core(
                params, state, buffers, step, batch, rng, lr_scale
            )

        def loss_fn(p):
            loss, metrics, new_buffers = self.train_net.forward(
                self._cast_compute(p), self._cast_compute(batch),
                training=True, rng=rng,
                buffers=buffers, return_buffers=True,
            )
            return loss, (metrics, new_buffers)

        (loss, (metrics, new_buffers)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        # grad_comm seam: zero_update pins the grads to the update
        # layout FIRST, so the data-axis grad sync lowers to a
        # reduce-scatter and everything downstream — the guard's norm,
        # the updater math — runs on each rank's shard only; quantized
        # mode additionally casts each bucket to the low-precision wire
        # format around that constraint, banking the compression error
        # in the residual buffers (the guard and the update consume the
        # DEQUANTIZED grads unchanged)
        grads, comm_bufs = self._reduce_grads(grads, buffers)
        new_buffers = {**new_buffers, **comm_bufs}
        ok = None
        if lr_scale is not None:
            ok = jnp.isfinite(loss) & jnp.isfinite(grad_norm_sq(grads))
            grads = jax.tree.map(
                lambda g: g * lr_scale.astype(g.dtype), grads
            )
        params, state = self._apply_update(step, params, grads, state)
        return params, state, new_buffers, metrics, ok

    # ------------------------------------------------------------------
    # update sharding (zero_update — parallel/shardings.py)
    # ------------------------------------------------------------------

    @property
    def update_mode(self) -> str:
        """How the weight update is laid out across the data axis:
        ``replicated`` (every rank applies the full update — the
        reference's ParamSync semantics) or ``zero`` (reduce-scatter
        grads, shard-local optimizer, allgather params)."""
        return "zero" if self._zero_sh is not None else "replicated"

    def opt_state_bytes_per_device(self) -> int:
        """Bytes of updater state resident on EACH device — the
        footprint zero_update shrinks by the data-parallel degree.
        Computed from the shard shapes: no host transfer, no sync."""
        total = 0
        for slots in self.state.values():
            for v in slots.values():
                shape = v.sharding.shard_shape(v.shape)
                total += int(np.prod(shape, dtype=np.int64)) * v.dtype.itemsize
        return total

    def _constrain_grads(self, grads: dict) -> dict:
        """Zero mode: constrain each grad to its update sharding, so
        GSPMD replaces the grad all-reduce with a reduce-scatter (each
        rank receives only its shard's sum) and the guard's grad-norm
        becomes shard-local partials psum'd to one scalar — no gather.
        Identity when the update is replicated. ``grads`` may cover a
        subset of params (the CD engine's greedy layerwise grads)."""
        if self._zero_sh is None:
            return grads
        return {n: self._constrain_one(n, g) for n, g in grads.items()}

    def _constrain_one(self, name: str, arr):
        """Per-tensor half of _constrain_grads — the ``constrain``
        callback the grad_comm reduction applies to each QUANTIZED wire
        tensor, so the data-axis reduce-scatter's operand is the
        low-precision value, not the fp32 gradient."""
        if self._zero_sh is None:
            return arr
        return jax.lax.with_sharding_constraint(arr, self._zero_sh[name])

    # ------------------------------------------------------------------
    # gradient collectives (grad_comm — parallel/collectives.py)
    # ------------------------------------------------------------------

    @property
    def comm_mode(self) -> str:
        """How gradients cross the data axis: ``exact`` (today's fp32
        collective) or ``quantized`` (scaled int8/bf16 wire cast with
        error feedback)."""
        return (
            "quantized"
            if self._comm is not None and self._comm.quantized
            else "exact"
        )

    @property
    def comm_dtype(self) -> str:
        """Wire dtype of the quantized gradient collective ("" when the
        collective is exact fp32)."""
        if self._comm is not None and self._comm.quantized:
            return self._comm.dtype
        return ""

    def _comm_buckets(self, names: frozenset) -> tuple:
        """Reverse-topo bucket partition for this grads keyset, cached
        (the CD engine's layerwise grads cover a param subset)."""
        if names not in self._comm_bucket_cache:
            from ..parallel.collectives import reverse_topo_buckets

            self._comm_bucket_cache[names] = reverse_topo_buckets(
                self.train_net, names, self._comm.buckets, self.specs
            )
        return self._comm_bucket_cache[names]

    def _reduce_grads(self, grads: dict, buffers: dict):
        """The grad_comm seam around _constrain_grads: -> (update-ready
        grads, residual-buffer updates). With no active ``grad_comm``
        block this IS _constrain_grads — the exact path traces
        bitwise-identically to pre-grad_comm main."""
        if self._comm is None:
            return self._constrain_grads(grads), {}
        from ..parallel.collectives import reduce_gradients

        return reduce_gradients(
            grads,
            buffers,
            self._comm,
            self._comm_buckets(frozenset(grads)),
            self._constrain_one,
        )

    # ------------------------------------------------------------------
    # quantized ring collective (kernels { grad_allreduce:
    # quantized_ring } — ops/quantized_collective.py)
    # ------------------------------------------------------------------

    @property
    def grad_wire_impl(self) -> str:
        """Which wire implementation the data-axis gradient reduction
        runs ("" when no grad_comm machinery is active): ``reference``
        (quantize around the GSPMD psum — fp32 bytes on the wire),
        ``quantized_ring`` (int8 bytes in explicit ppermutes), or
        ``q8_hier`` (the hierarchical two-level ring)."""
        if self._comm is None:
            return ""
        return self._comm.wire_impl

    def _ring_ndata(self) -> int:
        """Total reduction width: the data-axis width for the flat
        ring, K*M for the hierarchical form (the named-axes variant
        reduces over the PRODUCT of its two mesh axes)."""
        if self._ring_hier is not None:
            return self._ring_hier[2] * self._ring_hier[3]
        return dict(self.mesh.shape).get("data", 1)

    def _ring_axes(self) -> tuple:
        """Mesh axes the ring's chunk layout shards over, major-first
        (chunk index = g*K + p, so the inter axis is the major one)."""
        if self._ring_hier is not None:
            intra_ax, inter_ax, _, _ = self._ring_hier
            if intra_ax != inter_ax:
                return (inter_ax, intra_ax)
        return ("data",)

    def _setup_ring_collective(self) -> None:
        """Resolve the ring's per-param geometry and reject un-runnable
        configs loudly at construction (netlint KRN002 is the static
        mirror, consulting the SAME ``ring_reducible`` /
        ``hier_ring_geometry`` predicates). The flat ring keeps its
        loud composed-mesh rejection; ``q8_hier`` is the acceptance
        path — any mesh whose reduction the two-level factorization
        covers runs, with the chunkability predicates applied at the
        TOTAL width K*M."""
        from ..ops.quantized_collective import (
            hier_ring_geometry,
            ring_fusable,
            ring_reducible,
        )

        impl = self._comm.wire_impl
        if not self._supports_ring_collective:
            raise ConfigError(
                f"{type(self).__name__} does not support kernels "
                f"{{ grad_allreduce: {impl} }} (the ring wraps the "
                "backward in a data-axis shard_map; this engine's step "
                "does not take that shape)"
            )
        widths = dict(self.mesh.shape)
        if self._comm.hier:
            geom = hier_ring_geometry(widths, self._comm)
            if isinstance(geom, str):
                raise ConfigError(
                    f"kernels {{ grad_allreduce: q8_hier }} cannot "
                    f"run: {geom}"
                )
            if geom[0] != geom[1] and self._zero_sh is not None:
                raise ConfigError(
                    "kernels { grad_allreduce: q8_hier } with named "
                    "intra_axis/inter_axis does not compose with "
                    "zero_update (the update layout shards over the "
                    "data axis only) — use the factored "
                    "ring { intra_degree } form"
                )
            self._ring_hier = geom
        else:
            other = {
                a: w for a, w in widths.items() if a != "data" and w > 1
            }
            if other:
                raise ConfigError(
                    "kernels { grad_allreduce: quantized_ring } runs over "
                    f"the data axis only, but the mesh also shards {other} "
                    "— kernels { grad_allreduce: q8_hier } with a "
                    "ring { intra_axis/inter_axis } block is the "
                    "hierarchical (intra/inter-slice) two-level form "
                    "that covers composed meshes"
                )
        ndata = self._ring_ndata()
        bs = self.train_net.batchsize
        if bs % max(1, ndata):
            raise ConfigError(
                f"{impl} needs the data-reduction width ({ndata}) to "
                f"divide the batch ({bs}): each shard computes its own "
                "local partial gradients"
            )
        if self.train_net.buffer_specs():
            # batch-stat layers (kBatchNorm is the only buffer owner)
            # get their "sync BN over the global batch" semantics from
            # GSPMD's implicit psums (layers/norm.py); inside the
            # ring's shard_map the forward sees only its local shard,
            # so batch moments would silently become per-shard stats —
            # a biased variance, not the documented tolerance caveat
            raise ConfigError(
                f"kernels {{ grad_allreduce: {impl} }} cannot run "
                "a net with batch-statistics buffers (kBatchNorm): the "
                "ring's per-shard backward would turn sync BatchNorm "
                "into local-shard BN — cross-shard batch moments inside "
                "the ring are a ROADMAP carry-over"
            )
        chunk_dims: dict[str, int] = {}
        gather: dict[str, bool] = {}
        for name, spec in self.specs.items():
            d, g = 0, True
            if self._zero_sh is not None:
                d_zero = self._zero_data_dim(name)
                if d_zero is not None:
                    # the ring's scatter output lands each shard's chunk
                    # exactly where the zero update wants it — the
                    # allgather phase is skipped for this param
                    d, g = d_zero, False
            chunk_dims[name] = d
            gather[name] = g
        shapes = {n: s.shape for n, s in self.specs.items()}
        reason = ring_reducible(shapes, ndata, chunk_dims)
        if reason is not None:
            raise ConfigError(
                f"kernels.grad_allreduce {impl} cannot run: "
                f"{reason}"
            )
        if not self._comm.interpret:
            reason = ring_fusable(
                shapes, ndata, chunk_dims, interpret=False
            )
            if reason is not None:
                raise ConfigError(
                    f"kernels.grad_allreduce {impl} with "
                    f"interpret off cannot run: {reason}"
                )
        self._ring_chunk_dims = chunk_dims
        self._ring_gather = gather

    def _zero_data_dim(self, name: str) -> int | None:
        """The dim zero_update lays over the data axis for ``name``
        (None = the replicate fallback: no divisible free dim)."""
        spec = self._zero_sh[name].spec
        for i, entry in enumerate(spec):
            axes = entry if isinstance(entry, tuple) else (entry,)
            if "data" in axes:
                return i
        return None

    def _buffer_sharding(self, name: str):
        """Placement for one buffer: a ring-mode error-feedback
        residual lives in its ring-chunk layout — each data shard owns,
        and banks the owner-side quantization error for, exactly its
        own chunk, which is how the shard_map step emits it — so
        sharded checkpoints save and restore matching shard boxes.
        Everything else (and every buffer off the ring path) is
        replicated."""
        from ..parallel.collectives import RESIDUAL_PREFIX

        if self._ring_chunk_dims is not None and name.startswith(
            RESIDUAL_PREFIX
        ):
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            d = self._ring_chunk_dims.get(name[len(RESIDUAL_PREFIX):])
            if d is not None:
                axes = self._ring_axes()
                entry = axes if len(axes) > 1 else axes[0]
                return NamedSharding(
                    self.mesh, P(*([None] * d + [entry]))
                )
        return self._repl

    def _ring_specs(self):
        """(grad out_specs, residual specs) pytrees for the ring
        shard_map: gathered grads come out replicated (bitwise
        identical on every shard by the allgather-from-identical-bytes
        construction), zero-mode grads in the update layout, residuals
        chunk-sharded over the data axis (each shard owns — and banks
        the quantization error for — exactly its own chunk)."""
        from jax.sharding import PartitionSpec as P

        from ..parallel.collectives import residual_key

        axes = self._ring_axes()
        entry = axes if len(axes) > 1 else axes[0]

        def cspec(name):
            d = self._ring_chunk_dims[name]
            return P(*([None] * d + [entry]))

        gspecs = {
            n: (P() if self._ring_gather[n] else cspec(n))
            for n in self.specs
        }
        rspecs = (
            {residual_key(n): cspec(n) for n in self.specs}
            if self._comm.wants_residuals
            else {}
        )
        return gspecs, rspecs

    def _ring_step_core(
        self, params, state, buffers, step, batch, rng, lr_scale
    ):
        """The quantized-ring twin of ``_step_core``: forward + backward
        run PER DATA SHARD inside a shard_map, so each shard holds its
        own local partial gradients — the thing GSPMD's implicit psum
        never exposes — and the data-axis reduction is the explicit
        int8-on-the-wire ring (ops/quantized_collective.py). Loss and
        metrics are pmean'd across shards (equal shard sizes, so the
        mean of per-shard means is the global mean; reduction-order
        parity with the reference path is tolerance-level, the PR 9
        cross-shape caveat). Nets with batch-stat buffers are rejected
        at construction — inside shard_map their moments would be
        per-shard, not the sync-BN semantics GSPMD gives. Everything
        downstream — the guard verdict, lr backoff, the updater — runs
        on the reduced grads unchanged."""
        from jax.sharding import PartitionSpec as P

        from ..ops.quantized_collective import ring_reduce_gradients
        from ..parallel.collectives import is_residual_key, residual_key

        spec = self._comm
        ndata = self._ring_ndata()
        hier = self._ring_hier
        axes = self._ring_axes()
        bentry = axes if len(axes) > 1 else axes[0]
        buckets = self._comm_buckets(frozenset(params))
        res_in = {
            k: v for k, v in buffers.items() if is_residual_key(k)
        }
        passthru = {
            k: v for k, v in buffers.items() if not is_residual_key(k)
        }
        gspecs, rspecs = self._ring_specs()

        def body(params, passthru, res, batch, rng):
            if len(axes) > 1:
                # named-axes hier: linear rank = g*K + p (the batch's
                # composite in_spec slices in the same order)
                me = jax.lax.axis_index(axes[0]) * hier[2] + (
                    jax.lax.axis_index(axes[1])
                )
            else:
                me = jax.lax.axis_index("data")
            lrng = jax.random.fold_in(rng, me)

            def loss_fn(p):
                loss, metrics, new_buffers = self.train_net.forward(
                    self._cast_compute(p), self._cast_compute(batch),
                    training=True, rng=lrng,
                    buffers=passthru, return_buffers=True,
                )
                return loss, (metrics, new_buffers)

            (loss, (metrics, new_buffers)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params)
            # each shard's loss is its LOCAL batch mean; /ndata makes
            # the ring's cross-shard sum the global mean gradient
            grads = {n: g / ndata for n, g in grads.items()}
            grads, new_res = ring_reduce_gradients(
                grads, res, buckets,
                axis_name="data", nshards=ndata,
                chunk_dims=self._ring_chunk_dims,
                gather=self._ring_gather,
                dtype=spec.dtype,
                error_feedback=spec.error_feedback,
                overlapped=spec.overlapped,
                residual_key=residual_key,
                fused_hop=not spec.interpret,
                fused_interpret=False,
                hier=hier,
            )

            def fold(tree):
                # float leaves are per-shard means -> pmean; non-float
                # leaves (e.g. integer counters riding the buffer
                # pytree) pass through untouched — they entered
                # replicated and nothing here wrote them
                return jax.tree.map(
                    lambda x: jax.lax.pmean(x, axes)
                    if jnp.issubdtype(x.dtype, jnp.floating)
                    else x,
                    tree,
                )

            return (
                jax.lax.pmean(loss, axes),
                fold(metrics),
                fold(new_buffers),
                grads,
                new_res,
            )

        fn = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(P(), P(), rspecs, P(bentry), P()),
            out_specs=(P(), P(), P(), gspecs, rspecs),
            check_vma=False,
        )
        loss, metrics, new_buffers, grads, new_res = fn(
            params, passthru, res_in, batch, rng
        )
        new_buffers = {**new_buffers, **new_res}
        ok = None
        if lr_scale is not None:
            ok = jnp.isfinite(loss) & jnp.isfinite(grad_norm_sq(grads))
            grads = jax.tree.map(
                lambda g: g * lr_scale.astype(g.dtype), grads
            )
        params, state = self._apply_update(step, params, grads, state)
        return params, state, new_buffers, metrics, ok

    def _ring_reduce_probe(self, grads: dict, res: dict):
        """The ring reduction in isolation (no forward) for the comm
        probe (``_record_comm_probe``): each shard treats the replicated
        input as its local partial, so the program exercises exactly the
        step's quantize/ppermute/accumulate work."""
        from jax.sharding import PartitionSpec as P

        from ..ops.quantized_collective import ring_reduce_gradients
        from ..parallel.collectives import residual_key

        spec = self._comm
        ndata = self._ring_ndata()
        buckets = self._comm_buckets(frozenset(grads))
        gspecs, rspecs = self._ring_specs()
        gspecs = {n: gspecs[n] for n in grads}
        rspecs = {
            residual_key(n): rspecs[residual_key(n)]
            for n in grads
            if residual_key(n) in rspecs
        }

        def body(grads, res):
            return ring_reduce_gradients(
                {n: g / ndata for n, g in grads.items()}, res, buckets,
                axis_name="data", nshards=ndata,
                chunk_dims=self._ring_chunk_dims,
                gather=self._ring_gather,
                dtype=spec.dtype,
                error_feedback=spec.error_feedback,
                overlapped=spec.overlapped,
                residual_key=residual_key,
                fused_hop=not spec.interpret,
                fused_interpret=False,
                hier=self._ring_hier,
            )

        fn = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(P(), rspecs), out_specs=(gspecs, rspecs),
            check_vma=False,
        )
        return fn(grads, res)

    def wire_bytes_model(self, ndata: int | None = None) -> dict | None:
        """Both sides of the wire-bytes comparison for THIS trainer's
        real param set (None with no grad_comm machinery): modeled
        per-device bytes crossing an ``ndata``-wide data axis per step
        as ``{"reference": .., "quantized_ring": .., "ndata": ..}`` —
        the reference fp32 ring all-reduce (reduce-scatter alone under
        zero_update) vs the quantized ring's ppermute payloads. The one
        place the model's trainer plumbing (sizes, buckets, gather map,
        zero sharding) lives: the ``kernel_select`` event and the
        wire-bytes audit of tests/test_quantized_collective.py consult
        it. ``ndata`` defaults to the mesh's real data-axis width; a
        caller may price a nominal width when the host's own axis is
        1-wide (an empty wire) — a nominal width the chunking could not
        actually divide is halved until ``ring_reducible`` accepts it
        (never below the real width), so
        the model's floor divisions stay exact and the priced geometry
        is one the ring could really run. Under ``q8_hier`` the dict
        additionally carries the per-level split — ``intra`` /
        ``inter`` / ``intra_degree`` — with ``quantized_ring`` staying
        the active ring's TOTAL (intra + inter), so every downstream
        consumer of the total keeps working unchanged."""
        from ..ops.quantized_collective import (
            modeled_wire_bytes,
            modeled_wire_bytes_levels,
            reference_wire_bytes,
            ring_reducible,
        )

        if self._comm is None:
            return None
        hier_k = 0
        if self._comm.hier and self._ring_hier is not None:
            hier_k = self._ring_hier[2]
            if ndata is not None and ndata > self._ring_ndata() and (
                self._comm.intra_degree > 0
            ):
                # nominal pricing keeps the CONFIGURED factored degree
                # (the host's real axis may be 1-wide, degenerating the
                # runtime geometry to 1x1)
                hier_k = self._comm.intra_degree
        n = self._ring_ndata() if ndata is None else ndata
        if ndata is not None and n > self._ring_ndata():
            shapes = {nm: s.shape for nm, s in self.specs.items()}
            while n > self._ring_ndata() and (
                ring_reducible(shapes, n, self._ring_chunk_dims)
                is not None
                or (hier_k > 1 and n % hier_k)
            ):
                n //= 2
            n = max(n, self._ring_ndata())
        sizes = {
            nm: int(np.prod(s.shape, dtype=np.int64))
            for nm, s in self.specs.items()
        }
        out = {
            "reference": int(
                reference_wire_bytes(
                    sizes, n, scatter_only=self._zero_sh is not None
                )
            ),
            "quantized_ring": int(
                modeled_wire_bytes(
                    sizes, self._comm_buckets(frozenset(sizes)), n,
                    dtype=self._comm.dtype, gather=self._ring_gather,
                )
            ),
            "ndata": n,
        }
        if hier_k:
            k = hier_k if n % hier_k == 0 else 1
            # the flat single-level ring over the same n — the baseline
            # the hierarchical gate (inter x intra_degree <= flat)
            # divides against
            out["flat_ring"] = out["quantized_ring"]
            levels = modeled_wire_bytes_levels(
                sizes, self._comm_buckets(frozenset(sizes)), n,
                intra_degree=k, dtype=self._comm.dtype,
                gather=self._ring_gather,
            )
            out["quantized_ring"] = levels["total"]
            out["intra"] = levels["intra"]
            out["inter"] = levels["inter"]
            out["intra_degree"] = k
        return out

    def modeled_wire_bytes_per_step(self) -> int:
        """Modeled per-device bytes the ACTIVE gradient collective
        moves across the data axis per step (0 with no machinery or a
        1-wide axis) — ``wire_bytes_model``'s entry for the configured
        wire implementation, what the ``kernel_select`` telemetry event
        reports."""
        model = self.wire_bytes_model()
        if model is None:
            return 0
        return model[
            "quantized_ring" if self._comm.ring else "reference"
        ]

    def _maybe_emit_kernel_select(self) -> None:
        """Run-start ``kernel_select`` event for the grad_allreduce
        site (the scheduler emits the serving-tier sibling): which wire
        implementation this run reduces gradients through, plus the
        modeled per-step wire bytes — what ``trace.py --summarize``
        reports as ``grad_wire_impl`` / ``wire_bytes_per_step``."""
        if self.telemetry is None or self._comm is None:
            return
        self.telemetry.event(
            "kernel_select",
            step=self.start_step,
            site="train.grad_allreduce",
            impl=self.grad_wire_impl,
            wire_bytes_per_step=int(self.modeled_wire_bytes_per_step()),
            wire_dtype=self._comm.dtype if self._comm.quantized else "f32",
        )

    def _maybe_record_comm_probe(self) -> None:
        """One-shot comm-cost calibration (the flight recorder's ``comm``
        span track): when the grad_comm machinery is active and
        telemetry is attached, time a short isolated chained-reduce
        program under the ``comm`` phase — the span's duration over its
        round count is the per-step cost of the gradient-collective
        machinery, which tools/trace.py --summarize reports next to the
        train/data stall shares. Runs ONCE, before the cadence loop —
        never on the step path — and a probe failure is logged and
        dropped (calibration must not sink training)."""
        if (
            self.telemetry is None
            or self._comm is None
            or self._comm_probe_done
        ):
            return
        self._comm_probe_done = True
        try:
            self._record_comm_probe()
        except Exception as e:  # pragma: no cover - defensive
            self.log(f"TELEMETRY: comm probe failed: {e}")

    def _record_comm_probe(self) -> None:
        """Run 16 chained reduction rounds (the constrain +
        quantize + dequantize + residual-update machinery, nothing else)
        ONCE under the ``comm`` phase, compile + warmup outside the
        timed region, so the flight recorder gets a real measured span
        whose dur/steps is the per-reduction cost, and emit a
        ``comm_probe`` event carrying the host-side number. A
        quantized_ring trainer's rounds run the real shard_map'd ring
        (``_ring_reduce_probe`` — each round's ppermutes move the int8
        chunks); every other mode rides ``_reduce_grads``."""
        from ..parallel.collectives import is_residual_key

        rounds = 16
        spec = self._comm
        reduce = (
            self._ring_reduce_probe if spec.ring else self._reduce_grads
        )

        def prog(grads, res):
            def body(carry, i):
                g, r = carry
                g2, r2 = reduce(g, r)
                return (g2, {**r, **r2}), jnp.float32(0)

            (g, _), _ = jax.lax.scan(
                body, (grads, res), jnp.arange(rounds)
            )
            return g

        # inputs are live-state-shaped (and the residuals ARE the live
        # buffers) — never donate them
        fn = jax.jit(prog)  # netlint: disable=JAX003
        # ones in the live params' stored shapes (an all-zero gradient
        # would pin the int8 scale to its floor — not the representative
        # regime), plus the trainer's actual residual buffers
        grads = jax.tree.map(jnp.ones_like, dict(self.params))
        res = {
            k: v for k, v in self.buffers.items() if is_residual_key(k)
        }

        def run() -> float:
            g = fn(grads, res)
            # a host pull of a reduction over the result: it cannot
            # return before the work is done
            return float(jnp.sum(jnp.abs(next(iter(g.values())))))

        run()  # compile + warm, outside the span
        t0 = time.perf_counter()
        with self.timers.phase("comm", steps=rounds):
            run()
        ms = (time.perf_counter() - t0) / rounds * 1e3
        self.telemetry.event(
            "comm_probe",
            step=self.start_step,
            mode=self.comm_mode,
            dtype=self.comm_dtype,
            buckets=spec.buckets,
            rounds=rounds,
            comm_ms=round(ms, 4),
        )

    @jax.named_scope("update")
    def _apply_update(self, step, params: dict, grads: dict, state: dict):
        """Updater.apply under the configured ``update_mode``; in a
        trace its operations carry the scope ``update``.

        ``replicated``: every rank runs the full elementwise update.
        ``zero``: params are viewed through the update layout (a slice
        of the replicated value — free), the updater math runs on each
        rank's shard against the already-reduce-scattered grads and the
        resident sharded slots, and the fresh params are constrained
        back to their forward shardings, which GSPMD satisfies with one
        allgather. Loss-identical to the replicated update: every op
        between the constraints is elementwise, so shard boundaries
        cannot change any value."""
        if self._zero_sh is None:
            return self.updater.apply(step, params, grads, state, self.specs)
        wsc = jax.lax.with_sharding_constraint
        shard_view = {
            n: wsc(p, self._zero_sh[n]) for n, p in params.items()
        }
        new_p, new_s = self.updater.apply(
            step, shard_view, grads, state, self.specs
        )
        new_p = {n: wsc(v, self.param_sh[n]) for n, v in new_p.items()}
        new_s = {
            n: {s: wsc(v, self._zero_sh[n]) for s, v in slots.items()}
            for n, slots in new_s.items()
        }
        return new_p, new_s

    def _eval_batch_metrics(self, net: Net, params, buffers, batch) -> dict:
        """One eval batch -> {losslayer: metrics}. The single overridable
        seam both eval paths share (per-step _eval_step_for and the
        chunked scan body) — subclasses with custom eval semantics (the
        CD trainer's per-RBM reconstruction error) override THIS, and
        both paths follow."""
        batch = self._resolve_batch(net, batch)
        _, metrics = net.forward(
            self._cast_compute(params), self._cast_compute(batch),
            training=False, buffers=buffers,
        )
        return metrics

    def _eval_step_for(self, net: Net) -> Callable:
        if id(net) not in self._eval_steps:

            def eval_fn(params, buffers, batch):
                return self._eval_batch_metrics(net, params, buffers, batch)

            # eval traces the LIVE training params/buffers; donating them
            # would invalidate the arrays the next train step needs
            self._eval_steps[id(net)] = jax.jit(eval_fn)  # netlint: disable=JAX003
        return self._eval_steps[id(net)]

    # ------------------------------------------------------------------
    # input feeders (data/device_prefetch.py)
    # ------------------------------------------------------------------

    @property
    def feeder_mode(self) -> str:
        """How train batches reach the device:

        ``cached``    whole dataset resident in HBM, on-device index
                      gather inside the jitted step
        ``stream``    staged scan-chunk blocks, double-buffered at chunk
                      granularity (the streaming chunk engine)
        ``prefetch``  per-step double-buffered device feeder (batch k+1
                      transfers while step k runs)
        ``sync``      batch assembly + transfer on the step path (the
                      reference's unprefetched behavior)
        """
        if self._cached:
            return "cached"
        if self._prefetch_input and self._stream_ok():
            return "stream"
        if self._prefetch_input:
            return "prefetch"
        return "sync"

    def _stream_ok(self) -> bool:
        """Streaming chunks share every non-cache opt-out with
        _can_chunk: debug wants per-step batches, a pending fault plan
        wants exact step boundaries, SINGA_TPU_CHUNK=1 is the escape
        hatch; SINGA_TPU_STREAM_CHUNK=0 disables just this mode."""
        if not self._stream_chunks or self.cfg.debug:
            return False
        if self.resilience is not None and self.resilience.per_step:
            return False
        return self._chunk_cap() > 1

    def _reset_feeders(self) -> None:
        """Discard all feeder read-ahead and park the threads (restore /
        rollback paths — the streams are about to be re-seeked)."""
        for f in (getattr(self, "_feeder", None),
                  getattr(self, "_stager", None)):
            if f is not None:
                f.reset()
        self._feeder_positions = {}

    def _device_feeder(self):
        """The per-step double-buffered device feeder, lazily built."""
        if self._feeder is None:
            from ..data.device_prefetch import DeviceFeeder

            # prefetch mode never stages blocks; a stash kept because
            # the mode was "stream" until a fault plan bound is dead
            self.__dict__.pop("_compact_train", None)
            net = self.train_net
            pipes = self._pipelines[id(net)]

            def positions():
                return {
                    f"{net.phase}|{name}": pipe.position
                    for name, pipe in pipes.items()
                }

            def assemble():
                # feeder-thread span (obs/): assembly + device_put of
                # the read-ahead batch becomes its own trace track
                rec = self.telemetry
                if rec is None:
                    return self._assemble_host_batch(net)
                with rec.span("assemble_batch", track="feeder"):
                    return self._assemble_host_batch(net)

            self._feeder = DeviceFeeder(assemble, positions)
        return self._feeder

    def _chunk_stager(self):
        """The streaming-chunk block stager, lazily built. Byte-valued
        datasets stage uint8 (the device-cache compaction, decided ONCE
        over the full array so the staged dtype never flips mid-run);
        _resolve_batch restores the decoded dtype inside the program."""
        if self._stager is None:
            from ..data.device_prefetch import ChunkStager

            net = self.train_net
            pipes = self._pipelines[id(net)]
            # consume the compaction _maybe_cache_datasets already did
            # for the over-budget datasets stream mode targets (POP: the
            # stager owns the arrays from here, no second copy lives on)
            stash = self.__dict__.pop("_compact_train", {})
            sources = {}
            for name, pipe in pipes.items():
                arr, orig = stash.get(name) or self._compact_cache_array(
                    np.asarray(pipe.images)
                )
                if arr.dtype != orig:
                    self._cache_cast[(id(net), name)] = jnp.dtype(orig)
                sources[name] = (arr, pipe.labels, pipe.batchsize)
            def put(a, name, kind):
                # staged blocks land DATA-SHARDED along the stacked
                # batch dim (the same batch shardings the sync path
                # uses): each device receives only its 1/ndata slice of
                # the block instead of a full-block broadcast — on wide
                # meshes the host->device traffic drops by the data
                # width. The scan body's gather + batch constraint
                # reassemble exactly the sync path's per-step batches.
                sh = self.batch_sh.get(name)
                sh = sh[kind] if sh is not None else self._repl
                # stager-thread span (obs/): each staged block's
                # host->device commit becomes its own trace track
                rec = self.telemetry
                if rec is None:
                    return jax.device_put(jnp.asarray(a), sh)
                with rec.span("stage_block", track="stager"):
                    return jax.device_put(jnp.asarray(a), sh)

            self._stager = ChunkStager(
                sources,
                self._batches_per_step,
                schedule=self._stream_schedule,
                cursors=lambda: {
                    name: pipe.position for name, pipe in pipes.items()
                },
                put=put,
            )
        return self._stager

    def _stream_schedule(self, step: int) -> int:
        """The stager's window-length oracle: exactly the run() loop's
        chunk lengths (deterministic in ``step``), 0 past the end."""
        if step >= self.cfg.train_steps:
            return 0
        return self._chunk_len(step)

    def _step_via_chunk(self, step: int) -> bool:
        """Whether a length-1 window in stream mode still runs through
        train_chunk (keeping the stager's schedule unbroken). Subclasses
        with a per-step warmup phase (the replica trainer) defer."""
        del step
        return True

    # ------------------------------------------------------------------
    # host-side loop
    # ------------------------------------------------------------------

    def _next_batch(self, net: Net) -> dict:
        """One batch dict for ``net``'s data layers: index feeds
        (device-cached), a feeder buffer swap (prefetch mode), or
        host assembly + transfer on the calling thread."""
        if self._cached:
            out = {}
            for name, pipe in self._pipelines[id(net)].items():
                d = self._dev_data[id(net)][name]
                out[name] = {
                    "__idx__": jnp.asarray(pipe.next_indices()), **d
                }
            return out
        if net is self.train_net and self.feeder_mode == "prefetch":
            feeder = self._device_feeder()
            batch = feeder.next()
            self._feeder_positions = dict(feeder.consumed_positions)
            return batch
        return self._assemble_host_batch(net)

    def _assemble_host_batch(self, net: Net) -> dict:
        """Host-side batch assembly + device_put (the synchronous path;
        also the body the device feeder runs on its thread)."""
        out = {}
        for name, pipe in self._pipelines[id(net)].items():
            images, labels = pipe.next_batch()
            sh = self.batch_sh.get(name)
            leaf_i = sh["image"] if sh and net is self.train_net else self._repl
            leaf_l = sh["label"] if sh and net is self.train_net else self._repl
            out[name] = {
                "image": jax.device_put(images, leaf_i),
                "label": jax.device_put(labels, leaf_l),
            }
        return out

    def train_one_batch(self, step: int) -> None:
        """TrainOneBatch (worker.cc:304-316): one forward+backward+update."""
        if self.telemetry is not None:
            self.telemetry.step = step  # cheap attribute stamp, no I/O
        with self.timers.phase("data"):
            batch = self._next_batch(self.train_net)
        if self.resilience is not None:
            # nanloss@step fault seam (resilience/faults.py)
            batch = self.resilience.inject_batch_faults(self, step, batch)
        self._last_batch = batch  # debug dumps reuse it (no stream skew)
        rng = jax.random.fold_in(self._step_key, step)
        with self.timers.phase("train"):
            (self.params, self.state, self.buffers, metrics) = (
                self._train_step(
                    self.params, self.state, self.buffers,
                    jnp.int32(step), batch, rng,
                )
            )
        self.perf.update(metrics)

    # ------------------------------------------------------------------
    # multi-step chunks (device-cached datasets only)
    # ------------------------------------------------------------------

    def _can_chunk(self) -> bool:
        """Chunking folds N steps into one lax.scan dispatch. It needs the
        dataset on device (batch = index math inside the program) and no
        per-step host work (debug dumps want _last_batch)."""
        if not self._cached or self.cfg.debug:
            return False
        if self.resilience is not None and self.resilience.per_step:
            # a pending fault plan needs exact per-step boundaries
            return False
        return self._chunk_cap() > 1

    def _chunk_cap(self) -> int:
        return int(os.environ.get("SINGA_TPU_CHUNK", "64"))

    @staticmethod
    def _flat_batch_indices(pos0, i, bs: int, n: int):
        """Sequential-wraparound record indices of batch ``i`` from
        stream position ``pos0`` — the base stream-index math shared by
        the train chunk and the (always-flat) eval chunk."""
        return (pos0 + i * bs + jnp.arange(bs)) % n

    def _chunk_batch_indices(self, pos0, i, bs: int, n: int):
        """Record indices of scan-iteration ``i``'s batch (the replica
        trainer overrides with a (replicas, batch) grid)."""
        return self._flat_batch_indices(pos0, i, bs, n)

    def _chunk_meta(self, nsteps: int) -> dict[str, tuple[int, int]]:
        """{layer: (batchsize, gather length)} for a chunk program over
        ``nsteps`` steps: the device-cached dataset's record count, or —
        streaming — the staged block's length. With pos0 = 0 and n = the
        block length, the SAME wraparound index math that walks the
        cached dataset walks the staged block row-exactly (the real
        stream's wraparound was applied at staging time, on the host)."""
        pipes = self._pipelines[id(self.train_net)]
        if self.feeder_mode == "stream":
            return {
                name: (
                    pipe.batchsize,
                    nsteps * self._batches_per_step * pipe.batchsize,
                )
                for name, pipe in pipes.items()
            }
        return {
            name: (pipes[name].batchsize, pipes[name].n)
            for name in self._dev_data[id(self.train_net)]
        }

    def _chunk_body(self, nsteps: int, meta=None) -> Callable:
        """The UNJITTED nsteps-step scan body: (params, state, buffers,
        step0, pos0s, data) -> (params, state, buffers, summed_metrics).
        _make_chunk_fn jits it; the replica trainer composes it with a
        protocol round in one program (fused sync windows — which pass
        the WHOLE multi-window meta so inner windows index into the
        full staged block)."""
        if meta is None:
            meta = self._chunk_meta(nsteps)

        # the cached dataset enters as an ARGUMENT, not a closure capture:
        # captured arrays lower to embedded constants, which some runtimes
        # re-upload on every execution; as an argument it stays resident
        # and is passed by ref
        def chunk_fn(params, state, buffers, step0, pos0s, data):
            def body(carry, i):
                params, state, buffers = carry
                step = step0 + i
                batch = {}
                for name, d in data.items():
                    bs, n = meta[name]
                    idx = self._chunk_batch_indices(pos0s[name], i, bs, n)
                    batch[name] = {"__idx__": idx, **d}
                batch = self._resolve_batch(self.train_net, batch)
                rng = jax.random.fold_in(self._step_key, step)
                params, state, buffers, metrics = self._train_step_fn(
                    params, state, buffers, step, batch, rng
                )
                return (params, state, buffers), metrics

            (params, state, buffers), metrics = jax.lax.scan(
                body, (params, state, buffers), jnp.arange(nsteps)
            )
            # sum the per-step metrics inside the program: one dispatch
            # total, no (nsteps,)-stacked metrics round trip
            return params, state, buffers, jax.tree.map(
                lambda a: a.sum(axis=0), metrics
            )

        return chunk_fn

    def _make_chunk_fn(self, nsteps: int) -> Callable:
        return jax.jit(self._chunk_body(nsteps), donate_argnums=(0, 1, 2))

    def train_chunk(self, step0: int, nsteps: int) -> None:
        """Run nsteps consecutive train steps as ONE compiled program.

        Semantically identical to nsteps train_one_batch calls: the same
        sequential-wraparound batch indices (computed on device from the
        stream positions), the same per-step rng folds, the same updater
        schedule (each scan iteration sees its true step number)."""
        if nsteps not in self._chunk_fns:
            self._chunk_fns[nsteps] = self._make_chunk_fn(nsteps)
        self._run_chunk(self._chunk_fns[nsteps], (), step0, nsteps)

    def _run_chunk(self, fn, extra_in: tuple, step0: int, nsteps: int):
        """Shared chunk-dispatch scaffolding (ONE copy — the replica
        trainer's fused sync windows reuse it).

        ``fn(params, state, buffers, *extra_in, step0, pos0s, data) ->
        (params, state, buffers, *extra_out, summed_metrics)``;
        ``extra_out`` (protocol state carried through a fused program)
        is handed to _store_chunk_extras. ``data`` is the device-cached
        dataset, or — streaming — the double-buffered staged block
        (normally already transferred; the data phase then times only
        the buffer swap)."""
        pipes = self._pipelines[id(self.train_net)]
        streaming = self.feeder_mode == "stream"
        if self.telemetry is not None:
            self.telemetry.step = step0  # cheap attribute stamp, no I/O
        with self.timers.phase("data", steps=nsteps):
            if streaming:
                data, after = self._chunk_stager().take(step0, nsteps)
                pos0s = {name: jnp.int32(0) for name in pipes}
            else:
                pos0s = {
                    name: jnp.int32(pipe.position)
                    for name, pipe in pipes.items()
                }
                data = self._dev_data[id(self.train_net)]
        with self.timers.phase("train", steps=nsteps):
            out = fn(
                self.params, self.state, self.buffers, *extra_in,
                jnp.int32(step0), pos0s, data,
            )
        self.params, self.state, self.buffers, *extra_out, summed = out
        if extra_out:
            self._store_chunk_extras(tuple(extra_out))
        if streaming:
            # the stager owns the stream cursor (its thread must not
            # race the pipelines); re-sync the pipelines at the window
            # boundary so checkpoints see the consumed position
            for name, pipe in pipes.items():
                pipe.seek(after[name])
        else:
            for name, pipe in pipes.items():
                pipe.advance(nsteps * self._batches_per_step)
        # metrics arrive pre-summed over the chunk; Performance pulls to
        # host only at display time
        self.perf.update_summed(summed, nsteps)

    def _store_chunk_extras(self, extra: tuple) -> None:
        raise NotImplementedError(
            "chunk fn returned extra outputs but no handler is defined"
        )

    def _next_fire(self, cur: int, freq: int, after: int) -> float:
        """Smallest s >= cur with _now(s, freq, after), or +inf."""
        if freq <= 0:
            return float("inf")
        base = max(cur, after)
        return base + (-(base - after)) % freq

    def _chunk_len(self, step: int) -> int:
        """Steps until the next cadence event bounds the chunk: val/test
        run BEFORE their trigger step (chunk must stop short of it);
        display/checkpoint run AFTER theirs (it may close the chunk)."""
        cfg = self.cfg
        n = min(cfg.train_steps - step, self._chunk_cap())
        if self.val_net is not None:
            fire = self._next_fire(
                step + 1, cfg.validation_frequency, cfg.validation_after_steps
            )
            n = min(n, fire - step)
        if self.test_net is not None:
            fire = self._next_fire(
                step + 1, cfg.test_frequency, cfg.test_after_steps
            )
            n = min(n, fire - step)
        fire = self._next_fire(
            step, cfg.display_frequency, cfg.display_after_steps
        )
        n = min(n, fire - step + 1)
        # checkpoint at step s saves "done = s+1" (see run_one_batch)
        fire = self._next_fire(
            step + 1, cfg.checkpoint_frequency, cfg.checkpoint_after_steps
        )
        n = min(n, fire - step)
        if self._guard is not None and self._guard.policy == "kRollback":
            # the rollback policy reads the consecutive-bad counter at
            # chunk boundaries; cap the chunk so detection lag stays
            # within one rollback window
            n = min(n, self._guard.rollback_after)
        return max(1, int(n))

    def _eval_params(self):
        """Params used by eval steps; replica trainers override this to
        evaluate a single replica's view."""
        return self.params

    def _eval_buffers(self):
        """Buffers used by eval steps (replica trainers evaluate replica
        0's running stats)."""
        return self.buffers

    def _eval_batches(self, net: Net, nsteps: int):
        """Yield ``nsteps`` eval batches. Uncached eval streams ride a
        bounded BurstFeeder (the serving tier's request-batching
        machinery applied to the eval plane — the ROADMAP's eval-stream
        feeder gap): batch k+1 assembles + device_puts on a worker
        thread while eval step k runs, and exactly ``nsteps`` batches
        are drawn, so stream positions advance identically to the
        synchronous path (resume/rollback replay stays exact). Cached
        nets and prefetch-off jobs keep the direct path."""
        if self._cached or not self._prefetch_input:
            for _ in range(nsteps):
                yield self._next_batch(net)
            return
        from ..data.device_prefetch import BurstFeeder

        rec = self.telemetry

        def assemble():
            if rec is None:
                return self._assemble_host_batch(net)
            with rec.span("assemble_batch", track="feeder"):
                return self._assemble_host_batch(net)

        feeder = BurstFeeder(assemble, nsteps)
        try:
            for _ in range(nsteps):
                yield feeder.next()
        finally:
            feeder.reset()

    def _make_eval_chunk_fn(self, net: Net, nsteps: int) -> Callable:
        """One compiled program for a whole eval cadence: scan nsteps
        batches (on-device index math, like _make_chunk_fn) and sum the
        metrics inside the program — one dispatch and one host pull
        per cadence instead of one per batch."""
        pipes = self._pipelines[id(net)]
        meta = {
            name: (pipes[name].batchsize, pipes[name].n)
            for name in self._dev_data[id(net)]
        }

        def chunk_fn(params, buffers, pos0s, data):
            def body(carry, i):
                batch = {}
                for name, d in data.items():
                    bs, n = meta[name]
                    # eval streams are always flat (no replica grid) —
                    # deliberately the base index math, not
                    # _chunk_batch_indices
                    idx = self._flat_batch_indices(pos0s[name], i, bs, n)
                    batch[name] = {"__idx__": idx, **d}
                metrics = self._eval_batch_metrics(
                    net, params, buffers, batch
                )
                return carry, metrics

            _, metrics = jax.lax.scan(body, 0, jnp.arange(nsteps))
            return jax.tree.map(lambda a: a.sum(axis=0), metrics)

        # like _eval_step_for: params stay live across the eval chunk
        return jax.jit(chunk_fn)  # netlint: disable=JAX003

    def evaluate(self, net: Net, nsteps: int, phase: str, step: int) -> dict:
        """Test/Validate (worker.cc:318-348): nsteps batches, averaged."""
        perf = Performance()
        eval_params = self._eval_params()
        eval_buffers = self._eval_buffers()
        # same opt-outs as the train chunk (_can_chunk: device cache,
        # cfg.debug, SINGA_TPU_CHUNK=1 escape hatch)
        if self._can_chunk() and nsteps > 1 and id(net) in self._dev_data:
            key = (id(net), nsteps)
            if key not in self._eval_chunk_fns:
                self._eval_chunk_fns[key] = self._make_eval_chunk_fn(
                    net, nsteps
                )
            pipes = self._pipelines[id(net)]
            pos0s = {
                name: jnp.int32(pipe.position)
                for name, pipe in pipes.items()
            }
            with self.timers.phase("eval", steps=nsteps):
                summed = self._eval_chunk_fns[key](
                    eval_params, eval_buffers, pos0s,
                    self._dev_data[id(net)],
                )
            for pipe in pipes.values():
                pipe.advance(nsteps)
            perf.update_summed(summed, nsteps)
        else:
            fn = self._eval_step_for(net)
            with self.timers.phase("eval", steps=nsteps):
                for batch in self._eval_batches(net, nsteps):
                    perf.update(fn(eval_params, eval_buffers, batch))
        avg = perf.avg()
        self.log(f"step {step}: {phase} {perf.to_string(avg)}")
        if self.telemetry is not None:
            # avg is already on host (computed for the display line) —
            # the event reuses it, no second device round trip
            self.telemetry.event(
                "eval", step=step, phase=phase, batches=nsteps,
                metrics={l: dict(b) for l, b in avg.items()},
            )
        return avg

    def _pre_events(self, step: int) -> None:
        """Validation/test run BEFORE the train step of their trigger step
        (worker.cc:190-200)."""
        cfg = self.cfg
        if self.val_net is not None and _now(
            step, cfg.validation_frequency, cfg.validation_after_steps
        ):
            self.evaluate(
                self.val_net, cfg.validation_steps, "validation", step
            )
        if self.test_net is not None and _now(
            step, cfg.test_frequency, cfg.test_after_steps
        ):
            self.evaluate(self.test_net, cfg.test_steps, "test", step)

    def _post_events(self, step: int) -> None:
        """Display/checkpoint run AFTER the train step."""
        cfg = self.cfg
        if _now(step, cfg.display_frequency, cfg.display_after_steps):
            sps = steps_s = 0.0
            t = self.timers.total("train") + self.timers.total("data")
            if t > 0:
                sps = self.perf.count * self._batch_size / t
                # steps/s (and tok/s for LM configs) straight from the
                # existing accumulators — perf.count already counts the
                # window's steps, no new host syncs
                steps_s = self.perf.count / t
            rate = f"{sps:.0f} samples/s, {steps_s:.1f} steps/s"
            if self._tokens_per_step and steps_s > 0:
                rate += f", {steps_s * self._tokens_per_step:.0f} tok/s"
            # input-stall readout (the guard-counter pattern): per-window
            # data time and its share of the step path, straight from the
            # timers' existing aggregation — no new per-step host syncs
            stall = ""
            if t > 0:
                stall = (
                    f" data {self.timers.mean_ms('data'):.1f}ms "
                    f"({100.0 * self.timers.share('data', 'train'):.0f}%)"
                )
            # divergence-guard counters ride the display line (ONE host
            # sync, at display cadence — never per step); rollbacks are
            # the context's count
            guard = ""
            g = {}
            if self._guard is not None:
                g = self.guard_counters()
                rb = getattr(self.resilience, "rollbacks", 0)
                guard = (
                    f" guard[bad {g['bad_steps']}, rollbacks {rb}, "
                    f"lr x{g['lr_scale']:g}]"
                )
            # metrics pulled ONCE (the display line's existing sync);
            # the telemetry step record reuses the same host values
            avg = self.perf.avg()
            self.log(
                f"step {step}: train {self.perf.to_string(avg)} "
                f"[{self.timers.to_string()}; {rate}]"
                f"{stall}{guard}"
            )
            if self.telemetry is not None:
                self.telemetry.event(
                    "step",
                    step=step,
                    metrics={l: dict(b) for l, b in avg.items()},
                    phase_ms={
                        p: round(self.timers.mean_ms(p), 3)
                        for p in self.timers.phases()
                    },
                    steps=self.perf.count,
                    samples_per_s=round(sps, 1),
                    steps_per_s=round(steps_s, 3),
                    **(
                        {"tokens_per_s": round(
                            steps_s * self._tokens_per_step, 1
                        )}
                        if self._tokens_per_step
                        else {}
                    ),
                    **({"guard": g} if g else {}),
                )
            if cfg.debug:
                self.log(self.debug_string(step))
            self.perf.reset()
            self.timers.reset()
            if self.telemetry is not None:
                # the cadence boundary is the ONLY step-loop flush point
                self.telemetry.flush()
        # snapshot labels carry the RESUME step (steps completed), matching
        # the end-of-run save and restore_into's start_step contract — so a
        # resumed run never replays the step it saved after
        done = step + 1
        if (
            _now(done, cfg.checkpoint_frequency, cfg.checkpoint_after_steps)
            and done > self.start_step
            and done < cfg.train_steps  # run() writes the final snapshot
        ):
            self.save(done)

    def run_one_batch(self, step: int) -> None:
        """RunOneBatch (worker.cc:187-213): cadences around the train step."""
        self._pre_events(step)
        self.train_one_batch(step)
        self._post_events(step)

    def run(self) -> None:
        """Worker::Run (worker.cc:98-106): the full training loop.

        With a device-cached dataset the loop advances in multi-step
        chunks (one compiled scan per cadence window); otherwise it is the
        reference's step-at-a-time loop."""
        if self.cluster is not None and self.cluster.workspace:
            vis = os.path.join(
                self.cluster.workspace, self.cluster.vis_subfolder
            )
            for net in (self.train_net, self.test_net, self.val_net):
                if net is not None:
                    dump_net_json(net, vis)
        # comm-cost calibration span (grad_comm + telemetry only; a
        # one-shot probe off the step path) + the grad_allreduce
        # kernel_select run-start event
        self._maybe_emit_kernel_select()
        self._maybe_record_comm_probe()
        # streaming scan chunks: a non-cached dataset no longer falls
        # back to one dispatch per step — the stager feeds the same
        # _run_chunk scan path from double-buffered staged blocks
        streaming = self.feeder_mode == "stream"
        chunking = self._can_chunk() or streaming
        ctx = self.resilience
        step = self.start_step
        self.completed_steps = step
        while step < self.cfg.train_steps:
            if ctx is not None:
                # step-boundary seam: watchdog heartbeat, fault
                # injection, preemption drain (may raise)
                ctx.before_step(self, step)
            n = self._chunk_len(step) if chunking else 1
            self._pre_events(step)
            if n > 1 or (streaming and self._step_via_chunk(step)):
                # streaming routes length-1 windows through train_chunk
                # too: the stager's block schedule stays unbroken
                self.train_chunk(step, n)
            else:
                self.train_one_batch(step)
            self._post_events(step + n - 1)
            step += n
            if ctx is not None:
                # guard rollback may rewind to the last checkpoint
                step = ctx.after_step(self, step)
            self.completed_steps = step
        if self._checkpoint_dir() is not None:
            self.save(self.cfg.train_steps)

    # ------------------------------------------------------------------
    # checkpoint + debug
    # ------------------------------------------------------------------

    def _checkpoint_dir(self) -> str | None:
        if self.cluster is not None and self.cluster.workspace:
            return os.path.join(self.cluster.workspace, "checkpoints")
        return None

    def _stream_positions(self) -> dict[str, int]:
        out = {}
        for net in (self.train_net, self.test_net, self.val_net):
            if net is None:
                continue
            for name, pipe in self._pipelines[id(net)].items():
                out[f"{net.phase}|{name}"] = pipe.position
        # device-feeder mode: the pipelines run ahead of the trainer by
        # the feeder's read-ahead — checkpoint the CONSUMED positions
        out.update(self._feeder_positions)
        return out

    def save(self, step: int) -> str | None:
        folder = self._checkpoint_dir()
        if folder is None:
            return None
        ctx = self.resilience
        writer = ctx.async_ckpt if ctx is not None else None
        rec = self.telemetry
        if writer is None:
            # the ckpt phase times the save's step-path cost (sync: the
            # whole serialize; async below: snapshot + submit only) —
            # tools/trace.py's stall shares read it
            with self.timers.phase("ckpt"):
                path, write = self._prepare_save(folder, step, snapshot=False)
                write()
            self.log(f"step {step}: checkpoint -> {path}")
            if rec is not None:
                rec.event("ckpt_save", step=step, path=path, mode="sync")
            if ctx is not None:
                # corrupt_ckpt fault, completeness validation, LATEST
                # marking, keep-last-N retention (resilience/retention.py)
                ctx.checkpoint_written(self, path, step)
            return path
        # --- zero-stall path (resilience/async_ckpt.py): snapshot the
        # state with one non-donating device-copy program, start the
        # device->host DMA, and hand serialization to the writer thread.
        # The step loop continues immediately; validation/LATEST/
        # retention run from the writer via the same checkpoint_written
        # seam, in submit (= step) order. ---
        with self.timers.phase("ckpt"):
            path, write = self._prepare_save(folder, step, snapshot=True)
            writer.submit(
                step, path, write,
                on_written=lambda p, s: ctx.checkpoint_written(self, p, s),
            )
        self.log(f"step {step}: checkpoint (async) -> {path}")
        if rec is not None:
            rec.event("ckpt_save", step=step, path=path, mode="async")
        return path

    def _manifest_extra(self) -> dict:
        """Extra promises for a sharded save's manifest. The replica
        engine overrides to promise its ``.server`` sidecar
        (``{"sidecar": True}``) so retention can refuse a save whose
        sidecar tore or never landed (resilience/coord.py sidecar
        commit markers)."""
        return {}

    def _prepare_save(self, folder: str, step: int, snapshot: bool):
        """-> (final path, zero-arg write closure) for one checkpoint.

        ``snapshot=False`` captures the LIVE arrays (the synchronous
        path — the closure runs before the next step). ``snapshot=True``
        captures fresh device-side COPIES with their host transfers
        already started, so the closure is safe to run from the async
        writer thread while the (donating) train loop advances: it only
        materializes host buffers and writes files, never dispatches new
        device programs."""
        # a model axis spanning process boundaries (cross-process
        # kLayerPartition) leaves params PARTITIONED with shards this
        # host cannot see: the host-gathering npz writer cannot
        # materialize them. The per-process sharded format exists for
        # exactly this topology — auto-upgrade rather than crash at the
        # end of a training run. Fully-replicated multi-process arrays
        # are fine for npz (every host holds the whole value), so they
        # keep the configured format.
        def _spanning(arrs):
            return any(
                not v.is_fully_addressable
                and not v.sharding.is_fully_replicated
                for v in arrs
            )

        # check params AND state AND buffers: they can disagree — e.g.
        # the replica engine's protocol round returns params replicated
        # (the scan re-lays them out) while updater slots keep the
        # process-spanning replica sharding
        spans_procs = (
            _spanning(self.params.values())
            or _spanning(
                v for slots in self.state.values() for v in slots.values()
            )
            or _spanning(self.buffers.values())
        )
        sharded = self.cfg.checkpoint_format == "sharded" or spans_procs
        streams = self._stream_positions()
        if snapshot:
            # the sharded format stores STORED (padded) shapes; npz
            # stores LOGICAL ones, so its snapshot program unpads inside
            # the same dispatch
            params, state, buffers = self._snapshot_trees(unpad=not sharded)
            for leaf in jax.tree.leaves((params, state, buffers)):
                if hasattr(leaf, "copy_to_host_async"):
                    leaf.copy_to_host_async()
        elif sharded:
            params, state, buffers = self.params, self.state, self.buffers
        else:
            # npz checkpoints are host-gathered and mesh-portable: store
            # LOGICAL shapes (a resume onto a different model-axis width
            # re-pads for its own mesh)
            params = self._unpad_stored(self.params)
            state = self._unpad_state(self.state)
            buffers = self.buffers
        if sharded:
            from .sharded_ckpt import save_sharded

            path = os.path.join(folder, f"step_{step}.ckpt")
            extra = self._manifest_extra()

            def write() -> None:
                save_sharded(
                    path, step, params, state, buffers, streams=streams,
                    manifest_extra=extra,
                )

        else:
            path = os.path.join(folder, f"step_{step}.npz")
            if jax.process_index() != 0:
                # npz checkpoints are host-gathered and identical on
                # every rank (the spanning check above upgraded any
                # partitioned state to the sharded format): one writer
                # suffices, and N ranks racing os.replace on the same
                # shared-FS file is N-1 wasted writes plus a window for
                # a half-renamed observation. Rank 0 writes.
                def write() -> None:
                    return None

            else:

                def write() -> None:
                    save_checkpoint(
                        path, step, params, state, buffers, streams=streams
                    )

        return path, write

    def _snapshot_trees(self, unpad: bool):
        """Donation-safe device copies of (params, state, buffers) in
        ONE compiled program (npz variant also unpads inside it). The
        copies are fresh buffers the async writer owns outright — the
        live training arrays stay valid for the next, donating, train
        step, and the writer thread never has to dispatch device work."""
        if unpad not in self._snapshot_fns:

            def snap(params, state, buffers):
                params, state, buffers = jax.tree.map(
                    jnp.copy, (params, state, buffers)
                )
                if unpad:
                    params = self._unpad_stored(params)
                    state = self._unpad_state(state)
                return params, state, buffers

            # snapshots must NOT donate: the inputs are the live params
            self._snapshot_fns[unpad] = jax.jit(snap)  # netlint: disable=JAX003
        return self._snapshot_fns[unpad](self.params, self.state, self.buffers)

    # ------------------------------------------------------------------
    # resilience: rollback + guard state (resilience/context.py calls)
    # ------------------------------------------------------------------

    def rollback_to(self, path: str) -> int:
        """Mid-run restore of params/state/buffers/stream-positions from
        checkpoint ``path`` (the divergence guard's rollback). Returns
        the checkpoint's step — where the cadence loop continues."""
        self.cfg.checkpoint = path
        # take the checkpoint's own step: the pre-rollback resume step
        # is ahead of where training is being rewound to
        self.start_step = 0
        self._materialize_params()
        self._seek_resumed_streams()
        self.completed_steps = self.start_step
        return self.start_step

    def set_guard_state(
        self, consec: int | None = None, lr_scale: float | None = None
    ) -> None:
        """Host-side overwrite of the guard counters (rollback resets
        the consecutive count and compounds the LR backoff)."""
        if consec is not None:
            self.buffers[GUARD_CONSEC] = jax.device_put(
                jnp.int32(consec), self._repl
            )
        if lr_scale is not None:
            self.buffers[GUARD_LR] = jax.device_put(
                jnp.float32(lr_scale), self._repl
            )

    def guard_counters(self) -> dict[str, float]:
        """Pull the guard counters to host — ONE device sync, so call at
        cadence boundaries (display, end of run), never per step."""
        if self._guard is None:
            return {}
        return {
            "consecutive_bad": int(self.buffers[GUARD_CONSEC]),
            "bad_steps": int(self.buffers[GUARD_BAD]),
            "lr_scale": float(self.buffers[GUARD_LR]),
        }

    def debug_string(self, step: int) -> str:
        """Per-layer mean-|activation| + per-param mean-|value| lines, the
        reference's debug dump (worker.cc:262-265, neuralnet.cc:350-378).
        Reuses the step's own batch — debug mode must not consume extra
        training data or shift the stream position."""
        batch = self._resolve_batch(
            self.train_net, self._last_batch, constrain=False
        )
        rng = jax.random.fold_in(self._step_key, step)
        _, _, acts = self.train_net.forward(
            self.params, batch, training=True, rng=rng,
            buffers=self.buffers, return_acts=True,
        )
        lines = [
            "debug: "
            + ", ".join(
                f"{name} {float(jnp.mean(jnp.abs(a))):.4g}"
                for name, a in acts.items()
                if hasattr(a, "dtype")
            )
        ]
        lines.append(
            "params: "
            + ", ".join(
                f"{n} {float(jnp.mean(jnp.abs(v))):.4g}"
                for n, v in sorted(self.params.items())
            )
        )
        return "\n".join(lines)
