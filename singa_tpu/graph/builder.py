"""Neural-net DAG builder.

Replaces NeuralNet::ConstructNeuralNet (reference:
src/worker/neuralnet.cc:72-110) and the Worker's phase filtering
(src/worker/worker.cc:69-95): layers are filtered by ``exclude`` for the
requested phase, topo-sorted from their ``srclayers`` edges, instantiated
through the registry, and shape-inferred in order. The partition rewriter
(PartitionNeuralNet, neuralnet.cc:112-323) has NO counterpart here by
design — partitioning is expressed as GSPMD shardings over the unmodified
graph (see singa_tpu.parallel), which is the entire point of the TPU-native
re-design.

``Net.forward`` is a pure function of (params, batch, rng) and is traced
into the jitted train step; the reference's Forward hot loop
(worker.cc:240-268) with its bridge spins and WaitUpdate blocking dissolves
into one XLA program.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from ..config.schema import ConfigError, LayerConfig, ModelConfig, NetConfig
from ..layers import Layer, create_layer
from ..layers.connector import SliceLayer
from ..params import ParamSpec
from .kahn import kahn_order

PHASES = ("kTrain", "kValidation", "kTest")


def topo_sort(configs: list[LayerConfig]) -> list[LayerConfig]:
    """Kahn's algorithm over srclayers edges, stable wrt config order
    (the reference DFS-sorts in Graph::Sort, src/utils/graph.cc:80-101).

    Fail-fast wrapper over the shared core (graph/kahn.py — the same
    loop lint's report-all cycle pass uses): unknown srclayers and
    cycles abort the build with ConfigError."""
    by_name = {c.name: c for c in configs}
    if len(by_name) != len(configs):
        names = [c.name for c in configs]
        dupes = sorted({n for n in names if names.count(n) > 1})
        raise ConfigError(f"duplicate layer names after phase filter: {dupes}")
    for c in configs:
        for src in c.srclayers:
            if src not in by_name:
                raise ConfigError(
                    f"layer {c.name!r} references unknown srclayer {src!r}"
                )
    order, residue = kahn_order(
        [c.name for c in configs], {c.name: c.srclayers for c in configs}
    )
    if residue:
        raise ConfigError(f"cycle in layer graph involving {sorted(residue)}")
    return [by_name[n] for n in order]


class Net:
    """An ordered, shape-inferred layer DAG for one phase."""

    def __init__(self, layers: list[Layer], phase: str):
        self.phase = phase
        self.layers = layers
        #: set by the trainer when the cluster declares a pipe axis and
        #: the net places layers by locationid (graph/pipeline_plan.py)
        self.pipeline_plan = None
        self.pipeline_mesh = None
        #: {param name: logical shape} for params whose STORED arrays are
        #: pad-to-multiple for an indivisible kLayerPartition dim
        #: (parallel/shardings.py param_paddings); forward slices the
        #: stored array back to the logical shape before layers see it
        self.param_logical: dict[str, tuple] = {}
        self.name2layer = {l.name: l for l in layers}
        self.datalayers = [l for l in layers if l.is_datalayer]
        self.parserlayers = [l for l in layers if l.is_parserlayer]
        self.losslayers = [l for l in layers if l.is_losslayer]
        # consumer lists drive Slice output routing (k-th dst gets slice k,
        # reference base_layer.cc:136-151)
        self.dstlayers: dict[str, list[str]] = {l.name: [] for l in layers}
        for l in layers:
            for src in l.srclayers:
                self.dstlayers[src].append(l.name)

    # ---------------- build ----------------

    def setup(self) -> None:
        shapes: dict[str, tuple] = {}
        batchsize = 0
        for layer in self.layers:
            src_shapes = [shapes[s] for s in layer.srclayers]
            out = layer.setup(src_shapes, batchsize)
            layer.validate([self.name2layer[s] for s in layer.srclayers])
            if layer.is_datalayer:
                batchsize = layer.batchsize
            if isinstance(layer, SliceLayer):
                # consumers each see one slice
                shapes[layer.name] = out
            else:
                shapes[layer.name] = out
            layer.out_shape = out
        self.batchsize = batchsize

    def bind_mesh(self, mesh) -> None:
        """Attach the device mesh to every layer (static metadata read by
        mesh-aware layers — ring attention, kMoE). The trainer calls this
        once the mesh is resolved; nets built without a trainer keep
        mesh=None and the layers' single-device fallbacks."""
        for layer in self.layers:
            layer.mesh = mesh

    def param_specs(self) -> dict[str, ParamSpec]:
        specs: dict[str, ParamSpec] = {}
        for layer in self.layers:
            for name, spec in layer.param_specs().items():
                if name in specs:
                    raise ConfigError(f"duplicate param name {name!r}")
                specs[name] = spec
        return specs

    def buffer_specs(self) -> dict:
        """Non-trainable state (BufferSpec) declared by stateful layers."""
        specs = {}
        for layer in self.layers:
            specs.update(layer.buffer_specs())
        return specs

    def init_buffers(self) -> dict[str, jnp.ndarray]:
        return {
            name: jnp.full(spec.shape, spec.init, dtype=jnp.float32)
            for name, spec in self.buffer_specs().items()
        }

    # ---------------- trace ----------------

    def resolve_params(self, params: dict) -> dict:
        """Param view every graph walk shares (forward AND the serving
        tier's incremental decode, serve/conf_decode.py): shared params
        resolve through their owner's array (ParamSpec.owner), and
        pad-to-multiple storage (uneven kLayerPartition dims) slices
        back to the logical shape. Ellipsis keeps any leading replica
        axis (ReplicaTrainer stacks params as (R, ...)). The slice of
        the zero tail has zero cotangent, so gradients/updater slots on
        the tail stay exactly zero."""
        resolved = dict(params)
        for layer in self.layers:
            for name, spec in layer.param_specs().items():
                if spec.owner is not None:
                    resolved[name] = params[spec.owner]
        for name, logical in self.param_logical.items():
            v = resolved.get(name)
            if v is not None and v.shape[-len(logical):] != tuple(logical):
                resolved[name] = v[
                    (Ellipsis, *(slice(0, s) for s in logical))
                ]
        return resolved

    def forward(
        self,
        params: dict[str, jnp.ndarray],
        batch: dict[str, Any],
        *,
        training: bool,
        rng: jax.Array | None = None,
        buffers: dict[str, jnp.ndarray] | None = None,
        return_buffers: bool = False,
        return_acts: bool = False,
        layer_hook=None,
    ):
        """Run all layers; returns (total_loss, {losslayer: metrics}).

        ``batch`` maps each data layer's name to its input dict
        ({"image": ..., "label": ...}); shared params resolve through their
        owner's array (ParamSpec.owner). With ``return_acts`` the per-layer
        activation dict is appended — the debug-mode hook (the reference
        dumps per-layer L1 norms, neuralnet.cc:350-378). ``layer_hook``
        optionally overrides a layer's compute: called as
        hook(layer, resolved_params, inputs, layer_rng); a non-None return
        replaces layer.apply — this is how the CD trainer swaps RBM layers
        to Gibbs-chain updates without re-implementing the traversal.

        ``buffers`` feeds stateful layers (batch norm running stats);
        omitted, they use their init values. With ``return_buffers`` the
        post-step buffer dict is appended (before acts): the trainer
        carries it between steps.
        """
        if buffers is None:
            buffers = self.init_buffers()
        new_buffers = dict(buffers)
        resolved = self.resolve_params(params)

        acts: dict[str, Any] = {}
        slice_cursor: dict[str, int] = {}
        total_loss = jnp.float32(0.0)
        metrics: dict[str, dict[str, jnp.ndarray]] = {}
        staged_names: set[str] = set()
        if self.pipeline_plan is not None:
            staged_names = {
                l.name for st in self.pipeline_plan.stages for l in st
            }
        for i, layer in enumerate(self.layers):
            if layer.name in staged_names:
                # the whole staged region executes as one GPipe schedule
                # when its first layer is reached; later staged layers
                # are already covered
                plan = self.pipeline_plan
                if layer is plan.stages[0][0]:
                    from .pipeline_plan import pipeline_forward_region

                    acts[plan.exits[-1]] = pipeline_forward_region(
                        plan, resolved, acts[plan.entry_src],
                        self.pipeline_mesh,
                    )
                continue
            if layer.is_datalayer:
                inputs = [batch[layer.name]]
            else:
                inputs = []
                for src in layer.srclayers:
                    val = acts[src]
                    if isinstance(self.name2layer.get(src), SliceLayer):
                        k = slice_cursor.get(src, 0)
                        slice_cursor[src] = k + 1
                        val = val[k]
                    inputs.append(val)
            lrng = jax.random.fold_in(rng, i) if rng is not None else None
            out = None
            # the ONE place a conf layer's operations get their name in
            # a trace: ``kBatchNorm.s1b2_c_bn`` in every ``op_name``,
            # the backward's inside ``transpose(jvp(...))``. No ``/``:
            # it separates the scopes of a path.
            with jax.named_scope(
                f"{layer.TYPE}.{layer.name}".replace("/", "_")
            ):
                if layer_hook is not None:
                    out = layer_hook(layer, resolved, inputs, lrng)
                if out is None:
                    if layer.has_buffers:
                        out, updates = layer.apply_stateful(
                            resolved, buffers, inputs,
                            training=training, rng=lrng,
                        )
                        new_buffers.update(updates)
                    else:
                        out = layer.apply(
                            resolved, inputs, training=training, rng=lrng
                        )
            if layer.is_losslayer:
                loss, m = out
                total_loss = total_loss + loss
                metrics[layer.name] = m
                acts[layer.name] = loss
            elif layer.has_aux_loss:
                # e.g. kMoE load balancing: apply returns (out, aux);
                # aux joins the total loss at the layer's declared weight
                out, aux = out
                total_loss = total_loss + layer.aux_weight * aux
                acts[layer.name] = out
            else:
                acts[layer.name] = out
        extra = []
        if return_buffers:
            extra.append(new_buffers)
        if return_acts:
            extra.append(acts)
        return (total_loss, metrics, *extra)

    # ---------------- observability ----------------

    def to_json(self) -> dict:
        """Node-link dump matching NeuralNet::ToString's shape
        (reference: neuralnet.cc:325-332, src/utils/graph.cc:8-59)."""
        nodes = [
            {
                "id": l.name,
                "type": l.TYPE,
                "shape": list(l.out_shape or ()),
                "partition_dim": l.partition_dim,
            }
            for l in self.layers
        ]
        links = [
            {"source": src, "target": l.name}
            for l in self.layers
            for src in l.srclayers
        ]
        return {"phase": self.phase, "nodes": nodes, "links": links}


def active_phases(model_cfg: ModelConfig) -> list[str]:
    """Phases this job actually builds nets for (Trainer.__init__ builds
    from this list): kTrain always, kTest/kValidation only when their
    step counts are set.
    Lint passes check exactly these — a conf whose two ``data`` layers
    exclude kTrain/kTest respectively is fine unless validation_steps
    makes the kValidation net (where both would be live) real."""
    phases = ["kTrain"]
    if model_cfg.test_steps:
        phases.append("kTest")
    if model_cfg.validation_steps:
        phases.append("kValidation")
    return phases


def filter_phase(net_cfg: NetConfig, phase: str) -> list[LayerConfig]:
    """Drop layers whose ``exclude`` lists the phase (worker.cc:69-95)."""
    if phase not in PHASES:
        raise ConfigError(f"unknown phase {phase!r}")
    return [l for l in net_cfg.layer if phase not in (l.exclude or [])]


def build_net(model_cfg: ModelConfig, phase: str = "kTrain") -> Net:
    """Config -> phase-filtered, topo-sorted, shape-inferred Net."""
    if model_cfg.neuralnet is None:
        raise ConfigError("model config has no neuralnet block")
    configs = filter_phase(model_cfg.neuralnet, phase)
    if not configs:
        raise ConfigError(f"no layers left for phase {phase}")
    order = topo_sort(configs)
    net_partition = model_cfg.neuralnet.partition_type
    net = Net([create_layer(c, net_partition) for c in order], phase)
    net.setup()
    return net
