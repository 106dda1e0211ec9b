"""Partition semantics -> GSPMD sharding annotations.

The reference's partitioner rewrites the layer graph: kDataPartition splits
every blob's batch dim 0, kLayerPartition splits the neuron dim 1, and
Slice/Concate/Split/Bridge connectors plus ZeroMQ shuffles move the pieces
(src/worker/neuralnet.cc:198-323, partition_dimension at
base_layer.h:121-128). Here the graph is left untouched; the same semantics
are expressed as shardings on the jitted step's inputs:

  kDataPartition  -> batch arrays sharded over the data axis; params
                     replicated; XLA psums grads (= ParamSync, replacing
                     param_manager.cc:160-231).
  kLayerPartition -> each param sharded over the model axis along its
                     declared ``neuron_axis``; XLA's propagation pass then
                     shards the matching activations and inserts exactly the
                     slice/concat/shuffle collectives the reference built by
                     hand ("the most complex scenario", neuralnet.cc:265-280).

The reference gives the last partition any remainder (neuralnet.cc:160-162);
XLA shards evenly, so an indivisible neuron dim pads its STORED array up to
the next multiple (see _param_layout), and an indivisible expert count falls
back to replication (documented divergence, SURVEY hard-part #3). Both
fallbacks announce themselves via ``warnings.warn`` and are surfaced
statically by netlint as SHD001 (``python -m singa_tpu.tools.lint``).
"""

from __future__ import annotations

import warnings

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..graph.builder import Net
from .mesh import DATA_AXIS, MODEL_AXIS


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_shardings(mesh: Mesh, net: Net) -> dict:
    """Sharding pytree for the step's batch input: every array in every
    data layer's feed dict is sharded on dim 0 over the data axis. Token
    feeds additionally shard their sequence dim over the seq axis when
    the mesh has one (sequence parallelism — ring attention then keeps
    K/V sharded end to end)."""
    leaf = NamedSharding(mesh, P(DATA_AXIS))
    nseq = dict(mesh.shape).get("seq", 1)
    out = {}
    for layer in net.datalayers:
        img = leaf
        if nseq > 1 and layer.TYPE == "kSequenceData":
            img = NamedSharding(mesh, P(DATA_AXIS, "seq"))
        out[layer.name] = {"image": img, "label": leaf}
    return out


def _param_layout(mesh: Mesh, net: Net, *, warn: bool = False):
    """-> iterator of (name, spec, sharded_axis | None, pad).

    ``sharded_axis`` is the param dim sharded over a mesh axis (with the
    axis name), ``pad`` the extra length the STORED array needs on that
    dim so jax's even-shard requirement holds. kLayerPartition neuron
    dims honor the reference's uneven-partition contract by
    pad-to-multiple (the reference gives the last partition the
    remainder, neuralnet.cc:160-162; padding the last shard is the GSPMD
    expression of the same split — Net.forward slices the tail back off
    before any layer sees it). Expert axes never pad: a phantom expert
    would need routing masks, so indivisible expert counts replicate.
    """
    nmodel = mesh.shape[MODEL_AXIS]
    nexpert = dict(mesh.shape).get("expert", 1)
    for layer in net.layers:
        for name, spec in layer.param_specs().items():
            if (
                layer.partition_dim == 1
                and spec.neuron_axis is not None
                and nmodel > 1
            ):
                d = spec.shape[spec.neuron_axis]
                pad = -d % nmodel
                if pad and warn:
                    # lint surfaces the same condition statically (SHD001)
                    warnings.warn(
                        f"layer {layer.name!r}: kLayerPartition dim "
                        f"{spec.neuron_axis} of param {name!r} (size {d}) "
                        f"is not divisible by the model axis ({nmodel}); "
                        f"storage pads to {d + pad}",
                        stacklevel=3,
                    )
                yield name, spec, (spec.neuron_axis, MODEL_AXIS), pad
            elif spec.expert_axis is not None and nexpert > 1:
                if spec.shape[spec.expert_axis] % nexpert:
                    if warn:
                        warnings.warn(
                            f"layer {layer.name!r}: expert dim "
                            f"{spec.expert_axis} of param {name!r} (size "
                            f"{spec.shape[spec.expert_axis]}) is not "
                            f"divisible by the expert axis ({nexpert}); "
                            "falling back to replication",
                            stacklevel=3,
                        )
                    yield name, spec, None, 0
                else:
                    # kMoE expert weights split over the expert axis
                    # regardless of partition_type — expert parallelism is
                    # the layer's intrinsic layout, not a net-wide choice
                    yield name, spec, (spec.expert_axis, "expert"), 0
            else:
                yield name, spec, None, 0


def param_shardings(mesh: Mesh, net: Net) -> dict[str, NamedSharding]:
    """Per-param shardings implementing the layer's partition_type.

    Only layers whose partition_dim is 1 (kLayerPartition) shard their
    params, along each param's neuron_axis; everything else replicates
    (data-parallel grads sync via psum, which GSPMD inserts because the
    loss is a mean over the sharded batch dim). Indivisible neuron dims
    are still sharded — the trainer pads their storage (see
    param_paddings / _param_layout).
    """
    out: dict[str, NamedSharding] = {}
    for name, spec, sharded, _pad in _param_layout(mesh, net, warn=True):
        if sharded is None:
            out[name] = replicated(mesh)
        else:
            dim, axis = sharded
            axes: list = [None] * len(spec.shape)
            axes[dim] = axis
            out[name] = NamedSharding(mesh, P(*axes))
    return out


def param_paddings(mesh: Mesh, net: Net) -> dict[str, tuple]:
    """{name: np.pad-style widths} for params whose STORED array must be
    longer than the logical shape (indivisible kLayerPartition dims).
    Only padded params appear. The logical shape stays spec.shape;
    Net.forward slices the stored array back down before layers see it.
    """
    out: dict[str, tuple] = {}
    for name, spec, sharded, pad in _param_layout(mesh, net):
        if pad:
            dim = sharded[0]
            widths = [(0, 0)] * len(spec.shape)
            widths[dim] = (0, pad)
            out[name] = tuple(widths)
    return out


def zero_update_shardings(
    mesh: Mesh,
    net: Net,
    param_sh: dict[str, NamedSharding],
    *,
    warn: bool = False,
) -> dict[str, NamedSharding]:
    """ZeRO-style UPDATE layout (PAPERS.md arxiv 2004.13336): each
    param's forward sharding plus the data axis on the first
    still-replicated dim the data-parallel degree divides evenly.

    Constraining grads to this layout makes GSPMD lower the data-axis
    grad sync to a reduce-scatter (each rank receives only its shard's
    sum); updater slots STORED in it shrink per-device by the data
    width; constraining the fresh params back to their forward
    shardings after the update is the allgather. This composes with
    the existing fallbacks: dims padded for an indivisible model axis
    use their STORED (padded) length, and a param with no evenly
    divisible free dim keeps its forward sharding — its update stays
    replicated, the same replicate fallback as indivisible expert
    counts, announced via ``warnings.warn`` when ``warn``.
    """
    ndata = mesh.shape[DATA_AXIS]
    out: dict[str, NamedSharding] = {}
    for name, spec, sharded, pad in _param_layout(mesh, net):
        shape = list(spec.shape)
        if pad:
            shape[sharded[0]] += pad
        axes = list(tuple(param_sh[name].spec))
        axes += [None] * (len(shape) - len(axes))
        dim = None
        if ndata > 1:
            dim = next(
                (
                    d
                    for d, size in enumerate(shape)
                    if axes[d] is None and size and size % ndata == 0
                ),
                None,
            )
        if dim is None:
            if ndata > 1 and warn:
                warnings.warn(
                    f"zero_update: no free dim of param {name!r} (stored "
                    f"shape {tuple(shape)}) is divisible by the data axis "
                    f"({ndata}); its update stays replicated",
                    stacklevel=3,
                )
            out[name] = param_sh[name]
        else:
            axes[dim] = DATA_AXIS
            out[name] = NamedSharding(mesh, P(*axes))
    return out


def serving_kv_shardings(
    mesh: Mesh, n_heads: int, *, warn: bool = False
) -> tuple[NamedSharding, NamedSharding]:
    """-> (pool_sharding, state_sharding) for the serving engine's paged
    KV state (serve/engine.py).

    The pools are ``(n_blocks, block_len, heads * head_dim)``, heads
    major within the last dim (serve/kv_pool.py): that dim shards over
    the ``model`` axis when the axis divides ``n_heads``, so every
    shard holds WHOLE heads — the serving analog of kLayerPartition
    (each model shard holds its heads' K/V, attention contracts
    locally, GSPMD reassembles the output exactly as it does for the
    TP projections) — else the pool replicates, announced like every
    other indivisible-dim fallback. The block dim NEVER shards: block
    ids are a global namespace the host allocator hands out, and a
    table must be resolvable on every shard. Slot-lane state
    (tokens/pos/live/rng/tables) is tiny and always replicates."""
    repl = replicated(mesh)
    nmodel = dict(mesh.shape).get(MODEL_AXIS, 1)
    if nmodel <= 1:
        return repl, repl
    if n_heads % nmodel:
        if warn:
            warnings.warn(
                f"serving: n_heads {n_heads} not divisible by the model "
                f"axis ({nmodel}); KV pools fall back to replication",
                stacklevel=2,
            )
        return repl, repl
    return NamedSharding(mesh, P(None, None, MODEL_AXIS)), repl


def state_shardings(
    param_sh: dict[str, NamedSharding],
    slots: tuple[str, ...],
    update_sh: dict[str, NamedSharding] | None = None,
) -> dict[str, dict[str, NamedSharding]]:
    """Updater slots (history/update) mirror their param's sharding, like
    the reference keeps history blobs beside data blobs (param.h:136).
    Under ``zero_update`` the slots follow the UPDATE layout instead
    (``update_sh`` from zero_update_shardings) — each rank holds only
    its shard of the optimizer state, the per-device shrink that is the
    point of ZeRO."""
    src = update_sh if update_sh is not None else param_sh
    return {name: {s: sh for s in slots} for name, sh in src.items()}
