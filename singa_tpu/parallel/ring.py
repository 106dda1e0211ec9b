"""Ring attention: sequence/context parallelism over the device ring.

The reference has no sequence dimension anywhere (SURVEY §5: pre-
transformer system), but its generic partition machinery (kLayerPartition
slicing an arbitrary dim, src/worker/neuralnet.cc:198-323) is the
structural seam SURVEY identifies for sequence-dim sharding. This module
is that seam made real, TPU-native: Q/K/V live sequence-sharded across a
mesh axis; each chip computes attention for its local query block while
K/V shards rotate around the ring via ``lax.ppermute`` (one ICI hop per
step, compute overlapping communication under XLA's scheduler), folding
each visiting block into flash-style online-softmax statistics
(singa_tpu/ops/attention.py). No chip ever holds the full sequence or an
S x S score matrix, so max context length scales linearly with ring size.

Causal masking stays exact under rotation: each shard knows its global
offset from ``lax.axis_index``, so a visiting K block is masked by global
positions, and fully-masked visits contribute nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from ..ops.attention import (
    block_attn_finish,
    block_attn_init,
    block_attn_update,
)

SEQ_AXIS = "seq"


def build_sp_mesh(ndata: int = 1, nseq: int = 1, devices=None) -> Mesh:
    """A (data, seq) mesh: batch shards over data, sequence over seq.

    The seq axis is innermost so the K/V ring rides neighboring devices
    (fastest ICI hops), like the model axis in build_mesh."""
    from .mesh import axis_pair_mesh

    return axis_pair_mesh(ndata, nseq, SEQ_AXIS, devices, "sp mesh")


def _ring_attn_local(q, k, v, *, axis_name: str, causal: bool):
    """Per-shard ring attention body (runs under shard_map).

    q/k/v: (batch_local, heads, seq_local, head_dim)."""
    nshards = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    s_local = q.shape[2]
    in_dtype = q.dtype
    # accumulate flash statistics in fp32 (matching the Pallas kernel's
    # upcast) — bf16 exp-sums folded across many ring steps drift; K/V
    # stay in the input dtype so ring traffic is not inflated
    q32 = q.astype(jnp.float32)
    out, m, l = block_attn_init(q32)

    def step(i, carry):
        out, m, l, k, v = carry
        # the K/V block visiting at step i originated on shard (my - i)
        src = (my - i) % nshards
        out, m, l = block_attn_update(
            q32, k.astype(jnp.float32), v.astype(jnp.float32), out, m, l,
            q_offset=my * s_local,
            k_offset=src * s_local,
            causal=causal,
        )
        # rotate K/V one hop around the ring: shard j's block moves to
        # shard j+1, so the next visitor originated one shard earlier
        perm = [(j, (j + 1) % nshards) for j in range(nshards)]
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        return out, m, l, k, v

    out, m, l, k, v = jax.lax.fori_loop(
        0, nshards, step, (out, m, l, k, v)
    )
    return block_attn_finish(out, m, l).astype(in_dtype)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    *,
    causal: bool = False,
    axis: str = SEQ_AXIS,
) -> jnp.ndarray:
    """Sequence-parallel attention over ``mesh``'s ``axis``.

    Inputs/outputs are global (batch, heads, seq, head_dim) arrays whose
    seq dim is (or becomes) sharded over ``axis``; batch rides any "data"
    axis the mesh has. Differentiable: autodiff traces back through the
    ppermute rotations, so grads flow with the same ring traffic pattern.
    """
    if dict(mesh.shape).get(axis, 1) == 1:
        from ..ops.attention import attention

        return attention(q, k, v, causal=causal)
    data = "data" if "data" in mesh.shape else None
    spec = P(data, None, axis, None)
    fn = jax.shard_map(
        functools.partial(
            _ring_attn_local, axis_name=axis, causal=causal
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    return fn(q, k, v)
