"""Pipeline parallelism: GPipe-style microbatch scheduling over a mesh axis.

The reference's closest precursor is layer placement: ``locationid`` puts
different layers on different processes with blocking bridge handshakes
and NO microbatch interleaving (SURVEY §2.5: "layer placement without
pipelining"). This module supplies the real thing, TPU-native: stages'
params shard over a "pipe" mesh axis, activations hop stage-to-stage via
``lax.ppermute``, and a ``lax.scan`` over nmicro + nstages - 1 ticks
keeps every stage busy once the pipeline fills. Backward is jax autodiff
through the scan — the reverse schedule with reversed hops, for free.

Constraints (documented, enforced): every stage maps activations of one
shared shape to the same shape (the reference's own shape-invariance rule
for partitioned nets, neuralnet.cc:187-193); microbatch count should be
>= the stage count to amortize the fill/drain bubble.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import axis_pair_mesh

PIPE_AXIS = "pipe"


def build_pp_mesh(ndata: int = 1, npipe: int = 1, devices=None) -> Mesh:
    """A (data, pipe) mesh: batch shards over data, stages over pipe."""
    return axis_pair_mesh(ndata, npipe, PIPE_AXIS, devices, "pp mesh")


def stage_param_shardings(mesh: Mesh, params, axis: str = PIPE_AXIS):
    """Shard every (nstages, ...) param leaf over the pipe axis."""
    return jax.tree.map(
        lambda _: NamedSharding(
            mesh, P(axis, *([None] * (np.ndim(_) - 1)))
        ),
        params,
    )


def pipeline_apply(
    stage_fn,
    stage_params,
    x: jnp.ndarray,
    mesh: Mesh,
    *,
    axis: str = PIPE_AXIS,
):
    """Run microbatches through the stage pipeline.

    stage_fn(params_one_stage, act) -> act applies ONE stage; its pytree
    ``stage_params`` has a leading nstages dim on every leaf, sharded
    over ``axis``. x is (nmicro, mb, ...) microbatched input (batch may
    shard over "data"). Returns (nmicro, mb, ...) outputs of the final
    stage. With a 1-wide pipe axis this is just a scan over microbatches.
    """
    nstages = mesh.shape[axis]
    if nstages == 1:
        one = jax.tree.map(lambda p: p[0], stage_params)
        return jax.vmap(lambda m: stage_fn(one, m))(x)
    nmicro = x.shape[0]
    data = "data" if "data" in mesh.shape else None

    def local(params_local, xm):
        params_one = jax.tree.map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(axis)
        is_first = stage == 0
        is_last = stage == nstages - 1
        mb_shape = xm.shape[1:]
        perm = [(j, (j + 1) % nstages) for j in range(nstages)]

        def tick(carry, t):
            recv = carry
            # stage 0 injects microbatch t (zeros once drained)
            mb = jnp.where(
                t < nmicro,
                jax.lax.dynamic_index_in_dim(
                    xm, jnp.minimum(t, nmicro - 1), keepdims=False
                ),
                jnp.zeros(mb_shape, xm.dtype),
            )
            inp = jnp.where(is_first, mb, recv)
            y = stage_fn(params_one, inp)
            # schedule validity: stage s works on microbatch t - s
            valid = (t >= stage) & (t - stage < nmicro)
            out = jnp.where(valid & is_last, y, jnp.zeros_like(y))
            send = jax.lax.ppermute(y, axis, perm)
            return send, (out, valid & is_last, t - stage)

        # the carry must already wear the vma of its steady state: derive
        # from xm (data axis) and mark pipe-varying (send crosses hops)
        zero = jax.lax.pcast(xm[0] * 0.0, (axis,), to="varying")
        _, (outs, valids, idxs) = jax.lax.scan(
            tick, zero, jnp.arange(nmicro + nstages - 1)
        )
        # scatter valid ticks' outputs into microbatch order; on non-last
        # stages everything is zero and the result is discarded via the
        # psum below (each microbatch written by exactly one stage)
        buf = jnp.zeros_like(xm)
        buf = buf.at[jnp.where(valids, idxs, nmicro)].set(
            outs, mode="drop"
        )
        return jax.lax.psum(buf, axis)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(axis), stage_params),
            P(None, data),
        ),
        out_specs=P(None, data),
    )
    return fn(stage_params, x)
