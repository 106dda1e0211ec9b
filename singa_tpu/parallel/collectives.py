"""Quantized + overlapped gradient collectives (``grad_comm``).

The reference hid gradient-sync cost behind its asynchronous parameter
server (Elastic-SGD / RandomSync over ZeroMQ, src/server/server.cc);
the synchronous GSPMD step instead pays one full-precision gradient
collective at every step end. This module is the trainer-side seam that
attacks that cost with the two levers PAPERS.md names:

**Quantized gradient reduction** (EQuARX, arxiv 2506.17615): each
bucket's gradients are cast to a scaled low-precision wire format —
symmetric int8 (per-bucket max-abs scale) or bf16 — so the value the
data-axis collective moves is a quarter / half the bytes, then
dequantized after the reduction. The compression error is NOT discarded:
with ``error_feedback`` (default on) each param carries a persistent
residual in the buffer pytree (``__gradres__/<param>``), the residual is
re-injected into the next step's gradient before quantization, and the
new residual is the fresh quantization error — the EF-SGD construction
that keeps compressed training converging to the uncompressed optimum.
Residuals thread the jitted step with the other buffers, so they
checkpoint, restore, and roll back with training state for free.

On this repo's CPU-hosted virtual meshes the collectives are emulated
(memcpys), so the quantized path here is the *numerics model* and the
*program seam*: the cast sits exactly where the data-axis reduction
materializes (composing with ``zero_update``'s reduce-scatter layout —
the sharding constraint is applied to the quantized tensor, and the
residuals live shard-local), which is where an XLA with EQuARX-style
quantized collectives picks the wire format up. The convergence harness
(tools/convergence.py ``--grad_comm q8``) validates the numerics end to
end.

**Comm/compute overlap** (the async parameter-server heritage, made
synchronous): ``buckets: N`` partitions the params into N groups in
REVERSE topological order — the order backward produces their gradients
— and chains the groups with ``lax.optimization_barrier`` so the lowered
program issues bucket k's reduction before bucket k+1's, instead of
letting the scheduler sink every collective to the step end. On a real
accelerator the latency-hiding scheduler then overlaps bucket k's
collective with bucket k+1's still-running backward segment; bucket
granularity also sets the quantization-scale granularity (one max-abs
scale per bucket; ``buckets: 0`` = one scale per param, no ordering
chain).

``mode: exact`` (the default, also the behavior with no ``grad_comm``
block) is structurally inert: the step traces bitwise-identically to a
config with no block at all.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..ops.quantized_collective import (
    dequantize_int8,
    quantize_int8,
    symmetric_scale,
)

#: buffer-pytree namespace for the error-feedback residuals (dunder
#: prefix like the guard counters — never collides with layer buffers,
#: which are namespaced by layer name)
RESIDUAL_PREFIX = "__gradres__/"


def residual_key(name: str) -> str:
    """Buffer key of param ``name``'s error-feedback residual."""
    return RESIDUAL_PREFIX + name


def is_residual_key(key: str) -> bool:
    return key.startswith(RESIDUAL_PREFIX)


@dataclasses.dataclass(frozen=True)
class GradCommSpec:
    """The trainer-facing slice of the ``grad_comm`` config block (plus
    the ``kernels { grad_allreduce }`` wire-implementation knob)."""

    mode: str = "exact"  # "exact" | "quantized"
    dtype: str = "int8"  # wire dtype for quantized mode: "int8" | "bf16"
    error_feedback: bool = True
    buckets: int = 0  # 0/1 = per-param granularity, no ordering chain
    #: how the quantized reduction crosses the data axis: "reference"
    #: (grad_comm's cast around the GSPMD psum — fp32 on the wire, the
    #: bitwise-pinned oracle), "quantized_ring" (the explicit
    #: int8-on-the-wire ppermute ring, ops/quantized_collective.py),
    #: or "q8_hier" (the hierarchical two-level ring: f32 intra-slice,
    #: int8 inter-slice — geometry from the ``ring {}`` fields below)
    wire_impl: str = "reference"
    #: the ring hop's form — NOT the Pallas interpreter: True is the
    #: pure-XLA ppermute hop (a real compiled program on the chip, and
    #: the default on every platform), False the Pallas ``quant_acc``
    #: hop, whose (8, 128)-aligned-chunk gate no shipped conf passes
    interpret: bool = True
    #: ``ring {}`` geometry for q8_hier (hier_ring_geometry resolves
    #: these against the mesh): named axes, or the factored data-axis
    #: group width. All empty/0 for the flat impls.
    intra_axis: str = ""
    inter_axis: str = ""
    intra_degree: int = 0

    @property
    def quantized(self) -> bool:
        return self.mode == "quantized"

    @property
    def overlapped(self) -> bool:
        return self.buckets > 1

    @property
    def ring(self) -> bool:
        """Whether the data-axis reduction is an explicit quantized
        ring (int8 bytes in the ppermutes) — flat or hierarchical —
        rather than the reference dequantize-then-psum seam."""
        return self.wire_impl in ("quantized_ring", "q8_hier")

    @property
    def hier(self) -> bool:
        """Whether the ring is the hierarchical two-level form."""
        return self.wire_impl == "q8_hier"

    @property
    def wants_residuals(self) -> bool:
        """Whether the step carries error-feedback residual buffers."""
        return self.quantized and self.error_feedback

    @staticmethod
    def from_config(cfg, kernels=None, ring=None) -> "GradCommSpec | None":
        """-> GradCommSpec, or None when the block is absent OR
        structurally inert (mode exact, no bucketization). Returning
        None for an inert block is the bitwise-exactness guarantee:
        ``grad_comm { mode: exact }`` must trace the identical program
        a config with no block traces — and ``kernels { grad_allreduce:
        reference }`` (the default) changes nothing about it.

        ``kernels`` is the model conf's ``kernels {}`` block; both ring
        impls (``quantized_ring`` flat, ``q8_hier`` hierarchical)
        require an active quantized ``grad_comm`` block (the ring IS
        the quantized collective's wire implementation — with nothing
        quantized there is no wire value to narrow) and raise
        ConfigError without one. ``ring`` is the model conf's
        ``ring {}`` geometry block, carried verbatim for q8_hier (the
        mesh-aware validation lives in ``hier_ring_geometry``)."""
        impl = (
            kernels.grad_allreduce if kernels is not None else "reference"
        )
        # unset keeps the plain-XLA hop on every platform (the serving
        # kernel, not the ring, follows the platform when unset)
        interpret = (
            True if kernels is None or kernels.interpret is None
            else bool(kernels.interpret)
        )
        if impl in ("quantized_ring", "q8_hier") and (
            cfg is None or cfg.mode != "quantized"
        ):
            from ..config.schema import ConfigError

            raise ConfigError(
                f"kernels {{ grad_allreduce: {impl} }} needs an "
                "active grad_comm { mode: quantized } block: the ring is "
                "the quantized collective's wire implementation"
            )
        if cfg is None:
            return None
        spec = GradCommSpec(
            mode=cfg.mode,
            dtype=cfg.dtype,
            error_feedback=bool(cfg.error_feedback),
            buckets=max(0, int(cfg.buckets)),
            wire_impl=impl,
            interpret=interpret,
            intra_axis=(
                ring.intra_axis if ring is not None else ""
            ),
            inter_axis=(
                ring.inter_axis if ring is not None else ""
            ),
            intra_degree=(
                max(0, int(ring.intra_degree)) if ring is not None else 0
            ),
        )
        if not spec.quantized and not spec.overlapped:
            return None
        return spec


def apply_grad_comm_tag(cfg, tag: str):
    """CLI shorthand -> ``cfg.grad_comm`` (sweep / convergence / bench):
    ``q8`` = quantized int8 + error feedback, ``bf16`` = quantized bf16,
    ``q8wire`` = q8 with the int8-on-the-wire ring collective
    (``kernels { grad_allreduce: quantized_ring }``), ``q8hier`` = q8
    with the hierarchical two-level ring (``q8_hier`` + a factored
    ``ring { intra_degree: 2 }`` when the conf declares no geometry),
    ``exact`` = an explicit (inert) exact block, "" = leave
    untouched."""
    if not tag:
        return cfg
    from ..config.schema import GradCommConfig, KernelsConfig, RingConfig

    gc = GradCommConfig()
    if tag == "exact":
        gc.mode = "exact"
    elif tag in ("q8", "q8wire", "q8hier"):
        gc.mode, gc.dtype = "quantized", "int8"
    elif tag == "bf16":
        gc.mode, gc.dtype = "quantized", "bf16"
    else:
        raise ValueError(
            f"unknown grad_comm tag {tag!r} (choose exact, q8, q8wire, "
            "q8hier, bf16)"
        )
    cfg.grad_comm = gc
    if tag in ("q8wire", "q8hier"):
        kern = cfg.kernels if cfg.kernels is not None else KernelsConfig()
        kern.grad_allreduce = (
            "q8_hier" if tag == "q8hier" else "quantized_ring"
        )
        cfg.kernels = kern
    if tag == "q8hier" and cfg.ring is None:
        ring = RingConfig()
        ring.intra_degree = 2
        cfg.ring = ring
    return cfg


def init_residuals(params: dict, spec: GradCommSpec | None) -> dict:
    """Fresh zero residuals (STORED shapes — grads of padded params are
    padded) for every param, keyed by ``residual_key``. Empty when the
    spec carries none."""
    if spec is None or not spec.wants_residuals:
        return {}
    return {
        residual_key(n): jnp.zeros(v.shape, dtype=jnp.float32)
        for n, v in params.items()
    }


def reverse_topo_buckets(
    net, names: frozenset, nbuckets: int, specs: dict
) -> tuple[tuple[str, ...], ...]:
    """Partition ``names`` into reduction buckets in REVERSE topological
    layer order — the order the backward pass produces their gradients,
    so the bucket chain's issue order matches gradient readiness.

    ``nbuckets <= 1`` yields one bucket per param (per-param
    quantization scale, no ordering chain); otherwise at most
    ``nbuckets`` contiguous groups, greedily balanced by element count
    (``specs`` supplies the shapes). Every name appears exactly once.
    """
    ordered: list[str] = []
    seen: set[str] = set()
    for layer in reversed(net.layers):
        for n in layer.param_specs():
            if n in names and n not in seen:
                seen.add(n)
                ordered.append(n)
    # grads for params no layer declares (defensive): stable tail
    ordered.extend(sorted(names - seen))
    if nbuckets <= 1:
        return tuple((n,) for n in ordered)
    sizes = {
        n: max(1, int(functools.reduce(
            lambda a, b: a * b, specs[n].shape, 1
        ))) if n in specs else 1
        for n in ordered
    }
    total = sum(sizes[n] for n in ordered)
    target = total / nbuckets
    out: list[tuple[str, ...]] = []
    cur: list[str] = []
    acc = 0
    for n in ordered:
        cur.append(n)
        acc += sizes[n]
        # close the bucket once it reaches its share — unless closing
        # would leave more names than remaining buckets can hold
        if acc >= target and len(out) < nbuckets - 1:
            out.append(tuple(cur))
            cur, acc = [], 0
    if cur:
        out.append(tuple(cur))
    return tuple(out)


def _chain(gs: dict, token):
    """Pin this bucket's ops after ``token`` (one reduced array from the
    previous bucket): ``optimization_barrier`` is a value-identity that
    adds a scheduling edge, keeping the lowered collectives in
    reverse-topo issue order — bucket k's reduction can run while bucket
    k+1's backward segment is still computing, instead of every
    collective sinking to the step end."""
    if token is None:
        return gs
    names = list(gs)
    fused = jax.lax.optimization_barrier(
        tuple(gs[n] for n in names) + (token,)
    )
    return dict(zip(names, fused[:-1]))


def _bucket_scale(es: dict) -> jnp.ndarray:
    """One symmetric int8 scale for the bucket — the shared
    ``symmetric_scale`` helper (ops/quantized_collective.py), so the
    reference path and the quantized ring consult ONE formula: max-abs
    over every gradient, floored away from zero (max is exactly
    associative, so the scale is bitwise-independent of layout; NaN/Inf
    gradients poison it, the guard contract)."""
    return symmetric_scale(es.values())


def reduce_gradients(
    grads: dict,
    buffers: dict,
    spec: GradCommSpec,
    buckets: tuple[tuple[str, ...], ...],
    constrain,
) -> tuple[dict, dict]:
    """The grad_comm reduction: -> (update-ready grads, residual-buffer
    updates).

    Per bucket, in reverse-topo order: re-inject the error-feedback
    residuals, cast to the wire dtype (int8 with the bucket's max-abs
    scale, or bf16), apply ``constrain(name, arr)`` — the trainer's
    per-tensor data-axis reduction layout (zero_update's reduce-scatter
    constraint, identity for the replicated update) — ON THE QUANTIZED
    TENSOR, dequantize, and bank the fresh quantization error as the
    next step's residual. A NaN/Inf gradient poisons its bucket's scale
    and survives dequantization as NaN, so the divergence guard's
    verdict over the dequantized grads still fires.

    ``mode: exact`` never reaches here bucketed with buckets <= 1 (the
    spec is inert then); with buckets > 1 the buckets only carry the
    ordering chain — the values are untouched.
    """
    out: dict = {}
    new_res: dict = {}
    token = None
    for bucket in buckets:
        gs = _chain({n: grads[n] for n in bucket}, token)
        if not spec.quantized:
            for n, g in gs.items():
                out[n] = constrain(n, g)
        else:
            es = {}
            for n, g in gs.items():
                r = (
                    buffers.get(residual_key(n))
                    if spec.error_feedback
                    else None
                )
                es[n] = g if r is None else g + r.astype(g.dtype)
            scale = _bucket_scale(es) if spec.dtype == "int8" else None
            for n, e in es.items():
                if spec.dtype == "int8":
                    q = quantize_int8(e, scale)
                    ghat = dequantize_int8(
                        constrain(n, q), scale
                    ).astype(e.dtype)
                else:  # bf16
                    ghat = constrain(
                        n, e.astype(jnp.bfloat16)
                    ).astype(e.dtype)
                if spec.error_feedback:
                    new_res[residual_key(n)] = (
                        e.astype(jnp.float32) - ghat.astype(jnp.float32)
                    )
                out[n] = ghat
        if spec.overlapped:
            # the ordering chain exists only in bucketized mode —
            # buckets <= 1 is per-param granularity with NO chain (the
            # documented contract), leaving the scheduler free
            token = out[bucket[0]]
    return out, new_res
