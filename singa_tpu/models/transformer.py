"""Decoder-only transformer LM, TPU-first.

No counterpart exists in the reference (pre-transformer system, SURVEY
§5); this family exists to make long-context training first-class. The
design keeps the framework's conventions: params are a flat name-keyed
pytree (like the layer zoo's "<layer>/<param>" naming), the forward is a
pure function traced into one jitted step, and distribution is sharding
metadata, not code:

- attn="flash" routes through the Pallas flash kernel
  (singa_tpu/ops/attention.py) on TPU;
- attn="ring" shards the sequence dim over a mesh axis and streams K/V
  around the ICI ring (singa_tpu/parallel/ring.py) — context length
  scales linearly with ring size;
- the batch dim shards over any "data" mesh axis exactly like the
  proto-driven nets (grad psum = ParamSync).

Weights use bf16-friendly shapes (head_dim, d_ff multiples of 128 map
cleanly onto the MXU); compute dtype is the caller's choice via the
params' dtype.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..ops.attention import attention
from ..parallel.ring import ring_attention


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab: int
    d_model: int = 256
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 1024
    max_len: int = 1024
    attn: str = "dense"  # dense | flash | ring
    #: >0 replaces every block's FFN with a Switch MoE of this many
    #: experts (parallel/moe.py); pair with an "expert" mesh axis for
    #: expert parallelism. The load-balancing aux joins lm_loss.
    moe_experts: int = 0
    moe_aux_weight: float = 0.01

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads


def init_lm(rng: jax.Array, cfg: TransformerConfig) -> dict:
    """Flat name-keyed param pytree; scaled-normal init."""
    params: dict[str, jnp.ndarray] = {}

    def norm(key, shape, scale):
        return scale * jax.random.normal(key, shape, dtype=jnp.float32)

    keys = iter(jax.random.split(rng, 2 + 4 * cfg.n_layers))
    params["embed/tok"] = norm(next(keys), (cfg.vocab, cfg.d_model), 0.02)
    params["embed/pos"] = norm(next(keys), (cfg.max_len, cfg.d_model), 0.02)
    for i in range(cfg.n_layers):
        p = f"blk{i}"
        d, f = cfg.d_model, cfg.d_ff
        params[f"{p}/ln1/scale"] = jnp.ones((d,))
        params[f"{p}/ln1/bias"] = jnp.zeros((d,))
        params[f"{p}/attn/qkv"] = norm(next(keys), (d, 3 * d), 1 / math.sqrt(d))
        params[f"{p}/attn/out"] = norm(
            next(keys), (d, d), 1 / math.sqrt(d * 2 * cfg.n_layers)
        )
        params[f"{p}/ln2/scale"] = jnp.ones((d,))
        params[f"{p}/ln2/bias"] = jnp.zeros((d,))
        if cfg.moe_experts:
            from ..parallel.moe import init_moe

            moe = init_moe(next(keys), d, f, cfg.moe_experts)
            for k, v in moe.items():
                params[f"{p}/moe/{k}"] = v
        else:
            params[f"{p}/mlp/up"] = norm(
                next(keys), (d, f), 1 / math.sqrt(d)
            )
            params[f"{p}/mlp/down"] = norm(
                next(keys), (f, d), 1 / math.sqrt(f * 2 * cfg.n_layers)
            )
    params["ln_f/scale"] = jnp.ones((cfg.d_model,))
    params["ln_f/bias"] = jnp.zeros((cfg.d_model,))
    return params


def lm_param_shardings(mesh, params: dict, axis: str = "model") -> dict:
    """Tensor-parallel specs for the code-API param tree.

    The MLP gets the classic Megatron column/row pair (``up`` shards its
    output dim, ``down`` the matching contraction dim: one psum per
    block, gelu stays local). The attention projections (``qkv``,
    ``out``) shard their CONTRACTION dim instead: the packed ``(d, 3d)``
    qkv layout reshapes to ``(3, heads, head_dim)`` downstream, and a
    contiguous column shard of the 3d dim crosses the q|k|v thirds for
    every practical width (head-parallel attention would need an
    unpacked/interleaved weight layout) — contraction sharding still
    divides the projection FLOPs and weight memory evenly and never
    fights the reshape; only the S^2 attention core itself stays
    replicated. Embeddings / norms / MoE trees stay replicated. A dim
    ``axis`` does not divide — or a mesh without ``axis`` at all —
    falls back to replicated: the annotation is a performance hint,
    never a constraint. Beyond-parity extension: the conf surface gets
    TP from kLayerPartition (parallel/shardings.py); this gives the
    code-API LM (init_lm / lm_apply / generate) the same axis without a
    conf.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    width = dict(mesh.shape).get(axis, 0)

    def spec_for(name: str, v) -> PartitionSpec:
        if not width:  # mesh has no such axis: everything replicated
            return PartitionSpec()
        if name.endswith("/mlp/up"):
            dim = 1
        elif name.endswith(("/attn/qkv", "/attn/out", "/mlp/down")):
            dim = 0
        else:
            return PartitionSpec()
        if v.ndim != 2 or v.shape[dim] % width:
            return PartitionSpec()
        return PartitionSpec(*(axis if d == dim else None for d in range(2)))

    return {k: NamedSharding(mesh, spec_for(k, v)) for k, v in params.items()}


def _layernorm(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _attend(q, k, v, cfg: TransformerConfig, mesh):
    if cfg.attn == "ring":
        if mesh is None:
            raise ValueError("attn='ring' requires a mesh with a seq axis")
        return ring_attention(q, k, v, mesh, causal=True)
    if cfg.attn == "flash":
        # dense below the per-device score-footprint threshold, kernel
        # above — "flash" means "don't blow memory", not "always
        # kernel" (ops.attention.auto_attention)
        from ..ops.attention import auto_attention

        return auto_attention(
            q, k, v, causal=True,
            n_devices=mesh.size if mesh is not None else 1,
        )
    return attention(q, k, v, causal=True)


def _block_apply(params, p, x, attend, cfg, mesh=None,
                 moe_capacity_factor=None):
    """One transformer block with a pluggable attention implementation.

    ``attend(q, k, v) -> (o, extra)`` receives/returns (B, H, S, D);
    ``extra`` passes through (K/V caches for decode, None otherwise).
    The SINGLE definition of block semantics — lm_apply, generate()'s
    prefill, and the KV-cache decode step all run this body, so the
    train->decode bit-exact parity cannot silently diverge.
    ``moe_capacity_factor`` overrides the MoE capacity (decode passes E
    so routing is drop-free; None keeps the training default).

    Every operation is named: the block's ``p`` (``blk3``) and inside it
    ``ln1``, ``qkv``, ``attend`` (whatever implements it), ``attn_out``,
    ``ln2``, ``mlp`` or ``moe``. A trace is read by these names."""
    b, s, _ = x.shape
    scope = jax.named_scope
    with scope(p):
        with scope("ln1"):
            h = _layernorm(
                x, params[f"{p}/ln1/scale"], params[f"{p}/ln1/bias"]
            )
        with scope("qkv"):
            qkv = h @ params[f"{p}/attn/qkv"]
            qkv = qkv.reshape(b, s, 3, cfg.n_heads, cfg.head_dim)
            q, k, v = (jnp.moveaxis(qkv[:, :, j], 2, 1) for j in range(3))
        with scope("attend"):
            o, extra = attend(q, k, v)
        with scope("attn_out"):
            o = jnp.moveaxis(o, 1, 2).reshape(b, s, cfg.d_model)
            x = x + o @ params[f"{p}/attn/out"]
        with scope("ln2"):
            h = _layernorm(
                x, params[f"{p}/ln2/scale"], params[f"{p}/ln2/bias"]
            )
        aux = jnp.float32(0.0)
        if cfg.moe_experts:
            from ..parallel.moe import moe_ffn, moe_ffn_dense

            moe_params = {
                k2: params[f"{p}/moe/{k2}"] for k2 in ("gate", "up", "down")
            }
            with scope("moe"):
                if mesh is not None and "expert" in getattr(
                    mesh, "shape", {}
                ):
                    y, aux = moe_ffn(h, moe_params, mesh)
                elif moe_capacity_factor is not None:
                    y, aux = moe_ffn_dense(
                        h, moe_params, capacity_factor=moe_capacity_factor
                    )
                else:
                    y, aux = moe_ffn_dense(h, moe_params)
                x = x + y
        else:
            with scope("mlp"):
                h = jax.nn.gelu(h @ params[f"{p}/mlp/up"])
                x = x + h @ params[f"{p}/mlp/down"]
    return x, aux, extra


@jax.named_scope("lm_head")
def lm_head(params: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Final layernorm + tied-embedding projection — the ONE LM head
    every forward shares (lm_apply, generate()'s prefill and decode
    scan, and the serving engine's decode/prefill/verify programs in
    serve/engine.py). Shared for the same reason ``_block_apply`` is:
    the speculative verify step's per-position logits must be the SAME
    head math as the one-token decode tick, so acceptance decisions
    cannot drift from what sequential decode would have emitted."""
    xf = _layernorm(x, params["ln_f/scale"], params["ln_f/bias"])
    return xf @ params["embed/tok"].T


def lm_apply(
    params: dict,
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    mesh=None,
    *,
    return_aux: bool = False,
):
    """tokens (B, S) int32 -> logits (B, S, vocab); causal.

    With ``return_aux`` also returns the summed MoE load-balancing loss
    (0.0 for dense-FFN configs)."""
    b, s = tokens.shape
    with jax.named_scope("embed"):
        x = params["embed/tok"][tokens] + params["embed/pos"][:s]
    aux_total = jnp.float32(0.0)
    attend = lambda q, k, v: (_attend(q, k, v, cfg, mesh), None)  # noqa: E731
    for i in range(cfg.n_layers):
        x, aux, _ = _block_apply(params, f"blk{i}", x, attend, cfg, mesh)
        aux_total = aux_total + aux
    logits = lm_head(params, x)
    if return_aux:
        return logits, aux_total
    return logits


@jax.named_scope("cache_attend")
def cache_attend(q, k_cache, v_cache, positions):
    """Masked attention of Q queries against a FULL cache — the single
    attention body every serving path shares (generate()'s prefill and
    decode scan here, the paged-KV engine's gathered blocks in
    serve/engine.py, the conf-net decode in serve/conf_decode.py).

    ``q`` (B, H, Q, D) holds queries whose absolute sequence positions
    are ``positions`` (B, Q); ``k_cache``/``v_cache`` (B, H, C, D) hold
    the whole (zero-padded) cache. Cache entries beyond a query's
    position score -1e30, so their softmax weight underflows to exactly
    0.0 — the cache tail (and any garbage a paged pool gathers there)
    never moves a bit of the output. Because the math is shared, "paged
    KV == dense cache" parity is bitwise by construction, not tested
    luck."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k_cache) * scale
    mask = (
        jnp.arange(k_cache.shape[2])[None, None, None, :]
        <= positions[:, None, :, None]
    )
    s = jnp.where(mask, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v_cache)


def _block_step(params, p, x, k_cache, v_cache, pos, cfg):
    """One transformer block on Q tokens (B, Q, d) against the
    (B, H, C, D) caches; returns (x', new_k, new_v) where new_k/v are
    the caches with positions [pos, pos+Q) filled. Q == 1 is the decode
    step; Q == prompt length (pos == 0) is prefill — ONE body serves
    both, shared with lm_apply via _block_apply. The MoE capacity is E
    (drop-free, batch-independent)."""

    def attend(q, k, v):
        nk = jax.lax.dynamic_update_slice_in_dim(k_cache, k, pos, axis=2)
        nv = jax.lax.dynamic_update_slice_in_dim(v_cache, v, pos, axis=2)
        positions = jnp.broadcast_to(
            pos + jnp.arange(q.shape[2])[None, :], q.shape[:1] + q.shape[2:3]
        )
        return cache_attend(q, nk, nv, positions), (nk, nv)

    x, _, (nk, nv) = _block_apply(
        params, p, x, attend, cfg,
        moe_capacity_factor=float(max(cfg.moe_experts, 1)),
    )
    return x, nk, nv


def generate(
    params: dict,
    prompt: jnp.ndarray,
    cfg: TransformerConfig,
    n_tokens: int,
    *,
    rng: jax.Array | None = None,
    temperature: float = 0.0,
    prefill_chunk: int | None = None,
) -> jnp.ndarray:
    """Autoregressive decode with a KV cache, TPU-first.

    ``prompt`` (B, P) int32 -> (B, P + n_tokens). Greedy when
    ``temperature`` == 0, else softmax sampling at that temperature
    (``rng`` required). The whole decode is ONE jittable program:
    prefill feeds the prompt through the SAME cached-attention
    ``_block_step`` body the decode scan uses (in chunks of
    ``prefill_chunk`` tokens, default min(P, 512), so a long-context
    prompt never materializes more than a chunk x max_len score
    tensor), then a ``lax.scan`` over ``n_tokens`` steps feeds each
    sampled token back through single-token block steps against the
    (B, H, max_len, D) caches — static shapes throughout, position
    handled by masking, no dynamic Python control flow. Chunking is
    bitwise split-invariant, so ``prefill_chunk`` is a memory knob,
    never a semantics knob.

    Beyond-parity extension: the reference is a pre-transformer system
    with no inference path at all (SURVEY §5); this completes the LM
    family's train -> sample loop.

    MoE semantics at decode: prefill and every decode step route with
    capacity_factor = E, which makes GShard capacity vacuous (capacity
    >= token count), so NO token is ever dropped at inference — and a
    row's output never depends on what else shares the batch. That is
    the standard deployment behavior; it also means exact parity with a
    recompute-the-whole-prefix oracle (which uses the TRAINING
    capacity) is only defined for dense-FFN configs
    (tests/test_generate.py pins dense parity bit-exactly, MoE
    batch-independence explicitly).
    """
    b, plen = prompt.shape
    if plen < 1:
        raise ValueError("generate: prompt must hold at least one token")
    total = plen + n_tokens
    if total > cfg.max_len:
        raise ValueError(
            f"generate: prompt {plen} + n_tokens {n_tokens} exceeds "
            f"max_len {cfg.max_len}"
        )
    if temperature > 0.0 and rng is None:
        raise ValueError("generate: sampling (temperature > 0) needs rng")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if prefill_chunk is None:
        prefill_chunk = max(1, min(plen, 512))

    # ---- prefill: the SAME _block_step body the decode scan (and the
    # serving engine, serve/engine.py) runs, at Q = chunk length against
    # zero-initialized caches. Chunking bounds the (B, H, Q, max_len)
    # score footprint for long prompts — the serving tier's chunked
    # prefill — and is bitwise chunk-split-invariant: each query attends
    # the full masked cache regardless of which chunk computed it.
    shape = (b, cfg.n_heads, cfg.max_len, cfg.head_dim)
    k_caches = [jnp.zeros(shape) for _ in range(cfg.n_layers)]
    v_caches = [jnp.zeros(shape) for _ in range(cfg.n_layers)]
    x_last = None
    for c0 in range(0, plen, prefill_chunk):
        n = min(prefill_chunk, plen - c0)
        x = (
            params["embed/tok"][prompt[:, c0:c0 + n]]
            + params["embed/pos"][c0:c0 + n]
        )
        for i in range(cfg.n_layers):
            x, k_caches[i], v_caches[i] = _block_step(
                params, f"blk{i}", x, k_caches[i], v_caches[i],
                jnp.int32(c0), cfg,
            )
        x_last = x
    last_logits = lm_head(params, x_last)[:, -1]

    def sample(logits, key):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(prompt.dtype)
        return jax.random.categorical(
            key, logits / temperature, axis=-1
        ).astype(prompt.dtype)

    k0, rng = jax.random.split(rng)
    first = sample(last_logits, k0)

    # ---- decode: scan over single-token steps ----
    def step(carry, key):
        token, pos, ks, vs = carry
        x = (
            params["embed/tok"][token][:, None, :]
            + params["embed/pos"][pos][None, None, :]
        )
        new_ks, new_vs = [], []
        for i in range(cfg.n_layers):
            x, nk, nv = _block_step(
                params, f"blk{i}", x, ks[i], vs[i], pos, cfg
            )
            new_ks.append(nk)
            new_vs.append(nv)
        logits = lm_head(params, x)[:, 0]
        nxt = sample(logits, key)
        return (nxt, pos + 1, new_ks, new_vs), token

    keys = jax.random.split(rng, n_tokens)
    (last, _, _, _), out = jax.lax.scan(
        step, (first, jnp.int32(plen), k_caches, v_caches), keys
    )
    # out is (n_tokens, B): the token EMITTED at each step, i.e. the
    # sequence [first, ...]; drop nothing — `last` is the (unemitted)
    # n_tokens+1-th sample
    gen = jnp.moveaxis(out, 0, 1)
    return jnp.concatenate([prompt, gen], axis=1)


def lm_loss(
    params: dict,
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    mesh=None,
) -> jnp.ndarray:
    """Next-token cross entropy, mean over all predicting positions.

    The forward runs on the full (ring-divisible) sequence; the loss
    drops the last position's prediction instead of trimming the input,
    so ring sharding never sees an odd S-1 length. MoE configs add the
    weighted load-balancing aux."""
    logits, aux = lm_apply(params, tokens, cfg, mesh, return_aux=True)
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    targets = tokens[:, 1:]
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll) + cfg.moe_aux_weight * aux
