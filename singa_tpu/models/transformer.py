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


#: what ``TransformerConfig.layers`` may name
LAYER_KINDS = ("mamba", "attn", "moe", "mlp", "shortconv")


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
    # -- the block's vocabulary beyond GPT-2's. Every default leaves the
    # model above bit for bit; ONE ``_block_apply`` lowers them all.
    #: "layernorm" (scale and bias) | "rmsnorm" (scale alone, float32)
    norm: str = "layernorm"
    norm_eps: float = 1e-5
    #: "learned" (an ``embed/pos`` table) | "rope" (rotate-half on q and
    #: k at each token's own position; no table, so no length to size)
    pos: str = "learned"
    rope_theta: float = 10000.0
    #: K/V heads; 0 = as many as query heads. Query head j reads K/V
    #: head ``j // (n_heads // n_kv_heads)``.
    n_kv_heads: int = 0
    #: width of a head; 0 = ``d_model // n_heads``
    head_dim: int = 0
    #: RMSNorm over each head's ``head_dim`` on q and k, before rotation
    qk_norm: bool = False
    #: False = a head of its own (``head/out``) instead of ``embed/tok.T``
    tied_head: bool = True
    #: > 0: every token goes to its ``moe_top_k`` best experts of a
    #: softmax over all ``moe_experts``, weights renormalised, SwiGLU
    #: experts ``moe_d_ff`` wide, no capacity and no dropped token
    #: (parallel/moe.py ``moe_topk_ffn``). 0 = the Switch top-1 layer.
    moe_top_k: int = 0
    moe_d_ff: int = 0
    #: > 0: generation by diffusion over blocks of this many positions
    #: (serve/engine.py ``_block_step``): a query sees every position up
    #: to the END of its own block, and ``mask_id`` stands where a
    #: position is still masked.
    diffusion_block: int = 0
    mask_id: int = 0
    #: the dense MLP: "gelu" (``mlp/up``, ``mlp/down``) | "swiglu"
    #: (``(silu(x Wg) * (x Wu)) Wd`` with ``mlp/gate`` beside them)
    mlp: str = "gelu"
    #: layer types: the first ``dense_layers`` blocks keep the dense MLP
    #: (``d_ff`` wide) in a model whose other blocks are top-k expert
    #: layers
    dense_layers: int = 0
    #: the top-k router's scores: "softmax" | "sigmoid" (each expert on
    #: its own)
    moe_score: str = "softmax"
    #: a bias per expert (``moe/bias``) added to the scores to CHOOSE the
    #: top k, and left out of the gates
    moe_bias: bool = False
    #: the gates, normalised over the chosen, times this
    moe_scale: float = 1.0
    #: > 0: a shared SwiGLU expert this wide runs on every token beside
    #: the routed ones (``moe/s_gate``, ``s_up``, ``s_down``)
    moe_shared_d_ff: int = 0
    #: the experts this device holds of each layer's ``moe_experts``:
    #: (first, how many). The router stays ``moe_experts`` wide, the
    #: expert weights hold that many, and the layer leaves out what the
    #: others would add (parallel/moe.py). () = all of them.
    moe_held: tuple = ()
    #: > 0: latent attention. K and V of every head come out of ONE
    #: latent of this width a token (``attn/kv_a``, its RMSNorm,
    #: ``attn/kv_b``) and a rotary key of ``rope_dim`` shared by all
    #: heads; queries come through a latent of ``q_latent``
    #: (``attn/q_a``, its RMSNorm, ``attn/q_b``). ``head_dim`` is then
    #: the width of a head's UNROTATED query/key part, ``rope_dim`` of
    #: its rotated part and ``v_head_dim`` of its value. What a cache
    #: holds is the latent after its norm beside the rotated key:
    #: ``latent_width`` values a token a layer (serve/engine.py).
    kv_latent: int = 0
    q_latent: int = 0
    rope_dim: int = 0
    v_head_dim: int = 0
    #: YaRN scaling of the rotary frequencies: (factor, original length,
    #: beta_fast, beta_slow, mscale, mscale_all_dim); () = none
    rope_yarn: tuple = ()
    #: layer kinds, one a layer: a block of such a model is ONE mixer,
    #: ``x + mixer(norm(x))``, the mixer a Mamba-2 layer ("mamba",
    #: ops/ssm.py), attention ("attn"), a top-k expert layer ("moe"),
    #: the dense MLP ("mlp") or a gated short convolution ("shortconv",
    #: ops/ssm.py ``short_conv``). () = today's blocks: attention then
    #: MLP or experts in every one.
    layers: tuple = ()
    #: the Mamba-2 layers' sizes: heads of ``mamba_head_dim``, a state
    #: of ``ssm_state`` a head channel, B and C shared by the heads of
    #: each of ``ssm_groups`` groups, a causal depthwise convolution
    #: ``conv_kernel`` long (a short convolution's taps too), the
    #: chunked scan's blocks of ``ssm_block``
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    conv_kernel: int = 4
    ssm_block: int = 128
    #: the routed and the shared experts: "swiglu" (three matrices an
    #: expert) | "relu2" (``relu(x U)^2 D``, no gate matrix)
    moe_act: str = "swiglu"
    #: > 0: the routed experts live in a latent this wide (``moe/
    #: lat_down`` before them, ``moe/lat_up`` after the combine); router
    #: and shared expert read the full width
    moe_latent: int = 0

    def __post_init__(self):
        if not self.head_dim:
            assert self.d_model % self.n_heads == 0
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if not self.n_kv_heads:
            object.__setattr__(self, "n_kv_heads", self.n_heads)
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_kv_heads {self.n_kv_heads} does not divide n_heads "
                f"{self.n_heads}"
            )
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm {self.norm!r}: layernorm or rmsnorm")
        if self.pos not in ("learned", "rope", "none"):
            raise ValueError(f"pos {self.pos!r}: learned, rope or none")
        if self.moe_top_k and not (
            0 < self.moe_top_k <= self.moe_experts and self.moe_d_ff > 0
        ):
            raise ValueError(
                f"moe_top_k {self.moe_top_k} needs moe_experts >= it "
                f"({self.moe_experts}) and a moe_d_ff ({self.moe_d_ff})"
            )
        if self.mlp not in ("gelu", "swiglu", "relu2"):
            raise ValueError(f"mlp {self.mlp!r}: gelu, swiglu or relu2")
        if self.moe_act not in ("swiglu", "relu2"):
            raise ValueError(f"moe_act {self.moe_act!r}: swiglu or relu2")
        if self.moe_score not in ("softmax", "sigmoid"):
            raise ValueError(
                f"moe_score {self.moe_score!r}: softmax or sigmoid"
            )
        if self.moe_held:
            first, n = self.moe_held
            if not (self.moe_top_k and 0 <= first and n > 0
                    and first + n <= self.moe_experts):
                raise ValueError(
                    f"moe_held {self.moe_held} names no share of "
                    f"{self.moe_experts} top-k experts"
                )
        if self.kv_latent and not (
            self.q_latent and self.rope_dim and self.v_head_dim
            and self.pos == "rope" and self.norm == "rmsnorm"
            and not self.gqa and not self.qk_norm
        ):
            raise ValueError(
                "kv_latent needs q_latent, rope_dim and v_head_dim, "
                "pos = 'rope', norm = 'rmsnorm', and neither fewer K/V "
                "heads nor qk_norm"
            )
        if self.layers:
            unknown = set(self.layers) - set(LAYER_KINDS)
            if unknown or len(self.layers) != self.n_layers:
                raise ValueError(
                    f"layers {self.layers}: n_layers = {self.n_layers} "
                    f"kinds out of {LAYER_KINDS}"
                )
            if "moe" in self.layers and not self.moe_top_k:
                raise ValueError("layers: a 'moe' layer needs moe_top_k")
            if "mamba" in self.layers and not (
                self.mamba_heads and self.mamba_head_dim and self.ssm_state
                and self.mamba_heads % self.ssm_groups == 0
            ):
                raise ValueError(
                    "layers: a 'mamba' layer needs mamba_heads (a multiple "
                    "of ssm_groups), mamba_head_dim and ssm_state"
                )
            if "shortconv" in self.layers and self.conv_kernel < 2:
                raise ValueError(
                    "layers: a 'shortconv' layer needs conv_kernel >= 2 "
                    "(taps, of which the last K - 1 rows are its state)"
                )
            if self.kv_latent or self.diffusion_block or self.dense_layers:
                raise ValueError(
                    "layers: kv_latent, diffusion_block and dense_layers "
                    "belong to blocks of two mixers"
                )

    @property
    def qkv_width(self) -> int:
        """Columns of the packed ``attn/qkv``: q's heads, then k's, v's."""
        return (self.n_heads + 2 * self.n_kv_heads) * self.head_dim

    @property
    def gqa(self) -> bool:
        return self.n_kv_heads != self.n_heads

    def expert_layer(self, i: int) -> bool:
        """Whether block ``i`` is a top-k expert layer."""
        if self.layers:
            return self.layers[i] == "moe"
        return bool(self.moe_top_k) and i >= self.dense_layers

    def layers_of(self, kind: str) -> tuple:
        """The indices of the blocks that hold a ``kind`` mixer; every
        block holds attention where the model has no ``layers``."""
        if not self.layers:
            return tuple(range(self.n_layers)) if kind == "attn" else ()
        return tuple(i for i, k in enumerate(self.layers) if k == kind)

    @property
    def conv_dim(self) -> int:
        """Channels of a Mamba-2 layer's convolution: x, B and C."""
        return (
            self.mamba_heads * self.mamba_head_dim
            + 2 * self.ssm_groups * self.ssm_state
        )

    @property
    def latent_width(self) -> int:
        """What a latent cache holds a token a layer."""
        return self.kv_latent + self.rope_dim

    @property
    def attn_scale(self) -> float:
        """What the scores are multiplied by before the softmax: one
        over the root of a query's width, and under YaRN with an
        ``mscale_all_dim`` the square of its magnitude correction."""
        scale = (self.head_dim + self.rope_dim) ** -0.5
        if self.rope_yarn and self.rope_yarn[5]:
            scale *= _yarn_mscale(self.rope_yarn[0], self.rope_yarn[5]) ** 2
        return scale


def init_lm(rng: jax.Array, cfg: TransformerConfig) -> dict:
    """Flat name-keyed param pytree; scaled-normal init. The names
    follow the config's fields: no ``embed/pos`` under rotary positions,
    no norm bias under RMSNorm, ``attn/q_norm`` / ``attn/k_norm`` with
    QK-norm, ``head/out`` for an untied head, and the expert layer's own
    tree (parallel/moe.py) under ``moe/``."""
    params: dict[str, jnp.ndarray] = {}
    bias = cfg.norm == "layernorm"

    def norm(key, shape, scale):
        return scale * jax.random.normal(key, shape, dtype=jnp.float32)

    def norm_params(name, width):
        params[f"{name}/scale"] = jnp.ones((width,))
        if bias:
            params[f"{name}/bias"] = jnp.zeros((width,))

    # (a latent block draws five attention matrices, a gated MLP three)
    per_layer = 8 if cfg.kv_latent or cfg.mlp == "swiglu" else 4
    keys = iter(jax.random.split(rng, 2 + per_layer * cfg.n_layers))
    params["embed/tok"] = norm(next(keys), (cfg.vocab, cfg.d_model), 0.02)
    pos_key = next(keys)
    if cfg.pos == "learned":
        params["embed/pos"] = norm(pos_key, (cfg.max_len, cfg.d_model), 0.02)
    for i in range(cfg.n_layers):
        p = f"blk{i}"
        kind = cfg.layers[i] if cfg.layers else None   # None: two mixers
        norm_params(f"{p}/ln1", cfg.d_model)
        if kind == "mamba":
            _init_mamba(params, p, cfg, norm, keys)
        if kind == "shortconv":
            _init_shortconv(params, p, cfg, norm, keys)
        if kind in (None, "attn"):
            _init_attention(params, p, cfg, norm, keys)
        if kind is None:
            norm_params(f"{p}/ln2", cfg.d_model)
        if kind in (None, "moe", "mlp"):
            _init_ffn(params, p, i, cfg, norm, keys)
    norm_params("ln_f", cfg.d_model)
    if not cfg.tied_head:
        params["head/out"] = norm(
            jax.random.fold_in(rng, 1), (cfg.d_model, cfg.vocab), 0.02
        )
    return params


def _init_attention(params, p, cfg: TransformerConfig, norm, keys) -> None:
    """A block's attention parameters under ``p``, drawn from ``keys``
    by ``norm(key, shape, scale)``."""
    d = cfg.d_model
    if cfg.kv_latent:
        h, rq, rkv = cfg.n_heads, cfg.q_latent, cfg.kv_latent
        params[f"{p}/attn/q_a"] = norm(next(keys), (d, rq), 1 / math.sqrt(d))
        params[f"{p}/attn/q_a_norm"] = jnp.ones((rq,))
        params[f"{p}/attn/q_b"] = norm(
            next(keys), (rq, h * (cfg.head_dim + cfg.rope_dim)),
            1 / math.sqrt(rq),
        )
        params[f"{p}/attn/kv_a"] = norm(
            next(keys), (d, cfg.latent_width), 1 / math.sqrt(d)
        )
        params[f"{p}/attn/kv_a_norm"] = jnp.ones((rkv,))
        params[f"{p}/attn/kv_b"] = norm(
            next(keys), (rkv, h * (cfg.head_dim + cfg.v_head_dim)),
            1 / math.sqrt(rkv),
        )
    else:
        params[f"{p}/attn/qkv"] = norm(
            next(keys), (d, cfg.qkv_width), 1 / math.sqrt(d)
        )
    params[f"{p}/attn/out"] = norm(
        next(keys),
        (cfg.n_heads * (cfg.v_head_dim or cfg.head_dim), d),
        1 / math.sqrt(d * 2 * cfg.n_layers),
    )
    if cfg.qk_norm:
        params[f"{p}/attn/q_norm"] = jnp.ones((cfg.head_dim,))
        params[f"{p}/attn/k_norm"] = jnp.ones((cfg.head_dim,))


def _init_ffn(params, p, i: int, cfg: TransformerConfig, norm, keys) -> None:
    """Block ``i``'s position-wise parameters under ``p``: the expert
    layer's own tree (parallel/moe.py) under ``moe/``, or the dense
    MLP's."""
    d, f = cfg.d_model, cfg.d_ff
    if cfg.moe_experts and (cfg.expert_layer(i) or not cfg.moe_top_k):
        from ..parallel.moe import init_moe, init_moe_topk

        moe = (
            init_moe_topk(
                next(keys), d, cfg.moe_d_ff, cfg.moe_experts,
                held=cfg.moe_held[1] if cfg.moe_held else 0,
                bias=cfg.moe_bias, shared_d_ff=cfg.moe_shared_d_ff,
                act=cfg.moe_act, latent=cfg.moe_latent,
            )
            if cfg.moe_top_k
            else init_moe(next(keys), d, f, cfg.moe_experts)
        )
        for k, v in moe.items():
            params[f"{p}/moe/{k}"] = v
        return
    if cfg.mlp == "swiglu":
        params[f"{p}/mlp/gate"] = norm(next(keys), (d, f), 1 / math.sqrt(d))
    params[f"{p}/mlp/up"] = norm(next(keys), (d, f), 1 / math.sqrt(d))
    params[f"{p}/mlp/down"] = norm(
        next(keys), (f, d), 1 / math.sqrt(f * 2 * cfg.n_layers)
    )


def _init_mamba(params, p, cfg: TransformerConfig, norm, keys) -> None:
    """A block's Mamba-2 parameters under ``p`` (ops/ssm.py
    ``MAMBA_PARAMS``), the lineage's initialisation: the step ``dt``
    log-uniform in [1e-3, 1e-1] through the inverse of the softplus,
    ``A = -(1..H)``, the convolution uniform in +-1/sqrt(K)."""
    d, h, k = cfg.d_model, cfg.mamba_heads, cfg.conv_kernel
    d_in = h * cfg.mamba_head_dim
    dt = jnp.exp(jax.random.uniform(
        next(keys), (h,), minval=math.log(1e-3), maxval=math.log(1e-1)
    ))
    params.update({
        f"{p}/mamba/in_proj": norm(
            next(keys), (d, d_in + cfg.conv_dim + h), 1 / math.sqrt(d)
        ),
        f"{p}/mamba/conv_w": jax.random.uniform(
            next(keys), (k, cfg.conv_dim), minval=-1 / math.sqrt(k),
            maxval=1 / math.sqrt(k),
        ),
        f"{p}/mamba/conv_b": jnp.zeros((cfg.conv_dim,)),
        f"{p}/mamba/dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        f"{p}/mamba/A_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
        f"{p}/mamba/D": jnp.ones((h,)),
        f"{p}/mamba/norm": jnp.ones((d_in,)),
        f"{p}/mamba/out_proj": norm(
            next(keys), (d_in, d), 1 / math.sqrt(d_in * 2 * cfg.n_layers)
        ),
    })


def _init_shortconv(params, p, cfg: TransformerConfig, norm, keys) -> None:
    """A block's short-convolution parameters under ``p`` (ops/ssm.py
    ``SHORTCONV_PARAMS``): the projections scaled normals, the taps
    uniform in +-1/sqrt(K) (a depthwise convolution's fan-in)."""
    d, k = cfg.d_model, cfg.conv_kernel
    params.update({
        f"{p}/shortconv/in_proj": norm(
            next(keys), (d, 3 * d), 1 / math.sqrt(d)
        ),
        f"{p}/shortconv/conv_w": jax.random.uniform(
            next(keys), (k, d), minval=-1 / math.sqrt(k),
            maxval=1 / math.sqrt(k),
        ),
        f"{p}/shortconv/out_proj": norm(
            next(keys), (d, d), 1 / math.sqrt(d * 2 * cfg.n_layers)
        ),
    })


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


def _rmsnorm(x, scale, eps):
    """x / rms(x) * scale over the last axis, worked in float32 whatever
    ``x`` is stored in, and handed back in ``x``'s type."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


def _norm(params, name, x, cfg):
    """The config's norm over the model width: ``<name>/scale`` (and
    ``<name>/bias`` for LayerNorm)."""
    if cfg.norm == "rmsnorm":
        return _rmsnorm(x, params[f"{name}/scale"], cfg.norm_eps)
    return _layernorm(
        x, params[f"{name}/scale"], params[f"{name}/bias"], cfg.norm_eps
    )


def _yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction for a context stretched ``factor``
    times."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _yarn_inv_freq(dim: int, theta: float, yarn: tuple):
    """The ``dim // 2`` rotary frequencies under YaRN, float32: pair i
    keeps ``theta ** (-2i / dim)`` where it turns more than
    ``beta_fast`` times within the original length, takes it divided by
    ``factor`` where it turns fewer than ``beta_slow`` times, and a
    linear blend of the two between."""
    factor, orig, beta_fast, beta_slow = yarn[:4]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta)
        )

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    freq = theta ** (-i / (dim // 2))
    keep = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return freq / factor * (1.0 - keep) + freq * keep


@jax.named_scope("rope")
def _rope(x, positions, theta, yarn=()):
    """Rotate-half rotary embedding: ``x`` (B, H, S, D) at ``positions``
    (B, S). Pair (i, i + D/2) turns by ``pos * theta ** (-2i / D)``, or
    by ``yarn``'s frequencies (``TransformerConfig.rope_yarn``), whose
    cos and sin also carry the ratio of its two magnitude corrections;
    angles, sines and the rotation are float32, the result ``x``'s type."""
    half = x.shape[-1] // 2
    if yarn:
        inv = _yarn_inv_freq(2 * half, theta, yarn)
    else:
        inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, :, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn:
        m = _yarn_mscale(yarn[0], yarn[4]) / _yarn_mscale(yarn[0], yarn[5])
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def embed(params, tokens, positions, cfg):
    """Token embedding, plus the learned position rows where the config
    has a table (rotary positions enter in the block, on q and k; a
    model with ``pos = "none"`` has no positional term at all: its
    recurrent layers carry the order)."""
    x = params["embed/tok"][tokens]
    if cfg.pos == "learned":
        x = x + params["embed/pos"][positions]
    return x


def block_limits(positions, cfg):
    """The last position each query may see: its own for a causal
    model; the end of its block under diffusion over blocks (M[i, j] = 0
    iff ``j // B <= i // B``). Every attention body masks by
    ``arange(C) <= limit``, so this one number a query is the whole
    block-causal mask."""
    b = cfg.diffusion_block
    return positions // b * b + (b - 1) if b else positions


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


def _latent_qk(params, p, h, positions, cfg):
    """Latent attention's side of ``qkv``: h (B, S, d) -> the queries
    (B, H, S, head_dim + rope_dim), each head's unrotated part then its
    rotated part, and what a cache keeps of the token, (B, S,
    latent_width): the K/V latent after its norm, then the ONE rotary
    key all heads share, rotated."""
    b, s, _ = h.shape
    scope = jax.named_scope
    hq, dn, r = cfg.n_heads, cfg.head_dim, cfg.kv_latent

    def rope(x):
        return _rope(x, positions, cfg.rope_theta, cfg.rope_yarn)

    with scope("q_latent"):
        cq = _rmsnorm(
            h @ params[f"{p}/attn/q_a"], params[f"{p}/attn/q_a_norm"],
            cfg.norm_eps,
        )
        q = jnp.moveaxis(
            (cq @ params[f"{p}/attn/q_b"]).reshape(b, s, hq, -1), 2, 1
        )
        q = jnp.concatenate([q[..., :dn], rope(q[..., dn:])], axis=-1)
    with scope("kv_latent"):
        kv = h @ params[f"{p}/attn/kv_a"]
        ckv = _rmsnorm(
            kv[..., :r], params[f"{p}/attn/kv_a_norm"], cfg.norm_eps
        )
        kpe = rope(kv[:, None, :, r:])[:, 0]
        return q, jnp.concatenate([ckv, kpe], axis=-1)


def _packed_qkv(params, p, h, positions, cfg):
    """The packed projection's side of ``qkv``: h (B, S, d) -> q
    (B, H, S, D) and k, v (B, n_kv_heads, S, D), with the config's
    QK-norm and rotation on q and k."""
    b, s, _ = h.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qkv = h @ params[f"{p}/attn/qkv"]
    if cfg.gqa:
        q, k, v = (
            jnp.moveaxis(part.reshape(b, s, -1, hd), 2, 1)
            for part in jnp.split(qkv, [hq * hd, (hq + hkv) * hd], axis=-1)
        )
    else:
        qkv = qkv.reshape(b, s, 3, hq, hd)
        q, k, v = (jnp.moveaxis(qkv[:, :, j], 2, 1) for j in range(3))
    if cfg.qk_norm:
        with jax.named_scope("qk_norm"):
            q = _rmsnorm(q, params[f"{p}/attn/q_norm"], cfg.norm_eps)
            k = _rmsnorm(k, params[f"{p}/attn/k_norm"], cfg.norm_eps)
    if cfg.pos == "rope":
        q = _rope(q, positions, cfg.rope_theta, cfg.rope_yarn)
        k = _rope(k, positions, cfg.rope_theta, cfg.rope_yarn)
    return q, k, v


def _attention(params, p, h, x, attend, cfg, positions):
    """The attention mixer on the normed ``h``: ``qkv``, ``attend``
    (whatever implements it), ``attn_out``. -> (x + what attention
    gives, ``attend``'s extra)."""
    b, s, _ = x.shape
    scope = jax.named_scope
    with scope("qkv"):
        if cfg.kv_latent:
            q, k, v = *_latent_qk(params, p, h, positions, cfg), None
        else:
            q, k, v = _packed_qkv(params, p, h, positions, cfg)
    with scope("attend"):
        o, extra = attend(q, k, v)
    with scope("attn_out"):
        o = jnp.moveaxis(o, 1, 2).reshape(b, s, -1)
        x = x + o @ params[f"{p}/attn/out"]
    return x, extra


def _ffn(params, p, h, x, cfg, mesh, moe_capacity_factor, valid,
         experts: bool):
    """The position-wise mixer on the normed ``h``: the top-k expert
    layer (``experts``), the Switch layer, or the config's dense MLP.
    -> (x + what it gives, aux)."""
    scope = jax.named_scope
    aux = jnp.float32(0.0)
    if experts:
        from ..parallel import moe

        names = moe.topk_param_names(
            cfg.moe_act, cfg.moe_bias, bool(cfg.moe_shared_d_ff),
            bool(cfg.moe_latent),
        )
        with scope("moe"):
            y, aux = moe.moe_topk_ffn(
                h, {k2: params[f"{p}/moe/{k2}"] for k2 in names},
                cfg.moe_top_k, valid=valid, score=cfg.moe_score,
                scale=cfg.moe_scale,
                held_from=cfg.moe_held[0] if cfg.moe_held else 0,
            )
            x = x + y
    elif cfg.moe_experts and not cfg.moe_top_k:
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
    elif cfg.mlp == "swiglu":
        with scope("mlp"):
            f32 = jnp.float32
            a = (h @ params[f"{p}/mlp/gate"]).astype(f32)
            u = (h @ params[f"{p}/mlp/up"]).astype(f32)
            h = (jax.nn.silu(a) * u).astype(x.dtype)
            x = x + h @ params[f"{p}/mlp/down"]
    elif cfg.mlp == "relu2":
        with scope("mlp"):
            u = (h @ params[f"{p}/mlp/up"]).astype(jnp.float32)
            h = jnp.square(jax.nn.relu(u)).astype(x.dtype)
            x = x + h @ params[f"{p}/mlp/down"]
    else:
        with scope("mlp"):
            h = jax.nn.gelu(h @ params[f"{p}/mlp/up"])
            x = x + h @ params[f"{p}/mlp/down"]
    return x, aux


def _block_apply(params, p, x, attend, cfg, mesh=None,
                 moe_capacity_factor=None, positions=None, valid=None,
                 carried=None):
    """One transformer block with a pluggable attention implementation.

    ``attend(q, k, v) -> (o, extra)`` receives q (B, H, S, D) and k, v
    (B, n_kv_heads, S, D) and returns (B, H, S, D); ``extra`` passes
    through (K/V caches for decode, None otherwise). Under latent
    attention (``cfg.kv_latent``) it receives q (B, H, S, head_dim +
    rope_dim), the tokens' latents (B, S, latent_width) for k and None
    for v, and returns (B, H, S, v_head_dim): ``latent_attend`` is the
    body behind it.
    The SINGLE definition of block semantics — lm_apply, generate()'s
    prefill, and the KV-cache decode step all run this body, so the
    train->decode bit-exact parity cannot silently diverge. The config's
    fields choose among its operations (LayerNorm or RMSNorm, QK-norm,
    rotary positions, fewer K/V heads, GELU MLP, Switch or top-k
    experts); there is no second body.

    A model with ``cfg.layers`` has ONE mixer a block,
    ``x + mixer(ln1(x))``, of the block's kind: attention as above (an
    ``attend`` is only read there), the top-k expert layer or the dense
    MLP, a Mamba-2 layer (ops/ssm.py ``mamba2_mixer``) or a gated short
    convolution (``short_conv``). The last two start from ``carried`` —
    a (state, convolution tail) a sequence for Mamba, the tail alone for
    the short convolution, or None for a sequence's start — step over
    the positions ``valid`` marks, and hand what they carry on back as
    ``extra``, in the same form.
    ``moe_capacity_factor`` overrides the Switch MoE's capacity (decode
    passes E so routing is drop-free; None keeps the training default).
    ``positions`` (B, S) are the tokens' own positions, read by rotary
    embedding alone. ``valid`` (B, S) marks the tokens that count in the
    top-k expert layer's two counters and in a recurrent state (None =
    all).

    -> (x, aux, extra): ``aux`` is the Switch layer's load-balancing
    loss (0.0 for a dense FFN), or for the top-k layer its counters,
    int32 ``[experts hit, most tokens one expert took]``.

    Every operation is named: the block's ``p`` (``blk3``) and inside it
    ``ln1``, ``qkv`` (holding ``qk_norm`` and ``rope``), ``attend``
    (whatever implements it), ``attn_out``, ``ln2``, ``mlp`` or ``moe``
    (the top-k layer: ``route``, ``experts``, ``combine``, ``shared``,
    and ``latent_down`` / ``latent_up`` where the experts live in a
    latent), ``mamba`` (``in_proj``, ``conv``, ``scan`` or ``step``,
    ``gate_norm``, ``out_proj``), or ``shortconv`` (``in_proj``,
    ``conv``, ``out_proj``). Latent attention's ``qkv`` holds
    ``q_latent`` and ``kv_latent``. A trace is read by these names."""
    scope = jax.named_scope
    i = int(p.removeprefix("blk"))
    kind = cfg.layers[i] if cfg.layers else None
    aux, extra = jnp.float32(0.0), None
    with scope(p):
        with scope("ln1"):
            h = _norm(params, f"{p}/ln1", x, cfg)
        if kind == "mamba":
            from ..ops import ssm

            with scope("mamba"):
                y, extra = ssm.mamba2_mixer(
                    {k: params[f"{p}/mamba/{k}"] for k in ssm.MAMBA_PARAMS},
                    h, heads=cfg.mamba_heads, head_dim=cfg.mamba_head_dim,
                    state_dim=cfg.ssm_state, groups=cfg.ssm_groups,
                    block=cfg.ssm_block, eps=cfg.norm_eps, carried=carried,
                    valid=valid,
                )
                x = x + y
        if kind == "shortconv":
            from ..ops import ssm

            with scope("shortconv"):
                y, extra = ssm.short_conv(
                    {k: params[f"{p}/shortconv/{k}"]
                     for k in ssm.SHORTCONV_PARAMS},
                    h, carried=carried, valid=valid,
                )
                x = x + y
        if kind in (None, "attn"):
            x, extra = _attention(params, p, h, x, attend, cfg, positions)
        if kind is None:
            with scope("ln2"):
                h = _norm(params, f"{p}/ln2", x, cfg)
        if kind in (None, "moe", "mlp"):
            x, aux = _ffn(
                params, p, h, x, cfg, mesh, moe_capacity_factor, valid,
                experts=cfg.expert_layer(i),
            )
    return x, aux, extra


@jax.named_scope("lm_head")
def lm_head(params: dict, x: jnp.ndarray, cfg) -> jnp.ndarray:
    """Final norm + projection — the ONE LM head every forward shares
    (lm_apply, generate()'s prefill and decode scan, and the serving
    engine's programs in serve/engine.py). Shared for the same reason
    ``_block_apply`` is: the speculative verify step's per-position
    logits must be the SAME head math as the one-token decode tick, so
    acceptance decisions cannot drift from what sequential decode would
    have emitted. The config's norm; the embedding transposed, or the
    model's own ``head/out`` where the head is untied; logits are
    accumulated and returned in float32 whatever the weights' type."""
    xf = _norm(params, "ln_f", x, cfg)
    w = params["embed/tok"].T if cfg.tied_head else params["head/out"]
    return jnp.matmul(xf, w, preferred_element_type=jnp.float32)


def lm_apply(
    params: dict,
    tokens: jnp.ndarray,
    cfg: TransformerConfig,
    mesh=None,
    *,
    return_aux: bool = False,
):
    """tokens (B, S) int32 -> logits (B, S, vocab); causal, or
    block-causal where the config has a ``diffusion_block`` (row t then
    scores the token AT t: feed ``mask_id`` where a position is masked).

    With ``return_aux`` also returns the summed MoE load-balancing loss
    (0.0 for dense-FFN and top-k configs)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    with jax.named_scope("embed"):
        x = embed(params, tokens, slice(0, s), cfg)
    aux_total = jnp.float32(0.0)
    limits = block_limits(positions, cfg)

    def mk_attend(i):
        if cfg.kv_latent:
            # the sequence's own latents are the whole cache, and K and
            # V of every head are made from them: the prefill's form
            w_kvb = params[f"blk{i}/attn/kv_b"]
            return lambda q, lat, _: (
                latent_attend(q, lat, w_kvb, limits, cfg, absorbed=False),
                None,
            )
        if cfg.gqa or cfg.diffusion_block:
            # the cache-free side of the serving parity tests: the
            # sequence's own K and V are the whole cache, each query's
            # limit its mask
            return lambda q, k, v: (cache_attend(q, k, v, limits), None)
        return lambda q, k, v: (_attend(q, k, v, cfg, mesh), None)

    for i in range(cfg.n_layers):
        x, aux, _ = _block_apply(
            params, f"blk{i}", x, mk_attend(i), cfg, mesh,
            positions=positions,
        )
        if not cfg.moe_top_k:
            aux_total = aux_total + aux
    logits = lm_head(params, x, cfg)
    if return_aux:
        return logits, aux_total
    return logits


@jax.named_scope("cache_attend")
def cache_attend(q, k_cache, v_cache, positions):
    """Masked attention of Q queries against a FULL cache — the single
    attention body every serving path shares (generate()'s prefill and
    decode scan here, the paged-KV engine's gathered blocks in
    serve/engine.py, the conf-net decode in serve/conf_decode.py).

    ``q`` (B, H, Q, D) holds queries that may see the cache up to and
    including ``positions`` (B, Q) — a query's own position in a causal
    model, the end of its block under diffusion over blocks
    (``block_limits``); ``k_cache``/``v_cache`` (B, Hkv, C, D) hold
    the whole (zero-padded) cache. Cache entries beyond a query's
    limit score -1e30, so their softmax weight underflows to exactly
    0.0 — the cache tail (and any garbage a paged pool gathers there)
    never moves a bit of the output. Because the math is shared, "paged
    KV == dense cache" parity is bitwise by construction, not tested
    luck.

    With fewer K/V heads than query heads, query head j reads K/V head
    ``j // (H // Hkv)``: the queries are grouped over the K/V heads in
    the products themselves and the cache is never repeated in memory.
    Scores and the softmax are float32 whatever the cache's type."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    b, h, nq, d = q.shape
    hkv = k_cache.shape[1]
    mask = (
        jnp.arange(k_cache.shape[2])[None, None, None, :]
        <= positions[:, None, :, None]
    )
    if h == hkv:
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k_cache,
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(mask, s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
        return jnp.einsum("bhqk,bhkd->bhqd", w, v_cache)
    qg = q.reshape(b, hkv, h // hkv, nq, d)
    s = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale
    s = jnp.where(mask[:, :, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    return jnp.einsum("bhgqk,bhkd->bhgqd", w, v_cache).reshape(b, h, nq, d)


#: cached positions a materialised latent pass makes keys and values of
#: at once: (H, Q, block) float32 scores for a 512-token chunk of 64
#: heads are 67 MB at 512, and 1.7 GB against all 12,800 positions of a
#: published serving limit
LATENT_KEY_BLOCK = 512


@jax.named_scope("absorb")
def latent_absorb(q, w_kvb, cfg, width: int):
    """Queries (B, H, Q, head_dim + rope_dim) taken INTO the latent
    space and laid out as a cache row is: ``q_nope_h W_UK_h^T``
    (kv_latent), the rotated part as it is, zeros up to ``width`` (a
    paged pool's rows end in zeros). A row's product with it is the
    query's score against that token."""
    h, dn = q.shape[1], cfg.head_dim
    w = w_kvb.reshape(cfg.kv_latent, h, -1)
    q_lat = jnp.einsum(
        "bhqn,rhn->bhqr", q[..., :dn], w[..., :dn],
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    qc = jnp.concatenate([q_lat, q[..., dn:]], axis=-1)
    return jnp.pad(qc, [(0, 0)] * 3 + [(0, width - qc.shape[-1])])


@jax.named_scope("lift")
def latent_lift(o_lat, w_kvb, cfg):
    """The weighted sum of latents (B, H, Q, kv_latent) lifted to each
    head's values by ``W_UV_h``: (B, H, Q, v_head_dim)."""
    w = w_kvb.reshape(cfg.kv_latent, o_lat.shape[1], -1)
    return jnp.einsum("bhqr,rhv->bhqv", o_lat, w[..., cfg.head_dim:])


@jax.named_scope("cache_attend")
def latent_attend(q, lat, w_kvb, positions, cfg, absorbed: bool):
    """Masked attention of Q queries against a FULL cache of latents —
    ``cache_attend``'s counterpart for latent attention, and as it is,
    the one body every path shares (``lm_apply`` here, the paged
    engine's gathered latents in serve/engine.py).

    ``q`` (B, H, Q, head_dim + rope_dim) holds each head's unrotated
    part and then its rotated part; ``lat`` (B, C, >= latent_width) the
    cache: a token's normed K/V latent, then the rotated key all heads
    share (a paged pool's rows may end in zeros, which the absorbed
    form meets with zeros of the query's); ``w_kvb`` (kv_latent,
    H * (head_dim + v_head_dim)) makes head h's unrotated keys
    (``W_UK_h``) and values (``W_UV_h``) out of a latent; ``positions`` (B, Q) is the last cache entry each query may
    see. -> (B, H, Q, v_head_dim).

        score_h(t, s) = scale * (q_nope_h(t) . (c_s W_UK_h)
                                 + q_pe_h(t) . k_pe(s))
        o_h(t) = sum_s p_h(t, s) (c_s W_UV_h)

    Two ways round the same sums. MATERIALISED (``absorbed`` False, a
    prefill chunk's many queries): keys and values of every head are
    made from the latents, ``LATENT_KEY_BLOCK`` cached positions at a
    time, and attended to with a running softmax; the walk ends at the
    last block any query may see, so a chunk early in a long cache pays
    for what it reads and not for the cache's length. ABSORBED (a
    decode tick's one query a sequence): the query is taken INTO the
    latent space, ``q_lat_h = q_nope_h W_UK_h^T``, scores and the
    weighted sum run over the latents themselves — the cache is read
    once, as it lies, for all heads — and ``W_UV_h`` lifts the result.
    Scores and the softmax are float32 whatever the cache's type;
    entries beyond a query's limit score -1e30 and weigh exactly 0."""
    b, h, nq, _ = q.shape
    n_cache = lat.shape[1]
    r, dn, dv = cfg.kv_latent, cfg.head_dim, cfg.v_head_dim
    scale = cfg.attn_scale
    f32 = jnp.float32
    w = w_kvb.reshape(r, h, dn + dv)

    if absorbed:
        mask = (
            jnp.arange(n_cache)[None, None, None, :]
            <= positions[:, None, :, None]
        )
        qc = latent_absorb(q, w_kvb, cfg, lat.shape[-1])
        s = jnp.einsum("bhqk,bck->bhqc", qc, lat, preferred_element_type=f32)
        p = jax.nn.softmax(jnp.where(mask, s * scale, -1e30), axis=-1)
        # the whole row, rotary key and all, so that the cache is read
        # as it lies; the key's columns of the sum are dropped
        o_lat = jnp.einsum("bhqc,bck->bhqk", p.astype(lat.dtype), lat)[..., :r]
        return latent_lift(o_lat, w_kvb, cfg)

    kb = LATENT_KEY_BLOCK if n_cache % LATENT_KEY_BLOCK == 0 else n_cache

    def block(j, carry):
        """Cached positions [j * kb, (j + 1) * kb) into the running
        maximum, the running sum and the weighted values so far."""
        m, l, acc = carry
        blk = jax.lax.dynamic_slice_in_dim(lat, j * kb, kb, axis=1)
        with jax.named_scope("materialise"):
            kv = jnp.einsum("bcr,rhx->bhcx", blk[..., :r], w)
            k = jnp.concatenate([
                kv[..., :dn],
                jnp.broadcast_to(
                    blk[:, None, :, r:r + cfg.rope_dim],
                    (b, h, kb, cfg.rope_dim),
                ),
            ], axis=-1)
        s = jnp.einsum(
            "bhqd,bhcd->bhqc", q, k, preferred_element_type=f32
        ) * scale
        seen = (
            j * kb + jnp.arange(kb)[None, None, None, :]
            <= positions[:, None, :, None]
        )
        s = jnp.where(seen, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        grow = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        acc = acc * grow[..., None] + jnp.einsum(
            "bhqc,bhcv->bhqv", p.astype(lat.dtype), kv[..., dn:],
            preferred_element_type=f32,
        )
        return m_new, l * grow + jnp.sum(p, axis=-1), acc

    # every query sees position 0, so block 0 leaves a real maximum
    # behind and a later block wholly beyond a query's limit weighs 0
    n_blocks = jnp.clip(jnp.max(positions) // kb + 1, 1, n_cache // kb)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, (
        jnp.full((b, h, nq), -1e30, f32), jnp.zeros((b, h, nq), f32),
        jnp.zeros((b, h, nq, dv), f32),
    ))
    return (acc / l[..., None]).astype(q.dtype)


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

    b, nq, _ = x.shape
    x, _, (nk, nv) = _block_apply(
        params, p, x, attend, cfg,
        moe_capacity_factor=float(max(cfg.moe_experts, 1)),
        positions=jnp.broadcast_to(pos + jnp.arange(nq)[None, :], (b, nq)),
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
    if cfg.kv_latent or cfg.layers:
        raise ValueError(
            "generate: a latent cache (kv_latent) and a model of "
            "one-mixer blocks (layers) are served by serve/engine.py alone"
        )
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
    shape = (b, cfg.n_kv_heads, cfg.max_len, cfg.head_dim)
    k_caches = [jnp.zeros(shape) for _ in range(cfg.n_layers)]
    v_caches = [jnp.zeros(shape) for _ in range(cfg.n_layers)]
    x_last = None
    for c0 in range(0, plen, prefill_chunk):
        n = min(prefill_chunk, plen - c0)
        x = embed(params, prompt[:, c0:c0 + n], jnp.arange(c0, c0 + n), cfg)
        for i in range(cfg.n_layers):
            x, k_caches[i], v_caches[i] = _block_step(
                params, f"blk{i}", x, k_caches[i], v_caches[i],
                jnp.int32(c0), cfg,
            )
        x_last = x
    last_logits = lm_head(params, x_last, cfg)[:, -1]

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
        x = embed(params, token, pos, cfg)[:, None, :]
        new_ks, new_vs = [], []
        for i in range(cfg.n_layers):
            x, nk, nv = _block_step(
                params, f"blk{i}", x, ks[i], vs[i], pos, cfg
            )
            new_ks.append(nk)
            new_vs.append(nv)
        logits = lm_head(params, x, cfg)[:, 0]
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
