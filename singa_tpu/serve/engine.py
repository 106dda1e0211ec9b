"""Serving engine: fixed-shape prefill/decode over a slot-batched state.

The compute plane of the serving tier. Device state is ONE pytree —
per-slot token/position/liveness/RNG lanes, per-slot block tables, and
the per-layer paged K/V pools — and exactly two compiled programs touch
it:

  decode   ONE donated, jitted step advancing EVERY live slot one
           token: embed the slots' last tokens, run each transformer
           block against the pool (scatter the new K/V into each slot's
           current block, gather each slot's table back to a dense
           (S, H, cache_len, D) view, ``cache_attend`` masked by
           position), sample per-slot. Dead slots ride along masked —
           admitting or retiring a stream flips ``live`` and never
           changes a shape, so the step NEVER recompiles.
  prefill  a fixed (1, max_prefill_chunk) chunk of one slot's prompt
           through the same block body; long prompts take several
           chunks, so a decode tick is never blocked behind an
           unbounded prompt. Padding positions write to the trash block
           and are masked out of every softmax, which makes chunking
           bitwise split-invariant.
  verify   the speculative tick (spec_k > 0, serve/speculate.py): ONE
           donated fixed-shape pass scoring every live slot's current
           token PLUS its k drafted candidates — (slots, k+1) query
           positions through the paged pool, the prefill chunk shape
           turned sideways. Greedy acceptance takes the longest prefix
           of the draft matching the model's own argmax continuations
           plus one bonus token (up to k+1 tokens per slot per weight
           stream); a masked KV REWIND keeps only positions sequential
           decode would have written — the pool after any accept/
           reject pattern is bitwise what one-token ticks leave.

  block_step  generation by diffusion over blocks (a model with a
           ``diffusion_block`` B): ONE donated fixed-shape pass over
           every live slot's CURRENT block of B positions, ``mask_id``
           fed where a position is still masked, each query seeing the
           cache and the whole block (``block_limits``). A slot whose
           block has masked positions DENOISES: the most confident of
           them take their greedy tokens for good. A slot whose block
           has none COMMITS: the block's K and V stand in the pool and
           the slot moves on to a fresh masked block. The gather path
           keeps verify's discipline: attention reads the gathered
           views with the block's fresh K/V overlaid, and the pool
           takes one masked scatter after the forward — to real blocks
           for committing slots, to the trash block for the rest. The
           kernel takes the decode tick's: every live slot's block is
           written to its own rows past its committed length first, and
           the block's queries read the slot's live blocks in place
           (``_block_step`` says why those rows are safe to write).
           Prefill of such a model is block-causal (the same limits)
           and yields no token.

All programs run the SAME ``_block_apply``/``cache_attend``/``lm_head``
body as models/transformer.generate — paged-vs-dense parity AND
speculative-vs-sequential parity are shared code, not a tolerance.
Admission-path work (table updates, first-token sampling) is small
host-driven device ops, off the decode hot path. Each such hand-over is
an ``obs.span`` on the profiler's clock — ``engine.admit``,
``engine.prefill`` (a chunk until its program is dispatched),
``engine.activate``, ``engine.retire`` — so a trace names the device
idle it leaves; a pass (decode, block step, verify) is timed by the
scheduler's ``sched.dispatch`` and has no span here.

Sampling is a per-slot TEMPERATURE LANE: a (slots,) array + masked
categorical, so mixed sampling configs (greedy and temperature slots
side by side) share one compiled program — admitting a temperature
request next to greedy ones never recompiles. Speculation is
greedy-only per slot: a temperature > 0 slot rides the verify tick
with zero drafts (it emits its one sampled token per tick; its key
discipline — one split per emitted token — is identical either way).

A LATENT CACHE (a model with ``kv_latent``, models/transformer.py): the
pool's row is ONE latent a token a layer — the K/V latent after its norm
beside the rotated key all heads share, ``latent_width`` values — and
there is no V pool beside it (``state["v"]`` is empty). The same two
programs serve it through the same closures' seam, each in the form its
shape wants (``latent_attend``): a prefill chunk MATERIALISES K and V of
the slot's gathered latents, a block of cached positions at a time up
to the last one a query may see; a decode tick ABSORBS its one query a
slot into the latent space and reads the latents as they lie, in place
through the block table where the latent kernel runs
(``ops/paged_attention.paged_latent_attention``, chosen as the MHA
kernel is), else as a gathered view. Which form is chosen in code from
the shape, as the pool's row is from the configuration. What has no
latent form yet (speculation, the prefix cache, slot export/import, a
TP mesh) is refused by the field's name.

Sharding: pass a mesh and the pools lay their last dim (H * D, heads
major: serve/kv_pool.py) out over the ``model`` axis, whole heads a
shard (parallel/shardings.serving_kv_shardings) — the serving analog
of kLayerPartition; everything else replicates.

ATTENTION IMPLEMENTATION is the engine's to choose (``choose_attend``):
``reference`` keeps the bitwise-pinned gather -> ``cache_attend`` path
above; ``fused`` swaps the Pallas paged-attention kernel
(ops/paged_attention.py) in at the ``attend`` closure seam of
``_block_apply`` — K/V blocks are read IN PLACE through the block
table, no dense ``(S, H, cache_len, D)`` materialization per layer.
Left unset (no ``kernels { paged_attention }`` in the model conf), the
kernel runs where it compiles — on a TPU, with no mesh: one token a
tick, or a block step's block as query rows of one token, over as many
K/V heads or fewer — and the gather path everywhere else; the
scheduler's ``kernel_select`` event says which and why. Naming either
in the conf pins it. The choice covers the decode tick, the block step
and the verify pass; a prefill chunk always takes the one-slot gather,
where the kernel's many-query shape does not win.
Fused output is allclose to the reference (online softmax reorders the
reduction — the PR 9 cross-shape caveat at kernel granularity); greedy
token STREAMS are pinned identical in tests. The kernel's form follows
the platform (ops/paged_attention._call): compiled through Mosaic on a
TPU, the Pallas interpreter — plain XLA ops, CPU-safe and
GSPMD-shardable — elsewhere; ``kernels { interpret }`` pins either.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..models.transformer import (
    TransformerConfig,
    _block_apply,
    block_limits,
    cache_attend,
    embed,
    latent_absorb,
    latent_attend,
    latent_lift,
    lm_head,
)
from ..obs import span
from .kv_pool import BlockAllocator, KVPool, PoolExhausted


#: what a decode pass of a model with top-k expert layers appends to its
#: tokens, int32, all over the pass's LIVE slots: held experts that drew
#: a token summed over the expert layers; the most tokens one held
#: expert of one layer took; token-expert pairs routed to held experts
#: summed over the layers; cache rows the pass's attention had to read
#: a layer (each live slot's position + 1); and the pairs the prefill
#: chunks since the pass before routed to held experts
DECODE_COUNTERS = (
    "experts_hit", "expert_max_load", "held_pairs", "cache_rows",
    "chunk_held_pairs",
)
#: and what a decode pass of a model with recurrent layers (of either
#: kind) appends after them: the slots whose state the pass advanced
#: (its live lanes)
STATE_COUNTERS = ("state_slots_live",)
#: the layer kinds that keep state a slot: a convolution tail each, and
#: a Mamba-2 layer its recurrent state beside it
RECURRENT_KINDS = ("mamba", "shortconv")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Serving-plane knobs (mirrors the ``serving`` model-conf block)."""

    slots: int = 8
    kv_block_len: int = 16
    kv_blocks: int = 0          # 0 = dense-equivalent sizing (see KVPool)
    max_prefill_chunk: int = 64
    #: draft tokens per live greedy slot per speculative tick
    #: (``serving { speculate { k } }``); 0 = one-token decode ticks
    spec_k: int = 0
    #: drafter name (serve/speculate.py DRAFTERS)
    spec_drafter: str = "ngram"
    #: ``serving { prefix_cache { enabled } }``: content-addressed,
    #: refcounted block sharing — admissions reuse cached full-block
    #: prompt prefixes instead of re-prefilling them
    prefix_cache: bool = False
    #: keep refcount-0 cached blocks on an LRU list (reclaimed lazily)
    #: instead of freeing them at retirement; False = share only among
    #: concurrently-live sequences
    prefix_lru: bool = True
    #: ``prefix_cache { tail_stride }``: > 0 indexes each prompt's last
    #: PARTIAL block at this sub-block token stride, so a prompt whose
    #: shared prefix ends mid-block COW-extends the deepest partial
    #: match instead of re-prefilling the whole block; must divide
    #: kv_block_len. 0 = full-block granularity only.
    prefix_tail_stride: int = 0
    #: ``prefix_cache { decode_blocks }``: register FULL decode-written
    #: blocks under the chained digest at retirement so multi-turn
    #: traffic hits its own history. Warm streams over these blocks are
    #: TOKEN-LEVEL identical to cold admission, not bitwise (the PR 9
    #: cross-shape caveat: decode/verify writes ride a different
    #: compiled shape than prefill).
    prefix_decode_blocks: bool = False
    #: ``prefix_cache { fetch_timeout_s }``: fleet hosts hold a request
    #: awaiting a peer's cache_ship this long before degrading to plain
    #: prefill (serve/fleet/host.py)
    prefix_fetch_timeout_s: float = 2.0
    #: ``kernels { paged_attention }``: None (the conf left it unset)
    #: lets the engine choose (``choose_attend``: the kernel on a TPU
    #: for a model it knows, the gather path elsewhere). "reference"
    #: pins the gather + cache_attend oracle path (bitwise-pinned),
    #: "fused" the Pallas kernel reading K/V blocks in place via the
    #: block table (ops/paged_attention.py)
    attend_impl: str | None = None
    #: ``kernels { interpret }``: None (the conf left it unset) lets
    #: the platform decide — Mosaic-compiled on a TPU, the Pallas
    #: interpreter (plain XLA ops — CPU-safe, GSPMD-shardable; what CI
    #: exercises) elsewhere. True/False pin the form.
    interpret: bool | None = None
    #: denoising passes a block for a model generated by diffusion over
    #: blocks (``TransformerConfig.diffusion_block`` B): each pass fixes
    #: the ``B // block_steps`` most confident masked positions
    #: (``low_confidence_static``). 0 = B passes, one token each.
    block_steps: int = 0

    @classmethod
    def from_conf(cls, serving, kernels=None) -> "EngineConfig":
        """From parsed ``serving { ... }`` / ``kernels { ... }`` config
        blocks (None = defaults)."""
        kw = {}
        if kernels is not None:
            kw = dict(
                attend_impl=kernels.paged_attention,
                interpret=kernels.interpret,
            )
        if serving is None:
            return cls(**kw)
        spec = serving.speculate
        pc = serving.prefix_cache
        return cls(
            slots=serving.slots,
            kv_block_len=serving.kv_block_len,
            kv_blocks=serving.kv_blocks,
            max_prefill_chunk=serving.max_prefill_chunk,
            spec_k=spec.k if spec is not None else 0,
            spec_drafter=spec.drafter if spec is not None else "ngram",
            prefix_cache=pc.enabled if pc is not None else False,
            prefix_lru=pc.lru if pc is not None else True,
            prefix_tail_stride=pc.tail_stride if pc is not None else 0,
            prefix_decode_blocks=(
                pc.decode_blocks if pc is not None else False
            ),
            prefix_fetch_timeout_s=(
                pc.fetch_timeout_s if pc is not None else 2.0
            ),
            **kw,
        )


def choose_attend(cfg, serving, mesh, platform: str) -> str:
    """Which attention the engine's programs run, from what the engine
    can see: ``"fused"`` or ``"reference"``, the latter followed by
    ``": <why>"`` where the engine chose it — the string the
    scheduler's ``kernel_select`` event carries. A pure function, so a
    CPU test can ask it about a TPU.

    A pinned ``serving.attend_impl`` is returned as it is. Unset, the
    kernel runs where it compiles through Mosaic, whatever the model:
    it reads one query a sequence (or a block step's queries, all seeing
    up to the block's end, as rows of one query) over K/V heads that one
    query head or several share, or over a latent cache's one row a
    token, so no field of ``cfg`` refuses it today. GSPMD cannot
    partition a Mosaic call (a mesh), and off a TPU it would run
    through the Pallas interpreter, a grid step at a time."""
    if serving.attend_impl is not None:
        return serving.attend_impl
    from ..ops.paged_attention import fusable

    why = fusable(serving.kv_block_len)
    if why is None and mesh is not None:
        why = "a tensor-parallel mesh"
    if why is None and platform != "tpu":
        why = f"platform = {platform}"
    return "fused" if why is None else f"reference: {why}"


@dataclasses.dataclass(frozen=True)
class Admission:
    """What admit() did for one request: the sequence's full block list
    (shared prefix blocks first), how many prompt tokens the prefix
    cache covered, where prefill must start (== ``cached_tokens``
    except on a WHOLE-prompt hit, where the last token re-runs through
    a COW'd block to re-derive the activation logits), and whether a
    copy-on-write happened."""

    blocks: list
    cached_tokens: int = 0
    prefill_from: int = 0
    cow_copied: bool = False
    #: tokens of ``cached_tokens`` served by COW-EXTENDING a registered
    #: partial tail (sub-block sharing: the deepest matched tail block
    #: was copied to a private fresh block and prefill starts past the
    #: covered tokens); 0 = the hit ended on a block boundary
    tail_tokens: int = 0


class Engine:
    """Slot-batched continuous-decode engine for the code-API LM."""

    def __init__(
        self,
        params: dict,
        cfg: TransformerConfig,
        serving: EngineConfig | None = None,
        *,
        mesh=None,
        temperature: float = 0.0,
    ):
        self.cfg = cfg
        self.serving = serving or EngineConfig()
        self.temperature = float(temperature)
        if self.serving.attend_impl not in (None, "reference", "fused"):
            raise ValueError(
                f"kernels.paged_attention must be 'reference' or "
                f"'fused', got {self.serving.attend_impl!r}"
            )
        #: what ``choose_attend`` said, reason included
        self.attend_choice = choose_attend(
            cfg, self.serving, mesh, jax.default_backend()
        )
        self._fused = self.attend_choice == "fused"
        #: the form each program's top-k expert layers compile in, with
        #: the chooser's reason (parallel/moe.py ``choose_expert_form``
        #: asked what ``moe_topk_ffn`` asks it while the program is
        #: traced); empty for a model without such layers
        self.expert_forms = self._expert_forms(
            cfg, self.serving, jax.default_backend()
        )
        #: and the form each program's Mamba-2 layers compute their
        #: recurrence in (ops/ssm.py ``choose_mamba_form``); empty for a
        #: model without such layers
        self.mamba_forms = self._mamba_forms(cfg, self.serving)
        if self._fused:
            from ..ops.paged_attention import fusable, one_query_fusable

            reason = fusable(self.serving.kv_block_len)
            if reason is None and (
                cfg.kv_latent or cfg.gqa or cfg.diffusion_block
            ):
                reason = one_query_fusable(
                    self.serving.kv_block_len, params["embed/tok"].dtype
                )
            if reason is not None:
                # the runtime rejection KRN001 statically mirrors
                raise ValueError(
                    f"kernels {{ paged_attention: fused }}: {reason}"
                )
        self._refuse_what_cannot_run(cfg, self.serving, mesh)
        #: tokens a denoising pass fixes in a block (0: no block steps)
        self.block_fix = 0
        if cfg.diffusion_block:
            steps = self.serving.block_steps or cfg.diffusion_block
            self.block_fix = cfg.diffusion_block // steps
        self.pool = KVPool.for_model(
            cfg.max_len, self.serving.kv_block_len,
            self.serving.kv_blocks, self.serving.slots,
        )
        #: layer -> its place in ``state["k"]`` / ``state["v"]``: pools
        #: are kept for the layers that hold attention alone; layer ->
        #: its place in ``state["conv"]`` for those that hold a recurrent
        #: mixer of either kind (a Mamba-2 layer or a short convolution:
        #: a convolution tail each), and in ``state["ssm"]`` for the
        #: Mamba-2 layers alone (every layer and none for a model
        #: without ``layers``)
        self._kv_at = {i: j for j, i in enumerate(cfg.layers_of("attn"))}
        self._state_at = {i: j for j, i in enumerate(sorted(
            i for kind in RECURRENT_KINDS for i in cfg.layers_of(kind)
        ))}
        self._ssm_at = {i: j for j, i in enumerate(cfg.layers_of("mamba"))}
        self.allocator = BlockAllocator(
            self.pool,
            prefix_cache=self.serving.prefix_cache,
            lru=self.serving.prefix_lru,
            tail_stride=self.serving.prefix_tail_stride,
        )
        self.params = params
        #: live-weight rollout versioning (serve/rollout.py): the tag of
        #: the LIVE param tree. A staged next-version tree sits alongside
        #: it until flip_params() swaps the reference at a tick boundary
        #: — every jitted program takes params per call, so the swap is
        #: atomic between ticks and recompiles nothing (same shapes).
        self.params_version = 0
        self._staged: tuple[int, dict] | None = None
        #: the pinned previous version a canary-abort rolls back to
        self._prev: tuple[int, dict] | None = None
        #: params version each slot was admitted/imported under — its
        #: K/V bytes are a function of THOSE weights, so registration
        #: into the prefix index is gated on the version still being live
        self._slot_version: dict[int, int] = {}
        s, mb = self.serving.slots, self.pool.max_blocks_per_seq
        # a pool row is one token's K (or V) for the K/V heads alone,
        # in the parameters' own type (float32 parameters: float32
        # pools); under latent attention ONE row a token, its latent
        # (``state["k"]`` holds those pools and ``state["v"]`` none)
        shape = (
            self.pool.array_shape(1, KVPool.latent_row(cfg.latent_width))
            if cfg.kv_latent
            else self.pool.array_shape(cfg.n_kv_heads, cfg.head_dim)
        )
        pool_dtype = params["embed/tok"].dtype
        pool_sh = state_sh = None
        if mesh is not None:
            from ..parallel.shardings import serving_kv_shardings

            pool_sh, state_sh = serving_kv_shardings(mesh, cfg.n_heads)
        def put(a, sh):
            return a if sh is None else jax.device_put(a, sh)
        self.state = {
            "tokens": put(jnp.zeros((s,), jnp.int32), state_sh),
            "pos": put(jnp.zeros((s,), jnp.int32), state_sh),
            "live": put(jnp.zeros((s,), bool), state_sh),
            # per-slot sampling temperature lane: one compiled program
            # serves mixed sampling configs (0 = greedy, masked select)
            "temp": put(jnp.zeros((s,), jnp.float32), state_sh),
            "rng": put(
                jnp.zeros((s, 2), jnp.uint32), state_sh
            ),
            "tables": put(jnp.zeros((s, mb), jnp.int32), state_sh),
            "k": tuple(
                put(jnp.zeros(shape, pool_dtype), pool_sh)
                for _ in self._kv_at
            ),
            "v": tuple(
                put(jnp.zeros(shape, pool_dtype), pool_sh)
                for _ in (() if cfg.kv_latent else self._kv_at)
            ),
        }
        if self._ssm_at:
            # slot-resident recurrent state, one array a Mamba-2 layer:
            # the float32 state, zeroed at admission, advanced by the
            # valid positions alone
            self.state["ssm"] = tuple(
                jnp.zeros(
                    (s, cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_state),
                    jnp.float32,
                ) for _ in self._ssm_at
            )
        if self._state_at:
            # and one convolution tail a recurrent layer, in the
            # parameters' type: the last K - 1 rows of what the taps
            # read, a Mamba-2 layer's x, B and C or a short
            # convolution's gated input, likewise zeroed and advanced
            # (the tail's few rows lead: (K - 1, slots, C) tiles whole,
            # and is how the TPU compiler lays it out whatever it is
            # handed; slots-major it copied the tail in and out of every
            # tick, read off the compiled text)
            self.state["conv"] = tuple(
                jnp.zeros(
                    (cfg.conv_kernel - 1, s,
                     cfg.conv_dim if i in self._ssm_at else cfg.d_model),
                    pool_dtype,
                ) for i in self._state_at
            )
        #: the counters a one-token decode pass appends to its tokens,
        #: by name (the scheduler reads them with the pass, one tick
        #: late): ``DECODE_COUNTERS`` for a model with top-k expert
        #: layers, then ``STATE_COUNTERS`` for one with recurrent layers
        self.decode_counter_names = (
            DECODE_COUNTERS
            if cfg.moe_top_k and not cfg.diffusion_block else ()
        ) + (STATE_COUNTERS if self._state_at else ())
        self.decode_counters = len(self.decode_counter_names)
        if "chunk_held_pairs" in self.decode_counter_names:
            # pairs the prefill chunks since the last decode pass routed
            # to held experts: the next pass hands it on and zeroes it
            self.state["chunk_pairs"] = jnp.zeros((), jnp.int32)
        if cfg.diffusion_block:
            # per-slot block lanes: the current block's tokens and which
            # of its positions are still masked (``pos`` is its start)
            lanes = (s, cfg.diffusion_block)
            self.state["blk_tok"] = jnp.zeros(lanes, jnp.int32)
            self.state["blk_masked"] = jnp.ones(lanes, bool)
        #: blocks owned per slot, freed at retire
        self._slot_blocks: dict[int, list[int]] = {}
        #: the admission-time digest chain per slot (register_prefix
        #: reuses it — one hashing pass per request, not two)
        self._slot_chain: dict[int, list[bytes]] = {}
        self._decode_jit = jax.jit(self._decode, donate_argnums=(1,))
        self._prefill_jit = jax.jit(self._prefill, donate_argnums=(1,))
        self._verify_jit = jax.jit(self._verify, donate_argnums=(1,))
        self._block_step_jit = jax.jit(
            self._block_step, donate_argnums=(1,)
        )
        self._activate_block_jit = jax.jit(
            self._activate_block_prog, donate_argnums=(0,)
        )
        # admission-path lane updates fused into one dispatch each —
        # a request admission must not stall live slots' ticks behind a
        # storm of single-element device ops
        self._admit_jit = jax.jit(self._admit_prog, donate_argnums=(0,))
        self._activate_jit = jax.jit(
            self._activate_prog, donate_argnums=(0,)
        )
        self._retire_jit = jax.jit(self._retire_prog, donate_argnums=(0,))
        # copy-on-write: one fixed-shape block copy (src/dst are traced
        # scalars, so every COW reuses ONE compiled program)
        self._cow_jit = jax.jit(self._cow_prog, donate_argnums=(0,))
        # block migration (serve/fleet/migrate.py): one fixed-shape
        # gather of a slot's whole paged state for export, one
        # fixed-shape scatter + lane install for import — slot/rows are
        # traced, so every migration reuses ONE compiled program each
        self._export_jit = jax.jit(self._export_prog)
        self._import_jit = jax.jit(self._import_prog, donate_argnums=(0,))
        # fleet prefix shipping (serve/fleet/host.py): one fixed-shape
        # gather of arbitrary registered blocks for a cache_ship reply,
        # one fixed-shape scatter installing shipped bytes WITHOUT
        # touching any lane (the warmed blocks belong to the cache, not
        # to a slot) — rows are traced, so every ship reuses ONE
        # compiled program each
        self._export_blocks_jit = jax.jit(self._export_blocks_prog)
        self._install_jit = jax.jit(
            self._install_prog, donate_argnums=(0,)
        )

    @staticmethod
    def _refuse_what_cannot_run(cfg, serving, mesh) -> None:
        """What no program here computes for a model with fewer K/V
        heads than query heads, with a latent cache, of one-mixer
        ``layers`` (recurrent state beside pools for some layers) or
        generated by diffusion over blocks is refused by the field's
        name, not run wrongly (ROADMAP Queue 2 keeps the list)."""
        why = Engine._beyond_gpt2(cfg)
        if why is None:
            return
        refused = {
            "speculate (spec_k)": serving.spec_k > 0,
            "prefix_cache": serving.prefix_cache,
            "a tensor-parallel mesh": mesh is not None,
        }
        for what, asked in refused.items():
            if asked:
                raise ValueError(
                    f"serving: {what} cannot run for a model with {why}"
                )
        b = cfg.diffusion_block
        if not b:
            return
        steps = serving.block_steps or b
        for name, value in (
            ("kv_block_len", serving.kv_block_len),
            ("max_prefill_chunk", serving.max_prefill_chunk),
            ("max_len", cfg.max_len),
        ):
            if value % b:
                raise ValueError(
                    f"serving: {name} = {value} must be a multiple of "
                    f"diffusion_block = {b}"
                )
        if b % steps:
            raise ValueError(
                f"serving: block_steps = {steps} must divide "
                f"diffusion_block = {b}"
            )

    def _refuse_for_slot_state(self, what: str) -> None:
        """Slot export/import and prefix shipping move pool bytes in the
        fleet's (L, n, H, BL, D) wire format and lanes that know nothing
        of a block in flight: refused for the same models."""
        why = self._beyond_gpt2(self.cfg)
        if why is not None:
            raise ValueError(
                f"serving: {what} cannot run for a model with {why}"
            )

    @staticmethod
    def _expert_forms(cfg, serving, platform: str) -> dict:
        """Program name -> ``choose_expert_form`` of the tokens a pass
        of it holds: every slot's one token (or block) for the tick, a
        whole chunk for the prefill. The shapes are static, so this is
        what each compiled program took."""
        if not cfg.moe_top_k:
            return {}
        from ..parallel.moe import choose_expert_form

        tick = (
            ("jit__block_step", serving.slots * cfg.diffusion_block)
            if cfg.diffusion_block else ("jit__decode", serving.slots)
        )
        held = cfg.moe_held[1] if cfg.moe_held else cfg.moe_experts
        return {
            name: choose_expert_form(
                n, held, cfg.moe_experts, cfg.moe_top_k, platform
            )
            for name, n in (tick, ("jit__prefill", serving.max_prefill_chunk))
        }

    @staticmethod
    def _mamba_forms(cfg, serving) -> dict:
        """Program name -> ``choose_mamba_form`` of the positions a
        sequence has in a pass of it: one for the tick, a whole chunk
        for the prefill, both from a slot's carried state."""
        if not cfg.layers_of("mamba"):
            return {}
        from ..ops.ssm import choose_mamba_form

        return {
            name: choose_mamba_form(n, cfg.ssm_block, True)
            for name, n in (
                ("jit__decode", 1), ("jit__prefill", serving.max_prefill_chunk)
            )
        }

    @staticmethod
    def _beyond_gpt2(cfg) -> str | None:
        """The field (with its value) for which the refusals above
        hold, None for a model that every path here serves."""
        if cfg.kv_latent:
            return f"kv_latent = {cfg.kv_latent}"
        if cfg.layers:
            # pools for some layers only, and recurrent state that no
            # block of a cache holds: nothing of it is indexed by
            # content, rewound, or in the fleet's wire format
            kinds = sorted(set(cfg.layers))
            return f"layers = {len(cfg.layers)} one-mixer blocks of {kinds}"
        if cfg.diffusion_block:
            # a block in flight lives in lanes that no prefix index,
            # draft, wire format or mesh rule knows
            return f"diffusion_block = {cfg.diffusion_block}"
        if cfg.gqa:
            # the prefix cache, the verify pass's overlay, the fleet's
            # wire format and a TP mesh know one K/V head a query head
            return f"n_kv_heads = {cfg.n_kv_heads} != n_heads = {cfg.n_heads}"
        return None

    # ------------------------------------------------------------------
    # compiled programs
    # ------------------------------------------------------------------

    def _gather(self, pool_arr, tables):
        """(NB, BL, H*D) pool (the stored shape, serve/kv_pool.py; H the
        K/V heads) + (S', MB) tables -> (S', H, CL, D) dense
        per-sequence cache views
        (CL = MB * BL = the dense cache_len): a sequence's blocks are
        consecutive rows of CL tokens, each H*D wide, and the heads come
        out of the row.

        Gather indices are promised in bounds: every table entry is an
        allocator-issued block id (rows beyond a sequence's allocation
        hold the trash block, 0), so XLA's per-index clamp — work whose
        only effect the attend mask would zero anyway — is skipped."""
        g = pool_arr.at[tables].get(mode="promise_in_bounds")
        g = g.reshape(                            # (S', CL, H, D)
            g.shape[0], self.pool.cache_len, self.cfg.n_kv_heads, -1
        )
        return jnp.moveaxis(g, 2, 1)

    @staticmethod
    @jax.named_scope("kv_write")
    def _kv_write(pool_arr, bid, off, fresh):
        """One (NB, BL, H*D) pool with ``fresh`` (..., H, D), one entry
        a token of ``bid`` / ``off``, scattered to offsets ``off`` of
        blocks ``bid`` as whole H*D-wide rows — the ONE write every
        program shares; in a trace its operations are ``kv_write``."""
        return pool_arr.at[bid, off].set(fresh.reshape(*bid.shape, -1))

    def _latent_write(self, pool_arr, bid, off, lat):
        """``_kv_write`` of latents ``lat`` (..., latent_width): each
        row's tail up to the pool's width is zeros."""
        pad = pool_arr.shape[-1] - lat.shape[-1]
        return self._kv_write(
            pool_arr, bid, off,
            jnp.pad(lat, [(0, 0)] * (lat.ndim - 1) + [(0, pad)]),
        )

    def _blocks_out(self, pool_arr, row):
        """Blocks ``row`` of one pool in the shape that leaves the
        engine: (n, H, BL, D), the fleet's wire format."""
        g = pool_arr[row]
        g = g.reshape(*g.shape[:2], self.cfg.n_kv_heads, -1)
        return jnp.moveaxis(g, 2, 1)

    @staticmethod
    def _blocks_in(pool_arr, row, blocks):
        """One pool with (n, H, BL, D) ``blocks`` from the wire written
        to blocks ``row``: ``_blocks_out``'s inverse."""
        b = jnp.moveaxis(blocks, 1, 2)
        return pool_arr.at[row].set(b.reshape(*b.shape[:2], -1))

    @jax.named_scope("gather_kv")
    def _gather_latent(self, pool_arr, tables):
        """(NB, BL, W) latent pool + (S', MB) tables -> the (S', CL, W)
        dense view of each sequence's latents, the rows' zero tails
        (``KVPool.latent_row``) with them: ``_gather`` with no head to
        take out of a row."""
        g = pool_arr.at[tables].get(mode="promise_in_bounds")
        return g.reshape(g.shape[0], self.pool.cache_len, -1)

    @jax.named_scope("gather_kv")
    def _gather_kv(self, kp, vp, tables):
        """Both dense views of one layer's K and V pools — the ONE
        helper the reference attends share (decode/prefill/verify each
        used to spell the pair out). In a trace its operations are
        ``gather_kv``: what the fused kernel exists to delete."""
        return self._gather(kp, tables), self._gather(vp, tables)

    def _write_targets(self, tables, p_safe, valid):
        """(S, Q) positions -> each one's (block id, offset) through
        its slot's table; where ``valid`` is False the block is the
        trash block."""
        row_idx = jnp.minimum(
            p_safe // self.pool.block_len, tables.shape[1] - 1
        )
        bid = jnp.take_along_axis(tables, row_idx, axis=1)
        return jnp.where(valid, bid, 0), p_safe % self.pool.block_len

    @jax.named_scope("gather_kv")
    def _overlay(self, pool_arr, tables, p_safe, new_shqd):
        """(S, H, C, D) gathered view of one pool with fresh K (or V)
        ``new_shqd`` (S, H, Q, D) laid over each slot's columns
        ``p_safe`` (S, Q) — the pool itself is NOT written (verify's
        rejected positions and a denoising block's must stay
        untouched); a query sees what is laid over only as far as its
        limit lets it."""
        dense = self._gather(pool_arr, tables)
        s_idx = jnp.arange(p_safe.shape[0])[:, None]
        return dense.at[s_idx, :, p_safe].set(jnp.moveaxis(new_shqd, 1, 2))

    @jax.named_scope("paged_attention")
    def _paged_attend(self, q, kp, vp, tables, positions):
        """The fused path's write-then-read attend (the decode tick):
        the fresh K/V were already scattered into ``kp``/``vp``, the
        kernel reads blocks in place through ``tables``."""
        from ..ops.paged_attention import paged_attention

        return paged_attention(
            q, kp, vp, tables, positions,
            interpret=self.serving.interpret,
        )

    @jax.named_scope("paged_attention")
    def _paged_latent_attend(self, q, pool_arr, w_kvb, state, live):
        """The fused path's attend over a latent pool (the decode tick):
        the one query a slot is taken into the latent space, the kernel
        reads the slot's live blocks in place through its table (a dead
        lane has none), and ``W_UV`` lifts what comes back."""
        from ..ops.paged_attention import paged_latent_attention

        mcfg = self.cfg
        qc = latent_absorb(q, w_kvb, mcfg, pool_arr.shape[-1])
        o_lat = paged_latent_attention(
            qc[:, :, 0], pool_arr, state["tables"],
            jnp.where(live, state["pos"], -1), scale=mcfg.attn_scale,
            out_width=mcfg.kv_latent, interpret=self.serving.interpret,
        )
        return latent_lift(o_lat[:, :, None], w_kvb, mcfg)

    def _sample(self, logits, keys, temps, live, prev):
        """Per-slot sampling through the temperature LANE: greedy argmax
        where a slot's temperature is 0 (bit-for-bit the generate()
        decision rule), per-slot categorical with the slot's own key
        stream otherwise — a masked select, so one compiled program
        serves any mix (slot-independent by construction: a stream's
        text can never depend on what shares the batch)."""
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sampled = jax.vmap(
            lambda k, l, t: jax.random.categorical(k, l / t)
        )(keys, logits, jnp.maximum(temps, 1e-6)).astype(jnp.int32)
        nxt = jnp.where(temps > 0.0, sampled, greedy)
        return jnp.where(live, nxt, prev)

    def _split_keys(self, state):
        """One key split per slot per tick (= per emitted token for
        temperature slots, both in one-token and speculative ticks —
        the key discipline speculation must preserve). Greedy slots'
        splits are dead lanes the masked select never reads."""
        split = jax.vmap(jax.random.split)(state["rng"])
        return split[:, 0], split[:, 1]

    def _carried(self, state, layer, slot=None):
        """What ``layer`` starts a pass from, None where it keeps no
        state: a short convolution's tail, a Mamba-2 layer's (state,
        tail), for every slot (tails as (slots, K - 1, C)) or for
        ``slot`` alone, as a batch of one."""
        j, m = self._state_at.get(layer), self._ssm_at.get(layer)
        if j is None:
            return None
        if slot is None:
            tail = jnp.moveaxis(state["conv"][j], 0, 1)
            return tail if m is None else (state["ssm"][m], tail)
        ssm = None if m is None else state["ssm"][m][slot][None]
        tail = state["conv"][j][:, slot][None]
        return tail if m is None else (ssm, tail)

    def _decode(self, params, state):
        cfg = self.pool
        tokens, pos, live = state["tokens"], state["pos"], state["live"]
        mcfg = self.cfg
        with jax.named_scope("embed"):
            x = embed(params, tokens, pos, mcfg)[:, None, :]
        # each slot's write target: its current block, current offset.
        # Dead lanes route to the trash block explicitly — a slot that
        # is admitted-but-still-prefilling has a REAL table whose first
        # block must not be clobbered by its stale decode lane.
        bid = jnp.take_along_axis(
            state["tables"], (pos // cfg.block_len)[:, None], axis=1
        )[:, 0]
        bid = jnp.where(live, bid, 0)
        off = pos % cfg.block_len
        new_k, new_v = [], []

        def mk_attend(layer):
            i = self._kv_at.get(layer)      # None: no attention there

            def attend_latent(q, lat, _):
                # the decode tick's form: queries taken into the latent
                # space, the latents read as they lie — in place by the
                # kernel, or as a gathered view
                lp = self._latent_write(state["k"][i], bid, off, lat[:, 0])
                w_kvb = params[f"blk{layer}/attn/kv_b"]
                if self._fused:
                    o = self._paged_latent_attend(q, lp, w_kvb, state, live)
                else:
                    o = latent_attend(
                        q, self._gather_latent(lp, state["tables"]), w_kvb,
                        pos[:, None], mcfg, absorbed=True,
                    )
                return o, (lp, None)

            def attend(q, k, v):
                kp = self._kv_write(state["k"][i], bid, off, k[:, :, 0, :])
                vp = self._kv_write(state["v"][i], bid, off, v[:, :, 0, :])
                if self._fused:
                    o = self._paged_attend(
                        q, kp, vp, state["tables"], pos[:, None]
                    )
                else:
                    o = cache_attend(
                        q,
                        *self._gather_kv(kp, vp, state["tables"]),
                        pos[:, None],
                    )
                return o, (kp, vp)
            return attend_latent if mcfg.kv_latent else attend

        stats, new_ssm, new_conv = [], [], []
        for i in range(mcfg.n_layers):
            j, m = self._state_at.get(i), self._ssm_at.get(i)
            x, aux, extra = _block_apply(
                params, f"blk{i}", x, mk_attend(i), mcfg,
                moe_capacity_factor=float(max(mcfg.moe_experts, 1)),
                positions=pos[:, None], valid=live[:, None],
                carried=self._carried(state, i),
            )
            if j is not None:
                # one step a live lane from its own state; a dead lane's
                # state and tail come back as they went in
                if m is not None:
                    new_ssm.append(extra[0])
                    extra = extra[1]
                new_conv.append(jnp.moveaxis(extra, 1, 0))
            elif i in self._kv_at:
                new_k.append(extra[0])
                new_v.append(extra[1])
            if mcfg.expert_layer(i):
                stats.append(aux)
        logits = lm_head(params, x, mcfg)[:, 0]
        with jax.named_scope("sample"):
            new_rng, keys = self._split_keys(state)
            nxt = self._sample(logits, keys, state["temp"], live, tokens)
        new_state = {
            **state,
            "tokens": nxt,
            "pos": pos + live.astype(jnp.int32),
            "rng": new_rng,
            "k": tuple(new_k),
            "v": tuple(v for v in new_v if v is not None),
        }
        if new_ssm:
            new_state["ssm"] = tuple(new_ssm)
        if new_conv:
            new_state["conv"] = tuple(new_conv)
        out = jnp.where(live, nxt, jnp.int32(-1))
        if self.decode_counters:
            counted = {}
            if stats:
                st = jnp.stack(stats)                            # (L, 3)
                counted.update(
                    experts_hit=jnp.sum(st[:, 0]),
                    expert_max_load=jnp.max(st[:, 1]),
                    held_pairs=jnp.sum(st[:, 2]),
                    cache_rows=jnp.sum(jnp.where(live, pos + 1, 0)),
                    chunk_held_pairs=state["chunk_pairs"],
                )
                new_state["chunk_pairs"] = jnp.zeros((), jnp.int32)
            if new_conv:
                counted["state_slots_live"] = jnp.sum(live, dtype=jnp.int32)
            out = jnp.concatenate([out, jnp.stack(
                [counted[name] for name in self.decode_counter_names]
            )])
        return new_state, out

    def _prefill(self, params, state, slot, chunk, pos0, n_valid):
        """One (1, C) prompt chunk of ``slot`` at absolute positions
        [pos0, pos0 + C): writes the chunk's K/V into the slot's blocks
        (padding positions to the trash block) and returns the logits
        at the last VALID position — garbage only where the mask
        already guarantees it cannot matter. Each query sees the cache
        up to ``block_limits`` of its position: itself, or under
        diffusion over blocks the end of its block — whole blocks a
        chunk, so the keys it may see are written — and such a model's
        prefill yields no token (no head is run; 0 comes back)."""
        cfg, mcfg = self.pool, self.cfg
        c = chunk.shape[0]
        p = pos0 + jnp.arange(c)
        valid = jnp.arange(c) < n_valid
        # clip the embedding/table lookups for padding positions; their
        # values are masked, only their indices must stay in range
        p_safe = jnp.minimum(p, mcfg.max_len - 1)
        with jax.named_scope("embed"):
            x = embed(params, chunk, p_safe, mcfg)[None]
        limits = block_limits(p, mcfg)
        row = state["tables"][slot]
        bid = jnp.where(
            valid,
            row[jnp.minimum(p_safe // cfg.block_len, row.shape[0] - 1)],
            0,
        )
        off = p_safe % cfg.block_len
        new_k, new_v = [], []

        def mk_attend(layer):
            i = self._kv_at.get(layer)      # None: no attention there

            def attend_latent(q, lat, _):
                # the chunk's form: K and V made from the slot's
                # gathered latents, as far as a query of it may see
                lp = self._latent_write(state["k"][i], bid, off, lat[0])
                o = latent_attend(
                    q, self._gather_latent(lp, row[None]),
                    params[f"blk{layer}/attn/kv_b"], limits[None], mcfg,
                    absorbed=False,
                )
                return o, (lp, None)

            def attend(q, k, v):
                kp = self._kv_write(
                    state["k"][i], bid, off, jnp.moveaxis(k[0], 1, 0)
                )
                vp = self._kv_write(
                    state["v"][i], bid, off, jnp.moveaxis(v[0], 1, 0)
                )
                # the one-slot gather whatever the decode tick runs: a
                # (1, cache_len) view a layer, which the kernel's
                # many-query shape does not beat (PERF.md §6, PR 29)
                o = cache_attend(
                    q,
                    *self._gather_kv(kp, vp, row[None]),
                    limits[None],
                )
                return o, (kp, vp)
            return attend_latent if mcfg.kv_latent else attend

        held_pairs = jnp.zeros((), jnp.int32)
        new_ssm, new_conv = [], []
        for i in range(mcfg.n_layers):
            j, m = self._state_at.get(i), self._ssm_at.get(i)
            x, aux, extra = _block_apply(
                params, f"blk{i}", x, mk_attend(i), mcfg,
                moe_capacity_factor=float(max(mcfg.moe_experts, 1)),
                positions=p[None], valid=valid[None],
                carried=self._carried(state, i, slot),
            )
            if j is not None:
                # the chunk starts from the slot's state (zeros at
                # admission) and leaves what its valid positions made
                if m is not None:
                    new_ssm.append(state["ssm"][m].at[slot].set(extra[0][0]))
                    extra = extra[1]
                new_conv.append(state["conv"][j].at[:, slot].set(extra[0]))
            elif i in self._kv_at:
                new_k.append(extra[0])
                new_v.append(extra[1])
            if mcfg.expert_layer(i):
                held_pairs = held_pairs + aux[2]
        new_state = {
            **state, "k": tuple(new_k),
            "v": tuple(v for v in new_v if v is not None),
        }
        if new_ssm:
            new_state["ssm"] = tuple(new_ssm)
        if new_conv:
            new_state["conv"] = tuple(new_conv)
        if "chunk_pairs" in state:
            new_state["chunk_pairs"] = state["chunk_pairs"] + held_pairs
        if mcfg.diffusion_block:
            return new_state, jnp.float32(0.0)
        logits = lm_head(params, x, mcfg)[0]
        last = jnp.take(logits, jnp.maximum(n_valid - 1, 0), axis=0)
        return new_state, last

    def _verify(self, params, state, draft, n_draft):
        """The speculative tick: score every live slot's current token
        plus its drafted candidates — (S, K+1) positions — in ONE
        forward through the paged pool, exactly the chunked-prefill
        shape discipline batched over slots.

        Sequence per slot: t_0 = the slot's current (last emitted)
        token at position pos, t_1..t_K = ``draft`` at pos+1..pos+K
        (``n_draft`` gates how many are real; the rest ride masked to
        the trash block, the prefill padding discipline). Query j's
        logits predict position pos+j+1 GIVEN the draft prefix — so
        greedy acceptance is the longest prefix of the draft matching
        the model's own argmax continuations (cumprod), plus the bonus
        token at the first mismatch. By induction every accepted
        token — and the bonus — is exactly what sequential one-token
        ticks would have emitted: speculation changes *when* tokens
        appear, never *which*.

        KV REWIND, by never writing what sequential decode would not
        have: attention runs against the GATHERED dense views with the
        chunk's fresh K/V OVERLAID (query j sees the draft prefix's
        entries without the pool being touched), and the pool itself
        takes ONE masked scatter after acceptance is known — accepted
        positions land, rejected/padding/dead positions route to the
        trash block. Un-advancing a rejected position is therefore a
        no-op on its pool bytes, and the pool after ANY accept/reject
        pattern is bitwise what one-token ticks leave (the parity
        tests pin it) at the same memory traffic as the decode tick
        (one gather + one scatter per pool array).

        Returns (state', emitted (S, K+1) — -1 beyond each slot's
        accepted run and on dead slots — and accepted (S,) draft-token
        counts for the acceptance-rate telemetry)."""
        cfg, mcfg = self.pool, self.cfg
        tokens, pos, live = state["tokens"], state["pos"], state["live"]
        kd = draft.shape[1]
        q = kd + 1
        seq = jnp.concatenate([tokens[:, None], draft], axis=1)  # (S, Q)
        j = jnp.arange(q)[None, :]
        p = pos[:, None] + j                                     # (S, Q)
        valid = live[:, None] & (j <= n_draft[:, None])
        p_safe = jnp.minimum(p, mcfg.max_len - 1)
        with jax.named_scope("embed"):
            x = embed(params, seq, p_safe, mcfg)
        bid, off = self._write_targets(state["tables"], p_safe, valid)
        fresh = []

        def overlay(pool_arr, new_shqd):
            return self._overlay(pool_arr, state["tables"], p_safe, new_shqd)

        def mk_attend(i):
            def attend(qh, kh, vh):
                if self._fused:
                    # the kernel's overlay form IS the rewind contract
                    # (pool never written before acceptance) at every
                    # draft width, so kd == 0 needs no special case —
                    # the post-acceptance scatter routes identically
                    from ..ops.paged_attention import (
                        paged_attention_overlay,
                    )

                    with jax.named_scope("paged_attention"):
                        o = paged_attention_overlay(
                            qh, state["k"][i], state["v"][i],
                            state["tables"], p, kh, vh, valid,
                            interpret=self.serving.interpret,
                        )
                    return o, (kh, vh)
                if kd == 0:
                    # zero draft width: rewind is definitionally inert
                    # (nothing can be rejected), so take the decode
                    # tick's write-then-gather memory pattern instead
                    # of double-buffering an overlay view — this shape
                    # IS serve_bench's isolated-machinery probe, and
                    # the write targets (bid routes dead lanes to
                    # trash) equal the post-acceptance routing below
                    kp = self._kv_write(
                        state["k"][i], bid, off, jnp.moveaxis(kh, 1, 2)
                    )
                    vp = self._kv_write(
                        state["v"][i], bid, off, jnp.moveaxis(vh, 1, 2)
                    )
                    o = cache_attend(
                        qh,
                        *self._gather_kv(kp, vp, state["tables"]),
                        p,
                    )
                    return o, (kp, vp)
                o = cache_attend(
                    qh,
                    overlay(state["k"][i], kh),
                    overlay(state["v"][i], vh),
                    p,
                )
                return o, (kh, vh)
            return attend

        for i in range(mcfg.n_layers):
            x, _, extras = _block_apply(
                params, f"blk{i}", x, mk_attend(i), mcfg,
                moe_capacity_factor=float(max(mcfg.moe_experts, 1)),
                positions=p,
            )
            fresh.append(extras)
        logits = lm_head(params, x, mcfg)                        # (S, Q, V)
        # position 0 samples through the temperature lane (temperature
        # slots ride the verify tick with n_draft == 0: their one
        # emitted token per tick is this sample); positions >= 1 are
        # greedy-only — temperature slots never accept drafts
        with jax.named_scope("sample"):
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            new_rng, keys = self._split_keys(state)
            first = self._sample(
                logits[:, 0], keys, state["temp"], live, tokens
            )
        g = jnp.concatenate([first[:, None], greedy[:, 1:]], axis=1)
        match = (draft == g[:, :kd]) & (
            jnp.arange(kd)[None, :] < n_draft[:, None]
        )
        acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
        emit_mask = live[:, None] & (j <= acc[:, None])
        emitted = jnp.where(emit_mask, g, jnp.int32(-1))
        last_tok = jnp.take_along_axis(g, acc[:, None], axis=1)[:, 0]
        # the rewind-by-construction scatter: ONLY positions sequential
        # decode would have written (j <= acc, live) land in real
        # blocks; everything else routes to trash. At kd == 0 the
        # reference attend already wrote the pool with that exact
        # routing (the fused path never writes in attend, so it takes
        # the scatter at every draft width — at kd == 0 emit_mask is
        # exactly ``live``, the same routing).
        if kd == 0 and not self._fused:
            new_k = [kp for kp, _ in fresh]
            new_v = [vp for _, vp in fresh]
        else:
            bid_keep = jnp.where(emit_mask, bid, 0)
            new_k, new_v = [], []
            for i, (kh, vh) in enumerate(fresh):
                with jax.named_scope(f"blk{i}"):
                    new_k.append(self._kv_write(
                        state["k"][i], bid_keep, off, jnp.moveaxis(kh, 1, 2)
                    ))
                    new_v.append(self._kv_write(
                        state["v"][i], bid_keep, off, jnp.moveaxis(vh, 1, 2)
                    ))
        new_state = {
            **state,
            "tokens": jnp.where(live, last_tok, tokens),
            "pos": pos + jnp.where(live, acc + 1, 0),
            "rng": new_rng,
            "k": tuple(new_k),
            "v": tuple(new_v),
        }
        return new_state, emitted, jnp.where(live, acc, 0)

    def _block_step(self, params, state):
        """One pass of generation by diffusion over blocks, for every
        live slot at once: the slot's current block — B positions from
        ``pos``, ``mask_id`` where ``blk_masked`` — through the model
        against the cache and each other (every query's limit is the
        block's end).

        What follows the forward depends on the slot's phase, in one
        fixed shape. A slot with NO masked position commits: the
        block's K and V (those of the fully unmasked block, which later
        blocks read) go to its real pool blocks, ``pos`` moves on B and
        the lanes hold a fresh, all-masked block. A slot with masked
        positions denoises: per masked position the greedy token and
        its confidence (the softmax probability of that token), and the
        ``block_fix`` most confident — all of them if fewer are left —
        take their tokens for good (``low_confidence_static``).

        How the block meets the cache follows the engine's choice. The
        gather path lays the block's K/V over gathered views and writes
        nothing before commit (``_verify``'s overlay-then-masked-write:
        a denoising slot's K/V go to the trash block). The kernel writes
        first and reads in place (the decode tick's way): every live
        slot's block goes to its own rows ``pos .. pos + B - 1``, and
        the block's B queries of a head ride the one-query kernel as B
        more query rows over that head's K/V head, since all of them
        see up to the block's end. Those rows lie past the slot's
        committed length and belong to it alone: every later pass of
        the slot writes them again before it reads them, the commit
        pass (nothing masked) leaves the final K/V, and nothing else
        reads a slot's rows (no prefix cache, speculation or export for
        such a model).

        -> (state', one int32 vector: the (S, B) tokens this pass
        fixed, by position in the block, -1 elsewhere, flattened; then
        the pass's expert counters, experts hit summed over layers and
        the most tokens one expert of one layer took — one pull)."""
        mcfg = self.cfg
        bl = mcfg.diffusion_block
        pos, live, masked = state["pos"], state["live"], state["blk_masked"]
        n_slots = pos.shape[0]
        p = pos[:, None] + jnp.arange(bl)[None, :]               # (S, B)
        p_safe = jnp.minimum(p, mcfg.max_len - 1)
        seq = jnp.where(masked, jnp.int32(mcfg.mask_id), state["blk_tok"])
        commit = live & ~jnp.any(masked, axis=1)
        valid = jnp.broadcast_to(live[:, None], p.shape)
        with jax.named_scope("embed"):
            x = embed(params, seq, p_safe, mcfg)
        limits = block_limits(p, mcfg)
        fresh, stats = [], []
        # where the block's K/V go: on the kernel's way every live slot's
        # to its own rows before the pass reads them, on the gather
        # path a committing slot's after the forward; the rest to the
        # trash block. The kernel sees a slot up to its block's end (a
        # dead lane: -1, nothing)
        bid, off = self._write_targets(
            state["tables"], p_safe,
            valid if self._fused
            else jnp.broadcast_to(commit[:, None], p.shape),
        )
        ends = jnp.where(live, limits[:, 0], -1)[:, None]

        def mk_attend(i):
            def attend(qh, kh, vh):
                if self._fused:
                    kp = self._kv_write(
                        state["k"][i], bid, off, jnp.moveaxis(kh, 1, 2)
                    )
                    vp = self._kv_write(
                        state["v"][i], bid, off, jnp.moveaxis(vh, 1, 2)
                    )
                    s, h, _, d = qh.shape
                    o = self._paged_attend(
                        qh.reshape(s, h * bl, 1, d), kp, vp,
                        state["tables"], ends,
                    )
                    return o.reshape(qh.shape), (kp, vp)
                o = cache_attend(
                    qh,
                    self._overlay(state["k"][i], state["tables"], p_safe, kh),
                    self._overlay(state["v"][i], state["tables"], p_safe, vh),
                    limits,
                )
                return o, (kh, vh)
            return attend

        for i in range(mcfg.n_layers):
            x, aux, extras = _block_apply(
                params, f"blk{i}", x, mk_attend(i), mcfg,
                moe_capacity_factor=float(max(mcfg.moe_experts, 1)),
                positions=p, valid=valid,
            )
            fresh.append(extras)
            if mcfg.moe_top_k:
                stats.append(aux)
        logits = lm_head(params, x, mcfg)                        # (S, B, V)
        with jax.named_scope("sample"):
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            top = jnp.max(logits, axis=-1)
            conf = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)
            _, best = jax.lax.top_k(
                jnp.where(masked, conf, -1.0), self.block_fix
            )
            fix = jnp.zeros(masked.shape, bool).at[
                jnp.arange(n_slots)[:, None], best
            ].set(True) & masked & live[:, None]
        if self._fused:
            # the pass's own writes already put every block in place
            new_k = [kp for kp, _ in fresh]
            new_v = [vp for _, vp in fresh]
        else:
            new_k, new_v = [], []
            for i, (kh, vh) in enumerate(fresh):
                with jax.named_scope(f"blk{i}"):
                    new_k.append(self._kv_write(
                        state["k"][i], bid, off, jnp.moveaxis(kh, 1, 2)
                    ))
                    new_v.append(self._kv_write(
                        state["v"][i], bid, off, jnp.moveaxis(vh, 1, 2)
                    ))
        moved = commit[:, None]
        new_state = {
            **state,
            "pos": pos + jnp.where(commit, bl, 0),
            "blk_tok": jnp.where(
                moved, 0, jnp.where(fix, greedy, state["blk_tok"])
            ),
            "blk_masked": moved | (masked & ~fix),
            "k": tuple(new_k),
            "v": tuple(new_v),
        }
        if stats:
            st = jnp.stack(stats)                                # (L, 2)
            counters = jnp.stack([jnp.sum(st[:, 0]), jnp.max(st[:, 1])])
        else:
            counters = jnp.zeros((2,), jnp.int32)
        out = jnp.concatenate([
            jnp.where(fix, greedy, jnp.int32(-1)).reshape(-1), counters,
        ])
        return new_state, out

    def _activate_block_prog(self, state, slot, pos0, tail, n_tail):
        """A slot whose prompt's whole blocks are prefilled goes live at
        block start ``pos0`` with the prompt's ``n_tail`` last tokens in
        place in its block lanes and the rest masked. No token is
        sampled: a model generated by diffusion has none to give yet."""
        return {
            **state,
            "pos": state["pos"].at[slot].set(pos0),
            "live": state["live"].at[slot].set(True),
            "temp": state["temp"].at[slot].set(0.0),
            "blk_tok": state["blk_tok"].at[slot].set(tail),
            "blk_masked": state["blk_masked"].at[slot].set(
                jnp.arange(tail.shape[0]) >= n_tail
            ),
        }

    def _admit_prog(self, state, slot, row):
        out = {
            **state,
            "tables": state["tables"].at[slot].set(row),
            "pos": state["pos"].at[slot].set(0),
            "live": state["live"].at[slot].set(False),
        }
        # a sequence starts from a zero state and an empty convolution
        # tail; retirement needs nothing more
        if "ssm" in state:
            out["ssm"] = tuple(a.at[slot].set(0) for a in state["ssm"])
        if "conv" in state:
            out["conv"] = tuple(a.at[:, slot].set(0) for a in state["conv"])
        return out

    def _activate_prog(self, state, slot, last_logits, plen, seed, temp):
        rng = jax.random.PRNGKey(seed)
        k0, rng = jax.random.split(rng)
        greedy = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        sampled = jax.random.categorical(
            k0, last_logits / jnp.maximum(temp, 1e-6)
        ).astype(jnp.int32)
        first = jnp.where(temp > 0.0, sampled, greedy)
        return {
            **state,
            "tokens": state["tokens"].at[slot].set(first),
            "pos": state["pos"].at[slot].set(plen),
            "live": state["live"].at[slot].set(True),
            "temp": state["temp"].at[slot].set(temp),
            "rng": state["rng"].at[slot].set(rng),
        }, first

    def _retire_prog(self, state, slot):
        return {
            **state,
            "live": state["live"].at[slot].set(False),
            "tables": state["tables"].at[slot].set(
                jnp.zeros((self.pool.max_blocks_per_seq,), jnp.int32)
            ),
        }

    def _export_prog(self, state, slot):
        """Gather one slot's paged K/V through its block table — every
        layer stacked into ONE (L, MB, H, BL, D) bulk value — plus its
        decode lanes. The device half of block migration's export: one
        gather per pool array, no per-block chatter (the one-shot
        transfer shape of arxiv 1805.08430), and ``slot`` is traced so
        every export reuses the same compiled program. Pad rows beyond
        the sequence's allocation gather the trash block; the host side
        trims them before serialization."""
        row = state["tables"][slot]
        k = jnp.stack([self._blocks_out(kp, row) for kp in state["k"]])
        v = jnp.stack([self._blocks_out(vp, row) for vp in state["v"]])
        return (
            k, v, state["tokens"][slot], state["pos"][slot],
            state["temp"][slot], state["rng"][slot],
        )

    def _import_prog(self, state, slot, table_row, scatter_row,
                     kblk, vblk, tok, pos, temp, rng):
        """Scatter a migrated sequence's (L, MB, H, BL, D) K/V bytes
        into this pool's freshly allocated blocks and install its lanes
        LIVE — the device half of block migration's import, one fused
        dispatch. ``table_row`` is the slot's new block table;
        ``scatter_row`` routes pad rows AND prefix-cache-shared rows to
        the trash block (a shared block's bytes already live in this
        pool bit-for-bit — writing them again is skipped, not risked),
        so duplicate trash writes can only disagree about garbage the
        attend mask zeroes exactly."""
        new_k = tuple(
            self._blocks_in(kp, scatter_row, kblk[i])
            for i, kp in enumerate(state["k"])
        )
        new_v = tuple(
            self._blocks_in(vp, scatter_row, vblk[i])
            for i, vp in enumerate(state["v"])
        )
        return {
            **state,
            "k": new_k,
            "v": new_v,
            "tables": state["tables"].at[slot].set(table_row),
            "tokens": state["tokens"].at[slot].set(tok),
            "pos": state["pos"].at[slot].set(pos),
            "temp": state["temp"].at[slot].set(temp),
            "rng": state["rng"].at[slot].set(rng),
            "live": state["live"].at[slot].set(True),
        }

    def _export_blocks_prog(self, state, row):
        """Gather an arbitrary block list's per-layer K/V into ONE
        (L, MB, H, BL, D) bulk value — the device half of serving a
        ``cache_fetch`` (pad rows gather the trash block; the host
        trims them before the ship frame is serialized)."""
        k = jnp.stack([self._blocks_out(kp, row) for kp in state["k"]])
        v = jnp.stack([self._blocks_out(vp, row) for vp in state["v"]])
        return k, v

    def _install_prog(self, state, scatter_row, kblk, vblk):
        """Scatter shipped (L, MB, H, BL, D) K/V bytes into freshly
        allocated blocks — the same one-compiled-scatter discipline as
        ``_import_prog`` minus the lane install: shipped prefix blocks
        warm the CACHE, no slot goes live. Pad rows route to the trash
        block."""
        return {
            **state,
            "k": tuple(
                self._blocks_in(kp, scatter_row, kblk[i])
                for i, kp in enumerate(state["k"])
            ),
            "v": tuple(
                self._blocks_in(vp, scatter_row, vblk[i])
                for i, vp in enumerate(state["v"])
            ),
        }

    def _cow_prog(self, state, src, dst):
        """Copy block ``src``'s K/V to block ``dst`` in every layer —
        the copy-on-write a whole-prompt prefix hit needs before its
        last-token prefill chunk may write (the source stays shared,
        only this sequence's table points at the copy)."""
        return {
            **state,
            "k": tuple(k.at[dst].set(k[src]) for k in state["k"]),
            "v": tuple(v.at[dst].set(v[src]) for v in state["v"]),
        }

    # ------------------------------------------------------------------
    # admission-path API (host-driven, one fused dispatch each, never on
    # the tick path of OTHER slots' decode)
    # ------------------------------------------------------------------

    def admit(self, slot: int, n_total_tokens: int,
              prompt=None) -> Admission:
        """Allocate ``blocks_for(n_total_tokens)`` blocks to ``slot`` and
        install its block table (raises PoolExhausted untouched —
        admission backpressure). The slot stays dead until activate().

        With the prefix cache on and a ``prompt`` given, the prompt's
        longest cached block-prefix is SHARED instead of allocated:
        matched blocks are retained (refcount bumped, LRU blocks
        revived) and only the uncached tail draws fresh blocks — the
        all-or-nothing contract still holds: hit-plus-tail feasibility
        is checked BEFORE any state is touched, so a backpressured
        admission raises PoolExhausted as a true no-op (free list,
        LRU order, index, and reclaim telemetry untouched — the
        request retries next tick). A hit covering the WHOLE
        prompt still needs the last prompt position's logits to sample
        the first token, so the final matched block is COPY-ON-WRITTEN
        (one fixed-shape compiled copy) and ``prefill_from`` points at
        the last prompt token — one 1-token chunk re-derives the
        activation logits, writing bitwise the bytes the shared source
        already holds, into the private copy only.

        With ``prefix_cache { tail_stride }`` on, a hit whose last
        shared tokens end MID-block COW-EXTENDS the deepest registered
        partial tail: the tail block is copied into this sequence's
        fresh block at the next chain position (same fixed-shape
        compiled copy) and prefill starts past the covered tokens —
        the copied positions are prefill-written bytes under the
        identical left context, so they are bitwise what this
        sequence's own cold prefill would write."""
        needed = self.pool.blocks_for(n_total_tokens)
        with span("engine.admit", slot=slot, blocks=needed):
            alloc = self.allocator
            hit: list[int] = []
            chain: list[bytes] = []
            if alloc.cache is not None and prompt is not None:
                # ONE digest pass per admission: the same chain serves the
                # match here and register_prefix() after prefill completes
                chain = alloc.cache.chain(prompt)
                hit = alloc.cache.match_chain(chain)
            cached = len(hit) * self.pool.block_len
            cow = bool(hit) and cached >= len(prompt)
            tail_src = tail_tokens = 0
            if (
                not cow
                and alloc.cache is not None
                and prompt is not None
                and alloc.cache.tail_stride
            ):
                tail_src, tail_tokens = alloc.cache.match_tail(
                    prompt, len(hit), chain
                )
                cached += tail_tokens
            fresh_n = needed - len(hit) + (1 if cow else 0)
            protect = hit + ([tail_src] if tail_tokens else [])
            if fresh_n > alloc.headroom_excluding(protect):
                raise PoolExhausted(
                    f"need {fresh_n} fresh blocks beyond a {len(hit)}-block "
                    f"prefix hit, {alloc.headroom_excluding(protect)} "
                    "allocatable"
                )
            if hit:
                alloc.retain(hit)
            if tail_tokens:
                # pin the tail source across alloc(): a fresh allocation may
                # otherwise LRU-reclaim the very block we are about to copy
                alloc.retain([tail_src])
            fresh = alloc.alloc(fresh_n)
            if cow:
                # the whole prompt is cached: COW the last matched block so
                # the re-derivation chunk can write without touching the
                # shared source, then drop our extra reference to it
                src, dst = hit[-1], fresh[0]
                blocks = hit[:-1] + [dst] + fresh[1:]
                self.state = self._cow_jit(
                    self.state, jnp.int32(src), jnp.int32(dst)
                )
                alloc.release([src])
            elif tail_tokens:
                # partial-tail hit: copy the matched tail block into this
                # sequence's own block at the next chain position; bytes
                # beyond the covered tokens are re-prefilled or causally
                # masked, so only the covered prefix is ever observed
                blocks = hit + fresh
                self.state = self._cow_jit(
                    self.state, jnp.int32(tail_src), jnp.int32(fresh[0])
                )
                alloc.release([tail_src])
            else:
                blocks = hit + fresh
            row = np.zeros((self.pool.max_blocks_per_seq,), np.int32)
            row[: len(blocks)] = blocks
            self.state = self._admit_jit(
                self.state, jnp.int32(slot), jnp.asarray(row)
            )
            self._slot_blocks[slot] = blocks
            self._slot_chain[slot] = chain
            self._slot_version[slot] = self.params_version
            return Admission(
                blocks=blocks,
                cached_tokens=cached,
                prefill_from=min(cached, max(len(prompt), 1) - 1)
                if prompt is not None else 0,
                cow_copied=cow,
                tail_tokens=tail_tokens,
            )

    def register_prefix(self, slot: int, prompt) -> int:
        """Index ``slot``'s fully-prompt-covered blocks by their chained
        content digests (called once the slot's prompt is completely
        prefilled — every registered position is prefill-written, so a
        later hit's bytes are bitwise a cold prefill's). Digests already
        present are skipped (shared blocks; concurrent identical
        prompts keep the first writer); new entries link to their
        parent digest, the chain structure eviction cascades through.
        -> newly registered blocks."""
        cache = self.allocator.cache
        if cache is None:
            return 0
        if self._slot_version.get(slot, self.params_version) \
                != self.params_version:
            # the slot's bytes were prefilled under a now-replaced
            # version (a rollout flipped mid-flight) — indexing them
            # would poison new-version admissions
            return 0
        blocks = self._slot_blocks.get(slot)
        if not blocks:
            return 0
        chain = self._slot_chain.get(slot) or cache.chain(prompt)
        new = 0
        for i, digest in enumerate(chain):
            if not cache.has(digest):
                new += cache.register(
                    digest, blocks[i],
                    parent=chain[i - 1] if i else None,
                )
        # partial-tail index: the prompt's LAST, partial block (if this
        # sequence owns one) registers at every covered stride multiple
        nb = len(chain)
        if cache.tail_stride and len(blocks) > nb:
            cache.register_tail(prompt, blocks[nb])
        return new

    def register_history(self, slot: int, tokens) -> int:
        """Index ``slot``'s FULL blocks under the chained digests of
        ``tokens`` — the whole prompt + emitted history, called at
        retirement with ``prefix_cache { decode_blocks }`` on, so a
        follow-up turn whose prompt replays this conversation hits the
        decode-written blocks too. Digests over the prompt prefix are
        identical to register_prefix()'s (chains are prefix-stable) and
        skip as already-present; the NEW registrations cover
        decode/verify-written bytes, which ride a different compiled
        shape than prefill — a warm stream over them is TOKEN-LEVEL
        identical to cold admission, not bitwise (the PR 9 cross-shape
        caveat). Only blocks every position of which was actually
        WRITTEN register: the last emitted token's K/V never is (a
        token's cache entry is written by the tick that processes it,
        which a finished stream never runs), so the chain clips to
        ``len(tokens) - 1`` positions. -> newly registered blocks."""
        cache = self.allocator.cache
        if cache is None:
            return 0
        if self._slot_version.get(slot, self.params_version) \
                != self.params_version:
            # stale-version slot (admitted before a rollout flip): its
            # decode-written bytes belong to the old weights — skip
            return 0
        blocks = self._slot_blocks.get(slot)
        if not blocks:
            return 0
        safe = (len(tokens) - 1) // self.pool.block_len
        chain = cache.chain(tokens)[:safe]
        new = 0
        for i, digest in enumerate(chain[: len(blocks)]):
            if not cache.has(digest):
                new += cache.register(
                    digest, blocks[i],
                    parent=chain[i - 1] if i else None,
                )
        return new

    def prefill_chunk(self, slot: int, tokens: np.ndarray, pos0: int):
        """Run one prompt chunk (<= max_prefill_chunk tokens) for
        ``slot``; returns the device logits at the chunk's last valid
        position (meaningful only for the final chunk)."""
        c = self.serving.max_prefill_chunk
        n = len(tokens)
        if n > c:
            raise ValueError(f"prefill chunk {n} > max_prefill_chunk {c}")
        with span("engine.prefill", slot=slot, tokens=n, pos0=pos0):
            buf = np.zeros((c,), np.int32)
            buf[:n] = tokens
            self.state, last = self._prefill_jit(
                self.params, self.state, jnp.int32(slot), jnp.asarray(buf),
                jnp.int32(pos0), jnp.int32(n),
            )
        return last

    def activate(self, slot: int, last_logits, plen: int, seed: int,
                 temperature: float | None = None):
        """Sample the first token from the final prefill chunk's logits
        (the same key discipline as generate(): k0 = first split of the
        request's key), install the slot's temperature lane, and flip
        it live. ``temperature`` None = the engine default. -> the
        first token as a device scalar: nothing is read here, so the
        caller can dispatch its decode before it waits for the chunk
        (``int()`` of it is that wait)."""
        temp = self.temperature if temperature is None else float(temperature)
        with span("engine.activate", slot=slot):
            self.state, first = self._activate_jit(
                self.state, jnp.int32(slot), last_logits,
                jnp.int32(plen), jnp.int32(seed), jnp.float32(temp),
            )
        return first

    def activate_block(self, slot: int, prompt) -> None:
        """Flip ``slot`` live for block steps once the whole blocks of
        its prompt are prefilled (``len(prompt) // B * B`` tokens): the
        prompt's tail starts its first block, the rest of it masked."""
        b = self.cfg.diffusion_block
        n_tail = len(prompt) % b
        with span("engine.activate", slot=slot):
            tail = np.zeros((b,), np.int32)
            tail[:n_tail] = prompt[len(prompt) - n_tail:]
            self.state = self._activate_block_jit(
                self.state, jnp.int32(slot), jnp.int32(len(prompt) - n_tail),
                jnp.asarray(tail), jnp.int32(n_tail),
            )

    def block_step(self):
        """One pass over every live slot's current block (a model with a
        ``diffusion_block``). -> one int32 device vector: the
        (slots, B) tokens the pass fixed, -1 elsewhere, flattened, then
        the pass's two expert counters (``_block_step``)."""
        self.state, out = self._block_step_jit(self.params, self.state)
        return out

    def decode(self):
        """One tick: every live slot advances one token. -> emitted
        (slots,) int32 device array, -1 on dead slots, followed by the
        pass's ``decode_counters`` counters where the model has any
        (``DECODE_COUNTERS``)."""
        self.state, emitted = self._decode_jit(self.params, self.state)
        return emitted

    def verify(self, draft, n_draft):
        """One speculative tick: every live slot advances by its
        accepted-prefix length + 1. ``draft`` (slots, K) int32 proposed
        tokens, ``n_draft`` (slots,) int32 how many are real (0 = the
        slot rides as a one-token tick; temperature slots always 0).
        K is fixed per engine (EngineConfig.spec_k sizes the compiled
        program; any K works but each distinct K is its own compile).
        -> (emitted (slots, K+1) int32 device array — -1 beyond each
        accepted run and on dead slots — accepted (slots,) int32 draft
        tokens accepted)."""
        self.state, emitted, accepted = self._verify_jit(
            self.params, self.state,
            jnp.asarray(draft, jnp.int32), jnp.asarray(n_draft, jnp.int32),
        )
        return emitted, accepted

    def export_slot(self, slot: int) -> dict:
        """One admitted slot's full migratable state as host values:
        per-layer K/V blocks gathered through the block table and
        TRIMMED to the sequence's actual allocation, the decode lanes
        (current token, position, temperature, RNG key — the key ships
        bit-for-bit, so a temperature stream's continuation samples
        through the exporter's exact key schedule), and the admission
        digest chain (so the importer can re-register prefix-cached
        blocks without re-hashing). The slot itself is untouched — the
        caller retires it once the bytes are safely on the wire."""
        self._refuse_for_slot_state("slot export")
        blocks = self._slot_blocks.get(slot)
        if not blocks:
            raise ValueError(f"slot {slot} owns no blocks (not admitted?)")
        n = len(blocks)
        k, v, tok, pos, temp, rng = self._export_jit(
            self.state, jnp.int32(slot)
        )
        return {
            "k": np.asarray(k)[:, :n],
            "v": np.asarray(v)[:, :n],
            "token": int(tok),
            "pos": int(pos),
            "temp": float(temp),
            "rng": np.asarray(rng),
            "chain": list(self._slot_chain.get(slot) or ()),
        }

    def import_slot(self, slot: int, payload: dict) -> dict:
        """Install an exported sequence into dead ``slot``: allocate
        blocks for its K/V — SHARING this pool's cached prefix blocks
        wherever the shipped digest chain already matches (cross-host
        cache reuse: a matched block's bytes here are bitwise what the
        exporter shipped, both being prefill-written under the same
        left context) — scatter the shipped bytes into the fresh
        blocks, install the lanes live, and register fully-prompt-
        covered blocks under their shipped digests for future local
        hits. Feasibility is checked BEFORE any state is touched, so a
        backpressured import raises PoolExhausted as a true no-op (the
        fleet host retries next tick). Only fully-prefilled (activated)
        sequences may migrate: the chain's registration contract needs
        every prompt position already written. -> {"blocks", "shared",
        "registered"}."""
        self._refuse_for_slot_state("slot import")
        alloc = self.allocator
        n = int(payload["k"].shape[1])
        chain = list(payload.get("chain") or ())
        hit: list[int] = []
        if alloc.cache is not None and chain:
            hit = alloc.cache.match_chain(chain)[:n]
        fresh_n = n - len(hit)
        if fresh_n > alloc.headroom_excluding(hit):
            raise PoolExhausted(
                f"import needs {fresh_n} fresh blocks beyond a "
                f"{len(hit)}-block prefix hit, "
                f"{alloc.headroom_excluding(hit)} allocatable"
            )
        if hit:
            alloc.retain(hit)
        fresh = alloc.alloc(fresh_n)
        blocks = hit + fresh
        mb = self.pool.max_blocks_per_seq
        table_row = np.zeros((mb,), np.int32)
        table_row[:n] = blocks
        # shared rows + pad rows scatter to trash: their bytes are
        # already here (shared) or masked garbage (pads)
        scatter_row = np.zeros((mb,), np.int32)
        scatter_row[len(hit):n] = fresh
        shape = (self.cfg.n_layers, mb) + payload["k"].shape[2:]
        kblk = np.zeros(shape, payload["k"].dtype)
        vblk = np.zeros(shape, payload["v"].dtype)
        kblk[:, :n] = payload["k"]
        vblk[:, :n] = payload["v"]
        self.state = self._import_jit(
            self.state, jnp.int32(slot),
            jnp.asarray(table_row), jnp.asarray(scatter_row),
            jnp.asarray(kblk), jnp.asarray(vblk),
            jnp.int32(payload["token"]), jnp.int32(payload["pos"]),
            jnp.float32(payload["temp"]),
            jnp.asarray(payload["rng"], jnp.uint32),
        )
        self._slot_blocks[slot] = blocks
        self._slot_chain[slot] = chain
        self._slot_version[slot] = self.params_version
        registered = 0
        if alloc.cache is not None:
            for i, digest in enumerate(chain[:n]):
                if not alloc.cache.has(digest):
                    registered += alloc.cache.register(
                        digest, blocks[i],
                        parent=chain[i - 1] if i else None,
                    )
        return {
            "blocks": blocks,
            "shared": len(hit),
            "registered": registered,
        }

    def export_blocks(self, blocks: list[int]) -> tuple:
        """Gather arbitrary registered blocks' per-layer K/V as host
        arrays ``(k, v)`` shaped (L, n, H, BL, D) — the byte payload of
        a ``cache_ship`` reply. The caller retains the blocks across
        the gather (an unlucky concurrent admission could otherwise
        LRU-reclaim them mid-read)."""
        self._refuse_for_slot_state("prefix shipping (export_blocks)")
        n = len(blocks)
        mb = self.pool.max_blocks_per_seq
        if n > mb:
            raise ValueError(
                f"export_blocks of {n} blocks exceeds the "
                f"{mb}-block fixed gather shape"
            )
        row = np.zeros((mb,), np.int32)
        row[:n] = blocks
        k, v = self._export_blocks_jit(self.state, jnp.asarray(row))
        return np.asarray(k)[:, :n], np.asarray(v)[:, :n]

    def install_prefix(self, chain: list[bytes], k, v) -> dict:
        """Warm this pool with a peer's shipped prefix: allocate fresh
        blocks for every chain position not already cached locally,
        scatter the shipped per-layer K/V bytes into them (one compiled
        dispatch, no lane touched), register them under the shipped
        digests, and PARK them on the LRU — the next admission matching
        this chain shares them exactly as if they had been prefilled
        here. Feasibility is checked before any state is touched:
        a backpressured install raises PoolExhausted as a true no-op
        (the fleet host degrades the request to plain prefill). ->
        {"installed", "shared"} block counts. Idempotent: re-delivering
        the same ship installs nothing."""
        self._refuse_for_slot_state("prefix shipping (install_prefix)")
        alloc = self.allocator
        if alloc.cache is None or not alloc.lru_enabled:
            # without LRU parking a refcount-0 block cannot outlive the
            # install call — nothing to warm (the host only fetches
            # when prefix_lru is on)
            return {"installed": 0, "shared": 0}
        n = len(chain)
        mb = self.pool.max_blocks_per_seq
        if n > mb or int(k.shape[1]) != n:
            raise ValueError(
                f"install_prefix: {n} digests vs {int(k.shape[1])} "
                f"shipped blocks (table width {mb})"
            )
        have = alloc.cache.match_chain(chain)
        todo = n - len(have)
        if todo == 0:
            return {"installed": 0, "shared": n}
        if todo > alloc.headroom_excluding(have):
            raise PoolExhausted(
                f"install needs {todo} fresh blocks beyond a "
                f"{len(have)}-block local prefix, "
                f"{alloc.headroom_excluding(have)} allocatable"
            )
        # pin the locally-matched parents across alloc(): evicting one
        # would orphan the chain we are about to extend
        if have:
            alloc.retain(have)
        fresh = alloc.alloc(todo)
        scatter_row = np.zeros((mb,), np.int32)
        scatter_row[:todo] = fresh
        shape = (self.cfg.n_layers, mb) + tuple(k.shape[2:])
        kblk = np.zeros(shape, k.dtype)
        vblk = np.zeros(shape, v.dtype)
        kblk[:, :todo] = k[:, len(have):]
        vblk[:, :todo] = v[:, len(have):]
        self.state = self._install_jit(
            self.state, jnp.asarray(scatter_row),
            jnp.asarray(kblk), jnp.asarray(vblk),
        )
        for i in range(len(have), n):
            alloc.cache.register(
                chain[i], fresh[i - len(have)],
                parent=chain[i - 1] if i else None,
            )
        # the warmed blocks belong to no sequence: release parks them
        # (registered, refcount 0) on the LRU for future admissions
        alloc.release(fresh)
        if have:
            alloc.release(have)
        return {"installed": todo, "shared": len(have)}

    def retire(self, slot: int) -> None:
        """Release the slot's blocks (refcount decrement: shared prefix
        blocks stay live for their other owners, registered refcount-0
        blocks park on the LRU list, the rest return to the free list
        as reusable garbage, masked wherever gathered) and kill its
        lane."""
        with span("engine.retire", slot=slot):
            self.state = self._retire_jit(self.state, jnp.int32(slot))
            self._slot_chain.pop(slot, None)
            self._slot_version.pop(slot, None)
            blocks = self._slot_blocks.pop(slot, None)
            if blocks:
                self.allocator.release(blocks)

    # ------------------------------------------------------------------
    # live weight rollout (serve/rollout.py): dual-version param slots
    # ------------------------------------------------------------------

    @property
    def staged_version(self) -> int | None:
        """Version tag of the staged (not yet live) tree, or None."""
        return self._staged[0] if self._staged is not None else None

    def stage_params(self, params: dict, version: int) -> int:
        """Hold next-version ``params`` ALONGSIDE the live tree (dual-
        resident: both fit in HBM until the flip — netlint ROL001 prices
        this statically). Validated against the live tree's exact
        key set, shapes, and dtypes: the compiled programs are reused
        across the flip, so a mismatched save must be rejected HERE,
        loudly, never staged. -> staged byte count."""
        version = int(version)
        if version == self.params_version:
            raise ValueError(
                f"stage_params: version {version} is already live"
            )
        cur = self.params
        missing = sorted(set(cur) - set(params))
        extra = sorted(set(params) - set(cur))
        if missing or extra:
            raise ValueError(
                f"stage_params v{version}: param tree mismatch "
                f"(missing {missing[:3]}, extra {extra[:3]})"
            )
        nbytes = 0
        for name, live in cur.items():
            a = np.asarray(params[name])
            if tuple(a.shape) != tuple(live.shape):
                raise ValueError(
                    f"stage_params v{version}: {name!r} shape "
                    f"{tuple(a.shape)} != live {tuple(live.shape)}"
                )
            if a.dtype != np.asarray(live).dtype:
                raise ValueError(
                    f"stage_params v{version}: {name!r} dtype "
                    f"{a.dtype} != live {np.asarray(live).dtype}"
                )
            nbytes += a.nbytes
        self._staged = (version, dict(params))
        return nbytes

    def unstage(self) -> None:
        """Drop the staged tree (a quarantined/aborted version)."""
        self._staged = None

    def flip_params(self) -> dict:
        """Atomic tick-boundary hot-swap: the staged tree becomes live,
        the previous tree stays PINNED for rollback, and the prefix
        cache is purged (its bytes were written under the old weights —
        a warm hit across versions would poison the pool). In-flight
        slots ride through on their already-written K/V; nothing drains.
        -> {"version", "prev_version", "purged_blocks"}."""
        if self._staged is None:
            raise ValueError("flip_params: nothing staged")
        version, params = self._staged
        self._prev = (self.params_version, self.params)
        self.params, self.params_version = params, version
        self._staged = None
        return {
            "version": version,
            "prev_version": self._prev[0],
            "purged_blocks": self.allocator.purge_cache(),
        }

    def rollback_params(self) -> dict:
        """Restore the pinned previous version (canary parity abort).
        Purges the cache again — blocks written under the aborted
        version are garbage to the restored one. Idempotent hazard-free:
        raises if no previous version is pinned."""
        if self._prev is None:
            raise ValueError("rollback_params: no previous version pinned")
        version, params = self._prev
        aborted = self.params_version
        self.params, self.params_version = params, version
        self._prev = None
        self._staged = None
        return {
            "version": version,
            "aborted_version": aborted,
            "purged_blocks": self.allocator.purge_cache(),
        }
