"""Prefix caching for the paged KV pool (serve/kv_pool.py): refcounted
copy-on-write block sharing, longest-prefix reuse at admission, LRU
eviction — plus the lint/trace/serve_bench satellites.

The correctness bar is the PR 9/10 parity discipline: with the cache
ON, token streams AND the post-run paged cache are BITWISE identical
to cache-disabled (cold) admission — across interleaved ragged
workloads, through a forced whole-prompt-hit copy-on-write, under
speculation, and on the TP mesh. A hit may only skip prefill work,
never move a token or a cache byte.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.models.transformer import (
    TransformerConfig,
    generate,
    init_lm,
)
from singa_tpu.serve import (
    BlockAllocator,
    Engine,
    EngineConfig,
    KVPool,
    PrefixCache,
    Request,
    Scheduler,
)
from singa_tpu.serve.kv_pool import PoolExhausted


def tiny_cfg(**kw):
    base = dict(
        vocab=32, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=32
    )
    base.update(kw)
    return TransformerConfig(**base)


def tiny_params(cfg, seed=0):
    return init_lm(jax.random.PRNGKey(seed), cfg)


def shared_prefix_workload(cfg, n=6, prefix_len=8, tail_len=3, seed=0):
    """Ragged requests sharing one common prefix: unique tails + ragged
    budgets, so admits/retires interleave while the prefix blocks are
    shared/reused across the whole run."""
    rs = np.random.RandomState(seed)
    prefix = rs.randint(0, cfg.vocab, size=(prefix_len,)).astype(np.int32)
    prompts = [
        np.concatenate(
            [prefix, rs.randint(0, cfg.vocab, size=(tail_len,))]
        ).astype(np.int32)
        for _ in range(n)
    ]
    budgets = [int(rs.randint(4, 9)) for _ in range(n)]
    return prefix, prompts, budgets


def serve_all(engine, prompts, budgets, recorder=None):
    sched = Scheduler(engine, recorder=recorder)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    assert sched.serve() is None
    return sched


def tokens_of(sched):
    return {r.rid: list(r.tokens) for r in sched.finished}


# ---------------------------------------------------------------------------
# allocator: refcounts, LRU, strict free
# ---------------------------------------------------------------------------


class TestRefcountedAllocator:
    def test_retain_release_refcounts(self):
        alloc = BlockAllocator(
            KVPool.for_model(64, 16, n_blocks=9), prefix_cache=True
        )
        a = alloc.alloc(2)
        assert [alloc.refcount(b) for b in a] == [1, 1]
        alloc.retain(a)  # a prefix hit shares both
        assert [alloc.refcount(b) for b in a] == [2, 2]
        alloc.release(a)  # first owner retires: still live
        assert [alloc.refcount(b) for b in a] == [1, 1]
        assert alloc.used_blocks == 2
        alloc.release(a)  # last owner: uncached blocks -> free list
        assert alloc.used_blocks == 0 and alloc.cached_blocks == 0
        assert alloc.free_blocks == 8

    def test_release_of_free_block_raises(self):
        alloc = BlockAllocator(KVPool.for_model(64, 16, n_blocks=9))
        a = alloc.alloc(1)
        alloc.release(a)
        with pytest.raises(ValueError, match="double release"):
            alloc.release(a)

    def test_free_raises_on_double_free_without_corrupting(self):
        """The latent pre-refcount hazard, now checkable: free() of an
        already-free block (or the same block twice in one call) raises
        BEFORE mutating anything, so the free list can never hold a
        duplicate id that two future owners would both receive."""
        alloc = BlockAllocator(KVPool.for_model(64, 16, n_blocks=9))
        a = alloc.alloc(3)
        alloc.free(a)
        free_before = alloc.free_blocks
        with pytest.raises(ValueError, match="double free"):
            alloc.free([a[0]])
        assert alloc.free_blocks == free_before
        b = alloc.alloc(2)
        with pytest.raises(ValueError, match="double free"):
            alloc.free([b[0], b[0]])  # dup inside ONE call
        # all-or-nothing: the failed call must not have released b[0]
        assert alloc.refcount(b[0]) == 1 and alloc.used_blocks == 2
        got = alloc.alloc(alloc.free_blocks)
        assert len(set(got) | set(b)) == len(got) + 2  # no id handed twice

    def test_free_of_shared_block_raises(self):
        alloc = BlockAllocator(
            KVPool.for_model(64, 16, n_blocks=9), prefix_cache=True
        )
        a = alloc.alloc(2)
        alloc.retain(a)
        with pytest.raises(ValueError, match="SHARED"):
            alloc.free(a)
        assert [alloc.refcount(b) for b in a] == [2, 2]  # untouched
        alloc.release(a)
        alloc.free(a)  # exclusive again: fine

    def test_registered_blocks_park_on_lru_and_reclaim_lazily(self):
        pool = KVPool.for_model(64, 16, n_blocks=5)  # 4 usable
        alloc = BlockAllocator(pool, prefix_cache=True)
        a = alloc.alloc(2)
        for i, b in enumerate(a):
            alloc.cache.register(bytes([i]), b)
        alloc.release(a)
        # registered refcount-0 blocks are CACHED, not freed...
        assert alloc.cached_blocks == 2 and alloc.used_blocks == 0
        assert alloc.cache.match is not None and len(alloc.cache) == 2
        # ...but still count as allocatable: no backpressure change
        assert alloc.free_blocks == 4 and alloc.can_alloc(4)
        events = []
        alloc.on_event = lambda kind, **p: events.append((kind, p))
        got = alloc.alloc(4)  # needs both LRU blocks -> lazy eviction
        assert len(got) == 4
        assert alloc.lru_evictions == 2 and len(alloc.cache) == 0
        assert [k for k, _ in events] == ["lru_evict", "lru_evict"]

    def test_lru_evicts_oldest_first_and_retain_revives(self):
        pool = KVPool.for_model(64, 16, n_blocks=6)  # 5 usable
        alloc = BlockAllocator(pool, prefix_cache=True)
        a, b, c = alloc.alloc(1)[0], alloc.alloc(1)[0], alloc.alloc(1)[0]
        for tag, blk in [(b"a", a), (b"b", b), (b"c", c)]:
            alloc.cache.register(tag, blk)
        alloc.release([a])          # oldest
        alloc.release([b])
        alloc.retain([a])           # revived: a leaves the LRU...
        assert alloc.lru_reclaims == 1
        alloc.release([c])
        alloc.release([a])          # ...and re-parks MRU-most
        # LRU order now b, c, a: exhausting the pool evicts b then c
        alloc.alloc(4)
        assert not alloc.cache.has(b"b") and not alloc.cache.has(b"c")
        assert alloc.cache.has(b"a")

    def test_release_parks_tail_first_so_eviction_shaves_chains(self):
        """A retiring sequence's blocks park deepest-first: eviction
        pressure drops the chain's TAIL and keeps the shorter — more
        widely shared — prefix matchable."""
        pool = KVPool.for_model(128, 16, n_blocks=9)  # 8 usable
        alloc = BlockAllocator(pool, prefix_cache=True)
        toks = list(range(64))  # 4 full blocks
        chain = alloc.cache.chain(toks)
        blocks = alloc.alloc(4)
        for i, (d, b) in enumerate(zip(chain, blocks)):
            alloc.cache.register(d, b, parent=chain[i - 1] if i else None)
        alloc.release(blocks)
        assert alloc.cached_blocks == 4
        alloc.alloc(5)  # 4 free + 1 eviction
        assert alloc.cache.match(toks) == blocks[:3]  # tail shaved
        assert alloc.cached_blocks == 3

    def test_head_eviction_cascades_and_frees_orphans(self):
        """Evicting a chain's HEAD must not strand its descendants as
        indexed-but-unmatchable warm weight: the subtree cascades out
        of the index and LRU-parked orphans return to the free list."""
        pool = KVPool.for_model(64, 16, n_blocks=6)  # 5 usable
        alloc = BlockAllocator(pool, prefix_cache=True)
        toks = list(range(32))  # 2 full blocks
        chain = alloc.cache.chain(toks)
        (head,) = alloc.alloc(1)
        (child,) = alloc.alloc(1)
        alloc.cache.register(chain[0], head)
        alloc.cache.register(chain[1], child, parent=chain[0])
        alloc.release([head])   # separate releases: head parks OLDEST
        alloc.release([child])
        assert alloc.cache.match(toks) == [head, child]
        got = alloc.alloc(4)  # 3 free + 1 eviction pops the head
        assert len(got) == 4
        # the orphaned child left the index AND the LRU (it is a plain
        # free block now, not dead warm weight)
        assert alloc.cached_blocks == 0 and len(alloc.cache) == 0
        assert alloc.cache.match(toks) == []
        assert alloc.lru_evictions == 2  # head + cascaded orphan
        assert alloc.free_blocks == 1

    def test_lru_disabled_frees_eagerly(self):
        alloc = BlockAllocator(
            KVPool.for_model(64, 16, n_blocks=5), prefix_cache=True,
            lru=False,
        )
        a = alloc.alloc(1)
        alloc.cache.register(b"x", a[0])
        alloc.release(a)
        assert alloc.cached_blocks == 0 and len(alloc.cache) == 0

    def test_backpressured_hit_admission_is_a_true_noop(self):
        """A request whose prefix HITS but whose tail cannot be
        allocated must raise PoolExhausted without touching anything:
        no phantom lru_reclaim events/counters, no LRU reordering —
        the retry next tick sees the identical pool."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        rs = np.random.RandomState(21)
        prompt = rs.randint(0, cfg.vocab, size=(8,)).astype(np.int32)
        eng = _engine(params, cfg, True, slots=2, block_len=8, chunk=8,
                      kv_blocks=5)  # 4 usable
        sched = Scheduler(eng)
        sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=4))
        sched.serve()  # registers the full prompt block -> LRU
        assert eng.allocator.cached_blocks == 1
        events = []
        eng.allocator.on_event = lambda kind, **p: events.append(kind)
        # same prompt (a whole-prompt hit) + a budget whose COW + tail
        # needs 4 fresh blocks with only 3 non-hit blocks allocatable:
        # must backpressure untouched
        with pytest.raises(PoolExhausted):
            eng.admit(0, 8 + 17, prompt=prompt)
        assert eng.allocator.lru_reclaims == 0 and events == []
        assert eng.allocator.cached_blocks == 1
        assert eng.allocator.used_blocks == 0

    def test_exhaustion_counts_lru_and_stays_all_or_nothing(self):
        alloc = BlockAllocator(
            KVPool.for_model(64, 16, n_blocks=5), prefix_cache=True
        )
        a = alloc.alloc(2)
        alloc.cache.register(b"p", a[0])
        alloc.release(a)  # a[0] -> LRU, a[1] -> free
        with pytest.raises(PoolExhausted):
            alloc.alloc(5)  # 4 allocatable (2 free + 1 lru + 1 free)
        # the failed alloc left LRU + index untouched
        assert alloc.cached_blocks == 1 and alloc.cache.has(b"p")


# ---------------------------------------------------------------------------
# content addressing
# ---------------------------------------------------------------------------


class TestPrefixCacheIndex:
    def test_identity_includes_left_context(self):
        """The chained digest: identical block TOKENS under different
        left contexts are different identities — a block is only
        reusable in the exact position/context it was written in."""
        cache = PrefixCache(block_len=4)
        tok = [7, 7, 7, 7]
        d1 = cache.chain([1, 2, 3, 4] + tok)[1]
        d2 = cache.chain([9, 9, 9, 9] + tok)[1]
        d0 = cache.chain(tok)[0]
        assert len({d1, d2, d0}) == 3

    def test_match_is_longest_cached_prefix(self):
        cache = PrefixCache(block_len=4)
        toks = list(range(12))  # 3 full blocks
        chain = cache.chain(toks)
        assert len(chain) == 3
        cache.register(chain[0], 5)
        cache.register(chain[2], 7)  # middle link missing
        assert cache.match(toks) == [5]  # chain stops at the gap
        cache.register(chain[1], 6)
        assert cache.match(toks) == [5, 6, 7]
        assert cache.match(toks[:11]) == [5, 6]  # partial tail: 2 full
        assert cache.match([99] + toks[1:]) == []

    def test_register_first_writer_wins_and_forget(self):
        cache = PrefixCache(block_len=4)
        d = cache.chain([1, 2, 3, 4])[0]
        assert cache.register(d, 3)
        assert not cache.register(d, 9)  # concurrent identical prompt
        assert cache.match([1, 2, 3, 4]) == [3]
        cache.forget(3)
        assert cache.match([1, 2, 3, 4]) == [] and len(cache) == 0


# ---------------------------------------------------------------------------
# warm == cold, bitwise
# ---------------------------------------------------------------------------


def _engine(params, cfg, enabled, slots=3, block_len=4, chunk=4, spec_k=0,
            kv_blocks=0, mesh=None):
    return Engine(
        params, cfg,
        EngineConfig(
            slots=slots, kv_block_len=block_len, max_prefill_chunk=chunk,
            kv_blocks=kv_blocks, spec_k=spec_k, prefix_cache=enabled,
        ),
        mesh=mesh,
    )


def test_interleaved_shared_prefix_streams_match_cold_and_generate():
    """The tentpole identity bar: ragged interleaved requests sharing a
    prefix — warm streams == cold streams == sequential generate, and
    the warm run actually hit (prefill chunks measurably dropped)."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    _, prompts, budgets = shared_prefix_workload(cfg)
    warm = serve_all(_engine(params, cfg, True), prompts, budgets)
    cold = serve_all(_engine(params, cfg, False), prompts, budgets)
    assert tokens_of(warm) == tokens_of(cold)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        want = np.asarray(generate(params, jnp.asarray(p)[None], cfg, m))[
            0, len(p):
        ]
        np.testing.assert_array_equal(want, tokens_of(warm)[i])
    assert warm.prefix_hits > 0
    assert warm.prefill_chunks < cold.prefill_chunks
    assert warm.prefill_chunks_saved == (
        cold.prefill_chunks - warm.prefill_chunks
    )


def test_warm_paged_cache_is_bitwise_the_cold_cache():
    """A hit sequence's gathered K/V must be bit-for-bit what its own
    cold prefill would have written — shared blocks included (prefill
    chunking is bitwise split-invariant, so starting the chunk loop
    mid-prompt cannot move a byte)."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    rs = np.random.RandomState(1)
    prefix = rs.randint(0, cfg.vocab, size=(8,)).astype(np.int32)
    tail = rs.randint(0, cfg.vocab, size=(5,)).astype(np.int32)
    prompt = np.concatenate([prefix, tail])
    n = 6

    def run(enabled):
        eng = _engine(params, cfg, enabled, slots=2)
        # seed the cache from slot 0 (a no-op when disabled)...
        adm = eng.admit(0, len(prefix) + 2, prompt=prefix)
        for c0 in range(adm.prefill_from, len(prefix), 4):
            eng.prefill_chunk(0, prefix[c0:c0 + 4], c0)
        eng.register_prefix(0, prefix)
        # ...then admit the measured prompt on slot 1
        adm = eng.admit(1, len(prompt) + n, prompt=prompt)
        last = None
        for c0 in range(adm.prefill_from, len(prompt), 4):
            last = eng.prefill_chunk(1, prompt[c0:c0 + 4], c0)
        got = [int(eng.activate(1, last, len(prompt), seed=0))]
        for _ in range(n - 1):
            got.append(int(np.asarray(eng.decode())[1]))
        caches = [
            (
                np.asarray(eng._gather(
                    eng.state["k"][i], eng.state["tables"][1:2]
                )[0]),
                np.asarray(eng._gather(
                    eng.state["v"][i], eng.state["tables"][1:2]
                )[0]),
            )
            for i in range(cfg.n_layers)
        ]
        return adm, got, caches

    warm_adm, warm_toks, warm = run(True)
    cold_adm, cold_toks, cold = run(False)
    assert warm_adm.cached_tokens == 8 and warm_adm.prefill_from == 8
    assert cold_adm.cached_tokens == 0
    assert warm_toks == cold_toks
    written = len(prompt) + n - 1  # the final sample is never cached
    for i, ((wk, wv), (ck, cv)) in enumerate(zip(warm, cold)):
        np.testing.assert_array_equal(
            wk[:, :written], ck[:, :written],
            err_msg=f"layer {i} K: warm gather != cold cache",
        )
        np.testing.assert_array_equal(
            wv[:, :written], cv[:, :written],
            err_msg=f"layer {i} V: warm gather != cold cache",
        )


def test_whole_prompt_hit_forces_cow_and_stays_bitwise():
    """A prompt whose EVERY block is cached still needs its last
    position's logits: the final matched block is copy-on-written, one
    1-token chunk re-derives the activation — streams bitwise cold's,
    and the SOURCE block's owner keeps decoding unperturbed."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    rs = np.random.RandomState(2)
    prompt = rs.randint(0, cfg.vocab, size=(8,)).astype(np.int32)  # 2 blocks

    def run(enabled):
        eng = _engine(params, cfg, enabled, slots=3)
        sched = Scheduler(eng)
        sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=6))
        sched.serve()
        # identical prompt while rid=0's blocks sit on the LRU; a third
        # rides CONCURRENTLY with the second (live sharing, refcount 2)
        sched.submit(Request(rid=1, prompt=prompt, max_new_tokens=8))
        sched.submit(Request(rid=2, prompt=prompt, max_new_tokens=8))
        sched.serve()
        return sched, eng

    warm, weng = run(True)
    cold, _ = run(False)
    assert tokens_of(warm) == tokens_of(cold)
    assert warm.cow_copies >= 1 and warm.prefix_hits >= 1
    assert weng.allocator.used_blocks == 0  # every reference returned
    # one 1-token chunk replaced the whole re-prefill for each hit
    assert warm.prefill_chunks < cold.prefill_chunks


def test_warm_matches_cold_under_speculation():
    """Prefix caching composes with the speculative verify tick: warm
    speculative streams == cold speculative streams == non-speculative
    greedy (drafts only ever write at pos >= prompt_len, so shared
    blocks are never touched)."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    rs = np.random.RandomState(3)
    motif = rs.randint(0, cfg.vocab, size=(4,))
    prefix = np.tile(motif, 2).astype(np.int32)  # drafting-friendly
    prompts = [
        np.concatenate([prefix, motif[:2]]).astype(np.int32)
        for _ in range(4)
    ]
    budgets = [6, 7, 5, 8]

    def run(enabled, spec_k):
        return serve_all(
            _engine(params, cfg, enabled, spec_k=spec_k), prompts, budgets
        )

    warm = run(True, 2)
    assert tokens_of(warm) == tokens_of(run(False, 2))
    assert tokens_of(warm) == tokens_of(run(False, 0))
    assert warm.prefix_hits > 0


def test_warm_matches_cold_on_tp_mesh():
    """Prefix caching under serving_kv_shardings: the COW block copy
    and shared-block gathers run on model-axis-sharded pools — every
    token equals the unsharded cold engine's."""
    from jax.sharding import Mesh

    from singa_tpu.models.transformer import lm_param_shardings

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    _, prompts, budgets = shared_prefix_workload(cfg, n=4, seed=5)
    cold = serve_all(_engine(params, cfg, False), prompts, budgets)
    mesh = Mesh(np.array(jax.devices()[:2]), ("model",))
    sh = lm_param_shardings(mesh, params)
    sharded = {k: jax.device_put(v, sh[k]) for k, v in params.items()}
    warm = serve_all(
        _engine(sharded, cfg, True, mesh=mesh), prompts, budgets
    )
    assert tokens_of(warm) == tokens_of(cold)
    assert warm.prefix_hits > 0


def test_drained_requests_resume_through_their_own_prefix():
    """A drain parks the handed-back requests' prefix blocks on the
    LRU; re-admission hits its OWN history — regeneration still equals
    sequential generate."""
    from singa_tpu.resilience.preemption import PreemptionHandler

    cfg = tiny_cfg()
    params = tiny_params(cfg)
    _, prompts, budgets = shared_prefix_workload(cfg, seed=7)
    eng = _engine(params, cfg, True)
    handler = PreemptionHandler()
    sched = Scheduler(eng, preemption=handler)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    for _ in range(5):
        sched.tick()
    handler.trigger("test preemption")
    acct = sched.serve()
    assert acct is not None and acct["handed_back"]
    assert eng.allocator.used_blocks == 0
    hits_at_drain = sched.prefix_hits
    handler._event.clear()
    assert sched.serve() is None
    assert sched.prefix_hits > hits_at_drain  # re-admission hit history
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        want = np.asarray(generate(params, jnp.asarray(p)[None], cfg, m))[
            0, len(p):
        ]
        np.testing.assert_array_equal(want, tokens_of(sched)[i])


def test_lru_eviction_keeps_small_pool_serving():
    """A pool far too small to cache every retired prompt — and
    DISTINCT prompts, so parked blocks are dead weight rather than
    future hits: allocation evicts LRU blocks lazily (backpressure
    semantics unchanged) and every stream still matches sequential
    generate."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    rs = np.random.RandomState(9)
    prompts = [
        rs.randint(0, cfg.vocab, size=(8,)).astype(np.int32)
        for _ in range(6)
    ]
    budgets = [int(rs.randint(4, 9)) for _ in range(6)]
    eng = _engine(params, cfg, True, slots=2, block_len=8, chunk=8,
                  kv_blocks=5)
    sched = serve_all(eng, prompts, budgets)
    assert eng.allocator.lru_evictions > 0  # cache pressure was real
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        want = np.asarray(generate(params, jnp.asarray(p)[None], cfg, m))[
            0, len(p):
        ]
        np.testing.assert_array_equal(want, tokens_of(sched)[i])


def test_hit_cow_and_reclaim_never_recompile():
    """The jit-cache contract extends to the cache: admission via
    prefix hit, the COW copy, and LRU reclaim/evict all reuse the SAME
    compiled programs — decode/prefill stay at one entry each, COW
    compiles exactly once."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prefix, prompts, budgets = shared_prefix_workload(cfg, n=8, seed=11)
    # block-aligned prefix repeats force COW (twice, so the second COW
    # must reuse the first's program); small pool forces evict/reclaim
    prompts += [prefix.copy(), prefix.copy()]
    budgets += [5, 6]
    eng = _engine(params, cfg, True, slots=3, kv_blocks=13)
    sched = serve_all(eng, prompts, budgets)
    assert sched.prefix_hits > 0 and sched.cow_copies >= 2
    assert eng._decode_jit._cache_size() == 1
    assert eng._prefill_jit._cache_size() == 1
    assert eng._cow_jit._cache_size() == 1


# ---------------------------------------------------------------------------
# satellites: telemetry, trace, lint, serve_bench
# ---------------------------------------------------------------------------


def test_prefix_lifecycle_events_ride_the_recorder(tmp_path):
    """prefix_hit / cow_copy / lru_evict / lru_reclaim land in the
    flight recorder and reconcile with the scheduler's own counters."""
    from singa_tpu.obs.recorder import FlightRecorder

    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prefix, prompts, budgets = shared_prefix_workload(cfg, n=6, seed=13)
    # a block-aligned repeat of the shared prefix: a whole-prompt hit,
    # forcing the COW path
    prompts.append(prefix.copy())
    budgets.append(5)
    rec = FlightRecorder(str(tmp_path / "events"), rank=0, run_id="t")
    eng = _engine(params, cfg, True, slots=3, kv_blocks=13)
    sched = serve_all(eng, prompts, budgets, recorder=rec)
    rec.flush()
    recs = [
        json.loads(l)
        for l in open(tmp_path / "events" / "rank_0.jsonl")
    ]
    kinds = [r["kind"] for r in recs]
    hits = [r for r in recs if r["kind"] == "prefix_hit"]
    assert len(hits) == sched.prefix_hits > 0
    assert sum(h["data"]["blocks_shared"] for h in hits) == (
        sched.blocks_shared
    )
    assert sum(h["data"]["chunks_saved"] for h in hits) == (
        sched.prefill_chunks_saved
    )
    assert kinds.count("cow_copy") == sched.cow_copies >= 1
    assert kinds.count("lru_evict") == eng.allocator.lru_evictions
    reclaimed = sum(
        r["data"]["blocks"] for r in recs if r["kind"] == "lru_reclaim"
    )
    assert reclaimed == eng.allocator.lru_reclaims > 0


def test_trace_summarize_prefix_columns(tmp_path):
    """Synthetic prefix events -> the serving summary grows
    prefix_hit_rate / blocks_shared / prefill_chunks_saved (+ cow/lru
    counts); a log without prefix events keeps hit rate None."""
    from singa_tpu.tools.trace import load_events, summarize

    events = tmp_path / "events"
    os.makedirs(events)
    base = {"ts": 1.0, "mono": 1.0, "rank": 0, "run": "r", "step": 0}
    recs = [
        {**base, "kind": "request_admit", "data": {"rid": 0}},
        {**base, "kind": "request_admit", "data": {"rid": 1}},
        {**base, "kind": "prefix_hit",
         "data": {"rid": 1, "cached_tokens": 16, "blocks_shared": 4,
                  "chunks_saved": 3}},
        {**base, "kind": "cow_copy", "data": {"rid": 1}},
        {**base, "kind": "lru_reclaim", "data": {"blocks": 2}},
        {**base, "kind": "lru_evict", "data": {"block": 5}},
        {**base, "kind": "retire", "data": {"rid": 0, "tokens": 5}},
    ]
    with open(events / "rank_0.jsonl", "w") as f:
        f.write("\n".join(json.dumps(r) for r in recs) + "\n")
    records, _ = load_events(str(tmp_path))
    s = summarize(records)["serving"]
    assert s["prefix_hit_rate"] == 0.5
    assert s["blocks_shared"] == 4
    assert s["prefill_chunks_saved"] == 3
    assert s["cow_copies"] == 1
    assert s["lru_reclaims"] == 2 and s["lru_evictions"] == 1

    plain = [{**base, "kind": "request_admit", "data": {"rid": 0}}]
    with open(events / "rank_0.jsonl", "w") as f:
        f.write("\n".join(json.dumps(r) for r in plain) + "\n")
    records, _ = load_events(str(tmp_path))
    s = summarize(records)["serving"]
    assert s["prefix_hit_rate"] is None and s["blocks_shared"] == 0


PREFIX_LINT_CONF = """
name: "prefix-lint"
train_steps: 1
updater {{ base_learning_rate: 0.05 }}
neuralnet {{
  layer {{ name: "data" type: "kSequenceData"
    data_param {{ path: "{shard}" batchsize: 8 }} }}
  layer {{ name: "embed" type: "kEmbedding" srclayers: "data"
    embedding_param {{ vocab_size: 64 embedding_dim: 32 max_len: 128 }}
    param {{ name: "tok" init_method: "kGaussian" std: 0.02 }}
    param {{ name: "pos" init_method: "kGaussian" std: 0.02 }} }}
  layer {{ name: "head" type: "kDense" srclayers: "embed"
    dense_param {{ num_output: 64 bias_term: false }}
    param {{ name: "weight" init_method: "kGaussian" std: 0.02 }} }}
  layer {{ name: "loss" type: "kLMLoss" srclayers: "head"
    srclayers: "data" }}
}}
serving {{ slots: 4 kv_block_len: 16 kv_blocks: 32
  prefix_cache {{ enabled: true lru: true }} }}
"""


@pytest.fixture()
def lint_conf(tmp_path):
    from singa_tpu.data.loader import synthetic_token_arrays, write_records

    shard = str(tmp_path / "tokens")
    write_records(shard, *synthetic_token_arrays(16, seq_len=16, vocab=64))
    return PREFIX_LINT_CONF.format(shard=shard)


def test_prefix_cache_conf_lint_did_you_mean(lint_conf):
    """netlint's schema walk covers the nested prefix_cache block:
    every knob typo'd gets CFG001 with a did-you-mean, and a typo'd
    block name points at prefix_cache (the PR 10 nested-block
    pattern)."""
    from singa_tpu.lint import Collector, lint_model_text

    col = Collector()
    lint_model_text(lint_conf, "job.conf", col)
    assert not any(
        d.code in ("CFG001", "SRV001") for d in col.sorted()
    ), [str(d) for d in col.sorted()]
    for typo, want in [
        ("enabled:", "enabled"),
        ("lru:", "lru"),
        ("prefix_cache {{", "prefix_cache"),
    ]:
        text = lint_conf.replace(
            typo.replace("{{", "{"),
            typo.replace("{{", "{")[:-2] + "x" + typo[-2:].replace(
                "{{", "{"
            ),
            1,
        )
        col = Collector()
        lint_model_text(text, "job.conf", col)
        assert any(
            d.code == "CFG001" and want in (d.fix_hint or "")
            for d in col.sorted()
        ), (typo, [str(d) for d in col.sorted()])


def test_srv001_admission_feasibility_lint(lint_conf):
    """SRV001: prefix_cache enabled with a pool that cannot admit one
    max-length prompt is a lint ERROR (kv_blocks < window/block_len +
    trash); a big-enough pool, dense-equivalent sizing (0), or a
    disabled cache stays clean."""
    from singa_tpu.lint import Collector, lint_model_text

    def codes(text):
        col = Collector()
        lint_model_text(text, "job.conf", col)
        return [d for d in col.sorted() if d.code == "SRV001"]

    bad = lint_conf.replace("kv_blocks: 32", "kv_blocks: 6")
    diags = codes(bad)
    assert len(diags) == 1 and "9" in diags[0].fix_hint, diags
    assert not codes(lint_conf)  # 32 >= 128/16 + 1
    assert not codes(bad.replace("kv_blocks: 6", "kv_blocks: 0"))
    assert not codes(bad.replace("enabled: true", "enabled: false"))


def test_serve_bench_shared_prefix_gate_smoke(capsys):
    """serve_bench --workload shared_prefix end to end at toy size:
    warm-vs-cold gate (the deterministic prefill-chunks arm must hold
    by construction), zero token mismatches, hits + COW recorded."""
    from singa_tpu.tools.serve_bench import main as sb_main

    rc = sb_main([
        "--d_model", "32", "--n_heads", "2", "--n_layers", "1",
        "--d_ff", "64", "--vocab", "32", "--max_len", "64",
        "--prompt_len", "24", "--max_new", "6", "--block_len", "4",
        "--prefill_chunk", "4", "--requests", "6", "--concurrency", "2",
        "--workload", "shared_prefix",
    ])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0, out
    assert out["pass"] and out["pass_mode"] is not None
    assert out["token_mismatches"] == 0
    assert out["prefix_hit_rate"] > 0
    assert out["prefill_chunk_ratio"] >= 2.0
    assert out["cow_copies"] >= 1
    assert out["prefill_chunks_cold"] > out["prefill_chunks_warm"]
