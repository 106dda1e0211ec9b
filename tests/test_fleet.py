"""Disaggregated serving fleet (singa_tpu/serve/fleet/): block
migration, the prefill/decode role split, the front-door router, and
the drain-to-peer path.

The three parity bars the subsystem stands on:

  - an imported sequence's subsequent token stream is BITWISE the
    stream the exporting host would have produced (migration copies
    pool bytes + lanes exactly; paged == dense is already bitwise);
  - fleet streams — routed, prefilled on one host, decoded on
    another — are IDENTICAL to a single unified host's (and to
    sequential ``generate``): routing and migration may never move a
    token;
  - a drained host's in-flight sequences resume on a PEER to full
    parity.
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.models.transformer import (
    TransformerConfig,
    generate,
    init_lm,
)
from singa_tpu.serve import Engine, EngineConfig, Request, Scheduler
from singa_tpu.serve.fleet import (
    FleetHost,
    LocalTransport,
    Mailbox,
    Router,
    fleet_topology,
    migrate,
    role_for_rank,
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


def mixed_workload(cfg, n=6, seed=0):
    rs = np.random.RandomState(seed)
    prompts = [
        rs.randint(0, cfg.vocab, size=(int(rs.randint(3, 9)),)).astype(
            np.int32
        )
        for _ in range(n)
    ]
    budgets = [int(rs.randint(4, 10)) for _ in range(n)]
    return prompts, budgets


def run_fleet_until_done(hosts, n_requests, max_rounds=2000):
    """Round-robin ticks until every request finished (messages sit in
    the transport for one round, so idleness only counts when
    consecutive)."""
    idle = 0
    for _ in range(max_rounds):
        for h in hosts:
            h.tick()
        done = sum(
            1 for h in hosts for r in h.sched.finished if r.rid >= 0
        )
        if done >= n_requests:
            return
        idle = idle + 1 if not any(h.busy for h in hosts) else 0
        assert idle < 5, "fleet stalled with requests unfinished"
    raise AssertionError("fleet did not finish in the round budget")


def fleet_streams(hosts):
    return {
        r.rid: list(r.tokens)
        for h in hosts
        for r in h.sched.finished
        if r.rid >= 0
    }


def single_host_streams(params, cfg, ec, prompts, budgets, **req_kw):
    eng = Engine(params, cfg, ec)
    sched = Scheduler(eng)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=i, prompt=p, max_new_tokens=m, **{
            k: (v[i] if isinstance(v, list) else v)
            for k, v in req_kw.items()
        }))
    sched.serve()
    return {r.rid: list(r.tokens) for r in sched.finished}


# ---------------------------------------------------------------------------
# block migration
# ---------------------------------------------------------------------------


class TestMigrate:
    def _filled_engine(self, params, cfg, prompt, budget, slot=1,
                       **ec_kw):
        ec = EngineConfig(slots=3, kv_block_len=8, max_prefill_chunk=4,
                          **ec_kw)
        eng = Engine(params, cfg, ec)
        eng.admit(slot, len(prompt) + budget, prompt=prompt)
        last = None
        for c0 in range(0, len(prompt), 4):
            last = eng.prefill_chunk(slot, prompt[c0:c0 + 4], c0)
        first = int(eng.activate(slot, last, len(prompt), seed=0))
        return eng, ec, [first]

    def test_migrated_continuation_bitwise(self):
        """The tentpole bar: export after a few decode ticks, import
        into a DIFFERENT slot of a fresh engine (with another sequence
        shifting its block ids), and the continuation is bit-for-bit
        what the exporter would have produced — and what generate()
        produces. The wire codec round-trips in between, so the bytes
        that move are the bytes that are proven."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        prompt = np.asarray([3, 1, 4, 1, 5, 9, 2], np.int32)
        n = 10
        ea, ec, toks = self._filled_engine(params, cfg, prompt, n)
        for _ in range(3):
            toks.append(int(np.asarray(ea.decode())[1]))
        req = Request(rid=7, prompt=prompt, max_new_tokens=n)
        req.tokens = list(toks)
        mseq = migrate.deserialize(
            migrate.serialize(migrate.export_sequence(ea, req, 1))
        )
        assert mseq.rid == 7 and mseq.n_blocks == 3
        # exporter-if-continued: the reference stream
        ref = list(toks)
        for _ in range(n - len(ref)):
            ref.append(int(np.asarray(ea.decode())[1]))
        eb = Engine(params, cfg, ec)
        eb.admit(0, 16)  # occupy: the import's block ids must differ
        migrate.import_sequence(eb, 2, mseq)
        got = list(mseq.emitted)
        for _ in range(n - len(got)):
            got.append(int(np.asarray(eb.decode())[2]))
        assert got == ref, "imported continuation diverged (not bitwise)"
        want = [
            int(t) for t in np.asarray(
                generate(params, jnp.asarray(prompt)[None], cfg, n)
            )[0, len(prompt):]
        ]
        assert got == want
        # the imported gathered cache equals the exporter's, bit for
        # bit, over every WRITTEN position (the final sample is never
        # cached; beyond it live trash-masked garbage that differs by
        # construction — the PR 9 mask contract)
        written = len(prompt) + n - 1
        for i in range(cfg.n_layers):
            np.testing.assert_array_equal(
                np.asarray(ea._gather(
                    ea.state["k"][i], ea.state["tables"][1:2]
                )[0])[:, :written],
                np.asarray(eb._gather(
                    eb.state["k"][i], eb.state["tables"][2:3]
                )[0])[:, :written],
                err_msg=f"layer {i} K diverged across migration",
            )
        # one compiled program per migration direction per engine
        assert ea._export_jit._cache_size() == 1
        assert eb._import_jit._cache_size() == 1

    def test_temperature_stream_rng_lane_migrates_bitwise(self):
        """A temperature slot's key schedule ships bit-for-bit: the
        imported stream samples exactly the tokens the exporter would
        have sampled."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        prompt = np.asarray([5, 3, 8], np.int32)
        ec = EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4)
        ea = Engine(params, cfg, ec)
        ea.admit(0, len(prompt) + 12)
        last = ea.prefill_chunk(0, prompt, 0)
        ea.activate(0, last, len(prompt), seed=9, temperature=0.8)
        for _ in range(4):
            ea.decode()
        req = Request(rid=0, prompt=prompt, max_new_tokens=12,
                      temperature=0.8, seed=9)
        mseq = migrate.deserialize(
            migrate.serialize(migrate.export_sequence(ea, req, 0))
        )
        ref = [int(np.asarray(ea.decode())[0]) for _ in range(5)]
        eb = Engine(params, cfg, ec)
        migrate.import_sequence(eb, 1, mseq)
        got = [int(np.asarray(eb.decode())[1]) for _ in range(5)]
        assert got == ref

    def test_cross_process_stamps_restamped(self, monkeypatch):
        """perf_counter origins are per-process: a same-process
        receiver keeps the queue-inclusive enqueue stamp (drills,
        bench), a cross-process receiver zeroes it so the scheduler
        re-stamps at arrival instead of mixing clock domains."""
        from singa_tpu.serve.fleet.router import (
            decode_request,
            encode_request,
        )

        req = Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                      max_new_tokens=2)
        req.enqueue_mono = 123.5
        wire = encode_request(req)
        payload = {
            "k": np.zeros((1, 1, 2, 8, 4), np.float32),
            "v": np.zeros((1, 1, 2, 8, 4), np.float32),
            "rng": np.zeros((2,), np.uint32),
            "token": 1, "pos": 3, "temp": 0.0, "chain": [],
        }
        mwire = migrate.serialize(migrate.MigratedSequence(
            rid=1, prompt=np.arange(3, dtype=np.int32), emitted=[1],
            max_new_tokens=4, temperature=0.0, seed=0, eos=None,
            payload=payload, enqueue_mono=9.25,
        ))
        assert decode_request(wire).enqueue_mono == 123.5
        assert migrate.deserialize(mwire).enqueue_mono == 9.25
        monkeypatch.setattr(os, "getpid", lambda: -1)
        assert decode_request(wire).enqueue_mono == 0.0
        assert migrate.deserialize(mwire).enqueue_mono == 0.0

    def test_wire_format_rejects_foreign(self):
        import io

        buf = io.BytesIO()
        np.savez(buf, meta=np.frombuffer(
            json.dumps({"format": "not-a-migration"}).encode(),
            dtype=np.uint8,
        ))
        with pytest.raises(ValueError, match="format"):
            migrate.deserialize(buf.getvalue())

    def test_import_backpressure_is_a_true_noop(self):
        """An import the pool cannot cover raises PoolExhausted with
        allocator state untouched — the fleet host retries next tick."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        prompt = np.asarray([1, 2, 3, 4, 5], np.int32)
        ea, ec, _ = self._filled_engine(params, cfg, prompt, 20)
        req = Request(rid=0, prompt=prompt, max_new_tokens=20)
        mseq = migrate.export_sequence(ea, req, 1)
        eb = Engine(params, cfg, EngineConfig(
            slots=3, kv_block_len=8, kv_blocks=5, max_prefill_chunk=4,
        ))
        eb.admit(0, 16)  # 2 of 4 usable blocks gone; the import needs 4
        free_before = eb.allocator.free_blocks
        with pytest.raises(PoolExhausted):
            migrate.import_sequence(eb, 1, mseq)
        assert eb.allocator.free_blocks == free_before
        assert not np.asarray(eb.state["live"])[1]


# ---------------------------------------------------------------------------
# the role split
# ---------------------------------------------------------------------------


def build_2host(params, cfg, ec, transport=None):
    t = transport or LocalTransport()
    pre = FleetHost("p0", "prefill", Engine(params, cfg, ec), t,
                    peers={"d0": "decode"})
    dec = FleetHost("d0", "decode", Engine(params, cfg, ec), t,
                    peers={"p0": "prefill"})
    return [pre, dec], t


class TestFleet:
    def test_streams_identical_and_roles_proven(self):
        """2-host prefill/decode fleet vs ONE unified host on ragged
        interleaved prompts: every stream identical, the decode host
        executed ZERO prefill chunks, the prefill host ran ZERO decode
        ticks, and each host's jit cache holds one program per shape
        (migration included)."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        prompts, budgets = mixed_workload(cfg)
        ec = EngineConfig(slots=3, kv_block_len=8, max_prefill_chunk=4)
        base = single_host_streams(params, cfg, ec, prompts, budgets)
        hosts, t = build_2host(params, cfg, ec)
        router = Router(t)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            router.submit(Request(rid=i, prompt=p, max_new_tokens=m))
        run_fleet_until_done(hosts, len(prompts))
        assert fleet_streams(hosts) == base
        pre, dec = hosts
        assert dec.sched.prefill_chunks == 0, "role split violated"
        assert pre.sched.decode_ticks == 0, "role split violated"
        assert dec.migrate_in == len(prompts)
        assert pre.migrate_out == len(prompts)
        for h in hosts:
            eng = h.engine
            assert eng._decode_jit._cache_size() <= 1
            assert eng._prefill_jit._cache_size() <= 1
            assert eng._export_jit._cache_size() <= 1
            assert eng._import_jit._cache_size() <= 1
        # blocks freed everywhere once streams retire
        assert all(h.engine.allocator.used_blocks == 0 for h in hosts)

    def test_mixed_temperature_lanes_survive_migration(self):
        """Greedy and temperature requests side by side: the fleet's
        streams (RNG lanes migrated mid-stream) equal the unified
        host's."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        prompts, budgets = mixed_workload(cfg, n=4, seed=3)
        temps = [0.0, 0.7, 0.0, 1.1]
        ec = EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4)
        base = single_host_streams(
            params, cfg, ec, prompts, budgets,
            temperature=temps, seed=[11 + i for i in range(4)],
        )
        hosts, t = build_2host(params, cfg, ec)
        router = Router(t)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            router.submit(Request(
                rid=i, prompt=p, max_new_tokens=m,
                temperature=temps[i], seed=11 + i,
            ))
        run_fleet_until_done(hosts, len(prompts))
        assert fleet_streams(hosts) == base

    def test_inadmissible_wire_request_rejected_not_fatal(self):
        """A routed request whose prompt + budget exceeds max_len must
        not take the host down (single-host submit raises to ITS
        caller; over the wire the caller is a peer): the host rejects
        it back to the front door with an error result and keeps
        serving everything else."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        prompts, budgets = mixed_workload(cfg, n=3, seed=4)
        ec = EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4)
        base = single_host_streams(params, cfg, ec, prompts, budgets)
        t = LocalTransport()
        t.register("frontdoor")
        pre = FleetHost("p0", "prefill", Engine(params, cfg, ec), t,
                        peers={"d0": "decode"}, results_to="frontdoor")
        dec = FleetHost("d0", "decode", Engine(params, cfg, ec), t,
                        peers={"p0": "prefill"}, results_to="frontdoor")
        router = Router(t)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            router.submit(Request(rid=i, prompt=p, max_new_tokens=m))
        router.submit(Request(
            rid=99, prompt=np.zeros((4,), np.int32),
            max_new_tokens=cfg.max_len,
        ))
        run_fleet_until_done([pre, dec], len(prompts))
        assert fleet_streams([pre, dec]) == base
        results = {}
        for msg in t.recv("frontdoor"):
            d = json.loads(msg.payload.decode())
            results[d["rid"]] = d
        assert "exceeds max_len" in results[99]["error"]
        assert results[99]["tokens"] == []

    def test_drain_grace_sweep_reroutes_in_flight_migrate(self):
        """A migrate message that lands in the draining host's inbox
        AFTER drain's first recv (a cross-process peer read our
        pre-tombstone status and sent — the message is the ONLY copy
        of that sequence) must be re-forwarded raw to a capable peer
        by the grace sweep, and the stream must still finish to
        parity."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        prompts, budgets = mixed_workload(cfg, n=2, seed=7)
        ec = EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4)
        base = single_host_streams(params, cfg, ec, prompts, budgets)

        class InFlight(LocalTransport):
            """Delivers a prepared message to d0 the moment d0's
            tombstone publishes — the tightest version of the race."""

            armed: list = []

            def publish(self, name, status):
                super().publish(name, status)
                if name == "d0" and status.get("role") == "drained":
                    while self.armed:
                        self._inbox["d0"].append(self.armed.pop())

        t = InFlight()
        topo = [("p0", "prefill"), ("d0", "decode"), ("d1", "decode")]
        hosts = [
            FleetHost(n, r, Engine(params, cfg, ec), t,
                      peers={m: s for m, s in topo if m != n})
            for n, r in topo
        ]
        p0, d0, d1 = hosts
        router = Router(t)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            router.submit(Request(rid=i, prompt=p, max_new_tokens=m))
        # tick ONLY the prefill host: both exports land in the decode
        # inboxes and stay unread — the in-flight state
        for _ in range(50):
            p0.tick()
            if p0.migrate_out == 2:
                break
        stolen = [
            m for box in (t._inbox["d0"], t._inbox["d1"])
            for m in box if m.kind == "migrate"
        ]
        for box in (t._inbox["d0"], t._inbox["d1"]):
            while box:
                box.pop()
        assert stolen, "no exported migrate in flight to steal"
        stolen_rids = {migrate.deserialize(m.payload).rid for m in stolen}
        InFlight.armed = stolen
        acct = d0.drain("test", grace_s=0.05)
        assert {m["rid"] for m in acct["migrated"]} == stolen_rids, acct
        assert all(m["dst"] == "d1" for m in acct["migrated"]), acct
        # the rerouted sequences finish on d1 to full parity
        run_fleet_until_done([p0, d1], len(prompts))
        assert fleet_streams([p0, d1]) == base

    def test_drain_to_peer_resumes_to_full_parity(self):
        """1 prefill + 2 decode hosts; one decode host's preemption
        plane fires mid-run: its decoding sequences MIGRATE to the
        surviving decode host, pending work re-enters through the
        prefill host, and every stream still equals the unified
        host's — the drained host's slots resumed on a peer."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        prompts, budgets = mixed_workload(cfg, n=8, seed=5)
        ec = EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4)
        base = single_host_streams(params, cfg, ec, prompts, budgets)
        t = LocalTransport()
        topo = [("p0", "prefill"), ("d0", "decode"), ("d1", "decode")]
        hosts = [
            FleetHost(n, r, Engine(params, cfg, ec), t,
                      peers={m: s for m, s in topo if m != n})
            for n, r in topo
        ]
        router = Router(t)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            router.submit(Request(rid=i, prompt=p, max_new_tokens=m))
        for _ in range(6):
            for h in hosts:
                h.tick()
        victim = hosts[1]
        # decode runs one pass ahead of the host: the drain finds one in
        # flight, reads it, and exports lanes that agree with the tokens
        assert victim.sched._in_flight is not None
        acct = victim.drain("test preemption")
        assert victim.sched._in_flight is None
        assert acct["migrated"] or acct["forwarded"], \
            "nothing was in flight on the drained host?"
        assert all(
            m["dst"] == "d1" for m in acct["migrated"]
        ), "decoding sequences must migrate to the surviving decode peer"
        assert victim.engine.allocator.used_blocks == 0
        alive = [hosts[0], hosts[2]]
        idle = 0
        for _ in range(2000):
            for h in alive:
                h.tick()
            done = len(fleet_streams(hosts))
            if done >= len(prompts):
                break
            idle = idle + 1 if not any(h.busy for h in alive) else 0
            assert idle < 5, "fleet stalled after the drain"
        assert fleet_streams(hosts) == base

    def test_latent_peer_gets_no_placements_until_join(self):
        """Elastic fleet: a declared-but-unlaunched (latent) decode
        peer must receive ZERO exports — a sequence shipped to a host
        that may never start would be stranded. Every stream runs
        through the live decode host."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        prompts, budgets = mixed_workload(cfg, n=4, seed=11)
        ec = EngineConfig(slots=3, kv_block_len=8, max_prefill_chunk=4)
        base = single_host_streams(params, cfg, ec, prompts, budgets)
        t = LocalTransport()
        peers_of = {
            "p0": {"d0": "decode", "d1": "decode"},
            "d0": {"p0": "prefill", "d1": "decode"},
        }
        p0 = FleetHost("p0", "prefill", Engine(params, cfg, ec), t,
                       peers=peers_of["p0"], latent={"d1"})
        d0 = FleetHost("d0", "decode", Engine(params, cfg, ec), t,
                       peers=peers_of["d0"], latent={"d1"})
        router = Router(t)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            router.submit(Request(rid=i, prompt=p, max_new_tokens=m))
        run_fleet_until_done([p0, d0], len(prompts))
        assert fleet_streams([p0, d0]) == base
        assert d0.migrate_in == len(prompts)  # all of it landed here
        assert p0._latent == {"d1"}  # never published, still latent

    def test_fleet_join_and_leave_streams_identical(self):
        """The elastic scale drill: a latent decode host JOINS mid-run
        (its status publish is the announce — peers log fleet_join and
        start placing onto it), then the ORIGINAL decode host LEAVES
        via drain-to-peer (tombstone -> fleet_leave, its mid-stream
        sequences migrate to the joiner) — and every token stream
        equals the fixed-topology single-host run throughout."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        prompts, budgets = mixed_workload(cfg, n=9, seed=13)
        ec = EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4)
        base = single_host_streams(params, cfg, ec, prompts, budgets)
        t = LocalTransport()
        topo = [("p0", "prefill"), ("d0", "decode"), ("d1", "decode")]

        def mk(name, role, latent):
            return FleetHost(
                name, role, Engine(params, cfg, ec), t,
                peers={m: r for m, r in topo if m != name},
                latent=latent - {name},
            )

        p0 = mk("p0", "prefill", {"d1"})
        d0 = mk("d0", "decode", {"d1"})
        router = Router(t)
        # phase 1: min_hosts fleet serves the first third
        for i in range(3):
            router.submit(Request(
                rid=i, prompt=prompts[i], max_new_tokens=budgets[i],
            ))
        run_fleet_until_done([p0, d0], 3)
        assert d0.migrate_in == 3 and p0._latent == {"d1"}
        # phase 2: d1 JOINS (construction registers + publishes its
        # serving status — the announce) and starts taking placements
        d1 = mk("d1", "decode", set())
        for i in range(3, 6):
            router.submit(Request(
                rid=i, prompt=prompts[i], max_new_tokens=budgets[i],
            ))
        run_fleet_until_done([p0, d0, d1], 6)
        assert p0._latent == set(), "join not observed by the prefill host"
        assert d1.migrate_in >= 1, (
            "the joined decode host took no placements"
        )
        # phase 3: scale DOWN — d0 drains mid-stream; its decoding
        # sequences migrate to the joiner, and peers re-latent it
        for i in range(6, 9):
            router.submit(Request(
                rid=i, prompt=prompts[i], max_new_tokens=budgets[i],
            ))
        for _ in range(4):
            for h in (p0, d0, d1):
                h.tick()
        acct = d0.drain("scale-down")
        assert all(m["dst"] == "d1" for m in acct["migrated"]), acct
        alive = [p0, d1]
        idle = 0
        for _ in range(2000):
            for h in alive:
                h.tick()
            if len(fleet_streams([p0, d0, d1])) >= len(prompts):
                break
            idle = idle + 1 if not any(h.busy for h in alive) else 0
            assert idle < 5, "fleet stalled after the scale-down"
        assert fleet_streams([p0, d0, d1]) == base
        # the next placement decision observes the tombstone: d0 is
        # latent again (a future status publish is a fresh join) and
        # never a candidate
        assert p0._pick_peer(("decode", "unified")) == "d1"
        assert "d0" in p0._latent, (
            "the drained host must be latent again (a future status "
            "publish is a fresh join)"
        )

    def test_decode_only_fleet_rejected(self):
        """The runtime arm netlint FLT001 mirrors: a split-role host
        with no peer for the other half refuses to construct — and a
        peer that is merely DECLARED (latent, may never launch) does
        not count as the other half."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        ec = EngineConfig(slots=2, kv_block_len=8)
        t = LocalTransport()
        with pytest.raises(ValueError, match="no prefill-capable peer"):
            FleetHost("d0", "decode", Engine(params, cfg, ec), t,
                      peers={"d1": "decode"})
        with pytest.raises(ValueError, match="no decode-capable peer"):
            FleetHost("p0", "prefill", Engine(params, cfg, ec), t,
                      peers={"d0": "decode"}, latent={"d0"})
        with pytest.raises(ValueError, match="no decode-capable peer"):
            FleetHost("p0", "prefill", Engine(params, cfg, ec), t,
                      peers={})

    def test_prefix_cache_reuse_crosses_hosts(self):
        """Imported registered blocks serve prefix hits: after a
        migrated sequence lands, admitting the SAME prompt on the
        importer shares its blocks (zero re-prefill of the covered
        prefix) and the warm stream is bitwise the cold one. A second
        import of the same prompt SHARES the already-registered blocks
        instead of re-writing them."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        # 16-token prompt = 2 FULL blocks at block_len 8
        prompt = np.arange(16, dtype=np.int32) % cfg.vocab
        n = 8
        ec = EngineConfig(slots=3, kv_block_len=8, max_prefill_chunk=8,
                          prefix_cache=True)
        ea = Engine(params, cfg, ec)
        ea.admit(0, len(prompt) + n, prompt=prompt)
        last = None
        for c0 in range(0, len(prompt), 8):
            last = ea.prefill_chunk(0, prompt[c0:c0 + 8], c0)
        ea.register_prefix(0, prompt)
        first = int(ea.activate(0, last, len(prompt), seed=0))
        req = Request(rid=0, prompt=prompt, max_new_tokens=n)
        req.tokens = [first]
        mseq = migrate.deserialize(
            migrate.serialize(migrate.export_sequence(ea, req, 0))
        )
        assert len(mseq.payload["chain"]) == 2
        eb = Engine(params, cfg, ec)
        info = migrate.import_sequence(eb, 0, mseq)
        assert info["registered"] == 2 and info["shared"] == 0
        # retire the imported stream: its registered blocks park on
        # the LRU, warm for the admissions below (the scheduler owns
        # the slots from here)
        eb.retire(0)
        # cold oracle for the same prompt (fresh uncached engine)
        cold = single_host_streams(
            params, cfg,
            EngineConfig(slots=3, kv_block_len=8, max_prefill_chunk=8),
            [prompt], [n],
        )[0]
        # admission on the importer now HITS the imported blocks
        sched = Scheduler(eb)
        sched.submit(Request(rid=1, prompt=prompt, max_new_tokens=n))
        sched.serve()
        assert sched.prefix_hits == 1 and sched.blocks_shared >= 1
        (warm,) = (r.tokens for r in sched.finished)
        assert list(warm) == cold
        # a second import of the same prompt shares, not re-scatters
        e2, req2 = self_export_engine(params, cfg, ec, prompt, n)
        info2 = migrate.import_sequence(
            eb, 2,
            migrate.deserialize(migrate.serialize(
                migrate.export_sequence(e2, req2, 0)
            )),
        )
        assert info2["shared"] == 2 and info2["registered"] == 0

    def test_speculation_composes_with_migration(self):
        """A migrated sequence keeps speculating: the decode host runs
        verify ticks (spec_k > 0), accepts drafted tokens AFTER the
        migration, and streams equal the unified host's one-token
        run."""
        cfg = tiny_cfg(max_len=64)
        params = tiny_params(cfg)
        # repeat workload: the n-gram drafter's home turf
        motif = np.asarray([7, 3, 9, 1], np.int32)
        prompts = [np.tile(motif, 3) for _ in range(4)]
        budgets = [16] * 4
        ec_plain = EngineConfig(slots=2, kv_block_len=8,
                                max_prefill_chunk=4)
        base = single_host_streams(
            params, cfg, ec_plain, prompts, budgets,
        )
        ec_spec = EngineConfig(slots=2, kv_block_len=8,
                               max_prefill_chunk=4, spec_k=3)
        hosts, t = build_2host(params, cfg, ec_spec)
        router = Router(t)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            router.submit(Request(rid=i, prompt=p, max_new_tokens=m))
        run_fleet_until_done(hosts, len(prompts))
        assert fleet_streams(hosts) == base
        dec = hosts[1]
        assert dec.sched.spec_accepted > 0, \
            "no drafts accepted post-migration"
        assert dec.engine._verify_jit._cache_size() <= 1

    @pytest.mark.slow
    def test_fused_kernels_compose_with_fleet(self):
        """kernels { paged_attention: fused } on every fleet host:
        streams still identical to the unified REFERENCE host (the
        fused-vs-reference stream bar riding the fleet bar)."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        prompts, budgets = mixed_workload(cfg, n=4, seed=9)
        ec_ref = EngineConfig(slots=2, kv_block_len=8,
                              max_prefill_chunk=4)
        base = single_host_streams(params, cfg, ec_ref, prompts, budgets)
        ec_fused = EngineConfig(slots=2, kv_block_len=8,
                                max_prefill_chunk=4,
                                attend_impl="fused", interpret=True)
        hosts, t = build_2host(params, cfg, ec_fused)
        router = Router(t)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            router.submit(Request(rid=i, prompt=p, max_new_tokens=m))
        run_fleet_until_done(hosts, len(prompts))
        assert fleet_streams(hosts) == base


def self_export_engine(params, cfg, ec, prompt, n):
    """A throwaway exporter holding ``prompt`` fully prefilled and
    activated in slot 0. -> (engine, request)."""
    e = Engine(params, cfg, ec)
    e.admit(0, len(prompt) + n, prompt=prompt)
    last = None
    c = ec.max_prefill_chunk
    for c0 in range(0, len(prompt), c):
        last = e.prefill_chunk(0, prompt[c0:c0 + c], c0)
    e.register_prefix(0, prompt)
    first = int(e.activate(0, last, len(prompt), seed=0))
    req = Request(rid=99, prompt=prompt, max_new_tokens=n)
    req.tokens = [first]
    return e, req


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------


class TestRouter:
    def test_least_loaded_placement(self):
        t = LocalTransport()
        t.publish("a", {"host": "a", "role": "prefill",
                        "free_slots": 1, "kv_blocks_free": 4,
                        "queue_depth": 3})
        t.publish("b", {"host": "b", "role": "prefill",
                        "free_slots": 2, "kv_blocks_free": 8,
                        "queue_depth": 0})
        t.publish("c", {"host": "c", "role": "decode",
                        "free_slots": 8, "kv_blocks_free": 99,
                        "queue_depth": 0})
        r = Router(t)
        # b: shallowest queue among prefill-capable (c is decode-only)
        assert r.route(np.asarray([1, 2, 3], np.int32)) == "b"

    def test_boot_raises_until_status_appears(self):
        r = Router(LocalTransport())
        with pytest.raises(LookupError):
            r.route(np.asarray([1], np.int32))

    def test_prefix_affinity_routes_to_block_holder(self):
        """A prompt whose cached block-prefix lives on host H routes to
        H even when H is more loaded; an unknown prompt falls back to
        least-loaded."""
        from singa_tpu.serve.kv_pool import PrefixCache

        block_len = 4
        chain = PrefixCache(block_len).chain(
            np.arange(8, dtype=np.int32)
        )
        t = LocalTransport()
        t.publish("warm", {"host": "warm", "role": "prefill",
                           "free_slots": 1, "kv_blocks_free": 2,
                           "queue_depth": 2,
                           "cached_digests": [d.hex() for d in chain]})
        t.publish("idle", {"host": "idle", "role": "prefill",
                           "free_slots": 8, "kv_blocks_free": 64,
                           "queue_depth": 0, "cached_digests": []})
        r = Router(t, block_len=block_len)
        affine = np.concatenate(
            [np.arange(8, dtype=np.int32),
             np.asarray([30, 31], np.int32)]
        )
        assert r.route(affine, rid=0) == "warm"
        assert r.affinity_hits == 1
        other = np.asarray([9, 9, 9, 9, 9], np.int32)
        assert r.route(other, rid=1) == "idle"
        assert r.routed == 2

    def test_route_events_recorded(self, tmp_path):
        from singa_tpu.obs.recorder import FlightRecorder

        rec = FlightRecorder(str(tmp_path / "events"), rank=9,
                             run_id="t")
        t = LocalTransport()
        t.register("a")
        t.publish("a", {"host": "a", "role": "unified",
                        "free_slots": 1, "kv_blocks_free": 1,
                        "queue_depth": 0})
        r = Router(t, recorder=rec)
        r.submit(Request(rid=5, prompt=np.asarray([1, 2], np.int32),
                         max_new_tokens=4))
        rec.flush()
        recs = [
            json.loads(l)
            for l in open(tmp_path / "events" / "rank_9.jsonl")
        ]
        route = next(x for x in recs if x["kind"] == "route")
        assert route["data"]["rid"] == 5
        assert route["data"]["host"] == "a"
        # the request actually landed as a message
        (msg,) = t.recv("a")
        assert msg.kind == "request"


# ---------------------------------------------------------------------------
# transports
# ---------------------------------------------------------------------------


class TestMailbox:
    def test_roundtrip_order_and_status(self, tmp_path):
        mb = Mailbox(str(tmp_path))
        mb.register("h")
        for i in range(5):
            mb.send("h", "request", f"m{i}".encode(), src="r")
        got = mb.recv("h")
        assert [m.payload for m in got] == [f"m{i}".encode()
                                            for i in range(5)]
        assert all(m.kind == "request" and m.src == "r" for m in got)
        assert mb.recv("h") == []  # read-and-delete
        mb.publish("h", {"host": "h", "role": "decode", "free_slots": 2})
        mb.publish("h", {"host": "h", "role": "decode", "free_slots": 1})
        assert mb.statuses()["h"]["free_slots"] == 1  # latest wins
        with pytest.raises(ValueError, match="kind"):
            mb.send("h", "bogus", b"", src="r")

    def test_torn_and_foreign_files_skipped(self, tmp_path):
        mb = Mailbox(str(tmp_path))
        mb.register("h")
        inbox = tmp_path / "h" / "inbox"
        (inbox / "zzz_foreign.msg").write_bytes(b"not json\npayload")
        mb.send("h", "shutdown", b"", src="r")
        got = mb.recv("h")
        assert len(got) == 1 and got[0].kind == "shutdown"
        # the foreign file is left in place, not deleted or fatal
        assert (inbox / "zzz_foreign.msg").exists()

    def test_fleet_runs_over_mailbox_in_process(self, tmp_path):
        """The SAME fleet wired over the filesystem transport (the
        OS-process wiring) produces the same streams — the transport
        is interchangeable by construction."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        prompts, budgets = mixed_workload(cfg, n=4, seed=2)
        ec = EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4)
        base = single_host_streams(params, cfg, ec, prompts, budgets)
        hosts, _ = build_2host(params, cfg, ec,
                               transport=Mailbox(str(tmp_path)))
        router = Router(Mailbox(str(tmp_path)))
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            router.submit(Request(rid=i, prompt=p, max_new_tokens=m))
        run_fleet_until_done(hosts, len(prompts))
        assert fleet_streams(hosts) == base


# ---------------------------------------------------------------------------
# conf block, role-by-rank, lint
# ---------------------------------------------------------------------------


FLEET_CONF = """
name: "fleet-test"
neuralnet {
  layer { name: "embed" type: "kEmbedding"
    embedding_param { vocab_size: 32 embedding_dim: 32 max_len: 32 } }
  layer { name: "attn" type: "kAttention" srclayers: "embed"
    attention_param { num_heads: 2 } }
}
serving { slots: 2 kv_block_len: 8 max_prefill_chunk: 4 }
fleet { role: "auto" prefill_hosts: 1 }
"""


class TestFleetConf:
    def test_role_for_rank_and_topology(self):
        from singa_tpu.config import parse_model_config

        cfg = parse_model_config(FLEET_CONF)
        fleet = cfg.fleet
        assert role_for_rank(fleet, 0) == "prefill"
        assert role_for_rank(fleet, 1) == "decode"
        assert fleet_topology(fleet, 3) == [
            ("host0", "prefill"), ("host1", "decode"),
            ("host2", "decode"),
        ]
        explicit = parse_model_config(FLEET_CONF.replace(
            'fleet { role: "auto" prefill_hosts: 1 }',
            'fleet { peers { name: "pf" role: "prefill" }\n'
            '        peers { name: "dc" role: "decode" } }',
        ))
        assert fleet_topology(explicit.fleet, 99) == [
            ("pf", "prefill"), ("dc", "decode"),
        ]

    def test_fleet_conf_lint_did_you_mean(self):
        from singa_tpu.lint import Collector, lint_model_text

        col = Collector()
        lint_model_text(FLEET_CONF, "job.conf", col)
        assert not any(
            d.code in ("CFG001", "CFG002") for d in col.sorted()
        ), [str(d) for d in col.sorted()]
        for typo, want, code in [
            ("role:", "role", "CFG001"),
            ("prefill_hosts:", "prefill_hosts", "CFG001"),
            ("fleet {", "fleet", "CFG001"),
        ]:
            text = FLEET_CONF.replace(typo, typo[:-2] + "x" + typo[-2:], 1)
            col = Collector()
            lint_model_text(text, "job.conf", col)
            assert any(
                d.code == code and want in (d.fix_hint or "")
                for d in col.sorted()
            ), (typo, [str(d) for d in col.sorted()])
        # enum value typo: CFG002 with did-you-mean
        col = Collector()
        lint_model_text(
            FLEET_CONF.replace('"auto"', '"decoed"'), "job.conf", col,
        )
        assert any(
            d.code == "CFG002" and "decode" in (d.fix_hint or "")
            for d in col.sorted()
        ), [str(d) for d in col.sorted()]
        # the elastic sizing knobs are schema-covered too
        for typo, want in (
            ("min_host: 1", "min_hosts"),
            ("max_hots: 3", "max_hosts"),
        ):
            col = Collector()
            lint_model_text(
                FLEET_CONF.replace(
                    'fleet { role: "auto"',
                    'fleet { ' + typo + ' role: "auto"',
                ),
                "job.conf", col,
            )
            assert any(
                d.code == "CFG001" and want in (d.fix_hint or "")
                for d in col.sorted()
            ), (typo, [str(d) for d in col.sorted()])

    def test_flt001_elastic_sizing(self):
        """FLT001's sizing arm: min_hosts above the declared topology
        (peers/max_hosts) can never launch; consistent sizing stays
        silent."""
        from singa_tpu.lint import Collector, lint_model_text

        def flt(block):
            col = Collector()
            lint_model_text(
                FLEET_CONF.replace(
                    'fleet { role: "auto" prefill_hosts: 1 }', block,
                ),
                "job.conf", col,
            )
            return [d for d in col.sorted() if d.code == "FLT001"]

        got = flt(
            'fleet { role: "auto" min_hosts: 5 max_hosts: 3 }'
        )
        assert len(got) == 1 and "min_hosts 5" in got[0].msg, got
        assert not flt(
            'fleet { role: "auto" min_hosts: 2 max_hosts: 3 }'
        )
        # without a declared bound the host count is a runtime fact
        assert not flt('fleet { role: "auto" min_hosts: 2 }')
        # explicit peers ARE the topology: max_hosts cannot invent
        # hosts beyond them, and min_hosts is measured against the
        # peers count (NOT a phantom max_hosts)
        peers2 = (
            'peers { name: "p" role: "prefill" }\n'
            'peers { name: "d" role: "decode" }'
        )
        got = flt(f'fleet {{ {peers2} max_hosts: 4 min_hosts: 3 }}')
        msgs = " | ".join(d.msg for d in got)
        assert "max_hosts 4 exceeds" in msgs, got
        assert "min_hosts 3 exceeds" in msgs, got
        # (d) a live prefix covering only one half: the decode half is
        # entirely latent, so the fleet would launch but never stream
        got = flt(f'fleet {{ {peers2} min_hosts: 1 }}')
        assert len(got) == 1 and "live prefix" in got[0].msg, got
        assert not flt(f'fleet {{ {peers2} min_hosts: 2 }}')
        # a unified live prefix is self-sufficient at any min_hosts
        assert not flt(
            'fleet { peers { name: "u" role: "unified" }\n'
            '        peers { name: "d" role: "decode" } min_hosts: 1 }'
        )
        # the auto rank-split live prefix is statically decidable too
        got = flt(
            'fleet { role: "auto" prefill_hosts: 1 min_hosts: 1 '
            'max_hosts: 3 }'
        )
        assert len(got) == 1 and "prefill-only" in got[0].msg, got
        assert not flt(
            'fleet { role: "auto" prefill_hosts: 1 min_hosts: 2 '
            'max_hosts: 3 }'
        )
        # the runtime mirror: run_from_conf rejects the same conf
        from singa_tpu.config import parse_model_config
        from singa_tpu.serve.fleet.host import run_from_conf

        bad = parse_model_config(FLEET_CONF.replace(
            'fleet { role: "auto" prefill_hosts: 1 }',
            f'fleet {{ {peers2} max_hosts: 4 }}',
        ))
        with pytest.raises(ValueError, match="cannot invent hosts"):
            run_from_conf(bad, None, procs_id=0)
        # and in the auto form, max_hosts is a CAP: a cluster conf
        # declaring more workers than it rejects instead of silently
        # synthesizing joinable hosts beyond the declared maximum
        from singa_tpu.config.schema import ClusterConfig

        capped = parse_model_config(FLEET_CONF.replace(
            'fleet { role: "auto" prefill_hosts: 1 }',
            'fleet { role: "auto" prefill_hosts: 1 max_hosts: 2 }',
        ))
        cl = ClusterConfig(nworkers=4, workspace="ws")
        with pytest.raises(ValueError, match="cannot exceed"):
            run_from_conf(capped, cl, procs_id=0)

    def test_flt001_prefill_pool_too_small(self):
        from singa_tpu.lint import Collector, lint_model_text

        text = FLEET_CONF.replace(
            "serving { slots: 2 kv_block_len: 8 max_prefill_chunk: 4 }",
            "serving { slots: 2 kv_block_len: 8 kv_blocks: 3 "
            "max_prefill_chunk: 4 }",
        )
        col = Collector()
        lint_model_text(text, "job.conf", col)
        flt = [d for d in col.sorted() if d.code == "FLT001"]
        assert len(flt) == 1 and "kv_blocks 3 < 5" in flt[0].msg
        # dense-equivalent sizing never fires
        col = Collector()
        lint_model_text(FLEET_CONF, "job.conf", col)
        assert not any(d.code == "FLT001" for d in col.sorted())

    def test_flt001_split_role_missing_other_half(self):
        """FLT001's topology arm mirrors FleetHost's construction
        rejections exactly: explicit peers ARE the topology (role is
        the no-peers dispatch), so an all-decode or all-prefill peer
        list fires, as does a peerless explicit single role; a
        complete split and the auto rank-split (host count unknown
        statically) stay silent."""
        from singa_tpu.lint import Collector, lint_model_text

        def flt(fleet_block):
            col = Collector()
            lint_model_text(
                FLEET_CONF.replace(
                    'fleet { role: "auto" prefill_hosts: 1 }',
                    fleet_block,
                ),
                "job.conf", col,
            )
            return [d for d in col.sorted() if d.code == "FLT001"]

        # decode-only topologies: nothing can fill their KV blocks
        for block in (
            'fleet { role: "decode" }',
            'fleet { peers { name: "d0" role: "decode" }\n'
            '        peers { name: "d1" role: "decode" } }',
        ):
            got = flt(block)
            assert len(got) == 1 and "no prefill-capable peer" \
                in got[0].msg, (block, [str(d) for d in got])
        # prefill-only topologies: filled sequences nowhere to stream
        for block in (
            'fleet { role: "prefill" }',
            'fleet { peers { name: "p0" role: "prefill" } }',
        ):
            got = flt(block)
            assert len(got) == 1 and "no decode-capable peer" \
                in got[0].msg, (block, [str(d) for d in got])
        # complete topologies and the rank-split stay silent
        for block in (
            'fleet { peers { name: "p" role: "prefill" }\n'
            '        peers { name: "d" role: "decode" } }',
            'fleet { role: "unified" }',
            'fleet { role: "auto" prefill_hosts: 2 }',
            'fleet { peers { name: "u" role: "unified" }\n'
            '        peers { name: "d" role: "decode" } }',
        ):
            assert not flt(block), block


# ---------------------------------------------------------------------------
# observability
# ---------------------------------------------------------------------------


def test_trace_summarize_fleet_section(tmp_path):
    """migrate_in/out + fleet_role + route events -> the serving
    summary grows migrations / migrated_blocks / routed and per-role
    host rows keyed by rank."""
    from singa_tpu.tools.trace import load_events, summarize

    events = tmp_path / "events"
    os.makedirs(events)
    recs0 = [
        {"ts": 1.0, "mono": 1.0, "rank": 0, "run": "r", "step": 0,
         "kind": "fleet_role", "data": {"host": "p0", "role": "prefill"}},
        {"ts": 1.1, "mono": 1.1, "rank": 0, "run": "r", "step": 1,
         "kind": "request_admit", "data": {"rid": 0, "slot": 0}},
        {"ts": 1.2, "mono": 1.2, "rank": 0, "run": "r", "step": 1,
         "kind": "prefill", "data": {"rid": 0, "tokens": 4}},
        {"ts": 1.3, "mono": 1.3, "rank": 0, "run": "r", "step": 2,
         "kind": "migrate_out",
         "data": {"rid": 0, "dst": "d0", "blocks": 3}},
    ]
    recs1 = [
        {"ts": 1.05, "mono": 1.05, "rank": 1, "run": "r", "step": 0,
         "kind": "fleet_role", "data": {"host": "d0", "role": "decode"}},
        {"ts": 1.4, "mono": 1.4, "rank": 1, "run": "r", "step": 1,
         "kind": "migrate_in",
         "data": {"rid": 0, "src": "p0", "blocks": 3, "shared": 1}},
        {"ts": 1.6, "mono": 1.6, "rank": 1, "run": "r", "step": 5,
         "kind": "retire", "data": {"rid": 0, "tokens": 6}},
    ]
    recs2 = [
        {"ts": 1.0, "mono": 1.0, "rank": 2, "run": "r", "step": 1,
         "kind": "route",
         "data": {"rid": 0, "host": "p0", "policy": "least_loaded"}},
    ]
    for i, recs in enumerate((recs0, recs1, recs2)):
        with open(events / f"rank_{i}.jsonl", "w") as f:
            f.write("\n".join(json.dumps(r) for r in recs) + "\n")
    s = summarize(load_events(str(tmp_path))[0])["serving"]
    assert s["migrations"] == 1
    assert s["migrated_blocks"] == 3
    assert s["routed"] == 1
    cache_zero = {
        "prefix_hits": 0, "partial_hits": 0, "chunks_saved": 0,
        "cache_fetches": 0, "cache_fetch_timeouts": 0,
        "cache_ships_in": 0, "cache_ships_out": 0,
        "ship_bytes_in": 0, "ship_bytes_out": 0,
    }
    assert s["hosts"] == {
        "0": {"role": "prefill", "admitted": 1, "prefill_chunks": 1,
              "migrate_in": 0, "migrate_out": 1, "retired": 0,
              "evicted": 0, "drains": 0, "prefix_hit_rate": 0.0,
              **cache_zero},
        "1": {"role": "decode", "admitted": 0, "prefill_chunks": 0,
              "migrate_in": 1, "migrate_out": 0, "retired": 1,
              "evicted": 0, "drains": 0, "prefix_hit_rate": None,
              **cache_zero},
    }
    assert s["fleet_cache"] is None


@pytest.mark.slow
def test_fleet_lifecycle_reconstructs_from_merged_trace(tmp_path):
    """An instrumented in-process fleet run leaves a cross-rank merged
    record from which route -> prefill -> migrate_out -> migrate_in ->
    retire reconstructs per request."""
    from singa_tpu.obs.recorder import FlightRecorder
    from singa_tpu.tools.trace import load_events, summarize

    cfg = tiny_cfg()
    params = tiny_params(cfg)
    prompts, budgets = mixed_workload(cfg, n=4, seed=1)
    ec = EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4)
    events = str(tmp_path / "events")
    recs = [
        FlightRecorder(events, rank=i, run_id="t") for i in range(3)
    ]
    t = LocalTransport()
    pre = FleetHost("p0", "prefill", Engine(params, cfg, ec), t,
                    peers={"d0": "decode"}, recorder=recs[0])
    dec = FleetHost("d0", "decode", Engine(params, cfg, ec), t,
                    peers={"p0": "prefill"}, recorder=recs[1])
    router = Router(t, recorder=recs[2])
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        router.submit(Request(rid=i, prompt=p, max_new_tokens=m))
    run_fleet_until_done([pre, dec], len(prompts))
    for r in recs:
        r.flush()
    records, skipped = load_events(events)
    assert skipped == 0
    s = summarize(records)["serving"]
    assert s["migrations"] == len(prompts)
    assert s["routed"] == len(prompts)
    assert s["hosts"]["0"]["role"] == "prefill"
    assert s["hosts"]["1"]["role"] == "decode"
    assert s["hosts"]["1"]["prefill_chunks"] == 0
    # per-request lifecycle order across ranks
    for rid in range(len(prompts)):
        times = {}
        for r in records:
            d = r.get("data") or {}
            if d.get("rid") == rid:
                times.setdefault(r["kind"], r["ts"])
        assert (
            times["route"] <= times["request_admit"]
            <= times["prefill"] <= times["migrate_out"]
            <= times["migrate_in"] <= times["retire"]
        ), (rid, times)


# ---------------------------------------------------------------------------
# serve_bench --fleet + the OS-process fleet (main.py plumbing)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_serve_bench_fleet_smoke(capsys):
    from singa_tpu.tools.serve_bench import main as sb_main

    rc = sb_main([
        "--fleet", "--d_model", "32", "--n_heads", "2", "--n_layers",
        "1", "--d_ff", "64", "--vocab", "32", "--max_len", "32",
        "--prompt_len", "4", "--max_new", "8", "--block_len", "8",
        "--prefill_chunk", "4", "--requests", "6", "--concurrency", "2",
    ])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and out["pass"], out
    assert out["token_mismatches"] == 0
    assert out["decode_prefill_chunks"] == 0
    assert out["migrations"] >= 6
    assert out["hosts"]["decode0"]["role"] == "decode"


@pytest.mark.slow
def test_two_os_process_fleet_through_main(tmp_path):
    """The reference launch line, serving edition: two OS processes
    run ``python -m singa_tpu.main -model_conf fleet.conf -procsID k``
    — rank 0 becomes the prefill host, rank 1 the decode host — and a
    driver plays front door over the shared mailbox. Streams must
    equal the in-process unified engine's (same seed, same geometry:
    the migration path crosses a REAL process boundary here)."""
    from singa_tpu.config import parse_model_config
    from singa_tpu.serve.fleet.host import lm_config_from_conf
    from singa_tpu.serve.fleet.router import encode_request

    ws = tmp_path / "ws"
    model_conf = tmp_path / "fleet.conf"
    cluster_conf = tmp_path / "cluster.conf"
    model_conf.write_text(FLEET_CONF)
    cluster_conf.write_text(
        f'nworkers: 2\nnprocs_per_group: 1\nworkspace: "{ws}"\n'
    )
    # the oracle: the same engine geometry in-process
    mcfg = parse_model_config(FLEET_CONF)
    cfg = lm_config_from_conf(mcfg)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    prompts, budgets = mixed_workload(cfg, n=3, seed=6)
    ec = EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4)
    base = single_host_streams(params, cfg, ec, prompts, budgets)

    env = {
        **os.environ, "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": os.path.dirname(os.path.dirname(__file__)),
    }
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "singa_tpu.main",
             "-model_conf", str(model_conf),
             "-cluster_conf", str(cluster_conf),
             "-procsID", str(k)],
            env=env, cwd=os.path.dirname(os.path.dirname(__file__)),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for k in range(2)
    ]
    try:
        mb = Mailbox(str(ws / "fleet"))
        mb.register("frontdoor")
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            mb.send(
                "host0", "request",
                encode_request(Request(rid=i, prompt=p,
                                       max_new_tokens=m)),
                src="frontdoor",
            )
        results = {}
        deadline = time.monotonic() + 300
        while len(results) < len(prompts):
            assert time.monotonic() < deadline, (
                "fleet processes did not deliver results",
                [p.poll() for p in procs],
            )
            for msg in mb.recv("frontdoor"):
                if msg.kind == "result":
                    d = json.loads(msg.payload.decode())
                    results[d["rid"]] = d
            time.sleep(0.05)
        for name in ("host0", "host1"):
            mb.send(name, "shutdown", b"", src="frontdoor")
        for p in procs:
            assert p.wait(timeout=120) == 0, p.stdout.read().decode()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    assert {i: r["tokens"] for i, r in results.items()} == base
    # the role split crossed the process boundary: every stream
    # FINISHED on the decode host
    assert {r["host"] for r in results.values()} == {"host1"}
