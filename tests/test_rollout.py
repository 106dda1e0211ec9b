"""Live weight rollout (serve/rollout.py + the engine's dual-version
param slots): versioned hot-swap into a RUNNING fleet with canary,
parity-gated promotion, and automatic rollback.

The bars this file pins:

  - flip identity: streams retired BEFORE the flip are bitwise the
    single-host oracle's — staging and flipping may never move a
    pre-flip token, and no stream is ever dropped or hung by a
    rollout, whatever the verdict;
  - every fault drill terminates in its DOCUMENTED verdict:
    torn_weights@K -> CRC reject, retries, then ``quarantined``;
    swap_die@K -> stage-ack timeout -> ``paused`` (flipped hosts stay
    flipped); canary parity mismatch -> fleet-wide ``rollback``;
  - version skew is safe: a cross-version migrate degrades to a cold
    re-prefill with IDENTICAL tokens, a cross-version cache_fetch is
    answered with an empty ship — mixed-version fleets never poison a
    pool;
  - the flip is a cache boundary: the prefix index is purged, and a
    slot admitted under the old version never registers its blocks
    under the new one.
"""

import io
import json
import os
import subprocess
import sys
import time
import zlib

import jax
import numpy as np
import pytest

from singa_tpu.models.transformer import TransformerConfig, init_lm
from singa_tpu.resilience import retention
from singa_tpu.resilience.faults import FaultPlan, InjectedCrash
from singa_tpu.resilience.reshard import ReshardError, load_serving_params
from singa_tpu.serve import Engine, EngineConfig, Request, Scheduler
from singa_tpu.serve.fleet import (
    FleetHost,
    LocalTransport,
    Mailbox,
    Router,
    migrate,
)
from singa_tpu.serve.rollout import (
    PROBE_SEED,
    RolloutController,
    probe_prompts,
)
from singa_tpu.trainer import save_checkpoint


def tiny_cfg(**kw):
    base = dict(
        vocab=32, d_model=32, n_heads=2, n_layers=2, d_ff=64, max_len=32
    )
    base.update(kw)
    return TransformerConfig(**base)


def tiny_params(cfg, seed=0):
    return init_lm(jax.random.PRNGKey(seed), cfg)


def mixed_workload(cfg, n=4, seed=0):
    rs = np.random.RandomState(seed)
    prompts = [
        rs.randint(0, cfg.vocab, size=(int(rs.randint(3, 9)),)).astype(
            np.int32
        )
        for _ in range(n)
    ]
    budgets = [int(rs.randint(4, 10)) for _ in range(n)]
    return prompts, budgets


def oracle_streams(params, cfg, ec, prompts, budgets, rid_base=0):
    eng = Engine(params, cfg, ec)
    sched = Scheduler(eng)
    for i, (p, m) in enumerate(zip(prompts, budgets)):
        sched.submit(Request(rid=rid_base + i, prompt=p,
                             max_new_tokens=m))
    sched.serve()
    return {r.rid: list(r.tokens) for r in sched.finished}


def fleet_streams(hosts, rid_min=0):
    return {
        r.rid: list(r.tokens)
        for h in hosts
        for r in h.sched.finished
        if r.rid >= rid_min
    }


def run_fleet_until_done(hosts, n_requests, max_rounds=2000):
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


class _Recorder:
    """Event sink with the recorder's .event() shape."""

    def __init__(self):
        self.events = []

    def event(self, kind, **payload):
        self.events.append((kind, payload))

    def record_span(self, *a, **kw):
        pass

    def kinds(self):
        return [k for k, _ in self.events]

    def of(self, kind):
        return [p for k, p in self.events if k == kind]


class FleetPump:
    """The controller's tick callable for in-process drills: tick every
    live host, tombstone one that dies mid-tick (the swap_die drill)."""

    def __init__(self, hosts):
        self.live = list(hosts)
        self.crashed = []

    def __call__(self):
        for h in list(self.live):
            try:
                h.tick()
            except InjectedCrash:
                self.live.remove(h)
                self.crashed.append(h)


def rollout_ec(**kw):
    base = dict(slots=4, kv_block_len=8, kv_blocks=64,
                max_prefill_chunk=4, prefix_cache=True, prefix_lru=True)
    base.update(kw)
    return EngineConfig(**base)


def build_unified2(params, cfg, ec, recorders=None, fault_plans=None):
    t = LocalTransport()
    names = ["u0", "u1"]
    hosts = [
        FleetHost(
            name, "unified", Engine(params, cfg, ec), t,
            peers={n: "unified" for n in names if n != name},
            recorder=(recorders or {}).get(name),
            fault_plan=(fault_plans or {}).get(name),
        )
        for name in names
    ]
    return hosts, t


# ---------------------------------------------------------------------------
# the engine's dual-version param slots
# ---------------------------------------------------------------------------


class TestEngineDualVersion:
    def test_stage_validate_flip_rollback(self):
        cfg = tiny_cfg()
        eng = Engine(tiny_params(cfg), cfg,
                     EngineConfig(slots=2, kv_block_len=8))
        nxt = tiny_params(cfg, seed=1)
        # validation: the staged tree must be hostable by the LIVE one
        with pytest.raises(ValueError, match="already live"):
            eng.stage_params(nxt, 0)
        broken = dict(nxt)
        dropped = sorted(broken)[0]
        del broken[dropped]
        with pytest.raises(ValueError, match="mismatch"):
            eng.stage_params(broken, 1)
        reshaped = dict(nxt)
        reshaped[dropped] = np.zeros((3, 3), np.float32)
        with pytest.raises(ValueError, match="shape"):
            eng.stage_params(reshaped, 1)
        with pytest.raises(ValueError, match="nothing staged"):
            eng.flip_params()
        # the lifecycle: stage -> flip -> rollback
        nbytes = eng.stage_params(nxt, 1)
        assert nbytes == sum(
            np.asarray(v).nbytes for v in nxt.values()
        )
        assert eng.staged_version == 1 and eng.params_version == 0
        res = eng.flip_params()
        assert res["version"] == 1 and res["prev_version"] == 0
        assert eng.params_version == 1 and eng.staged_version is None
        res = eng.rollback_params()
        assert res["version"] == 0 and res["aborted_version"] == 1
        assert eng.params_version == 0
        with pytest.raises(ValueError, match="no previous"):
            eng.rollback_params()
        # unstage drops a quarantined version without touching live
        eng.stage_params(nxt, 2)
        eng.unstage()
        assert eng.staged_version is None
        with pytest.raises(ValueError, match="nothing staged"):
            eng.flip_params()

    def test_flip_purges_cache_and_frees_lru_blocks(self):
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        ec = rollout_ec(slots=2)
        eng = Engine(params, cfg, ec)
        sched = Scheduler(eng)
        prompt = np.arange(16, dtype=np.int32) % cfg.vocab
        sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=4))
        sched.serve()
        alloc = eng.allocator
        assert len(alloc.cache) > 0 and alloc.cached_blocks > 0
        free_before = alloc.free_blocks
        eng.stage_params(tiny_params(cfg, seed=1), 1)
        res = eng.flip_params()
        # the whole index dropped, every LRU-parked block handed back
        # to the truly-free list — cached KV is a function of the
        # weights — and no block leaked in the move
        assert res["purged_blocks"] > 0
        assert len(alloc.cache) == 0 and alloc.cached_blocks == 0
        assert alloc.free_blocks == free_before

    def test_stale_slot_never_registers_post_flip(self):
        """A slot admitted under v0 whose prompt completes AFTER the
        flip must not index its blocks: its bytes were prefilled under
        replaced weights."""
        cfg = tiny_cfg()
        eng = Engine(tiny_params(cfg), cfg,
                     rollout_ec(slots=1, max_prefill_chunk=4))
        sched = Scheduler(eng)
        prompt = np.arange(16, dtype=np.int32) % cfg.vocab
        sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=4))
        sched.tick()  # one prefill chunk under v0
        eng.stage_params(tiny_params(cfg, seed=1), 1)
        eng.flip_params()
        while sched.busy:
            sched.tick()
        assert len(sched.finished) == 1  # the stream rode through
        assert len(eng.allocator.cache) == 0


# ---------------------------------------------------------------------------
# the weights codec (one bulk weight_ship frame, CRC-guarded)
# ---------------------------------------------------------------------------


class TestWeightsCodec:
    def test_roundtrip_bitwise(self):
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        frame = migrate.serialize_weights(7, params)
        version, tree = migrate.deserialize_weights(frame)
        assert version == 7
        assert sorted(tree) == sorted(params)
        for name, arr in params.items():
            want = np.asarray(arr)
            np.testing.assert_array_equal(tree[name], want)
            assert tree[name].dtype == want.dtype

    def test_torn_and_foreign_frames_rejected(self):
        frame = migrate.serialize_weights(
            1, {"w": np.arange(8, dtype=np.float32)}
        )
        # a truncated ship dies at deserialize, whatever layer notices
        with pytest.raises(Exception):
            migrate.deserialize_weights(frame[: len(frame) // 2])

        def reframe(mutate):
            with np.load(io.BytesIO(frame)) as z:
                arrays = {f: np.array(z[f]) for f in z.files}
            meta = json.loads(bytes(arrays["meta"]).decode("utf-8"))
            mutate(meta, arrays)
            arrays["meta"] = np.frombuffer(
                json.dumps(meta).encode("utf-8"), dtype=np.uint8
            )
            buf = io.BytesIO()
            np.savez(buf, **arrays)
            return buf.getvalue()

        # a bit-flipped artifact: the application-level CRC rejects it
        def flip_payload(meta, arrays):
            arrays["w0000"] = arrays["w0000"] + 1.0

        with pytest.raises(ValueError, match="torn weight_ship v1"):
            migrate.deserialize_weights(reframe(flip_payload))

        # a foreign format is rejected before any staging
        def foreign(meta, arrays):
            meta["format"] = "someone-elses-weights"

        with pytest.raises(ValueError, match="format"):
            migrate.deserialize_weights(reframe(foreign))

    def test_crc_is_chained_over_arrays(self):
        a = {"a": np.arange(4, dtype=np.int32),
             "b": np.arange(4, 8, dtype=np.int32)}
        frame = migrate.serialize_weights(2, a)
        with np.load(io.BytesIO(frame)) as z:
            meta = json.loads(bytes(z["meta"]).decode("utf-8"))
        crc = 0
        for name in sorted(a):
            crc = zlib.crc32(
                np.ascontiguousarray(a[name]).tobytes(), crc
            )
        assert meta["crc32"] == crc & 0xFFFFFFFF
        assert meta["names"] == ["a", "b"]


# ---------------------------------------------------------------------------
# in-process drills: the lifecycle and every fault verdict
# ---------------------------------------------------------------------------


class TestRolloutDrills:
    def _drill(self, *, force_parity_fail=False, fault_plans=None,
               stage_timeout_s=20.0, ship_retries=2, next_seed=1):
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        ec = rollout_ec()
        prompts, budgets = mixed_workload(cfg, n=4, seed=3)
        recs = {"u0": _Recorder(), "u1": _Recorder()}
        hosts, t = build_unified2(params, cfg, ec, recorders=recs,
                                  fault_plans=fault_plans)
        router = Router(t)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            router.submit(Request(rid=i, prompt=p, max_new_tokens=m))
        run_fleet_until_done(hosts, len(prompts))
        base = oracle_streams(params, cfg, ec, prompts, budgets)
        # flip identity, first half: everything retired pre-flip is
        # bitwise the oracle (nothing has flipped yet)
        assert fleet_streams(hosts) == base
        pump = FleetPump(hosts)
        ctl_rec = _Recorder()
        next_params = tiny_params(cfg, seed=next_seed)
        ctl = RolloutController(
            t, {"u0": "unified", "u1": "unified"},
            params=next_params, version=1, cfg=cfg, serving=ec,
            probes=2, probe_tokens=4, stage_timeout_s=stage_timeout_s,
            ship_retries=ship_retries, recorder=ctl_rec,
            force_parity_fail=force_parity_fail, tick=pump,
        )
        res = ctl.run()
        return dict(
            cfg=cfg, params=params, ec=ec, hosts=hosts, t=t,
            router=router, prompts=prompts, budgets=budgets,
            base=base, pump=pump, res=res, recs=recs,
            ctl_rec=ctl_rec, next_params=next_params,
        )

    def _serve_more(self, d, params_for_oracle, rid_base=100):
        """Post-verdict traffic: the fleet must still serve, and the
        streams must match the oracle for whichever weights WON."""
        prompts, budgets = mixed_workload(d["cfg"], n=3, seed=9)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            d["router"].submit(Request(rid=rid_base + i, prompt=p,
                                       max_new_tokens=m))
        run_fleet_until_done(
            d["pump"].live, len(d["prompts"]) + len(prompts)
        )
        got = fleet_streams(d["hosts"], rid_min=rid_base)
        want = oracle_streams(params_for_oracle, d["cfg"], d["ec"],
                              prompts, budgets, rid_base=rid_base)
        assert got == want

    def test_promote_end_to_end(self):
        d = self._drill()
        res = d["res"]
        assert res["verdict"] == "promoted", res
        assert sorted(res["flipped"]) == ["u0", "u1"]
        assert res["rollbacks"] == 0 and res["torn_ships"] == 0
        for h in d["hosts"]:
            assert h.engine.params_version == 1
            assert h.engine.staged_version is None
        # every host staged then flipped, and recorded it
        for name, rec in d["recs"].items():
            ships = rec.of("weight_ship")
            assert [s["ok"] for s in ships] == [True], name
            assert ships[0]["dir"] == "in"
            stages = rec.of("rollout_stage")
            assert stages and stages[0]["ok"] \
                and stages[0]["staged_bytes"] > 0
            flips = rec.of("rollout_flip")
            assert len(flips) == 1 and flips[0]["version"] == 1 \
                and flips[0]["prev_version"] == 0
        canary = d["ctl_rec"].of("rollout_canary")
        assert canary == [{"host": "u0", "version": 1, "parity": True,
                           "probes": 2}]
        done = d["ctl_rec"].of("rollout_done")
        assert done[-1]["verdict"] == "promoted"
        assert not d["ctl_rec"].of("rollout_abort")
        # the fleet now speaks v1: statuses say so, and new streams
        # are bitwise the NEXT weights' oracle
        self._serve_more(d, d["next_params"])
        assert d["router"].versions() == {"u0": 1, "u1": 1}
        # no probe ever leaks into the client-visible stream set
        assert all(rid >= 0 for rid in fleet_streams(d["hosts"]))

    def test_canary_parity_mismatch_rolls_back(self):
        d = self._drill(force_parity_fail=True)
        res = d["res"]
        assert res["verdict"] == "rollback", res
        # only the canary ever flipped; it was restored
        assert res["flipped"] == [] and res["rollbacks"] == 1
        for h in d["hosts"]:
            assert h.engine.params_version == 0
            assert h.engine.staged_version is None
        aborts = d["ctl_rec"].of("rollout_abort")
        assert len(aborts) == 1 and aborts[0]["reason"] == "parity"
        canary = d["ctl_rec"].of("rollout_canary")
        assert canary[-1]["parity"] is False
        # the canary recorded flip + rollback at tick boundaries
        flips = d["recs"]["u0"].of("rollout_flip")
        assert [f.get("rollback", False) for f in flips] == [
            False, True,
        ]
        assert flips[1]["aborted_version"] == 1
        # u1 never flipped (its staged copy was dropped)
        assert d["recs"]["u1"].of("rollout_flip") == []
        # zero dropped, zero hung: the fleet keeps serving CURRENT
        self._serve_more(d, d["params"])

    def test_torn_weights_quarantines_after_retries(self):
        """torn_weights@1..3 on the second host: every ship tears, the
        CRC rejects each one, retries exhaust -> ``quarantined``; the
        already-flipped canary rolls back and v0 keeps serving."""
        plan = FaultPlan.parse(
            "torn_weights@1,torn_weights@2,torn_weights@3"
        )
        d = self._drill(fault_plans={"u1": plan}, ship_retries=2)
        res = d["res"]
        assert res["verdict"] == "quarantined", res
        assert res["torn_ships"] == 3
        assert res["rollbacks"] == 1 and res["flipped"] == []
        for h in d["hosts"]:
            assert h.engine.params_version == 0
        # the torn frames were rejected at the CRC, loudly
        torn = d["recs"]["u1"].of("weight_ship")
        assert len(torn) == 3 and not any(s["ok"] for s in torn)
        aborts = d["ctl_rec"].of("rollout_abort")
        assert len(aborts) == 1 and aborts[0]["reason"] == "torn"
        done = d["ctl_rec"].of("rollout_done")
        assert done[-1]["verdict"] == "quarantined" \
            and done[-1]["torn_ships"] == 3
        self._serve_more(d, d["params"])

    def test_swap_die_pauses_rollout(self):
        """swap_die@1 on the second host: it dies mid-stage, the
        controller's stage-ack window expires -> ``paused``; the
        flipped canary STAYS flipped (the skew guards are what make
        the frozen mixed fleet safe)."""
        plan = FaultPlan.parse("swap_die@1")
        d = self._drill(fault_plans={"u1": plan}, stage_timeout_s=2.0)
        res = d["res"]
        assert res["verdict"] == "paused", res
        assert res["flipped"] == ["u0"]
        assert [h.name for h in d["pump"].crashed] == ["u1"]
        # the canary is serving the NEW version; the dead host froze
        # at the OLD one — a documented mixed-version fleet
        u0, u1 = d["hosts"]
        assert u0.engine.params_version == 1
        assert u1.engine.params_version == 0
        aborts = d["ctl_rec"].of("rollout_abort")
        assert len(aborts) == 1 and aborts[0]["reason"] == "paused"
        # pre-flip streams are intact — nothing dropped
        assert fleet_streams(d["hosts"]) == d["base"]

    def test_streams_straddling_the_flip_never_hang(self):
        """Requests admitted BEFORE the rollout and finished AFTER it:
        in-flight slots ride through the flip on their already-written
        KV — zero drops, zero hangs, and their count is exact."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        ec = rollout_ec()
        prompts, budgets = mixed_workload(cfg, n=4, seed=5)
        hosts, t = build_unified2(params, cfg, ec)
        router = Router(t)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            router.submit(Request(rid=i, prompt=p,
                                  max_new_tokens=max(m, 8)))
        for _ in range(3):  # a few ticks: admitted, not finished
            for h in hosts:
                h.tick()
        pump = FleetPump(hosts)
        ctl = RolloutController(
            t, {"u0": "unified", "u1": "unified"},
            params=tiny_params(cfg, seed=1), version=1, cfg=cfg,
            serving=ec, probes=2, probe_tokens=4,
            stage_timeout_s=20.0, tick=pump,
        )
        res = ctl.run()
        assert res["verdict"] == "promoted"
        run_fleet_until_done(hosts, len(prompts))
        got = fleet_streams(hosts)
        assert sorted(got) == list(range(len(prompts)))
        assert all(len(toks) > 0 for toks in got.values())


    def test_a_flip_finds_no_pass_in_flight(self, monkeypatch):
        """Decode runs one pass ahead of the host, and the flip is the
        tick boundary WHOLE: the handler reads the pass dispatched
        under the outgoing weights before they go, so at the swap every
        live slot's device lane holds the token its request holds."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        ec = rollout_ec()
        prompts, budgets = mixed_workload(cfg, n=4, seed=5)
        hosts, t = build_unified2(params, cfg, ec)
        router = Router(t)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            router.submit(Request(rid=i, prompt=p, max_new_tokens=16))
        for _ in range(6):
            for h in hosts:
                h.tick()
        serving = [h for h in hosts if h.sched._slot_req]
        assert serving and all(
            h.sched._in_flight is not None for h in serving
        )
        live_at_flip = []
        real_flip = Engine.flip_params

        def flip(engine):
            (host,) = [h for h in hosts if h.engine is engine]
            assert host.sched._in_flight is None
            lanes = np.asarray(engine.state["tokens"])
            for slot, req in host.sched._slot_req.items():
                if req.status == "decoding":
                    assert lanes[slot] == req.tokens[-1]
                    live_at_flip.append(req.rid)
            return real_flip(engine)

        monkeypatch.setattr(Engine, "flip_params", flip)
        t.register("ctl")
        # a flip with streams live and a pass in flight: the
        # controller's canary probes would let them finish first
        for h in hosts:
            h.engine.stage_params(tiny_params(cfg, seed=1), 1)
            t.send(h.name, "rollout",
                   json.dumps({"cmd": "flip"}).encode("utf-8"), src="ctl")
            h.tick()
        assert sorted(live_at_flip) == list(range(len(prompts)))
        run_fleet_until_done(hosts, len(prompts))
        got = fleet_streams(hosts)
        assert all(len(got[i]) == 16 for i in range(len(prompts)))


# ---------------------------------------------------------------------------
# version skew: the mixed-version fleet is safe by construction
# ---------------------------------------------------------------------------


class TestVersionSkew:
    def test_skew_migrate_degrades_to_cold_prefill_bitwise(self):
        """Prefill host at v0, decode host flipped to v1 (same weight
        VALUES, so token parity is decidable): every migrated frame is
        version-skewed, the decode host re-prefills cold — and the
        streams are still bitwise the oracle. migrate_in events carry
        the skew verdict; the decode host provably ran prefill."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        ec = EngineConfig(slots=3, kv_block_len=8, max_prefill_chunk=4)
        prompts, budgets = mixed_workload(cfg, n=4, seed=2)
        base = oracle_streams(params, cfg, ec, prompts, budgets)
        t = LocalTransport()
        rec = _Recorder()
        pre = FleetHost("p0", "prefill", Engine(params, cfg, ec), t,
                        peers={"d0": "decode"})
        dec = FleetHost("d0", "decode", Engine(params, cfg, ec), t,
                        peers={"p0": "prefill"}, recorder=rec)
        dec.engine.stage_params(
            {k: np.asarray(v) for k, v in params.items()}, 1
        )
        dec.engine.flip_params()
        router = Router(t)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            router.submit(Request(rid=i, prompt=p, max_new_tokens=m))
        run_fleet_until_done([pre, dec], len(prompts))
        assert fleet_streams([pre, dec]) == base
        skews = [e for e in rec.of("migrate_in") if e.get("skew")]
        assert len(skews) == len(prompts)
        assert all(
            e["frame_version"] == 0 and e["live_version"] == 1
            and e["slot"] == -1 and e["blocks"] == 0
            for e in skews
        )
        # the degrade IS a cold prefill on the decode host
        assert dec.sched.prefill_chunks > 0
        assert pre.engine.params_version == 0
        assert dec.engine.params_version == 1

    def test_skew_cache_fetch_answered_with_empty_ship(self):
        """A cache_fetch tagged v0 against a host flipped to v1 gets
        the EXISTING empty-ship answer — the requester degrades to
        plain prefill instead of installing cross-version bytes."""
        cfg = tiny_cfg()
        params = tiny_params(cfg)
        ec = rollout_ec(slots=2)
        t = LocalTransport()
        rec = _Recorder()
        host = FleetHost("u0", "unified", Engine(params, cfg, ec), t,
                         peers={}, recorder=rec)
        # warm the cache under v0, then flip to v1 with the same values
        sched = host.sched
        prompt = np.arange(16, dtype=np.int32) % cfg.vocab
        sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=4))
        while sched.busy:
            host.tick()
        chain = host.engine.allocator.cache.chain(prompt)
        host.engine.stage_params(
            {k: np.asarray(v) for k, v in params.items()}, 1
        )
        host.engine.flip_params()
        t.register("probe")
        t.send("u0", "cache_fetch",
               migrate.serialize_fetch(7, chain, version=0),
               src="probe")
        host.tick()
        ships = [m for m in t.recv("probe") if m.kind == "cache_ship"]
        assert len(ships) == 1
        ship = migrate.deserialize_ship(ships[0].payload)
        assert ship["chain"] == [] and ship["version"] == 1
        skew = [e for e in rec.of("cache_fetch") if e.get("skew")]
        assert len(skew) == 1 and skew[0]["dir"] == "serve"
        assert skew[0]["frame_version"] == 0
        assert skew[0]["live_version"] == 1

    def test_fetch_and_ship_frames_carry_version_tags(self):
        chain = [b"\x01" * 16, b"\x02" * 16]
        rid, got_chain, version = migrate.deserialize_fetch(
            migrate.serialize_fetch(3, chain, version=5)
        )
        assert (rid, got_chain, version) == (3, chain, 5)
        # pre-rollout senders (no explicit tag) read as version 0
        _, _, version = migrate.deserialize_fetch(
            migrate.serialize_fetch(3, chain)
        )
        assert version == 0
        k = np.zeros((2, 1, 2, 8, 8), np.float32)
        ship = migrate.deserialize_ship(
            migrate.serialize_ship(3, chain[:1], k, k, version=5)
        )
        assert ship["version"] == 5
        ship = migrate.deserialize_ship(
            migrate.serialize_ship(3, chain[:1], k, k)
        )
        assert ship["version"] == 0


# ---------------------------------------------------------------------------
# reshard-on-load: any save restores onto any serving topology
# ---------------------------------------------------------------------------


class TestLoadServingParams:
    def test_npz_overlay_and_shape_reject(self, tmp_path):
        cfg = tiny_cfg()
        init = tiny_params(cfg)
        name = sorted(init)[0]
        trained = {name: np.asarray(init[name]) + 1.0}
        path = str(tmp_path / "step_5.npz")
        save_checkpoint(path, 5, trained)
        out, info = load_serving_params(path, init)
        np.testing.assert_array_equal(
            np.asarray(out[name]), trained[name]
        )
        # absent names keep their init values
        other = sorted(init)[1]
        np.testing.assert_array_equal(
            np.asarray(out[other]), np.asarray(init[other])
        )
        assert info["format"] == "npz" and info["step"] == 5
        assert info["restored"] == 1 and info["resharded"] == 0
        # a shape mismatch is a loud reject, never a silent boot
        bad = str(tmp_path / "step_6.npz")
        save_checkpoint(bad, 6, {name: np.zeros((3, 3), np.float32)})
        with pytest.raises((ReshardError, ValueError), match="shape"):
            load_serving_params(bad, init)

    def test_retention_folder_resolves_latest(self, tmp_path):
        cfg = tiny_cfg()
        init = tiny_params(cfg)
        name = sorted(init)[0]
        folder = str(tmp_path)
        save_checkpoint(os.path.join(folder, "step_2.npz"), 2,
                        {name: np.asarray(init[name]) + 1.0})
        newest = os.path.join(folder, "step_4.npz")
        save_checkpoint(newest, 4, {name: np.asarray(init[name]) + 2.0})
        retention.mark_latest(folder, newest)
        out, info = load_serving_params(folder, init)
        assert info["step"] == 4
        np.testing.assert_array_equal(
            np.asarray(out[name]), np.asarray(init[name]) + 2.0
        )
        empty = str(tmp_path / "nothing")
        os.makedirs(empty)
        with pytest.raises(ReshardError, match="no complete save"):
            load_serving_params(empty, init)

    def test_sharded_save_restores_bitwise(self, tmp_path):
        from singa_tpu.trainer.sharded_ckpt import save_sharded

        cfg = tiny_cfg()
        saved = {
            n: np.asarray(v)
            for n, v in tiny_params(cfg, seed=9).items()
        }
        path = str(tmp_path / "step_3.ckpt")
        save_sharded(path, 3, saved)
        init = tiny_params(cfg, seed=0)
        out, info = load_serving_params(path, init)
        assert info["format"] == "sharded"
        assert info["saved_nprocs"] == 1
        assert info["restored"] == len(saved)
        for n, arr in saved.items():
            np.testing.assert_array_equal(
                np.asarray(out[n]), arr, err_msg=n
            )


# ---------------------------------------------------------------------------
# reshard-aware retention: stale-topology saves evict first
# ---------------------------------------------------------------------------


def _sharded_save(folder, step, nprocs=1):
    from singa_tpu.trainer.sharded_ckpt import save_sharded

    path = os.path.join(folder, f"step_{step}.ckpt")
    save_sharded(path, step, {"w": np.full((4,), step, np.float32)})
    if nprocs != 1:
        mpath = os.path.join(path, "manifest.json")
        with open(mpath) as f:
            manifest = json.load(f)
        manifest["nprocs"] = nprocs
        # keep the save complete: the loader wants proc_k for k < nprocs
        for k in range(1, nprocs):
            with open(os.path.join(path, f"proc_{k}.npz"), "wb") as f:
                np.savez(f)
        with open(mpath, "w") as f:
            json.dump(manifest, f)
        from singa_tpu.resilience import coord

        for k in range(nprocs):
            coord.write_commit(path, k)
    return path


def _npz_save(folder, step):
    path = os.path.join(folder, f"step_{step}.npz")
    save_checkpoint(path, step, {"w": np.zeros((2,), np.float32)})
    return path


class TestReshardAwareRetention:
    def test_stale_topology_saves_evict_first(self, tmp_path):
        """keep_last budgeted by topology: with current_nprocs given,
        the newest CURRENT-topology saves fill the budget and a
        stale-topology save evicts even when it is not the oldest.
        npz saves are topology-agnostic (always current)."""
        folder = str(tmp_path)
        stale = _sharded_save(folder, 2, nprocs=2)
        mid = _npz_save(folder, 4)
        cur = _sharded_save(folder, 6, nprocs=1)
        retention.mark_latest(folder, cur)
        deleted = retention.apply_retention(
            folder, 2, current_nprocs=1
        )
        assert deleted == [stale]
        assert retention.list_checkpoints(folder) == [cur, mid]

    def test_stale_newest_loses_to_older_current(self, tmp_path):
        """The inversion the plain newest-first order cannot express:
        the NEWEST save was written by a since-resized job, so it
        yields its keep slot to older current-topology saves."""
        folder = str(tmp_path)
        old = _npz_save(folder, 2)
        mid = _npz_save(folder, 4)
        newest_stale = _sharded_save(folder, 6, nprocs=4)
        retention.mark_latest(folder, mid)
        deleted = retention.apply_retention(
            folder, 2, current_nprocs=1
        )
        assert deleted == [newest_stale]
        assert retention.list_checkpoints(folder) == [mid, old]

    def test_without_nprocs_order_is_pure_newest_first(self, tmp_path):
        folder = str(tmp_path)
        old = _npz_save(folder, 2)
        mid = _npz_save(folder, 4)
        newest_stale = _sharded_save(folder, 6, nprocs=4)
        retention.mark_latest(folder, newest_stale)
        deleted = retention.apply_retention(folder, 2)
        assert deleted == [old]
        assert retention.list_checkpoints(folder) == [
            newest_stale, mid,
        ]


# ---------------------------------------------------------------------------
# lint: ROL001 feasibility + the conf block's did-you-means
# ---------------------------------------------------------------------------


ROLLOUT_CONF = """
name: "rollout-test"
neuralnet {{
  layer {{ name: "embed" type: "kEmbedding"
    embedding_param {{ vocab_size: 32 embedding_dim: 32 max_len: 32 }} }}
  layer {{ name: "attn" type: "kAttention" srclayers: "embed"
    attention_param {{ num_heads: 2 }} }}
}}
serving {{ slots: 2 kv_block_len: 8 max_prefill_chunk: 4 }}
fleet {{
  peers {{ name: "p" role: "prefill" }}
  peers {{ name: "d" role: "decode" }}
  rollout {{ {rollout} }}
}}
"""


def _rol(rollout, conf=None):
    from singa_tpu.lint import Collector, lint_model_text

    col = Collector()
    lint_model_text(
        (conf or ROLLOUT_CONF).format(rollout=rollout), "job.conf", col
    )
    return [d for d in col.sorted() if d.code == "ROL001"]


class TestRolloutLint:
    def test_rol001_missing_checkpoint(self):
        got = _rol("version: 2")
        assert len(got) == 1 and "without a checkpoint" in got[0].msg
        assert "checkpoint" in (got[0].fix_hint or "")

    def test_rol001_canary_arms(self):
        got = _rol('checkpoint: "ck.npz" canary: "zz"')
        assert len(got) == 1 and "not a declared" in got[0].msg
        got = _rol('checkpoint: "ck.npz" canary: "p"')
        assert len(got) == 1 and "role prefill" in got[0].msg
        # a decode canary is the intended shape: silent
        assert not _rol('checkpoint: "ck.npz" canary: "d"')

    def test_rol001_single_host_canary(self):
        conf = ROLLOUT_CONF.replace(
            'peers {{ name: "p" role: "prefill" }}\n'
            '  peers {{ name: "d" role: "decode" }}\n  ',
            'role: "unified" max_hosts: 1\n  ',
        )
        got = _rol('checkpoint: "ck.npz" canary: "host0"', conf=conf)
        assert len(got) == 1 and "single-host" in got[0].msg

    def test_rol001_degenerate_knobs(self):
        for knob, needle in (
            ("parity_probes: 0", "parity_probes 0"),
            ("probe_tokens: 0", "probe_tokens 0"),
            ("ship_retries: -1", "ship_retries -1"),
            ("stage_timeout_s: 0", "stage_timeout_s 0"),
        ):
            got = _rol(f'checkpoint: "ck.npz" {knob}')
            assert len(got) == 1 and needle in got[0].msg, (knob, got)

    def test_rol001_inert_block_and_clean_conf_silent(self):
        # an all-defaults rollout block is inert, not an error
        assert not _rol("")
        assert not _rol(
            'checkpoint: "ck.npz" version: 2 parity_probes: 4'
        )

    def test_rollout_conf_did_you_mean(self):
        from singa_tpu.lint import Collector, lint_model_text

        base = ROLLOUT_CONF.format(
            rollout='checkpoint: "ck.npz" parity_probes: 2'
        )
        col = Collector()
        lint_model_text(base, "job.conf", col)
        assert not any(
            d.code in ("CFG001", "CFG002") for d in col.sorted()
        ), [str(d) for d in col.sorted()]
        for typo, want in (
            ("rollout {", "rollout"),
            ("parity_probes:", "parity_probes"),
            ("checkpoint:", "checkpoint"),
        ):
            text = base.replace(typo, typo[:-2] + "x" + typo[-2:], 1)
            col = Collector()
            lint_model_text(text, "job.conf", col)
            assert any(
                d.code == "CFG001" and want in (d.fix_hint or "")
                for d in col.sorted()
            ), (typo, [str(d) for d in col.sorted()])


# ---------------------------------------------------------------------------
# observability: trace --summarize grows a rollout block
# ---------------------------------------------------------------------------


def test_trace_summarize_rollout_section(tmp_path):
    from singa_tpu.tools.trace import load_events, summarize

    events = tmp_path / "events"
    os.makedirs(events)
    recs0 = [  # the canary host: staged, flipped, rolled back
        {"ts": 1.0, "mono": 1.0, "rank": 0, "run": "r", "step": 1,
         "kind": "weight_ship",
         "data": {"dir": "in", "ok": True, "version": 1, "bytes": 900}},
        {"ts": 1.1, "mono": 1.1, "rank": 0, "run": "r", "step": 1,
         "kind": "rollout_stage",
         "data": {"version": 1, "ok": True, "staged_bytes": 800}},
        {"ts": 1.2, "mono": 1.2, "rank": 0, "run": "r", "step": 2,
         "kind": "rollout_flip",
         "data": {"version": 1, "prev_version": 0, "tick": 8,
                  "purged_blocks": 3}},
        {"ts": 1.6, "mono": 1.6, "rank": 0, "run": "r", "step": 3,
         "kind": "rollout_flip",
         "data": {"version": 0, "rollback": True, "aborted_version": 1,
                  "tick": 11, "purged_blocks": 0}},
    ]
    recs1 = [  # a host whose ship tore
        {"ts": 1.05, "mono": 1.05, "rank": 1, "run": "r", "step": 1,
         "kind": "weight_ship",
         "data": {"dir": "in", "ok": False, "bytes": 450,
                  "error": "torn weight_ship v1: CRC mismatch"}},
    ]
    recs2 = [  # the controller
        {"ts": 1.0, "mono": 1.0, "rank": 2, "run": "r", "step": 0,
         "kind": "weight_ship",
         "data": {"dir": "out", "host": "u0", "version": 1,
                  "bytes": 900, "attempt": 1}},
        {"ts": 1.4, "mono": 1.4, "rank": 2, "run": "r", "step": 0,
         "kind": "rollout_canary",
         "data": {"host": "u0", "version": 1, "parity": False,
                  "probes": 2}},
        {"ts": 1.5, "mono": 1.5, "rank": 2, "run": "r", "step": 0,
         "kind": "rollout_abort",
         "data": {"reason": "parity", "host": "u0", "version": 1,
                  "rollbacks": 1}},
        {"ts": 1.7, "mono": 1.7, "rank": 2, "run": "r", "step": 0,
         "kind": "rollout_done",
         "data": {"verdict": "rollback", "version": 1, "canary": "u0",
                  "flipped": 0, "rollbacks": 1, "torn_ships": 1}},
    ]
    for i, recs in enumerate((recs0, recs1, recs2)):
        with open(events / f"rank_{i}.jsonl", "w") as f:
            f.write("\n".join(json.dumps(r) for r in recs) + "\n")
    s = summarize(load_events(str(tmp_path))[0])["rollout"]
    assert s == {
        "ships_in": 1,
        "ship_bytes_in": 900,
        "torn_ships": 1,
        "stages": 1,
        "flips": 1,
        "rollbacks": 1,
        "canary": {"parity": False, "probes": 2},
        "aborts": [{"reason": "parity", "version": 1}],
        "verdict": "rollback",
        "version": 1,
        "hosts": {
            "0": {"version": 0, "flip_tick": 11, "flips": 2,
                  "rollbacks": 1},
        },
    }


def test_trace_summarize_rollout_absent_without_events(tmp_path):
    from singa_tpu.tools.trace import load_events, summarize

    events = tmp_path / "events"
    os.makedirs(events)
    with open(events / "rank_0.jsonl", "w") as f:
        f.write(json.dumps(
            {"ts": 1.0, "mono": 1.0, "rank": 0, "run": "r", "step": 1,
             "kind": "request_admit", "data": {"rid": 0, "slot": 0}}
        ) + "\n")
    assert summarize(load_events(str(tmp_path))[0])["rollout"] is None


# ---------------------------------------------------------------------------
# the OS-process drill: conf-launched fleet, checkpoint boot,
# promote then forced rollback across a REAL process boundary
# ---------------------------------------------------------------------------


OS_FLEET_CONF = """
name: "rollout-fleet"
checkpoint: "{boot}"
neuralnet {{
  layer {{ name: "embed" type: "kEmbedding"
    embedding_param {{ vocab_size: 32 embedding_dim: 32 max_len: 32 }} }}
  layer {{ name: "attn" type: "kAttention" srclayers: "embed"
    attention_param {{ num_heads: 2 }} }}
}}
serving {{ slots: 2 kv_block_len: 8 max_prefill_chunk: 4 }}
fleet {{
  peers {{ name: "host0" role: "unified" }}
  peers {{ name: "host1" role: "unified" }}
  rollout {{ checkpoint: "{next}" version: {version} }}
}}
"""


@pytest.mark.slow
def test_two_os_process_rollout_drill(tmp_path):
    """The reference launch line, rollout edition: two OS processes
    serve a conf-launched fleet booted from a CHECKPOINT (satellite:
    reshard-on-load threads through run_from_conf), the in-test
    controller promotes v1 through the real mailbox, a second forced
    parity-fail rollout of v2 rolls the fleet back to v1 — and the
    fleet answers traffic correctly before, between, and after. The
    merged cross-rank trace reconstructs the whole story."""
    from singa_tpu.config import parse_model_config
    from singa_tpu.serve.fleet.host import lm_config_from_conf
    from singa_tpu.serve.fleet.router import encode_request
    from singa_tpu.serve.rollout import run_rollout_from_conf
    from singa_tpu.tools.trace import load_events, summarize

    ws = tmp_path / "ws"
    cfg = tiny_cfg(d_ff=128)  # conf-derived geometry pins d_ff = 4*d
    # the boot weights (what the fleet serves as v0) and the
    # next-version weights the rollout ships
    boot_params = {
        n: np.asarray(v) for n, v in tiny_params(cfg, seed=7).items()
    }
    next_params = {
        n: np.asarray(v) for n, v in tiny_params(cfg, seed=8).items()
    }
    boot_ck = str(tmp_path / "boot_step_0.npz")
    next_ck = str(tmp_path / "next_step_1.npz")
    save_checkpoint(boot_ck, 0, boot_params)
    save_checkpoint(next_ck, 1, next_params)

    def write_confs(version):
        model_conf = tmp_path / f"fleet_v{version}.conf"
        model_conf.write_text(OS_FLEET_CONF.format(
            boot=boot_ck, next=next_ck, version=version,
        ))
        return model_conf

    model_conf = write_confs(1)
    cluster_conf = tmp_path / "cluster.conf"
    cluster_conf.write_text(
        f'nworkers: 2\nnprocs_per_group: 1\nworkspace: "{ws}"\n'
    )
    mcfg = parse_model_config(model_conf.read_text())
    lm_cfg = lm_config_from_conf(mcfg)
    ec = EngineConfig(slots=2, kv_block_len=8, max_prefill_chunk=4)
    prompts, budgets = mixed_workload(lm_cfg, n=2, seed=6)
    base_v0 = oracle_streams(boot_params, lm_cfg, ec, prompts, budgets)

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

    def collect(mb, want, rid_base=0):
        results = {}
        deadline = time.monotonic() + 300
        while len(results) < want:
            assert time.monotonic() < deadline, (
                "fleet processes did not deliver results",
                [p.poll() for p in procs],
            )
            for msg in mb.recv("frontdoor"):
                if msg.kind == "result":
                    d = json.loads(msg.payload.decode())
                    if d["rid"] >= rid_base:
                        results[d["rid"]] = d
            time.sleep(0.05)
        return {i: r["tokens"] for i, r in results.items()}

    try:
        mb = Mailbox(str(ws / "fleet"))
        mb.register("frontdoor")
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            mb.send("host0", "request",
                    encode_request(Request(rid=i, prompt=p,
                                           max_new_tokens=m)),
                    src="frontdoor")
        # pre-rollout: the fleet serves the BOOT checkpoint's weights
        # (reshard-on-load threaded through run_from_conf)
        assert collect(mb, len(prompts)) == base_v0

        # rollout 1: promote v1 across the process boundary
        quiet = lambda s: None  # noqa: E731
        ccfg = _cluster_cfg(cluster_conf)
        res = run_rollout_from_conf(mcfg, ccfg, log=quiet)
        assert res["verdict"] == "promoted", res
        assert sorted(res["flipped"]) == ["host0", "host1"]

        # between rollouts: streams now speak v1
        base_v1 = oracle_streams(next_params, lm_cfg, ec, prompts,
                                 budgets, rid_base=100)
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            mb.send("host0", "request",
                    encode_request(Request(rid=100 + i, prompt=p,
                                           max_new_tokens=m)),
                    src="frontdoor")
        assert collect(mb, len(prompts), rid_base=100) == base_v1

        # rollout 2: forced parity mismatch -> automatic fleet-wide
        # rollback, loud abort, zero dropped streams
        mcfg2 = parse_model_config(write_confs(2).read_text())
        res = run_rollout_from_conf(
            mcfg2, ccfg, force_parity_fail=True, log=quiet,
        )
        assert res["verdict"] == "rollback", res
        assert res["rollbacks"] == 1 and res["flipped"] == []

        # after the rollback the fleet still answers, still on v1
        for i, (p, m) in enumerate(zip(prompts, budgets)):
            mb.send("host0", "request",
                    encode_request(Request(rid=200 + i, prompt=p,
                                           max_new_tokens=m)),
                    src="frontdoor")
        got = collect(mb, len(prompts), rid_base=200)
        want = oracle_streams(next_params, lm_cfg, ec, prompts,
                              budgets, rid_base=200)
        assert got == want

        for name in ("host0", "host1"):
            mb.send(name, "shutdown", b"", src="frontdoor")
        for p in procs:
            assert p.wait(timeout=120) == 0, p.stdout.read().decode()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()

    # the merged cross-rank trace reconstructs the whole drill
    records, skipped = load_events(str(ws / "events"))
    assert skipped == 0
    s = summarize(records)["rollout"]
    assert s is not None
    assert s["verdict"] == "rollback"  # the LAST rollout's verdict
    assert s["ships_in"] >= 3 and s["torn_ships"] == 0
    assert s["flips"] >= 3 and s["rollbacks"] >= 1
    assert {"reason": "parity", "version": 2} in s["aborts"]
    # each host booted from the checkpoint and said so
    restores = [r for r in records
                if r.get("kind") == "weights_restored"]
    assert len(restores) == 2
    assert all(r["data"]["format"] == "npz" for r in restores)


def _cluster_cfg(cluster_conf):
    from singa_tpu.config import parse_cluster_config

    return parse_cluster_config(cluster_conf.read_text())


# ---------------------------------------------------------------------------
# probe determinism
# ---------------------------------------------------------------------------


def test_probe_prompts_deterministic_and_windowed():
    cfg = tiny_cfg()
    a = probe_prompts(cfg, 3, probe_tokens=8)
    b = probe_prompts(cfg, 3, probe_tokens=8)
    assert len(a) == 3
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
        assert x.dtype == np.int32
        assert 1 <= len(x) <= cfg.max_len - 8 - 1
        assert np.all((x >= 1) & (x < cfg.vocab))
    # a tight window still yields admissible prompts
    tight = probe_prompts(tiny_cfg(max_len=8), 2, probe_tokens=6)
    assert all(len(p) == 1 for p in tight)
    assert PROBE_SEED == 0x5EED
