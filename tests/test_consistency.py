"""Async consistency protocols: EASGD / RandomSync / SyncConfig.

Unit tests pin the protocol math to a hand-rolled numpy transcription of
the reference's message handlers (src/utils/param.cc:100-256); integration
tests run the ReplicaTrainer on the virtual 8-device mesh and check the
training-regime invariants (bootstrap broadcast, replica/center
contraction, accuracy).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.config import parse_cluster_config
from singa_tpu.config.schema import ConfigError
from singa_tpu.data.loader import synthetic_arrays
from singa_tpu.parallel import MODEL_AXIS, build_mesh
from singa_tpu.parallel.consistency import (
    elastic_sync,
    random_sync,
    sample_sync_indices,
    sync_now,
    sync_ratio,
)
from singa_tpu.trainer import ReplicaTrainer, make_trainer
from singa_tpu.trainer.trainer import Trainer

from test_trainer import make_conf


# ---------------------------------------------------------------------
# protocol math vs a straight-line numpy oracle
# ---------------------------------------------------------------------


def np_elastic(replicas, center, alpha):
    """ElasticParam handlers, straight from the wire protocol: worker
    ships w; server diff = alpha*(w - s), s += diff; worker w -= diff."""
    replicas = {k: v.copy() for k, v in replicas.items()}
    center = {k: v.copy() for k, v in center.items()}
    R = next(iter(replicas.values())).shape[0]
    for i in range(R):
        for k in replicas:
            diff = alpha * (replicas[k][i] - center[k])
            center[k] = center[k] + diff
            replicas[k][i] = replicas[k][i] - diff
    return replicas, center


def np_random_sync(replicas, snaps, center, indices):
    """RandomSyncParam handlers: delta vs snapshot at sampled coords;
    server adds and replies its old values; worker reconciles."""
    replicas = {k: v.copy() for k, v in replicas.items()}
    snaps = {k: v.copy() for k, v in snaps.items()}
    center = {k: v.copy() for k, v in center.items()}
    R = next(iter(replicas.values())).shape[0]
    for i in range(R):
        for k in replicas:
            w = replicas[k][i].ravel()
            s = snaps[k][i].ravel()
            c = center[k].ravel()
            for j in indices[k][i]:
                delta = w[j] - s[j]
                old = c[j]
                c[j] += delta
                w[j] = old + delta
                s[j] = w[j]
            replicas[k][i] = w.reshape(replicas[k][i].shape)
            snaps[k][i] = s.reshape(snaps[k][i].shape)
            center[k] = c.reshape(center[k].shape)
    return replicas, snaps, center


def _rand_trees(R=4, seed=0):
    rng = np.random.RandomState(seed)
    shapes = {"w": (3, 5), "b": (7,)}
    reps = {k: rng.randn(R, *s).astype(np.float32) for k, s in shapes.items()}
    center = {k: rng.randn(*s).astype(np.float32) for k, s in shapes.items()}
    return reps, center, shapes


class TestElastic:
    def test_matches_numpy_oracle(self):
        reps, center, _ = _rand_trees()
        want_r, want_c = np_elastic(reps, center, alpha=0.3)
        got_r, got_c = elastic_sync(
            {k: jnp.asarray(v) for k, v in reps.items()},
            {k: jnp.asarray(v) for k, v in center.items()},
            0.3,
        )
        for k in reps:
            np.testing.assert_allclose(got_r[k], want_r[k], rtol=1e-5)
            np.testing.assert_allclose(got_c[k], want_c[k], rtol=1e-5)

    def test_order_is_serial(self):
        """The server handles workers one at a time under a per-param lock
        (server.cc:110-143): replica 1 must see a center already moved by
        replica 0 — i.e. NOT the parallel all-reduce variant."""
        reps = {"w": np.array([[1.0], [1.0]], np.float32)}
        center = {"w": np.array([0.0], np.float32)}
        got_r, got_c = elastic_sync(
            jax.tree.map(jnp.asarray, reps),
            jax.tree.map(jnp.asarray, center),
            0.5,
        )
        # serial: c=0 -> +0.5 -> c=0.5; then diff=0.25, c=0.75
        np.testing.assert_allclose(np.asarray(got_c["w"]), [0.75])
        np.testing.assert_allclose(np.asarray(got_r["w"]), [[0.5], [0.75]])

    def test_contracts_replicas_toward_center(self):
        reps, center, _ = _rand_trees(R=8, seed=3)
        got_r, got_c = elastic_sync(
            jax.tree.map(jnp.asarray, reps),
            jax.tree.map(jnp.asarray, center),
            0.5,
        )
        for k in reps:
            before = np.abs(reps[k] - center[k]).mean()
            after = np.abs(np.asarray(got_r[k]) - np.asarray(got_c[k])).mean()
            assert after < before


class TestRandomSync:
    @pytest.mark.parametrize("dense_budget", [None, 0])
    def test_matches_numpy_oracle(self, dense_budget, monkeypatch):
        """Both partial-coverage formulations — the dense parallel
        prefix and the bounded-memory serial scan (budget 0 forces it)
        — match the straight-line transcription of the wire protocol."""
        if dense_budget is not None:
            from singa_tpu.parallel import consistency

            monkeypatch.setattr(
                consistency, "DENSE_PREFIX_MAX_ELEMS", dense_budget
            )
        reps, center, shapes = _rand_trees(R=3, seed=1)
        snaps = {
            k: v + np.random.RandomState(9).randn(*v.shape).astype(np.float32)
            for k, v in reps.items()
        }
        idx = sample_sync_indices(
            np.random.RandomState(5), shapes, nreplicas=3, ratio=0.4
        )
        want = np_random_sync(reps, snaps, center, idx)
        got = random_sync(
            jax.tree.map(jnp.asarray, reps),
            jax.tree.map(jnp.asarray, snaps),
            jax.tree.map(jnp.asarray, center),
            jax.tree.map(jnp.asarray, idx),
        )
        for want_t, got_t in zip(want, got):
            for k in want_t:
                np.testing.assert_allclose(
                    np.asarray(got_t[k]), want_t[k], rtol=1e-5, atol=1e-6
                )

    def test_full_ratio_single_replica_adopts_center_plus_delta(self):
        """With ratio 1 and one replica: w' = center_old + (w - snapshot)
        at every coordinate — the count==data_.count() fast path."""
        w = np.array([[2.0, 4.0]], np.float32)
        snap = np.array([[1.0, 1.0]], np.float32)
        c = np.array([10.0, 20.0], np.float32)
        idx = {"w": np.array([[0, 1]], np.int32)}
        got_r, got_s, got_c = random_sync(
            {"w": jnp.asarray(w)},
            {"w": jnp.asarray(snap)},
            {"w": jnp.asarray(c)},
            jax.tree.map(jnp.asarray, idx),
        )
        np.testing.assert_allclose(np.asarray(got_r["w"]), [[11.0, 23.0]])
        np.testing.assert_allclose(np.asarray(got_c["w"]), [11.0, 23.0])
        np.testing.assert_allclose(np.asarray(got_s["w"]), [[11.0, 23.0]])

    def test_sample_indices_unique_and_sized(self):
        shapes = {"w": (10, 10), "b": (7,)}
        idx = sample_sync_indices(
            np.random.RandomState(0), shapes, nreplicas=4, ratio=0.25
        )
        assert idx["w"].shape == (4, 25)
        assert idx["b"].shape == (4, 1)
        for row in idx["w"]:
            assert len(set(row.tolist())) == len(row)
            assert row.max() < 100


class TestCadence:
    def test_sync_now_predicate(self):
        # every 4 steps, strictly after warmup 10 (param_manager.cc:155-159)
        fires = [s for s in range(30) if sync_now(s, 4, 10)]
        assert fires == [11, 15, 19, 23, 27]
        assert not any(sync_now(s, 0, 0) for s in range(10))

    def test_sync_ratio_formula(self):
        # SyncConfig (param_manager.cc:85-93): ratio = B*nservers/throughput
        r = sync_ratio(
            compute_time_s=1.0,
            model_mb=200.0,
            nworkers=4,
            nservers=2,
            bandwidth_mbps=100.0,
        )
        assert r == pytest.approx(100.0 * 2 / (200.0 * 4))
        assert sync_ratio(1.0, 1.0, 1, 1, 1e9) == 1.0


# ---------------------------------------------------------------------
# ReplicaTrainer on the virtual mesh
# ---------------------------------------------------------------------


def _replica_conf(tmp_path, **kw):
    data = (
        synthetic_arrays(640, seed=1),
        synthetic_arrays(128, seed=1, noise_seed=2),
    )
    cfg = make_conf(tmp_path, *data, **kw)
    return cfg


def _set_sync(cfg, param_type, moving_rate=0.5, sync_frequency=2, warmup=4):
    cfg.updater.param_type = param_type
    cfg.updater.moving_rate = moving_rate
    cfg.updater.sync_frequency = sync_frequency
    cfg.updater.warmup_steps = warmup
    return cfg


class TestReplicaTrainer:
    def test_bootstrap_broadcasts_replica0(self, tmp_path):
        cfg = _set_sync(
            _replica_conf(tmp_path, train_steps=5), "Elastic", warmup=4
        )
        t = ReplicaTrainer(
            cfg, mesh=build_mesh(4, 1), seed=0, log=lambda s: None,
            prefetch=False,
        )
        # replicas start distinct (per-group init)
        w = np.asarray(t.params["fc1/weight"])
        assert np.abs(w[0] - w[1]).max() > 0
        for s in range(4):
            t.train_one_batch(s)
        # step 3 crosses warmup: center == every replica
        w = np.asarray(t.params["fc1/weight"])
        c = np.asarray(t.center["fc1/weight"])
        for i in range(4):
            np.testing.assert_allclose(w[i], c, rtol=1e-6)

    def test_elastic_trains_and_contracts(self, tmp_path):
        cfg = _set_sync(
            _replica_conf(tmp_path, train_steps=40, lr=0.1),
            "Elastic",
            moving_rate=0.3,
            sync_frequency=2,
            warmup=4,
        )
        t = ReplicaTrainer(
            cfg, mesh=build_mesh(8, 1), seed=0, log=lambda s: None,
            prefetch=False,
        )
        t.run()
        # replicas stay within a bounded spread of the center
        w = np.asarray(t.params["fc1/weight"])
        c = np.asarray(t.center["fc1/weight"])
        assert np.abs(w - c).max() < 1.0
        # and the center model actually learned the synthetic problem
        from test_trainer import final_test_accuracy

        assert final_test_accuracy(t) > 0.9

    def test_random_sync_trains(self, tmp_path):
        cfg = _set_sync(
            _replica_conf(tmp_path, train_steps=40, lr=0.1),
            "RandomSync",
            moving_rate=0.0,
            sync_frequency=2,
            warmup=4,
        )
        cluster = parse_cluster_config(
            'nworkers: 4 nservers: 1 workspace: "%s" bandwidth: 1e9'
            % str(tmp_path / "ws")
        )
        t = ReplicaTrainer(
            cfg, cluster, mesh=build_mesh(4, 1), seed=0, log=lambda s: None,
            prefetch=False,
        )
        t.run()
        assert t.sample_ratio == 1.0  # huge bandwidth -> full sync
        from test_trainer import final_test_accuracy

        assert final_test_accuracy(t) > 0.9

    def test_sample_ratio_adapts_to_bandwidth(self, tmp_path):
        cfg = _set_sync(
            _replica_conf(tmp_path, train_steps=8), "RandomSync", warmup=4
        )
        cluster = parse_cluster_config(
            'nworkers: 4 nservers: 1 workspace: "%s" bandwidth: 1e-6'
            % str(tmp_path / "ws")
        )
        t = ReplicaTrainer(
            cfg, cluster, mesh=build_mesh(4, 1), seed=0, log=lambda s: None,
            prefetch=False,
        )
        t.run()
        assert 0.0 < t.sample_ratio < 1.0

    def test_checkpoint_resume_reproduces_uninterrupted_run(self, tmp_path):
        """Kill-and-resume restores replicas AND the server state (center +
        snapshot live in the .server sidecar), reproducing the
        uninterrupted trajectory."""
        import os

        from singa_tpu.config.schema import ClusterConfig

        data = (
            synthetic_arrays(512, seed=1),
            synthetic_arrays(128, seed=1, noise_seed=2),
        )

        def mk(sub, steps, ckfreq=0):
            return _set_sync(
                make_conf(
                    tmp_path / sub, *data, train_steps=steps,
                    checkpoint_frequency=ckfreq,
                ),
                "Elastic", moving_rate=0.3, sync_frequency=2, warmup=4,
            )

        t_a = ReplicaTrainer(
            mk("a", 16), mesh=build_mesh(4, 1), seed=3, log=lambda s: None,
            prefetch=False,
        )
        t_a.run()

        cluster = ClusterConfig()
        cluster.workspace = str(tmp_path / "ws")
        t_b = ReplicaTrainer(
            mk("b", 12, ckfreq=8), cluster, mesh=build_mesh(4, 1), seed=3,
            log=lambda s: None, prefetch=False,
        )
        t_b.run()
        ckpt = os.path.join(cluster.workspace, "checkpoints", "step_8.npz")
        assert os.path.exists(ckpt) and os.path.exists(ckpt + ".server")

        cfg_c = mk("c", 16)
        cfg_c.checkpoint = ckpt
        t_c = ReplicaTrainer(
            cfg_c, mesh=build_mesh(4, 1), seed=3, log=lambda s: None,
            prefetch=False,
        )
        assert t_c.start_step == 8 and t_c._bootstrapped
        # stream positions ride in the checkpoint (no manual surgery)
        for pipe in t_c._pipelines[id(t_c.train_net)].values():
            assert pipe.position == (8 * 4 * 64) % pipe.n
        t_c.run()

        for name in t_a.params:
            np.testing.assert_allclose(
                np.asarray(t_a.params[name]),
                np.asarray(t_c.params[name]),
                rtol=2e-5, atol=2e-6,
                err_msg=f"param {name} diverged after resume",
            )
            np.testing.assert_allclose(
                np.asarray(t_a.center[name]),
                np.asarray(t_c.center[name]),
                rtol=2e-5, atol=2e-6,
            )

    def test_rejects_unknown_protocol(self, tmp_path):
        cfg = _set_sync(_replica_conf(tmp_path, train_steps=2), "Elastic")
        cfg.updater.param_type = "Bogus"
        with pytest.raises(ConfigError):
            ReplicaTrainer(
                cfg, mesh=build_mesh(2, 1), seed=0, log=lambda s: None,
                prefetch=False,
            )

    def test_make_trainer_dispatch(self, tmp_path):
        cfg = _set_sync(_replica_conf(tmp_path, train_steps=2), "Elastic")
        asyn = parse_cluster_config(
            'nworkers: 4 nservers: 2 workspace: "%s"' % str(tmp_path / "a")
        )
        sync = parse_cluster_config(
            'nworkers: 4 nservers: 2 synchronous: true workspace: "%s"'
            % str(tmp_path / "s")
        )
        t1 = make_trainer(
            cfg, asyn, mesh=build_mesh(4, 1), log=lambda s: None,
            prefetch=False,
        )
        assert isinstance(t1, ReplicaTrainer)
        t2 = make_trainer(
            cfg, sync, mesh=build_mesh(4, 1), log=lambda s: None,
            prefetch=False,
        )
        assert isinstance(t2, Trainer) and not isinstance(t2, ReplicaTrainer)


class TestReplicaComposition:
    """Replica protocols x kLayerPartition: the reference
    composes intra-group model partitioning with cross-group async sync
    freely (group_size>1 partitions the net, src/worker/neuralnet.cc:55-56,
    while Elastic/RandomSync reconcile the groups, src/utils/param.cc:
    216-256). Here that composition is the (replica, model) mesh branch of
    trainer/replica.py (_rep_param_sh prepends DATA_AXIS to each param's
    kLayerPartition spec). Oracle: a (4 replicas x 2-way model) mesh must
    reproduce the (4 replicas x 1) trajectory exactly — partitioning is a
    layout choice, the protocol math must not notice it."""

    def _run(self, tmp_path, mesh, protocol, **sync_kw):
        cfg = _set_sync(
            _replica_conf(tmp_path, train_steps=12, lr=0.1),
            protocol, **sync_kw,
        )
        cfg.neuralnet.partition_type = "kLayerPartition"
        cluster = parse_cluster_config(
            'nworkers: 8 nservers: 1 workspace: "%s" bandwidth: 1e9'
            % str(tmp_path / "ws")
        )
        t = ReplicaTrainer(
            cfg, cluster, mesh=mesh, seed=5, log=lambda s: None,
            prefetch=False,
        )
        t.run()
        return t

    def _assert_same(self, t_a, t_b):
        for n in t_a.params:
            np.testing.assert_allclose(
                np.asarray(t_a._unpad_stored(t_a.params)[n]),
                np.asarray(t_b._unpad_stored(t_b.params)[n]),
                rtol=2e-4, atol=1e-5, err_msg=f"param {n} diverged",
            )
        for n in t_a.center:
            np.testing.assert_allclose(
                np.asarray(t_a._unpad_one(n, t_a.center[n])),
                np.asarray(t_b._unpad_one(n, t_b.center[n])),
                rtol=2e-4, atol=1e-5, err_msg=f"center {n} diverged",
            )

    def test_elastic_on_replica_x_model_mesh(self, tmp_path):
        t41 = self._run(
            tmp_path / "e41", build_mesh(4, 1), "Elastic",
            moving_rate=0.3, sync_frequency=2, warmup=4,
        )
        t42 = self._run(
            tmp_path / "e42", build_mesh(4, 2), "Elastic",
            moving_rate=0.3, sync_frequency=2, warmup=4,
        )
        # the model-axis branch actually executed: params carry a real
        # (replica, ..., model) sharding, not full replication
        w = t42.params["fc1/weight"]
        assert MODEL_AXIS in jax.tree.leaves(
            [ax for ax in w.sharding.spec if ax is not None]
        )
        self._assert_same(t41, t42)

    def test_random_sync_on_replica_x_model_mesh(self, tmp_path):
        t41 = self._run(
            tmp_path / "r41", build_mesh(4, 1), "RandomSync",
            moving_rate=0.0, sync_frequency=2, warmup=4,
        )
        t42 = self._run(
            tmp_path / "r42", build_mesh(4, 2), "RandomSync",
            moving_rate=0.0, sync_frequency=2, warmup=4,
        )
        assert t41.sample_ratio == 1.0 and t42.sample_ratio == 1.0
        self._assert_same(t41, t42)


class TestReplicaProductionEngine:
    """Round-3 promotion: device cache + scan chunks + buffers make the
    ReplicaTrainer a first-class engine."""

    def test_chunked_run_matches_per_step_run(self, tmp_path):
        """run() (device-cached, sync-window chunks) reproduces the
        per-step trajectory exactly: same batch order, same rng folds,
        same protocol rounds at the same steps."""
        cfg_a = _set_sync(
            _replica_conf(tmp_path / "a", train_steps=14), "Elastic",
            moving_rate=0.3, sync_frequency=4, warmup=4,
        )
        t_a = ReplicaTrainer(
            cfg_a, mesh=build_mesh(4, 1), seed=2, log=lambda s: None,
            prefetch=False,
        )
        assert t_a._cached and t_a._can_chunk()
        t_a.run()

        cfg_b = _set_sync(
            _replica_conf(tmp_path / "b", train_steps=14), "Elastic",
            moving_rate=0.3, sync_frequency=4, warmup=4,
        )
        t_b = ReplicaTrainer(
            cfg_b, mesh=build_mesh(4, 1), seed=2, log=lambda s: None,
            prefetch=False, device_cache=False,
        )
        assert not t_b._cached
        for s in range(14):
            t_b.run_one_batch(s)
        for n in t_a.params:
            np.testing.assert_allclose(
                np.asarray(t_a.params[n]), np.asarray(t_b.params[n]),
                rtol=2e-5, atol=2e-6, err_msg=n,
            )
            np.testing.assert_allclose(
                np.asarray(t_a.center[n]), np.asarray(t_b.center[n]),
                rtol=2e-5, atol=2e-6,
            )

    def test_freq1_warmup_boundary_chunk_matches_per_step(self, tmp_path):
        """sync_frequency 1 starting exactly at the warmup boundary:
        sync_now requires step > warmup, so the first post-warmup step
        must NOT sync — a naive multi-window stack would give it a
        spurious round (review-caught r5). Oracle: chunked == per-step."""
        cfg_a = _set_sync(
            _replica_conf(tmp_path / "a", train_steps=10), "Elastic",
            moving_rate=0.3, sync_frequency=1, warmup=4,
        )
        t_a = ReplicaTrainer(
            cfg_a, mesh=build_mesh(4, 1), seed=2, log=lambda s: None,
            prefetch=False,
        )
        t_a.run()
        cfg_b = _set_sync(
            _replica_conf(tmp_path / "b", train_steps=10), "Elastic",
            moving_rate=0.3, sync_frequency=1, warmup=4,
        )
        t_b = ReplicaTrainer(
            cfg_b, mesh=build_mesh(4, 1), seed=2, log=lambda s: None,
            prefetch=False, device_cache=False,
        )
        for s in range(10):
            t_b.run_one_batch(s)
        for n in t_a.params:
            np.testing.assert_allclose(
                np.asarray(t_a.params[n]), np.asarray(t_b.params[n]),
                rtol=2e-5, atol=2e-6, err_msg=n,
            )

    def test_chunk_windows_respect_sync_cadence(self, tmp_path):
        cfg = _set_sync(
            _replica_conf(tmp_path, train_steps=20), "Elastic",
            moving_rate=0.3, sync_frequency=4, warmup=4,
        )
        t = ReplicaTrainer(
            cfg, mesh=build_mesh(4, 1), seed=0, log=lambda s: None,
            prefetch=False,
        )
        # pre-bootstrap: single steps; after: windows end at sync fires
        assert t._chunk_len(0) == 1
        for s in range(6):
            t.train_one_batch(s)
        assert t._bootstrapped
        # sync fires where (s+1) % 4 == 0. Step 8 is window-ALIGNED and
        # Elastic rounds are device-pure, so WHOLE windows stack into
        # one multi-window program: 12 remaining steps = 3 windows
        # (r5 multi-window fusion; every sub-window still ends at a
        # fire — the chunk==per-step oracle above pins equivalence)
        assert t._chunk_len(8) == 12
        # unaligned starts still stop at the next fire
        assert t._chunk_len(9) == 3

    def test_replica_batchnorm_trains_per_replica_buffers(self, tmp_path):
        """Stateful layers now work under async protocols: each replica
        evolves its own BN running stats (leading replica axis)."""
        from singa_tpu.data.loader import write_records

        from tests.test_resnet import _bn_net

        shard = str(tmp_path / "shard")
        write_records(shard, *synthetic_arrays(256, seed=4))
        cfg = _set_sync(
            _bn_net(shard, batch=16), "Elastic",
            moving_rate=0.3, sync_frequency=2, warmup=2,
        )
        cfg.train_steps = 8
        cfg.test_steps = 2
        t = ReplicaTrainer(
            cfg, mesh=build_mesh(4, 1), seed=0, log=lambda s: None,
            prefetch=False,
        )
        t.run()
        for name, buf in t.buffers.items():
            arr = np.asarray(buf)
            assert arr.shape[0] == 4, name  # per-replica state
            assert np.isfinite(arr).all()
        # running stats actually moved off their init values
        moved = [
            np.abs(np.asarray(b) - b0).max()
            for (n, b), b0 in zip(
                sorted(t.buffers.items()),
                [v for _, v in sorted(
                    t.train_net.init_buffers().items()
                )],
            )
        ]
        assert max(moved) > 0
        # eval path uses replica 0's stats without error
        acc = t.evaluate(t.test_net, 2, "test", 8)
        assert np.isfinite(list(acc.values())[0]["loss"])
