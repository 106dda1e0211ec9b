"""Attention stack: dense reference vs Pallas flash kernel (interpret
mode on CPU) vs ring attention on the virtual mesh; transformer LM
training with each attention path. These are singa-tpu extensions — the
reference is pre-transformer (SURVEY §5) — making long-context /
sequence-parallel training first-class."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.models import TransformerConfig, init_lm, lm_apply, lm_loss
from singa_tpu.ops.attention import (
    attention,
    block_attn_finish,
    block_attn_init,
    block_attn_update,
    flash_attention,
)
from singa_tpu.parallel.ring import build_sp_mesh, ring_attention


def qkv(shape=(2, 2, 256, 32), seed=0):
    rng = np.random.RandomState(seed)
    return tuple(
        jnp.asarray(rng.randn(*shape).astype(np.float32)) for _ in range(3)
    )


class TestFlashKernel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        q, k, v = qkv()
        ref = attention(q, k, v, causal=causal)
        got = flash_attention(q, k, v, causal, 128, 128, True)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-5
        )

    def test_gradients_match_dense(self):
        q, k, v = qkv((1, 2, 256, 32))

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True, 128, 128, True) ** 2)

        def f_ref(q, k, v):
            return jnp.sum(attention(q, k, v, causal=True) ** 2)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4
            )

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("blocks", [(128, 128), (64, 128), (128, 64)])
    def test_pallas_backward_matches_dense(self, causal, blocks):
        """The dedicated dq/dkv backward kernels (not dense recompute)
        reproduce reference gradients across block geometries."""
        bq, bk = blocks
        q, k, v = qkv((1, 2, 256, 32))
        g = jnp.asarray(
            np.random.RandomState(9).randn(1, 2, 256, 32).astype(np.float32)
        )

        def f_flash(q, k, v):
            return jnp.vdot(flash_attention(q, k, v, causal, bq, bk, True), g)

        def f_ref(q, k, v):
            return jnp.vdot(attention(q, k, v, causal=causal), g)

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4, err_msg=f"d{name}"
            )

    @pytest.mark.parametrize("causal", [False, True])
    def test_streamed_variant_matches_dense(self, causal, monkeypatch):
        """Force the HBM-streaming kernels (the long-context path that
        staged K/V cannot serve) and pin values AND all three grads
        against the dense reference."""
        # the staging budget is frozen at import (jit caches are not
        # keyed on env vars) — patch the module global, not the env
        from singa_tpu.ops import attention as attn_mod

        monkeypatch.setattr(attn_mod, "_FLASH_STAGE_BYTES", 0.0)
        q, k, v = qkv((1, 2, 256, 32))
        g = jnp.asarray(
            np.random.RandomState(11).randn(1, 2, 256, 32).astype(np.float32)
        )

        def f_flash(q, k, v):
            return jnp.vdot(flash_attention(q, k, v, causal, 64, 64, True), g)

        def f_ref(q, k, v):
            return jnp.vdot(attention(q, k, v, causal=causal), g)

        np.testing.assert_allclose(
            np.asarray(flash_attention(q, k, v, causal, 64, 64, True)),
            np.asarray(attention(q, k, v, causal=causal)),
            atol=1e-4,
        )
        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-4, err_msg=f"d{name}"
            )

    def test_cross_attention_lengths_fall_back(self):
        """Sq != Sk (e.g. cross-attention / decode) must hit the dense
        path, which supports it, instead of crashing in the kernel."""
        rng = np.random.RandomState(3)
        q = jnp.asarray(rng.randn(1, 1, 128, 16).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 1, 256, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(1, 1, 256, 16).astype(np.float32))
        ref = attention(q, k, v, causal=True)
        got = flash_attention(q, k, v, True, 128, 128, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)

    def test_uneven_seq_falls_back(self):
        q, k, v = qkv((1, 1, 100, 16))  # 100 % 128 != 0
        ref = attention(q, k, v)
        got = flash_attention(q, k, v)  # off a TPU: dense, quietly
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)

    def test_unserved_flash_request_on_a_tpu_says_so(self, monkeypatch):
        """On a TPU a call the kernel cannot serve runs dense — and
        says so at trace time, instead of a dense S x S score tensor
        arriving unannounced. (The platform answer is what the test
        steers; the kernel itself is never reached.)"""
        import jax

        from singa_tpu.ops import attention as A

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        q, k, v = qkv((1, 1, 100, 16))
        with pytest.warns(RuntimeWarning, match="DENSE reference"):
            assert A._use_kernel(q, k, 128, 128, None) is False
        q, k, v = qkv((1, 1, 256, 16))
        assert A._use_kernel(q, k, 128, 128, None) is True

    def test_block_accumulation_order_invariant(self):
        """Online-softmax folding gives the same answer whatever order the
        K/V blocks visit in — the property ring rotation relies on."""
        q, k, v = qkv((1, 1, 8, 16))
        kb = jnp.split(k, 4, axis=2)
        vb = jnp.split(v, 4, axis=2)
        offs = [0, 2, 4, 6]
        for order in ([0, 1, 2, 3], [3, 1, 0, 2]):
            out, m, l = block_attn_init(q)
            for i in order:
                out, m, l = block_attn_update(
                    q, kb[i], vb[i], out, m, l,
                    q_offset=0, k_offset=offs[i], causal=True,
                )
            got = block_attn_finish(out, m, l)
            np.testing.assert_allclose(
                np.asarray(got),
                np.asarray(attention(q, k, v, causal=True)),
                atol=1e-5,
            )


class TestRingAttention:
    @pytest.mark.parametrize("mesh_shape", [(1, 8), (2, 4)])
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, mesh_shape, causal):
        q, k, v = qkv()
        mesh = build_sp_mesh(*mesh_shape)
        got = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh, causal=causal)
        )(q, k, v)
        ref = attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), atol=2e-5
        )

    def test_gradients_match_dense(self):
        q, k, v = qkv((1, 2, 128, 16))
        mesh = build_sp_mesh(1, 8)
        # jitted (r5): the eager ring ppermute loop serialized per-op on
        # the virtual mesh — same equivalence assertion, less wall
        g1 = jax.jit(jax.grad(
            lambda q: jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)
        ))(q)
        g2 = jax.jit(jax.grad(
            lambda q: jnp.sum(attention(q, k, v, causal=True) ** 2)
        ))(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-4)

    def test_output_stays_seq_sharded(self):
        q, k, v = qkv()
        mesh = build_sp_mesh(1, 8)
        out = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh, causal=False)
        )(q, k, v)
        assert not out.sharding.is_fully_replicated

    def test_bf16_accumulates_in_fp32(self):
        """Ring statistics accumulate in fp32 like the Pallas kernel, so
        bf16 inputs track the fp32 dense result to bf16 resolution."""
        q, k, v = qkv((1, 2, 256, 32), seed=7)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        mesh = build_sp_mesh(1, 8)
        got = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh, causal=True)
        )(qb, kb, vb)
        assert got.dtype == jnp.bfloat16
        ref = attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got, dtype=np.float32), np.asarray(ref),
            atol=0.02, rtol=0.02,
        )

    def test_size_one_axis_short_circuits(self):
        q, k, v = qkv((1, 1, 64, 16))
        mesh = build_sp_mesh(1, 1, jax.devices()[:1])
        got = ring_attention(q, k, v, mesh, causal=True)
        np.testing.assert_allclose(
            np.asarray(got),
            np.asarray(attention(q, k, v, causal=True)),
            atol=1e-6,
        )


def _toy_tokens(n, s, vocab, seed=0):
    """Deterministic learnable streams: each sequence cycles a fixed
    class-dependent period, so next-token prediction is solvable."""
    rng = np.random.RandomState(seed)
    base = rng.randint(1, vocab, size=(4, 8))
    rows = []
    for i in range(n):
        pat = base[i % 4]
        rows.append(np.tile(pat, s // 8 + 1)[:s])
    return jnp.asarray(np.stack(rows).astype(np.int32))


class TestTransformerLM:
    def _train(self, cfg, tokens, mesh=None, steps=60, lr=1e-2):
        import optax

        params = init_lm(jax.random.PRNGKey(0), cfg)
        opt = optax.adam(lr)
        opt_state = opt.init(params)

        @jax.jit
        def step(params, opt_state):
            loss, g = jax.value_and_grad(
                lambda p: lm_loss(p, tokens, cfg, mesh)
            )(params)
            updates, opt_state = opt.update(g, opt_state)
            return optax.apply_updates(params, updates), opt_state, loss

        loss0 = None
        for _ in range(steps):
            params, opt_state, loss = step(params, opt_state)
            if loss0 is None:
                loss0 = float(loss)
        return loss0, float(loss)

    def test_dense_lm_learns(self):
        cfg = TransformerConfig(vocab=32, d_model=64, n_heads=2, n_layers=2,
                                d_ff=128, max_len=64)
        tokens = _toy_tokens(8, 64, 32)
        loss0, loss1 = self._train(cfg, tokens)
        assert loss1 < 0.3 * loss0, (loss0, loss1)

    def test_ring_lm_matches_dense_loss(self):
        """Same params, same batch: ring-sharded loss == dense loss."""
        cfg_d = TransformerConfig(vocab=32, d_model=64, n_heads=2,
                                  n_layers=1, d_ff=128, max_len=64)
        cfg_r = dataclasses.replace(cfg_d, attn="ring")
        tokens = _toy_tokens(4, 64, 32)
        params = init_lm(jax.random.PRNGKey(1), cfg_d)
        mesh = build_sp_mesh(1, 8)
        dense = float(lm_loss(params, tokens, cfg_d))
        ring = float(jax.jit(
            lambda p: lm_loss(p, tokens, cfg_r, mesh)
        )(params))
        assert abs(dense - ring) < 1e-4, (dense, ring)

    def test_ring_lm_learns(self):
        cfg = TransformerConfig(vocab=32, d_model=64, n_heads=2, n_layers=1,
                                d_ff=128, max_len=64, attn="ring")
        tokens = _toy_tokens(4, 64, 32)
        mesh = build_sp_mesh(2, 4)
        loss0, loss1 = self._train(cfg, tokens, mesh=mesh, steps=60)
        assert loss1 < 0.3 * loss0, (loss0, loss1)


class TestAutoAttention:
    """auto_attention picks dense below the per-device score-footprint
    threshold and the kernel above it."""

    def _spy(self, monkeypatch):
        from singa_tpu.ops import attention as A

        calls = []
        real_dense, real_flash = A.attention, A.flash_attention
        monkeypatch.setattr(
            A, "attention",
            lambda *a, **k: calls.append("dense") or real_dense(*a, **k),
        )
        monkeypatch.setattr(
            A, "flash_attention",
            lambda *a, **k: calls.append("flash") or real_flash(*a, **k),
        )
        return calls

    def test_small_goes_dense_large_goes_kernel(self, monkeypatch):
        import jax
        import jax.numpy as jnp

        from singa_tpu.ops.attention import auto_attention

        calls = self._spy(monkeypatch)
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 64, 16))
        auto_attention(q, q, q, causal=True)
        assert calls == ["dense"]  # 2*2*64*64*8B = 0.13 MB << 512

        calls.clear()
        monkeypatch.setenv("SINGA_TPU_DENSE_ATTN_MB", "0.05")
        out = auto_attention(q, q, q, causal=True)
        assert calls[0] == "flash"
        assert jnp.isfinite(out).all()

    def test_n_devices_scales_the_footprint(self, monkeypatch):
        import jax

        from singa_tpu.ops.attention import auto_attention

        calls = self._spy(monkeypatch)
        q = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 64, 16))
        monkeypatch.setenv("SINGA_TPU_DENSE_ATTN_MB", "0.05")
        # sharded over enough devices, the per-device scores fit again
        auto_attention(q, q, q, causal=True, n_devices=8)
        assert calls == ["dense"]
