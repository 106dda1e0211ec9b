"""The grouped form of ``moe_topk_ffn`` against the dense one (PR 35).

On the CPU the chooser always says dense, so these tests steer it
(``choose_expert_form`` is patched, the kernel runs through the Pallas
interpreter as ``_experts_grouped`` decides from the platform) and hold
the grouped form to the dense one: values, the three counters and the
gradients, over the routings a drop-free layer must survive. The
chooser itself is held to its table: which program of which cell takes
which form, and why.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu.models.transformer import TransformerConfig
from singa_tpu.parallel import moe
from singa_tpu.serve import Engine, EngineConfig

D, F, E, K = 32, 16, 8, 2


def layer(held=(2, 4), score="sigmoid", bias=True, shared=16, seed=0):
    """Parameters of a layer of 8 experts, top-2, holding ``held`` =
    (first, count) of them (None: all)."""
    p = moe.init_moe_topk(
        jax.random.PRNGKey(seed), D, F, E,
        held=held[1] if held else 0, bias=bias, shared_d_ff=shared,
    )
    kw = dict(score=score, scale=2.5 if score == "sigmoid" else 1.0,
              held_from=held[0] if held else 0)
    return p, kw


def tokens(n, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, n, D))


def both_forms(monkeypatch, fn):
    """``fn()`` under the dense form, then under the grouped one."""
    dense = fn()
    monkeypatch.setattr(
        moe, "choose_expert_form", lambda *a: "grouped: the test says so"
    )
    return dense, fn()


def steer(p, experts):
    """A selection bias that sends every token to ``experts``."""
    bias = np.zeros(E, np.float32)
    bias[list(experts)] = 10.0
    return {**p, moe.MOE_BIAS_PARAM: jnp.asarray(bias)}


# name -> (layer arguments, tokens a pass, experts every token is sent
# to or None, valid tokens or None, pairs the held experts must count)
ROUTINGS = {
    "flat_router": (dict(), 24, None, None, None),
    # one group holds every token: N pairs on one held expert
    "every_token_on_one_held_expert": (dict(), 160, (0, 2), None, 160),
    # the worst case a drop-free layer must hold: every token on its
    # full count of held experts, N x min(k, H) pairs, both windows full
    "every_token_on_every_expert_it_can": (dict(), 160, (2, 3), None, 320),
    "no_token_on_any_held_expert": (dict(), 24, (0, 1), None, 0),
    "a_tail_valid_marks_out": (dict(), 24, None, 13, None),
    "whole_layer_softmax": (
        dict(held=None, score="softmax", bias=False, shared=0),
        24, None, None, 48,
    ),
    "share_softmax_no_shared_expert": (
        dict(held=(4, 2), score="softmax", bias=False, shared=0),
        24, None, None, None,
    ),
    "share_sigmoid_with_bias": (dict(held=(5, 3)), 40, None, 33, None),
}


@pytest.mark.parametrize("name", sorted(ROUTINGS))
def test_grouped_is_the_dense_layer(name, monkeypatch):
    args, n, sent, n_valid, pairs = ROUTINGS[name]
    p, kw = layer(**args)
    if sent is not None:
        p = steer(p, sent)
    x = tokens(n)
    valid = None if n_valid is None else (jnp.arange(n) < n_valid)[None]
    (yd, sd), (yg, sg) = both_forms(
        monkeypatch, lambda: moe.moe_topk_ffn(x, p, K, valid, **kw)
    )
    np.testing.assert_array_equal(np.asarray(sd), np.asarray(sg))
    if pairs is not None:
        assert int(sg[2]) == pairs
    keep = slice(None) if n_valid is None else slice(0, n_valid)
    np.testing.assert_allclose(
        np.asarray(yg)[0, keep], np.asarray(yd)[0, keep],
        rtol=2e-5, atol=2e-5,
    )
    # rows that ``valid`` marks out are not computed, and are finite
    assert np.isfinite(np.asarray(yg)).all()


def test_the_worst_case_fills_every_window_and_drops_nothing(monkeypatch):
    """160 tokens x 2 held experts each = 320 pairs = both windows of
    256 rows' worth: each token's row is the sum of both its experts'
    outputs, compared term by term with a loop over experts."""
    p, kw = layer(shared=0)
    p = steer(p, (2, 3))
    x = tokens(160)
    monkeypatch.setattr(moe, "choose_expert_form", lambda *a: "grouped: test")
    y, stats = moe.moe_topk_ffn(x, p, K, **kw)
    assert list(np.asarray(stats)) == [2, 160, 320]
    gates, _ = moe.topk_gates(x[0], p, K, kw["score"], kw["scale"])
    want = 0.0
    for e in (2, 3):
        h = jax.nn.silu(x[0] @ p["w_gate"][e - 2]) * (x[0] @ p["w_up"][e - 2])
        want = want + gates[:, e:e + 1] * (h @ p["w_down"][e - 2])
    np.testing.assert_allclose(
        np.asarray(y[0]), np.asarray(want), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("name", [
    "flat_router", "every_token_on_every_expert_it_can",
    "a_tail_valid_marks_out",
])
def test_gradients_of_both_forms_agree(name, monkeypatch):
    """``_block_apply`` is the training forward too: the grouped
    product carries its own derivative (megablox ``gmm``/``tgmm``) and
    the rows it leaves unwritten are selected out on both sides."""
    args, n, sent, n_valid, _ = ROUTINGS[name]
    p, kw = layer(**args)
    if sent is not None:
        p = steer(p, sent)
    x = tokens(n)
    valid = None if n_valid is None else (jnp.arange(n) < n_valid)[None]
    keep = n if n_valid is None else n_valid

    def loss(p, x):
        y, _ = moe.moe_topk_ffn(x, p, K, valid, **kw)
        return jnp.sum(y[0, :keep] ** 2)

    dense, grouped = both_forms(
        monkeypatch, lambda: jax.grad(loss, argnums=(0, 1))(p, x)
    )
    flat_d, _ = jax.tree_util.tree_flatten_with_path(dense)
    flat_g = jax.tree.leaves(grouped)
    for (path, d), g in zip(flat_d, flat_g):
        assert np.isfinite(np.asarray(g)).all(), path
        scale = float(jnp.abs(d).max()) or 1.0
        np.testing.assert_allclose(
            np.asarray(g) / scale, np.asarray(d) / scale, atol=2e-5,
            err_msg=str(path),
        )


def test_a_tpu_training_pass_differentiates_through_the_form_it_takes(
    monkeypatch,
):
    """A pass of 512 tokens over 12 of 384 experts is grouped on a TPU
    (the chooser's own answer, not a patch): the loss differentiates."""
    assert moe.choose_expert_form(512, 4, 128, 2, "tpu").startswith("grouped")
    p = moe.init_moe_topk(jax.random.PRNGKey(0), D, F, 128, held=4)
    x = tokens(512)
    chose = []
    real = moe.choose_expert_form

    def as_on_a_tpu(n, held, experts, top_k, platform):
        chose.append(real(n, held, experts, top_k, "tpu"))
        return chose[-1]

    monkeypatch.setattr(moe, "choose_expert_form", as_on_a_tpu)
    g = jax.grad(
        lambda p: jnp.sum(moe.moe_topk_ffn(x, p, 2, held_from=8)[0] ** 2)
    )(p)
    assert chose and chose[0].startswith("grouped")
    assert all(np.isfinite(np.asarray(v)).all() for v in g.values())
    assert float(jnp.abs(g["w_down"]).max()) > 0


# -- the chooser ----------------------------------------------------------

# (tokens a pass, held, experts, top-k), platform -> form and a word of
# its reason. K = kimi_k2_serve_long (12 of 384 held, top-8), B =
# sdar_30b_a3b_serve_blocks (128 of 128, top-8)
TABLE = [
    ("K chunk", (512, 12, 384, 8), "tpu", "grouped", "over 300"),
    ("K tick", (48, 12, 384, 8), "tpu", "grouped", "36 % of the held"),
    ("B block step", (256, 128, 128, 8), "tpu", "dense", "weight reads"),
    ("B chunk", (256, 128, 128, 8), "tpu", "dense", "weight reads"),
    ("B at a chunk of 1024", (1024, 128, 128, 8), "tpu", "grouped", "64.0"),
    ("a top-2 of 8 at 512", (512, 8, 8, 2), "tpu", "dense", "half of 512"),
    ("a training pass", (8192, 128, 128, 8), "tpu", "dense", "over 2048"),
    ("K chunk on the CPU", (512, 12, 384, 8), "cpu", "dense", "platform = cpu"),
    ("K tick on the CPU", (48, 12, 384, 8), "cpu", "dense", "platform = cpu"),
    ("B on a GPU", (256, 128, 128, 8), "gpu", "dense", "platform = gpu"),
]


@pytest.mark.parametrize(
    "shape,platform,form,why", [t[1:] for t in TABLE],
    ids=[t[0].replace(" ", "_") for t in TABLE],
)
def test_the_chooser_by_table(shape, platform, form, why):
    said = moe.choose_expert_form(*shape, platform)
    assert said.split(":")[0] == form, said
    assert why in said, said


def tiny_engine(**serving):
    cfg = TransformerConfig(
        vocab=40, d_model=32, n_heads=4, n_layers=2, d_ff=48, max_len=32,
        norm="rmsnorm", pos="rope", head_dim=8, tied_head=False,
        mlp="swiglu", dense_layers=1, moe_experts=8, moe_top_k=2,
        moe_d_ff=16, moe_score="sigmoid", moe_bias=True,
        moe_shared_d_ff=16, moe_held=(2, 4),
    )
    from singa_tpu.models.transformer import init_lm

    return Engine(init_lm(jax.random.PRNGKey(0), cfg), cfg, EngineConfig(
        slots=2, kv_block_len=8, max_prefill_chunk=4, **serving
    ))


def test_the_engine_records_the_form_of_each_program():
    from singa_tpu.serve import Scheduler

    eng = tiny_engine()
    assert eng.expert_forms == {
        "jit__decode": "dense: platform = cpu",
        "jit__prefill": "dense: platform = cpu",
    }
    sched = Scheduler(eng)
    assert sched.occupancy()["expert_forms"] == eng.expert_forms
    # the same question about a TPU, at the cells' shapes
    k2 = TransformerConfig(
        vocab=64, d_model=64, n_heads=4, n_layers=2, d_ff=64, max_len=64,
        moe_experts=384, moe_top_k=8, moe_d_ff=16, moe_held=(96, 12),
        dense_layers=1,
    )
    forms = Engine._expert_forms(
        k2, EngineConfig(slots=48, max_prefill_chunk=512), "tpu"
    )
    assert [f.split(":")[0] for f in forms.values()] == ["grouped"] * 2
    assert list(forms) == ["jit__decode", "jit__prefill"]
    sdar = TransformerConfig(
        vocab=64, d_model=64, n_heads=4, n_layers=2, d_ff=64, max_len=64,
        moe_experts=128, moe_top_k=8, moe_d_ff=16, diffusion_block=4,
        mask_id=63,
    )
    forms = Engine._expert_forms(
        sdar, EngineConfig(slots=64, max_prefill_chunk=256), "tpu"
    )
    assert list(forms) == ["jit__block_step", "jit__prefill"]
    assert all(f.startswith("dense: 256 tokens") for f in forms.values())
    dense_model = TransformerConfig(
        vocab=64, d_model=64, n_heads=4, n_layers=2, d_ff=64, max_len=64,
    )
    assert Engine._expert_forms(dense_model, EngineConfig(), "tpu") == {}


def test_the_grouped_kernel_sits_under_the_layers_scopes(monkeypatch):
    """The three grouped products are megablox ``gmm`` calls
    (``jit(gmm)/pallas_call``: the name a compiled program's text and a
    device trace show) under ``experts`` and ``combine``; the sort and
    the group sizes under ``route``; the shared expert stays a plain
    product under ``shared``."""
    monkeypatch.setattr(moe, "choose_expert_form", lambda *a: "grouped: test")
    p, kw = layer()
    x = tokens(24)

    def run(x, p):
        with jax.named_scope("moe"):
            return moe.moe_topk_ffn(x, p, K, **kw)

    text = jax.jit(run).lower(x, p).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope, inside in (
        ("experts", "jit(gmm)/"), ("combine", "jit(gmm)/"),
        ("route", "sort"), ("shared", "dot_general"),
    ):
        assert any(
            n.startswith("jit(run)/moe/") and f"/{scope}/" in n
            and inside in n for n in names
        ), scope
    tiling = moe._grouped_tiling(7168, 2048, 2), moe._grouped_tiling(2048, 7168, 2)
    assert tiling == ((128, 1024, 2048), (128, 1024, 1792))
    # a weight block of 4 MiB or under, whole lanes
    assert all(tk * tn * 2 <= 4 << 20 and tn % 128 == 0 for _, tk, tn in tiling)
    assert moe._grouped_tiling(32, 16, 4) == (128, 32, 16)
