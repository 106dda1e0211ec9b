"""The main path's kernels, compiled for a DESCRIBED v5e — no chip.

The TPU's compiler is installed beside the CPU backend and compiles for
a topology that is described, not attached; what it refuses here it
refuses on the chip (interpret mode hid a refused block shape in the
paged kernel and a scoped-VMEM overrun in ``quant_acc`` until this file
asked). A compile that passes is not a chip run: ``chip_smoke.py``
checks the answers.

Rules this file keeps (the libtpu lock allows one process, and every
xdist worker imports every test file): the topology is described inside
a module-scoped, non-autouse fixture that skips where it cannot be;
nothing chip-related happens at import, in a ``skipif`` or in a
``parametrize`` argument; compiles run in the test's own process; the
persistent cache is off around them (a described-device entry cannot be
read back and would warn). One file, so one worker holds the library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from singa_tpu.ops.attention import flash_attention
from singa_tpu.ops.paged_attention import (
    fusable,
    paged_attention,
    paged_attention_overlay,
)
from singa_tpu.ops.quantized_collective import quant_acc


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def test_flash_forward_and_backward_compile(one_chip):
    """The streamed-K/V flash kernels at the long-context shape (one
    head of 128 over S=8192): forward, dQ and dK/dV all reach Mosaic."""
    qkv = jax.ShapeDtypeStruct(
        (1, 1, 8192, 128), jnp.bfloat16, sharding=one_chip
    )

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, None, None, False)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert text.count("tpu_custom_call") >= 3


# the serve cells' geometry (slots 8, 2 heads of 128, kv_block_len 16,
# 32-block tables over a 257-block pool), then a geometry off the
# (8, 128) register tile, which the repo's predicate used to refuse
SERVE = dict(slots=8, heads=2, head_dim=128, block_len=16)
OFF_TILE = dict(slots=3, heads=2, head_dim=96, block_len=12)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("q_len", [1, 5])
@pytest.mark.parametrize("form", ["write_then_read", "overlay"])
@pytest.mark.parametrize(
    "geometry", [SERVE, OFF_TILE], ids=["serve", "off_tile"]
)
def test_paged_attention_compiles(one_chip, geometry, form, q_len, dtype):
    """Both kernel forms at the decode (Q=1) and verify (Q=spec_k+1=5)
    shapes — and ``fusable`` says yes exactly where the compiler does."""
    s, h = geometry["slots"], geometry["heads"]
    d, bl, mb = geometry["head_dim"], geometry["block_len"], 32

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    q = sds((s, h, q_len, d), dtype)
    pool = sds((s * mb + 1, h, bl, d), dtype)
    tables = sds((s, mb), jnp.int32)
    rows = sds((s, q_len), jnp.int32)
    assert fusable(bl) is None
    if form == "overlay":
        text = _compiled_text(
            lambda *a: paged_attention_overlay(*a, interpret=False),
            q, pool, pool, tables, rows, q, q, rows,
        )
    else:
        text = _compiled_text(
            lambda *a: paged_attention(*a, interpret=False),
            q, pool, pool, tables, rows,
        )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "n_elements",
    [
        256 * 1024 // 4,   # tinylm_d128's b0_up weight, one of 4 shards
        1224 * 1024,       # aligned, and past what ONE VMEM block held
        1100 * 128,        # rows not a multiple of the grid's block
    ],
)
def test_quant_acc_compiles(one_chip, n_elements):
    """The ring's per-hop kernel walks its chunk through a grid: an
    aligned chunk of any size fits VMEM."""
    text = _compiled_text(
        lambda q, s, x: quant_acc(q, s, x, interpret=False),
        jax.ShapeDtypeStruct((n_elements,), jnp.int8, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n_elements,), jnp.float32, sharding=one_chip),
    )
    assert "tpu_custom_call" in text
