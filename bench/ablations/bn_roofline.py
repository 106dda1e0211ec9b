"""BatchNorm roofline on the chip: what can the stats pass ever give back?

VERDICT r4 #2 allows two outcomes for ResNet-50's BN cost (marginal
14.6 ms by layer ablation): close the gap to an estimated ~11 ms floor,
or measure that the stats pass is irreducible under XLA's fusion model.
This harness grounds that choice in real numbers:

  * ResNet-50's 53 BN instances touch 2.71 GB of bf16 activations per
    pass. The fused op's information-theoretic minimum is 8 touches
    (fwd: stats read, normalize read+write; bwd: reduction read of
    (dy, x), dx-pass read of (dy, x) + write) = 21.7 GB = 26.5 ms at
    the v5e's 819 GB/s — ABOVE the measured marginal cost. XLA already
    shares reads with neighboring fusions (conv-bwd reads the same x
    and dy); the r4 "~11 ms floor" arithmetic was mis-derived
    (5 x 2.9 GB / 819 GB/s = 17.7 ms, not 11).
  * The stats pass itself is ONE touch: 2.71 GB = 3.3 ms at peak.
    A perfect conv-epilogue stats kernel (two-phase conv+BN Pallas,
    which would mean reimplementing conv) can recover AT MOST that:
    46.6 ms -> 43.3 ms = 34.8% MFU. The >=35% bar is out of reach by
    same-math scheduling — hence the opt-in subsample-stats knob.

The microbench below measures the standalone fused op against a pure
elementwise chain of the same byte count, with CSE/constant-folding
defeated (distinct inputs per instance, random cotangents, dx carried).

Run (reserves the chip):  python bench/ablations/bn_roofline.py
"""

import os
import sys
import time

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp

from singa_tpu.ops.norm import batch_norm_train

# (shape, count) — ResNet-50 batch-128 BN instances
SHAPES = [
    ((128, 64, 112, 112), 1),
    ((128, 64, 56, 56), 6),
    ((128, 256, 56, 56), 4),
    ((128, 128, 28, 28), 8),
    ((128, 512, 28, 28), 5),
    ((128, 256, 14, 14), 12),
    ((128, 1024, 14, 14), 7),
    ((128, 512, 7, 7), 6),
    ((128, 2048, 7, 7), 4),
]


def _slope(fn, args, n1=10, n2=30):
    def loop(args, n):
        def body(c, _):
            return fn(c), None

        out, _ = jax.lax.scan(body, args, None, length=n)
        return out

    j1 = jax.jit(lambda a: loop(a, n1))
    j2 = jax.jit(lambda a: loop(a, n2))
    jax.block_until_ready(j1(args))
    jax.block_until_ready(j2(args))
    best = {}
    for name, j, n in (("n1", j1, n1), ("n2", j2, n2)):
        best[name] = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(j(args))
            best[name] = min(best[name], time.perf_counter() - t0)
    return (best["n2"] - best["n1"]) / (n2 - n1)


def make_args():
    """One distinct (x, dy) pair PER INSTANCE (53 total) so CSE cannot
    collapse repeated instances of a shape."""
    key = jax.random.PRNGKey(0)
    xs, dys, gs, bs = [], [], [], []
    for shape, cnt in SHAPES:
        for i in range(cnt):
            key, k1, k2 = jax.random.split(key, 3)
            xs.append(jax.random.normal(k1, shape, jnp.bfloat16))
            dys.append(jax.random.normal(k2, shape, jnp.bfloat16))
    for shape, cnt in SHAPES:
        for _ in range(cnt):
            gs.append(jnp.ones((shape[1],), jnp.bfloat16))
            bs.append(jnp.zeros((shape[1],), jnp.bfloat16))
    return xs, dys, gs, bs


def bn_chain(args):
    """Per instance: y, vjp = vjp(bn, x); (dx,..) = vjp(random dy).
    Carry x' = dx + eps*y so BOTH outputs materialize and the next
    iteration is data-dependent (nothing hoists, nothing folds)."""
    xs, dys, gs, bs = args
    new_xs = []
    for x, dy, g, b in zip(xs, dys, gs, bs):
        def f(x, g, b):
            y, m, v = batch_norm_train(x, g, b, 1e-5, None)
            return y

        y, vjp = jax.vjp(f, x, g, b)
        dx, dg, db = vjp(dy)
        new_xs.append(dx + y * jnp.bfloat16(1e-6))
    return new_xs, dys, gs, bs


def elementwise_chain(args):
    """Same nominal byte count as the BN chain's 8 touches, pure
    elementwise: 4 passes of read(x)+read(dy)->write per instance
    (= 8 array touches of x-sized data), data-dependent carry."""
    xs, dys, gs, bs = args
    new_xs = []
    for x, dy in zip(xs, dys):
        acc = x
        for _ in range(2):
            acc = acc + dy * jnp.bfloat16(0.3)   # read acc, dy; write
            acc = acc * jnp.bfloat16(0.999) + x * jnp.bfloat16(1e-3)
        new_xs.append(acc)
    return new_xs, dys, gs, bs


def main():
    dev = jax.devices()[0]
    print(f"device: {dev.device_kind}")
    args = make_args()
    elems = sum(
        cnt * int(jnp.prod(jnp.array(s))) for s, cnt in SHAPES
    )
    gb = elems * 2 / 1e9  # one touch of every instance, bf16
    print(f"activation footprint: {gb:.2f} GB per touch, 53 instances")
    for label, fn, touches in (
        ("fused BN fwd+bwd (8-touch minimum)", bn_chain, 8),
        ("pure elementwise, same 8-touch bytes", elementwise_chain, 8),
    ):
        s = _slope(fn, args)
        bw = gb * touches / s
        print(
            f"{label:42s} {s * 1e3:7.2f} ms"
            f"  ({gb * touches:5.1f} GB -> {bw:6.0f} GB/s apparent)"
        )
    print(
        "stats-pass upper bound: one touch = "
        f"{gb:.2f} GB = {gb / 819 * 1e3:.1f} ms at 819 GB/s peak"
    )


if __name__ == "__main__":
    main()
