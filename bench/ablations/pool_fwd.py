"""Max-pool FORWARD formulation A/B at AlexNet shapes (chip).

r4 attributed 92us of the AlexNet step to pools and fixed the backward
(phase-decomposed VJP, ops/nn.py); the forward stayed on
lax.reduce_window. Question: would a slice+max forward (k^2 static
strided slices reduced with jnp.maximum — the same trick the backward
uses) beat reduce_window at the AlexNet pool shapes?

MEASURED ANSWER (r5, chip, min-of-3, 200-vs-1000-iteration slope):
at (256,32,32,32) k3s2 — the largest AlexNet pool —
  reduce_window  47 us/call   (vs a 64 us harness floor: in the noise)
  slice+max     166 us/call   (3.5x WORSE: nine strided passes lose to
                               the fused window reduction)
At the two SMALLER AlexNet shapes (2.1M / 1.05M elems) the microbench
repeatedly showed slices ~5-20us cheaper — but the IN-MODEL A/B killed
it: gating a slice forward at <=3M elems into max_pool2d measured the
real cifar_alexnet bench row at 504k samples/sec vs 618k for
reduce_window, back-to-back same session (the microbench's `.sum()`
consumer fuses the slice chain in a way the conv consumer does not).
So the forward stays on reduce_window everywhere, and the r4 gate
(_PHASE_POOL_MAX_ELEMS applies the slice trick only to the BACKWARD,
where select_and_scatter is the alternative) is correct as shipped.
No code change — microbench wins must survive composition before they
ship.

Harness notes (they bit us): close each window on a float() pull of
the result. And a `pool(x + i)` loop body gets hoisted to ~0 cost —
cycle through 8 pre-materialized inputs via lax.dynamic_index_in_dim
instead. Windows must be large (200/1000) and each timed min-of-3.
"""
import time

import jax
import jax.numpy as jnp
from jax import lax


def pool_rw(x, k, s):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 1, k, k), (1, 1, s, s), "VALID"
    )


def pool_slices(x, k, s):
    b, c, h, w = x.shape
    ph = (h - k) // s + 1
    pw = (w - k) // s + 1
    need_h = (ph - 1) * s + k
    need_w = (pw - 1) * s + k
    if need_h > h or need_w > w:
        x = jnp.pad(
            x,
            ((0, 0), (0, 0), (0, max(0, need_h - h)), (0, max(0, need_w - w))),
            constant_values=-jnp.inf,
        )
    out = None
    for i in range(k):
        for j in range(k):
            sl = x[:, :, i : i + s * ph : s, j : j + s * pw : s]
            out = sl if out is None else jnp.maximum(out, sl)
    return out


def timed(fn, xs, k, s, n, reps=3):
    @jax.jit
    def many(xs):
        def body(i, acc):
            x = lax.dynamic_index_in_dim(xs, i % 8, keepdims=False)
            return acc + fn(x, k, s).sum()
        return lax.fori_loop(0, n, body, jnp.float32(0))

    float(many(xs))  # compile + settle
    best = 1e9
    for _ in range(reps):
        t0 = time.perf_counter()
        float(many(xs))  # the value pull closes the window
        best = min(best, time.perf_counter() - t0)
    return best


SHAPES = [  # (B,C,H,W), kernel, stride — alexnet.conf's three pools
    ((256, 32, 32, 32), 3, 2),
    ((256, 32, 16, 16), 3, 2),
    ((256, 64, 8, 8), 3, 2),
]

if __name__ == "__main__":
    for shape, k, s in SHAPES:
        xs = jax.random.normal(jax.random.PRNGKey(0), (8,) + shape,
                               jnp.bfloat16)
        rows = {}
        for name, fn in (("reduce_window", pool_rw),
                         ("slices", pool_slices),
                         ("floor", lambda x, k, s: x[:, :, ::s, ::s])):
            t1 = timed(fn, xs, k, s, 200)
            t2 = timed(fn, xs, k, s, 1000)
            rows[name] = (t2 - t1) / 800 * 1e6  # us per call, slope
        print(f"{shape} k{k}s{s}: " + "  ".join(
            f"{n} {v:.1f}us" for n, v in rows.items()))
