"""Metric averaging (the reference's Performance class).

Worker::Performance accumulates each loss layer's metric blob every step
and prints the element-wise average every display interval, then resets
(src/worker/worker.cc:350-386). Metrics arrive here as jnp scalars; they
are kept on device and only pulled to host at Avg() time so accumulation
never blocks the async dispatch queue.
"""

from __future__ import annotations

import numpy as np


class Performance:
    def __init__(self):
        self.reset()

    def reset(self) -> None:
        self._sums: dict[str, dict[str, object]] = {}
        self._count = 0

    def update(self, metrics: dict[str, dict]) -> None:
        """Accumulate one step's {losslayer: {metric: scalar}}.

        Sums are folded into one running device scalar per metric (a lazy
        device-side add) so memory stays constant over arbitrarily long
        display intervals and no step ever blocks on a host sync.
        """
        self._count += 1
        for lname, m in metrics.items():
            bucket = self._sums.setdefault(lname, {})
            for k, v in m.items():
                bucket[k] = v if k not in bucket else bucket[k] + v

    def update_summed(self, summed: dict[str, dict], nsteps: int) -> None:
        """Accumulate ``nsteps`` steps whose metrics are already summed
        on device (the chunk engine's lax.scan output reduced over its
        step axis) — no per-step host transfer, same averages.

        ``nsteps <= 0`` is a no-op: a zero-length window carries no
        steps, so folding its sums in while netting the count to zero
        would silently skew the next window's averages."""
        if nsteps <= 0:
            return
        self.update(summed)
        self._count += nsteps - 1

    @property
    def count(self) -> int:
        return self._count

    def avg(self) -> dict[str, dict[str, float]]:
        """Element-wise averages since the last reset (worker.cc:367-376).

        All metrics are pulled to host in ONE transfer: `float(total)`
        per metric costs a full device round trip each, several per
        display window."""
        n = max(self._count, 1)
        names = [(l, k) for l, b in self._sums.items() for k in b]
        if not names:
            return {}
        import jax.numpy as jnp

        vals = np.asarray(
            jnp.stack(
                [jnp.asarray(self._sums[l][k], jnp.float32) for l, k in names]
            )
        )
        out: dict[str, dict[str, float]] = {}
        for (l, k), v in zip(names, vals):
            out.setdefault(l, {})[k] = float(v) / n
        return out

    def to_string(self, avg: dict | None = None) -> str:
        """One-line display like Worker's "loss : 2.301, precision : 0.11".

        Pass an already-computed ``avg()`` dict to avoid a second device
        round trip (the eval path computes avg for its return value and
        logs in the same breath)."""
        parts = []
        for lname, bucket in sorted((avg or self.avg()).items()):
            inner = ", ".join(f"{k} : {v:.6g}" for k, v in sorted(bucket.items()))
            parts.append(f"{lname} [{inner}]" if len(self._sums) > 1 else inner)
        return ", ".join(parts) if parts else "no metrics"
