"""Weights from the seed, on the device, in one jitted call.

``specs`` maps a parameter's name to its shape and how it is drawn
(``benchmark/models/confnet.py`` ``param_specs``, or a driver's own
table). The program under test and the plain reference are both handed
what this returns; calling it again with the same seed gives the same
arrays, so the reference need not keep the program's copy alive.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31
    )


def make(specs: dict[str, dict], seed: int, dtype=jnp.float32) -> dict:
    def draw(key):
        out = {}
        for i, (name, spec) in enumerate(specs.items()):
            shape = tuple(spec["shape"])
            if spec["init"] == "constant":
                out[name] = jnp.full(shape, spec["value"], dtype)
            elif spec["init"] == "normal":
                out[name] = (
                    spec["std"]
                    * jax.random.normal(
                        jax.random.fold_in(key, i), shape, jnp.float32
                    )
                ).astype(dtype)
            else:
                raise ValueError(f"{name}: init {spec['init']!r} unknown")
        return out

    return jax.jit(draw)(seed_key(seed))
