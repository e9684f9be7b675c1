"""Weights drawn on the device from the seed, in one jitted call, in the
type they are served in: what the drivers put into the program's tables
in place of a host-side draw."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def key_of(seed: int) -> jax.Array:
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed % (2 ** 31)),
                              seed // (2 ** 31))


def seed_table(table, seed: int, scale: float) -> None:
    """Replace a ``MatrixTable``'s data by Uniform(-scale, scale) drawn on
    the device from ``seed``, padding rows zero, and adopt it."""
    shape, rows = table.padded_shape, table.shape[0]

    def draw(key):
        x = jax.random.uniform(key, shape, table.dtype, -scale, scale)
        return jnp.where(jnp.arange(shape[0])[:, None] < rows, x, 0)

    data = jax.jit(draw, out_shardings=table.sharding)(key_of(seed))
    table.adopt({"data": data, "ustate": table.state["ustate"]})
    jax.block_until_ready(table.raw())
