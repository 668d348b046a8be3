"""Helpers every parameter layout (``architectures/``) builds its random
weights with, so that a seed means the same thing in each.

Every matrix holds +-``1/sqrt(fan_in)`` with the signs balanced in each
128-row window of its contraction: half of each window's entries are
positive.  Gaussian weights leave the DS-CIM estimator with an offset per
output column that is larger than the product it estimates (it grows with
each window's absmax and operand sums); summed over the layers it swamps
what the tokens say, and greedy decoding then serves a handful of tokens
whatever the prompt.  With equal magnitudes and balanced signs that offset
is the same in every column.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

WINDOW = 128


def key_of(seed: int):
    """A PRNG key from any non-negative seed (also past 32 bits)."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def balanced_signs(key, shape, axis: int):
    """+-1 of ``shape`` with exactly half of each ``WINDOW``-long window
    along ``axis`` positive."""
    lead = tuple(n for i, n in enumerate(shape) if i != axis)
    k = shape[axis]
    half = jnp.concatenate([jnp.ones(WINDOW // 2), -jnp.ones(WINDOW // 2)])
    s = jax.random.permutation(
        key, jnp.broadcast_to(half, lead + (k // WINDOW, WINDOW)), axis=-1,
        independent=True)
    return jnp.moveaxis(s.reshape(lead + (k,)), -1, axis)
