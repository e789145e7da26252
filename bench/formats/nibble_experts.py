"""A bank of E experts' signed int4 codes, [E, K/2, N] bytes, each expert
packed two per byte along K as ``nibble.py`` packs one matrix (low nibble =
even k): the stacked format ``repro.models.moe._expert_einsum`` reads (an
integer einsum on XLA, no Pallas kernel).  Module interface: ``nibble.py``,
with ``make(key, E, K, N)``."""
import math

import jax
import jax.numpy as jnp

BITS = 4
KERNEL = ""                         # XLA's integer einsum, no kernel
CODE_RMS = math.sqrt(21.5)          # uniform over [-8, 7]


def make(key, E: int, K: int, N: int) -> dict:
    return {"w_q": jax.random.bits(key, (E, K // 2, N), jnp.uint8)}


def codes(leaf: dict) -> jax.Array:
    w = leaf["w_q"]
    lo = (w & 0xF).astype(jnp.int8)
    hi = (w >> 4).astype(jnp.int8)
    E, K2, N = w.shape
    x = jnp.stack([lo, hi], axis=2).reshape(E, 2 * K2, N)
    return jnp.where(x >= 8, x - 16, x)
