"""Ternary weight codes {-1, 0, +1} as two packed bitplanes (+1 mask, -1
mask; bit i of byte j is row 8j+i): the format the T-MAC kernel reads
under ``ternary_a*_tmac``.  The zero-size ``w_tmac`` and ``w_tern``
leaves mark the format for the program.  Module interface: ``nibble.py``.
"""
import math

import jax
import jax.numpy as jnp

BITS = 2
KERNEL = "tmac"
CODE_RMS = math.sqrt(0.5)           # +1 and -1 a quarter each, 0 half


def make(key, K: int, N: int) -> dict:
    kp, km = jax.random.split(key)
    p = jax.random.bits(kp, (K // 8, N), jnp.uint8)
    n = jax.random.bits(km, (K // 8, N), jnp.uint8)
    marker = jnp.zeros((0,), jnp.uint8)
    return {"w_q": jnp.stack([p & ~n, n & ~p]),
            "w_tmac": marker, "w_tern": marker}


def codes(leaf: dict) -> jax.Array:
    planes = leaf["w_q"]
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape(1, 1, 8, 1)
    bits = ((planes[:, :, None, :] >> shifts) & 1).astype(jnp.int8)
    bits = bits.reshape(2, planes.shape[1] * 8, planes.shape[2])
    return bits[0] - bits[1]
