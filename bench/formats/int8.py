"""Signed int8 weight codes, one per byte: the LM head of every serving
mode.  Module interface: ``nibble.py``."""
import math

import jax
import jax.numpy as jnp

BITS = 8
KERNEL = "int8"
CODE_RMS = math.sqrt(5461.5)        # uniform over [-128, 127]


def make(key, K: int, N: int) -> dict:
    return {"w_q": jax.lax.bitcast_convert_type(
        jax.random.bits(key, (K, N), jnp.uint8), jnp.int8)}


def codes(leaf: dict) -> jax.Array:
    return leaf["w_q"]
