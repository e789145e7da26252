"""Signed int4 weight codes packed two per byte along K (low nibble = even
k): the format the one-hot LUT kernel reads (``w4a4_lut``).

Every weight format module gives:
  BITS       stored bits per weight (bytes read, ``bench/cost.py``)
  KERNEL     the ``bench/kernels.json`` entry its projections run on
  CODE_RMS   rms of a code as ``make`` draws it (sets the scales)
  make(key, K, N) -> {leaf name: array}, the stored code leaves of one
             K x N projection (the per-channel ``w_scale`` is made apart)
  codes(leaf) -> int8 [K, N], the codes a leaf holds (the reference)
"""
import math

import jax
import jax.numpy as jnp

BITS = 4
KERNEL = "onehot"
CODE_RMS = math.sqrt(21.5)          # uniform over [-8, 7]


def make(key, K: int, N: int) -> dict:
    return {"w_q": jax.random.bits(key, (K // 2, N), jnp.uint8)}


def codes(leaf: dict) -> jax.Array:
    w = leaf["w_q"]
    lo = (w & 0xF).astype(jnp.int8)
    hi = (w >> 4).astype(jnp.int8)
    x = jnp.stack([lo, hi], axis=1).reshape(2 * w.shape[0], w.shape[1])
    return jnp.where(x >= 8, x - 16, x)
