"""Float weights kept unquantized, in bfloat16: a K x N matrix drawn
N(0, 1) / sqrt(K), so a unit-rms input gives a unit-rms output.  The
leaves a serving mode keeps in float (MLA's ``wkv_b``, an MoE router).
Module interface: ``nibble.py``; there are no codes and no scale leaf, so
``codes`` returns the weight itself."""
import jax
import jax.numpy as jnp

BITS = 16
KERNEL = ""                         # a plain XLA matmul, no kernel
CODE_RMS = 1.0


def make(key, K: int, N: int) -> dict:
    w = jax.random.normal(key, (K, N), jnp.float32) / jnp.sqrt(
        jnp.float32(K))
    return {"w": w.astype(jnp.bfloat16)}


def codes(leaf: dict) -> jax.Array:
    return leaf["w"]
