"""Pallas TPU kernel: fused multi-threshold activation epilogue.

The FPGA streams accumulator values through a comparator bank; the TPU
analogue holds the per-channel threshold bank [bn, K] in VMEM and emits uint
codes with a vectorized compare-and-sum — fused onto the lutmul accumulator
tile so the int32 accs never round-trip to HBM on the real target.

Block shapes align to (8, 128) int32 tiles; K (levels-1) is small (15 for
uint4) and lives entirely in registers after one VMEM load.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 256
DEFAULT_BN = 128


def _threshold_body(acc_ref, thr_ref, sign_ref, out_ref):
    acc = acc_ref[...].astype(jnp.float32)          # [bm, bn]
    thr = thr_ref[...]                              # [bn, K]
    sign = sign_ref[...]                            # [bn]
    a = acc * sign[None, :]
    # compare against every threshold level and popcount
    ge = a[:, :, None] >= thr[None, :, :]
    out_ref[...] = jnp.sum(ge.astype(jnp.int32), axis=-1)


def threshold_pallas(acc: jax.Array, thresholds: jax.Array, sign: jax.Array,
                     *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                     interpret: bool) -> jax.Array:
    """acc: [M, N] int32; thresholds: [N, K] f32; sign: [N] f32 -> int32 codes.

    M, N must be pre-padded to block multiples (ops.py handles it).
    """
    M, N = acc.shape
    K = thresholds.shape[1]
    grid = (M // bm, N // bn)
    return pl.pallas_call(
        _threshold_body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bn, K), lambda i, j: (j, 0)),
            pl.BlockSpec((bn,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        interpret=interpret,
        name="thresholds",
    )(acc, thresholds, sign)
