"""Property-based bit-exactness fuzz for the lutmul kernel family.

Every drawn (M, K, N, weight bits, block shape, contract dtype) combination
must make the Pallas kernels (interpret mode — the CPU lowering of the TPU
kernel) agree EXACTLY with the pure-jnp oracles in ``kernels/lutmul/ref.py``:
integer accumulators bit for bit, fused-dequant outputs bit for bit against
the oracle's epilogue order.  ``REPRO_FUZZ_EXAMPLES`` bounds the
Hypothesis example count so CI stays fast.
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.lut import (contraction_table, decode_planes, pack_int4,
                            plane_decomposition, unpack_bitplanes)
from repro.kernels.lutmul import kernel, ref
from repro.kernels.lutmul import ops as lut_ops

N_EXAMPLES = int(os.environ.get("REPRO_FUZZ_EXAMPLES", "8"))

# (bm, bn, bk) — (8, 128, 128)-aligned like ops._CANDIDATES, small enough
# that interpret mode stays fast
BLOCKS = st.sampled_from([(8, 128, 128), (16, 128, 128), (8, 256, 128),
                          (8, 128, 256)])
DIMS = st.tuples(st.integers(1, 24),                 # M
                 st.integers(1, 96).map(lambda k: 2 * k),   # K (even)
                 st.integers(1, 140))                # N
CONTRACT_DTYPE = st.sampled_from(["float32", "int8"])


def _codes(rng: np.random.Generator, m: int, k: int) -> np.ndarray:
    """Random 4-bit activation codes (two's-complement nibbles in uint8)."""
    return (rng.integers(-8, 8, (m, k)) & 0xF).astype(np.uint8)


def _packed_weights(rng: np.random.Generator, k: int, n: int) -> np.ndarray:
    w = rng.integers(-8, 8, (k, n)).astype(np.int8)
    return np.asarray(pack_int4(jnp.asarray(w).T).T)


def _int8_vals(rng: np.random.Generator, shape, bits: int) -> np.ndarray:
    qmax = 2 ** (bits - 1) - 1
    return rng.integers(-qmax, qmax + 1, shape).astype(np.int8)


@given(DIMS, st.integers(0, 2 ** 31 - 1))
@settings(max_examples=N_EXAMPLES, deadline=None)
def test_fuzz_lutmul_interpret_matches_ref(dims, seed):
    """ops.lutmul (onehot Pallas kernel, interpret) == ref, any shape —
    padding, block clipping, and the one-hot contraction all exact."""
    m, k, n = dims
    rng = np.random.default_rng(seed)
    a = _codes(rng, m, k)
    wp = _packed_weights(rng, k, n)
    got = lut_ops.lutmul(jnp.asarray(a), jnp.asarray(wp),
                         backend="interpret")
    want = ref.lutmul_ref(jnp.asarray(a), jnp.asarray(wp))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(DIMS, st.sampled_from([4, 8]), st.integers(0, 2 ** 31 - 1))
@settings(max_examples=N_EXAMPLES, deadline=None)
def test_fuzz_int_matmul_interpret_matches_ref(dims, bits, seed):
    """ops.int_matmul (interpret) == ref over 4- and 8-bit value ranges."""
    m, k, n = dims
    rng = np.random.default_rng(seed)
    a = _int8_vals(rng, (m, k), bits)
    w = _int8_vals(rng, (k, n), bits)
    got = lut_ops.int_matmul(jnp.asarray(a), jnp.asarray(w),
                             backend="interpret")
    want = ref.int_matmul_ref(jnp.asarray(a), jnp.asarray(w))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(BLOCKS, CONTRACT_DTYPE, st.integers(0, 2 ** 31 - 1))
@settings(max_examples=N_EXAMPLES, deadline=None)
def test_fuzz_onehot_contract_dtype_exact(blocks, contract_dtype, seed):
    """The one-hot/bitplane contraction itself is exact in BOTH contract
    dtypes: float32 (interpret-mode path) and int8 (the TPU MXU path) —
    the int8 variant is what real hardware runs, so the fuzz must pin it."""
    bm, bn, bk = blocks
    rng = np.random.default_rng(seed)
    a = _codes(rng, bm, bk).astype(np.int32)
    wp = _packed_weights(rng, bk, bn)
    table = jnp.asarray(contraction_table(a_signed=True), jnp.int32)
    acc = kernel._onehot_contract(jnp.asarray(a), jnp.asarray(wp),
                                  kernel.pack_partial_products(table),
                                  contract_dtype=jnp.dtype(contract_dtype))
    want = ref.lutmul_ref(jnp.asarray(a.astype(np.uint8)), jnp.asarray(wp))
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(want))


@given(BLOCKS, st.integers(1, 2), st.integers(1, 2), st.integers(1, 2),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=N_EXAMPLES, deadline=None)
def test_fuzz_lutmul_block_shapes_exact(blocks, gm, gn, gk, seed):
    """Explicit (bm, bn, bk) sweep through the raw Pallas entry point on
    multi-block grids: the K-accumulation order and block indexing never
    change the integer result."""
    bm, bn, bk = blocks
    M, N, K = gm * bm, gn * bn, gk * bk
    rng = np.random.default_rng(seed)
    a = _codes(rng, M, K)
    wp = _packed_weights(rng, K, N)
    table = jnp.asarray(contraction_table(a_signed=True), jnp.int32)
    got = kernel.lutmul_pallas(jnp.asarray(a), jnp.asarray(wp), table,
                               bm=bm, bn=bn, bk=bk, interpret=True)
    want = ref.lutmul_ref(jnp.asarray(a), jnp.asarray(wp))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(BLOCKS, st.sampled_from(["lut", "int"]),
       st.sampled_from(["float32", "bfloat16"]),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=N_EXAMPLES, deadline=None)
def test_fuzz_fused_dequant_matches_scaled_ref(blocks, which, out_dtype,
                                               seed):
    """Fused-epilogue kernels == the scaled oracle bit for bit: the in-kernel
    rescale must apply the exact epilogue order ``ref.scaled_lutmul_ref``
    documents, in both output dtypes."""
    bm, bn, bk = blocks
    M, N, K = bm, bn, 2 * bk                  # 2 K-blocks: epilogue at k=nk-1
    rng = np.random.default_rng(seed)
    a = _codes(rng, M, K)
    wp = _packed_weights(rng, K, N)
    a_scale = jnp.asarray(rng.uniform(1e-3, 1.0, (M, 1)), jnp.float32)
    w_scale = jnp.asarray(rng.uniform(1e-3, 1.0, (1, N)), jnp.float32)
    od = jnp.dtype(out_dtype)
    if which == "lut":
        table = jnp.asarray(contraction_table(a_signed=True), jnp.int32)
        got = kernel.lutmul_fused_pallas(
            jnp.asarray(a), jnp.asarray(wp), table, a_scale, w_scale,
            bm=bm, bn=bn, bk=bk, out_dtype=od, interpret=True)
        want = ref.scaled_lutmul_ref(jnp.asarray(a), jnp.asarray(wp),
                                     a_scale, w_scale, out_dtype=od)
    else:
        w = np.asarray(ref.decode_codes(jnp.asarray(_codes(rng, K, N)))
                       ).astype(np.int8)
        a8 = _int8_vals(rng, (M, K), 8)
        got = kernel.int_matmul_fused_pallas(
            jnp.asarray(a8), jnp.asarray(w), a_scale, w_scale,
            bm=bm, bn=bn, bk=bk, out_dtype=od, interpret=True)
        acc = ref.int_matmul_ref(jnp.asarray(a8), jnp.asarray(w))
        want = (acc.astype(jnp.float32) * a_scale * w_scale).astype(od)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# tmac: K must pack into bitplane bytes (K % 8 == 0); every weight width of
# the sub-4-bit serving family, both activation widths (a4 -> g=2 grouped
# tables, a8 -> g=1 direct contraction)
WBITS = st.sampled_from([1, 2, 3, 4, "ternary"])
TMAC_DIMS = st.tuples(st.integers(1, 24),                    # M
                      st.integers(1, 24).map(lambda k: 8 * k),   # K (mult 8)
                      st.integers(1, 140))                   # N


@given(TMAC_DIMS, WBITS, st.sampled_from([4, 8]),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=N_EXAMPLES, deadline=None)
def test_fuzz_tmac_matches_ref_and_dense_oracle(dims, wbits, abits, seed):
    """ops.lutmul_tmac (interpret kernel) == the faithful group-table oracle
    ``ref.lutmul_tmac_ref`` == the decoded dense int matmul, for every
    weight width in the family and both activation widths — padding, plane
    accumulation order, and the per-row const correction all exact."""
    m, k, n = dims
    rng = np.random.default_rng(seed)
    a = jnp.asarray(_int8_vals(rng, (m, k), abits))
    wf = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    planes, _ = lut_ops.quantize_weights_planes(wf, wbits)
    g = lut_ops.tmac_group_size(abits)
    got = lut_ops.lutmul_tmac(a, planes, wbits, abits=abits,
                              backend="interpret")
    want = ref.lutmul_tmac_ref(a, planes, wbits, g=g)
    dense = decode_planes(unpack_bitplanes(planes), wbits)
    oracle = a.astype(jnp.int32) @ dense.astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


@given(BLOCKS, WBITS, st.sampled_from(["float32", "bfloat16"]),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=N_EXAMPLES, deadline=None)
def test_fuzz_tmac_fused_matches_scaled_oracle(blocks, wbits, out_dtype,
                                               seed):
    """The fused-dequant tmac kernel == the scaled dense oracle bit for bit
    on multi-K-block grids (epilogue fires at k = nk-1), in both output
    dtypes."""
    bm, bn, bk = blocks
    M, N, K = bm, bn, 2 * bk
    rng = np.random.default_rng(seed)
    a = jnp.asarray(_int8_vals(rng, (M, K), 4))
    wf = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    planes, _ = lut_ops.quantize_weights_planes(wf, wbits)
    a_scale = jnp.asarray(rng.uniform(1e-3, 1.0, (M, 1)), jnp.float32)
    w_scale = jnp.asarray(rng.uniform(1e-3, 1.0, (1, N)), jnp.float32)
    _, coeffs, const = plane_decomposition(wbits)
    od = jnp.dtype(out_dtype)
    got = kernel.lutmul_tmac_fused_pallas(
        a, planes, a_scale, w_scale, coeffs=coeffs, const=const, g=2,
        bm=bm, bn=bn, bk=bk, out_dtype=od, interpret=True)
    dense = decode_planes(unpack_bitplanes(planes), wbits)
    acc = a.astype(jnp.int32) @ dense.astype(jnp.int32)
    want = (acc.astype(jnp.float32) * a_scale * w_scale).astype(od)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(TMAC_DIMS, WBITS, st.integers(0, 2 ** 31 - 1))
@settings(max_examples=N_EXAMPLES, deadline=None)
def test_fuzz_plane_prefix_is_low_width_code(dims, wbits, seed):
    """The self-speculative drafter's algebra: the top-``keep`` plane prefix
    of a wB tmac weight IS a valid w(keep) tmac operand whose decode is
    exactly ``floor(code / 2^(B-keep))`` of the full code — every truncated
    code lands in the keep-bit range, the residual is bounded by the dropped
    planes' mass, and the tmac kernel contracts the sliced planes exactly
    like their decoded dense codes.  Ternary and w1 have no positional
    prefix and must refuse."""
    m, k, n = dims
    rng = np.random.default_rng(seed)
    wf = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    planes, _ = lut_ops.quantize_weights_planes(wf, wbits)
    if wbits in (1, "ternary"):
        with pytest.raises(ValueError):
            lut_ops.truncate_planes(planes, wbits, 2)
        return
    with pytest.raises(ValueError):                    # keep == B: no draft
        lut_ops.truncate_planes(planes, wbits, wbits)
    full = np.asarray(decode_planes(unpack_bitplanes(planes), wbits))
    for keep in range(2, wbits):
        sliced, kept, mult = lut_ops.truncate_planes(planes, wbits, keep)
        assert (kept, mult) == (keep, 2 ** (wbits - keep))
        low = np.asarray(decode_planes(unpack_bitplanes(sliced), keep))
        qmax = 2 ** (keep - 1)
        assert low.min() >= -qmax and low.max() <= qmax - 1
        err = full - mult * low
        assert err.min() >= 0 and err.max() <= mult - 1
        a = jnp.asarray(_int8_vals(rng, (m, k), 4))
        got = lut_ops.lutmul_tmac(a, sliced, keep, abits=4,
                                  backend="interpret")
        oracle = np.asarray(a, np.int32) @ low.astype(np.int32)
        np.testing.assert_array_equal(np.asarray(got), oracle)


@given(st.tuples(st.integers(1, 8), st.integers(1, 32).map(lambda k: 2 * k),
                 st.integers(1, 48)),
       st.integers(0, 2 ** 31 - 1))
@settings(max_examples=N_EXAMPLES, deadline=None)
def test_fuzz_gather_impl_matches_ref(dims, seed):
    """The retained serial table-gather baseline stays bit-exact too (small
    dims: it is the slow A/B kernel)."""
    m, k, n = dims
    rng = np.random.default_rng(seed)
    a = _codes(rng, m, k)
    wp = _packed_weights(rng, k, n)
    got = lut_ops.lutmul_gather(jnp.asarray(a), jnp.asarray(wp),
                                backend="interpret")
    want = ref.lutmul_ref(jnp.asarray(a), jnp.asarray(wp))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
