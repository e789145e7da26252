"""Pallas TPU kernels: LUT-based quantized matmul (paper Sec. 3.5, TPU-adapted).

``lutmul`` (one-hot/bitplane contraction, the default): the table lookup
re-expressed as a tensor contraction so it runs on the MXU instead of scalar
gathers — the LUT-GEMM / T-MAC move.  For codes ``a[m,k]`` (4-bit
activations) and ``w[k,n]`` (4-bit weights) the accumulator is

    acc[m,n] = sum_k T[w[k,n], a[m,k]]
             = sum_{k,b} bit_b(a[m,k]) * TW[(k,b), n]            (b = 0..3)
    TW[(k,b), n] = T[w[k,n], 2^b]          (a lookup: one-hot in w[k,n])

Per block, each weight code selects its four power-of-two partial products
``T[w, 2^b]`` from the product table with a 4-level select tree on its
bits (the table sits in SMEM; the activation-code-8 column carries the top
bit's sign, so signed vs unsigned activations is purely a table-layout
choice), then bitplaned activation nibbles select-and-reduce over K on the
MXU (a [bm, 4*bk] x [4*bk, bn] dot with int32 accumulation).
Multiplication is still performed by *selection from the product table* —
the faithful LUT semantics: every partial product is a table entry, never
computed.  On TPU the dot is int8 (every operand value fits int8); its MAC
count is 4x an int8 matmul (the price of selection), and the selection
costs 15 selects per weight code and bit on the VPU, independent of M.
The serial per-row gather loop is kept as ``lutmul_gather``.

``lutmul_tmac``: the second formulation — T-MAC/BitNet-style *weight-plane*
decomposition against *activation-group* partial-sum tables.  Weights are
stored as P binary bitplanes with static integer coefficients
(``core.lut.plane_decomposition``: ``w = sum_b coeff_b * plane_b + const``),
activations are grouped into g-element chunks along K, and each block
precomputes the partial-sum table

    T[m, kg, c] = sum_{i<g} bit_i(c) * a[m, kg*g + i]       (c = 0..2^g-1)

(the T-MAC ``LUT[n, k, Abits]`` table, built in-VMEM per block with one
lane-aligned [bm, bk] x [bk, bk/g * 2^g] dot against a constant 0/1 group
matrix — N-independent, and free of the sub-tile reshapes the TPU compiler
refuses).  Each weight plane's
g-bit group codes then *select* from T via a one-hot contraction and the
coefficients fold into the one-hot operand, so the whole thing is ONE
``[bm, P * K/g * 2^g] x [P * K/g * 2^g, bn]`` MXU dot:

    acc[m,n] = sum_{b,kg} coeff_b * T[m, kg, gcode_b(kg, n)]  (+ const * sum_k a[m,k])

MAC cost per output is ``P * (2^g / g) * K`` — **linear in the weight bit
count P** where the one-hot kernel above is flat at ``4K`` regardless of
weight bits: w2 does half the MXU work of w4, ternary (2 planes) matches
w2, and binary w1 halves it again.  ``g=1`` degenerates the table to the
activation vector itself ({0, a}), so the kernel skips materializing T and
contracts the coefficient-scaled planes directly (inner dim ``P * K`` — the
cheapest MXU realization; ``g>=2`` trades more inner dim for the faithful
wide-input-LUT shape, PolyLUT-Add style).  On TPU both operands fit int8
for a4 activations and g <= 4 (|T| <= 8g <= 32, |coeff| <= 8); a8
activations require g=1 (ops.py clamps).

``lutmul_gather``: the previous faithful-but-serial adaptation — a per-k
``jnp.take`` loop over the 256-entry table — retained as the A/B baseline
for ``benchmarks/kernel_bench.py``.

``lutmul_fused`` / ``int_matmul_fused``: the same kernels with the dequant
epilogue fused in — per-token activation scale [bm, 1] and per-channel weight
scale [1, bn] applied to the int32 accumulator at the last K step, writing
``out_dtype`` directly so callers never materialize a separate fp32 [M, N]
intermediate.

``int_matmul``: the "DSP packing" baseline — int8 x int8 MXU dot with int32
accumulation under identical tiling, so the bench comparison isolates the
multiplication mechanism.

Block shapes are MXU/VPU aligned: (bm, bk, bn) multiples of (8, 128, 128);
the defaults keep the per-block VMEM footprint under ~2 MB:
  a tile      bm*bk            (uint8)
  a one-hot   bm*bk*16         (int8)
  w tile      bk*bn/2          (uint8, packed)
  TW tile     bk*16*bn         (int8)
  acc tile    bm*bn*4          (int32)
  table       16*16 int8/int32
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 128


def _unpack_codes(wp: jax.Array) -> jax.Array:
    """[bk//2, bn] packed nibbles -> [bk, bn] int32 codes (k-major)."""
    w_lo = wp & 0xF
    w_hi = (wp >> 4) & 0xF
    return jnp.stack([w_lo, w_hi], axis=1).reshape(-1, wp.shape[1])


def _select_tree(bits, leaves):
    """``leaves[code]`` for every element: a binary select tree on the
    code's bits (``bits[j]`` = bit j is set), lowest bit first."""
    for bit in bits:
        leaves = [jnp.where(bit, leaves[2 * i + 1], leaves[2 * i])
                  for i in range(len(leaves) // 2)]
    return leaves[0]


def _onehot_contract(a: jax.Array, wp: jax.Array, t2,
                     contract_dtype) -> jax.Array:
    """One block of the LUT contraction (module docstring).

    a: [bm, bk] int32 codes; wp: [bk//2, bn] packed codes (byte k2 holds
    w[2*k2] in its low nibble, w[2*k2+1] in its high one); t2: [16, 16]
    int32 product table (row = weight code, col = activation code) — an
    SMEM ref in the kernel, any indexable array elsewhere.  Returns the
    int32 [bm, bn] partial accumulator.

    ``contract_dtype``: int8 on the TPU path (an MXU-native int8 dot with
    int32 accumulation — every value involved fits int8); float32 in
    interpret mode, where XLA:CPU has no fast int8 GEMM.  f32 accumulation is
    exact here: per-block partial sums are bounded by bk * 64 << 2^24.
    """
    bk = a.shape[1]
    pref = jnp.float32 if contract_dtype == jnp.float32 else jnp.int32
    wp = wp.astype(jnp.int32)
    # selection stage, on the two nibble halves (even k, odd k) so no
    # sublane interleave is needed: TW rows are ordered (b, parity, k2)
    halves = [[(w >> j) & 1 == 1 for j in range(4)]
              for w in (wp & 0xF, wp >> 4)]
    tw = jnp.concatenate(
        [_select_tree(bits, [t2[c, 1 << b] for c in range(16)])
         for b in range(4) for bits in halves],
        axis=0).astype(contract_dtype)                          # [4*bk, bn]
    # accumulation stage: activation codes reordered to (parity, k2) by an
    # exact 0/1 permutation dot, bitplaned b-major to match TW's rows —
    # the MXU only ever selects and sums table entries
    k = jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (bk, bk), 1)
    half = bk // 2
    perm = (k == jnp.where(j < half, 2 * j, 2 * (j - half) + 1))
    a = jax.lax.dot_general(
        a.astype(contract_dtype), perm.astype(contract_dtype),
        (((1,), (0,)), ((), ())),
        preferred_element_type=pref).astype(jnp.int32)          # [bm, bk]
    a_bits = jnp.concatenate([(a >> b) & 1 for b in range(4)],
                             axis=1).astype(contract_dtype)     # [bm, 4*bk]
    acc = jax.lax.dot_general(
        a_bits, tw, (((1,), (0,)), ((), ())),
        preferred_element_type=pref)                            # [bm, bn]
    return acc.astype(jnp.int32)


def _lutmul_onehot_body(a_ref, w_ref, t_ref, out_ref, *,
                        contract_dtype):
    """Grid: (M/bm, N/bn, K/bk); K is the innermost ('arbitrary') dimension."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += _onehot_contract(a_ref[...].astype(jnp.int32),
                                     w_ref[...], t_ref, contract_dtype)


def _lutmul_gather_body(a_ref, w_ref, t_ref, out_ref, *, unroll: int = 8):
    """The retained serial baseline: per-k row gathers from the flat table."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...].astype(jnp.int32)                 # [bm, bk] 4-bit codes
    w = _unpack_codes(w_ref[...].astype(jnp.int32))  # [bk, bn]
    table = t_ref[...].reshape(-1)                   # [256] int32

    bk = a.shape[1]

    def body(i, acc):
        # the LUT6 analogue, literally: product via table gather per row
        idx = (w[i, :][None, :] << 4) | a[:, i][:, None]          # [bm, bn]
        return acc + jnp.take(table, idx, axis=0)

    acc = jax.lax.fori_loop(0, bk, body,
                            jnp.zeros(out_ref.shape, jnp.int32),
                            unroll=unroll)
    out_ref[...] += acc


def lutmul_pallas(a_codes: jax.Array, w_packed: jax.Array, table: jax.Array,
                  *, bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                  bk: int = DEFAULT_BK, impl: str = "onehot",
                  interpret: bool) -> jax.Array:
    """a_codes: [M, K] uint8; w_packed: [K//2, N] uint8; table: [16, 16] int32.

    Shapes must be pre-padded to block multiples (ops.py handles padding).
    ``impl``: "onehot" (MXU contraction) | "gather" (serial A/B baseline).
    """
    M, K = a_codes.shape
    N = w_packed.shape[1]
    grid = (M // bm, N // bn, K // bk)
    cd = jnp.float32 if interpret else jnp.int8
    body = (functools.partial(_lutmul_onehot_body, contract_dtype=cd)
            if impl == "onehot" else _lutmul_gather_body)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),      # product table
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        interpret=interpret,
    )(a_codes, w_packed, table)


# ---------------------------------------------------------------------------
# T-MAC formulation: weight bitplanes x activation-group partial-sum tables
# (module docstring) — kernel cost linear in the weight bit count
# ---------------------------------------------------------------------------


def _tmac_contract(a: jax.Array, wp: jax.Array, gmat: jax.Array | None,
                   coeffs: tuple[int, ...], g: int,
                   contract_dtype) -> jax.Array:
    """One block of the tmac contraction (WITHOUT the const correction).

    a: [bm, bk] int32 signed activation codes; wp: [P, bk//8, bn] packed
    bitplanes; gmat: the ``tmac_group_matrix(bk, g)`` block (None for
    g=1); coeffs: static per-plane integer coefficients.  Returns the
    int32 [bm, bn] partial accumulator ``sum_b coeff_b * (a . plane_b)``.
    """
    n_planes = wp.shape[0]
    bk = a.shape[1]
    bn = wp.shape[-1]
    pref = jnp.float32 if contract_dtype == jnp.float32 else jnp.int32
    if g == 1:
        # unpack bitplanes: [P, bk//8, bn] bytes -> [P, bk, bn] {0, 1}
        shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 8, 1), 2)
        w = ((wp.astype(jnp.int32)[:, :, None, :] >> shifts) & 1) \
            .reshape(n_planes, bk, bn)
        # degenerate table T[m, k, {0,1}] = {0, a}: contract the
        # coefficient-scaled planes directly (inner dim P * bk)
        ws = jnp.concatenate(
            [w[p] * coeffs[p] for p in range(n_planes)],
            axis=0).astype(contract_dtype)                      # [P*bk, bn]
        at = jnp.concatenate([a] * n_planes,
                             axis=1).astype(contract_dtype)     # [bm, P*bk]
        acc = jax.lax.dot_general(at, ws, (((1,), (0,)), ((), ())),
                                  preferred_element_type=pref)
        return acc.astype(jnp.int32)
    # g >= 2.  Group kg = r * (8/g) + j covers bits [g*j, g*j + g) of
    # packed byte row r, so every group code is a shift-and-mask of the
    # bytes and no sublane reshape is needed.  Table columns and selection
    # rows are both ordered (j, c, r), r fastest.
    c, per = 1 << g, 8 // g
    # table stage: T[m, (j, c, r)] = sum_i bit_i(c) * a[m, 8r + g*j + i] —
    # one lane-aligned dot against the constant 0/1 group matrix
    table = jax.lax.dot_general(
        a.astype(contract_dtype), gmat.astype(contract_dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=pref)
    table = table.astype(contract_dtype)                  # [bm, per*c*bk/8]
    # selection stage: per-plane g-bit group codes one-hot against the
    # table, coefficients folded into the one-hot operand -> ONE dot
    wp = wp.astype(jnp.int32)
    sel = jnp.concatenate(
        [(((wp[p] >> (g * j)) & (c - 1)) == code).astype(jnp.int32)
         * coeffs[p]
         for p in range(n_planes) for j in range(per) for code in range(c)],
        axis=0).astype(contract_dtype)                    # [P*per*c*bk/8, bn]
    at = jnp.concatenate([table] * n_planes, axis=1)      # plane-major
    acc = jax.lax.dot_general(at, sel, (((1,), (0,)), ((), ())),
                              preferred_element_type=pref)
    return acc.astype(jnp.int32)


def tmac_group_matrix(bk: int, g: int) -> np.ndarray:
    """[bk, (8/g) * 2^g * bk/8] int8 0/1 matrix whose product with a block of
    activations is the T-MAC partial-sum table in ``_tmac_contract``'s
    (j, c, r) column order: column (j, c, r) sums the activations at
    k = 8r + g*j + i over the bits i set in code c."""
    c, per, rows = 1 << g, 8 // g, bk // 8
    k = np.arange(bk)[:, None]
    col = np.arange(per * c * rows)[None, :]
    j, code, r = col // (c * rows), (col // rows) % c, col % rows
    i = k - 8 * r - g * j
    hit = (i >= 0) & (i < g) & ((code >> np.clip(i, 0, g - 1)) & 1 == 1)
    return hit.astype(np.int8)


def _tmac_operands(g: int, bk: int):
    """The constant group-matrix operand and its BlockSpec (none for g=1).
    Its block index never changes, so it is copied into VMEM once."""
    if g == 1:
        return [], []
    gm = jnp.asarray(tmac_group_matrix(bk, g))
    return [gm], [pl.BlockSpec(gm.shape, lambda i, j, k: (0, 0))]


def _tmac_block(a_ref, w_ref, g_refs, *, coeffs, const, g, contract_dtype):
    """Shared block body: tmac contraction + the binary-coding const
    correction (``const * sum_k a[m, k]``, exact per K block since padded
    activation codes are zero)."""
    a = a_ref[...].astype(jnp.int32)
    gmat = g_refs[0][...] if g_refs else None
    acc = _tmac_contract(a, w_ref[...], gmat, coeffs, g, contract_dtype)
    if const:
        acc = acc + const * jnp.sum(a, axis=1, keepdims=True)
    return acc


def _lutmul_tmac_body(*refs, coeffs, const, g, contract_dtype):
    a_ref, w_ref, *g_refs, out_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += _tmac_block(a_ref, w_ref, g_refs, coeffs=coeffs,
                                const=const, g=g,
                                contract_dtype=contract_dtype)


def _check_tmac_block(bk: int, g: int) -> None:
    if g not in (1, 2, 4) or bk % (8 * g):
        raise ValueError(f"tmac needs g in (1, 2, 4) and bk % (8*g) == 0, "
                         f"got bk={bk} g={g}")


def lutmul_tmac_pallas(a_q: jax.Array, w_planes: jax.Array, *,
                       coeffs: tuple[int, ...], const: int = 0, g: int = 2,
                       bm: int = DEFAULT_BM, bn: int = DEFAULT_BN,
                       bk: int = DEFAULT_BK, interpret: bool) -> jax.Array:
    """a_q: [M, K] int8 signed activation codes; w_planes: [P, K//8, N]
    packed bitplanes (core.lut.pack_bitplanes layout).  Shapes pre-padded to
    block multiples (ops.py pads); ``bk % (8 * g) == 0`` required."""
    M, K = a_q.shape
    n_planes, _, N = w_planes.shape
    _check_tmac_block(bk, g)
    grid = (M // bm, N // bn, K // bk)
    cd = jnp.float32 if interpret else jnp.int8
    body = functools.partial(_lutmul_tmac_body, coeffs=tuple(coeffs),
                             const=const, g=g, contract_dtype=cd)
    g_ops, g_specs = _tmac_operands(g, bk)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((n_planes, bk // 8, bn), lambda i, j, k: (0, k, j)),
            *g_specs,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        interpret=interpret,
    )(a_q, w_planes, *g_ops)


def _int_matmul_body(a_ref, w_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...]
    w = w_ref[...]
    out_ref[...] += jax.lax.dot_general(
        a, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def int_matmul_pallas(a: jax.Array, w: jax.Array, *, bm: int = DEFAULT_BM,
                      bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                      interpret: bool) -> jax.Array:
    """a: [M, K] int8; w: [K, N] int8 -> int32 [M, N]."""
    M, K = a.shape
    N = w.shape[1]
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _int_matmul_body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        interpret=interpret,
    )(a, w)


# ---------------------------------------------------------------------------
# fused dequant epilogue variants: int32 accumulate in VMEM scratch, rescale
# by per-token (a_scale [M, 1]) and per-channel (w_scale [1, N]) factors at
# the last K step, write out_dtype directly — no fp32 [M, N] intermediate
# ---------------------------------------------------------------------------


def _epilogue(acc, as_blk, ws_blk, out_dtype):
    return (acc.astype(jnp.float32) * as_blk * ws_blk).astype(out_dtype)


def _lutmul_fused_body(a_ref, w_ref, t_ref, as_ref, ws_ref, out_ref, acc_ref,
                       *, nk: int, out_dtype, contract_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _onehot_contract(a_ref[...].astype(jnp.int32),
                                     w_ref[...], t_ref, contract_dtype)

    @pl.when(k == nk - 1)
    def _finish():
        out_ref[...] = _epilogue(acc_ref[...], as_ref[...], ws_ref[...],
                                 out_dtype)


def lutmul_fused_pallas(a_codes: jax.Array, w_packed: jax.Array,
                        table: jax.Array, a_scale: jax.Array,
                        w_scale: jax.Array, *, bm: int = DEFAULT_BM,
                        bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                        out_dtype=jnp.bfloat16,
                        interpret: bool) -> jax.Array:
    """One-hot LUT matmul + fused dequant.  a_scale: [M, 1] f32 per-token,
    w_scale: [1, N] f32 per-channel; returns [M, N] ``out_dtype``."""
    M, K = a_codes.shape
    N = w_packed.shape[1]
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    body = functools.partial(_lutmul_fused_body, nk=nk, out_dtype=out_dtype,
                             contract_dtype=jnp.float32 if interpret
                             else jnp.int8)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),      # product table
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(a_codes, w_packed, table, a_scale, w_scale)


def _lutmul_tmac_fused_body(*refs, nk: int, out_dtype, coeffs, const, g,
                            contract_dtype):
    a_ref, w_ref, *g_refs, as_ref, ws_ref, out_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _tmac_block(a_ref, w_ref, g_refs, coeffs=coeffs,
                                const=const, g=g,
                                contract_dtype=contract_dtype)

    @pl.when(k == nk - 1)
    def _finish():
        out_ref[...] = _epilogue(acc_ref[...], as_ref[...], ws_ref[...],
                                 out_dtype)


def lutmul_tmac_fused_pallas(a_q: jax.Array, w_planes: jax.Array,
                             a_scale: jax.Array, w_scale: jax.Array, *,
                             coeffs: tuple[int, ...], const: int = 0,
                             g: int = 2, bm: int = DEFAULT_BM,
                             bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                             out_dtype=jnp.bfloat16,
                             interpret: bool) -> jax.Array:
    """T-MAC LUT matmul + fused dequant epilogue (see lutmul_tmac_pallas)."""
    M, K = a_q.shape
    n_planes, _, N = w_planes.shape
    _check_tmac_block(bk, g)
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    body = functools.partial(_lutmul_tmac_fused_body, nk=nk,
                             out_dtype=out_dtype, coeffs=tuple(coeffs),
                             const=const, g=g,
                             contract_dtype=jnp.float32 if interpret
                             else jnp.int8)
    g_ops, g_specs = _tmac_operands(g, bk)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((n_planes, bk // 8, bn), lambda i, j, k: (0, k, j)),
            *g_specs,
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(a_q, w_planes, *g_ops, a_scale, w_scale)


def _int_matmul_fused_body(a_ref, w_ref, as_ref, ws_ref, out_ref, acc_ref,
                           *, nk: int, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        a_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _finish():
        out_ref[...] = _epilogue(acc_ref[...], as_ref[...], ws_ref[...],
                                 out_dtype)


def int_matmul_fused_pallas(a: jax.Array, w: jax.Array, a_scale: jax.Array,
                            w_scale: jax.Array, *, bm: int = DEFAULT_BM,
                            bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                            out_dtype=jnp.bfloat16,
                            interpret: bool) -> jax.Array:
    """int8 matmul + fused dequant (w4a4_mxu / w8a8 serving path)."""
    M, K = a.shape
    N = w.shape[1]
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    body = functools.partial(_int_matmul_fused_body, nk=nk,
                             out_dtype=out_dtype)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(a, w, a_scale, w_scale)
