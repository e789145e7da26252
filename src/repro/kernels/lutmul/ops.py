"""jit'd wrappers around the LUT-multiplication kernels + the high-level
``quantized_matmul`` every model projection calls.

Backend selection:
  * "pallas"    — real TPU lowering (target hardware)
  * "interpret" — Pallas interpret mode (CPU correctness runs / tests)
  * "ref"       — pure-jnp oracle math (dry-run lowering on the CPU backend;
                  identical FLOP/byte structure at the roofline level)
Default: "ref" on CPU, "pallas" on TPU; override with
``repro.kernels.lutmul.ops.set_backend(...)`` or REPRO_KERNEL_BACKEND.

Kernel implementation selection (``impl``): "onehot" (MXU contraction,
default) or "gather" (the serial per-row table-gather baseline, kept for
A/B benchmarking — see kernel.py).

Block sizes come from :func:`pick_blocks`: a per-(op, M, K, N, backend)
cached choice.  The default is the aligned heuristic; with autotuning
enabled (``set_autotune(True)`` or REPRO_LUTMUL_AUTOTUNE=1) the first call
per shape times a small candidate sweep and caches the winner — intended
for the TPU backend (ROADMAP: hardware validation pending).
"""
from __future__ import annotations

import functools
import os
import re
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.lut import (contraction_table, decode_planes, pack_bitplanes,
                            pack_int4, plane_decomposition, planes_from_codes,
                            truncate_plane_spec, validate_weight_bits,
                            weight_bits)
from repro.kernels.lutmul import kernel, ref

_BACKEND: Optional[str] = None

# incremented on every *weight* quantization/packing event (the thing a
# cached QuantizedLinear must do once, not per forward call — tested)
WEIGHT_QUANT_COUNT = 0


def set_backend(name: Optional[str]) -> None:
    global _BACKEND
    _BACKEND = name


_BACKENDS = ("pallas", "interpret", "ref")


def get_backend() -> str:
    """The kernel backend: ``set_backend`` wins, then REPRO_KERNEL_BACKEND,
    then the platform default.  On a TPU the env var may not swap the
    kernels for ``interpret`` or ``ref`` outside a test — a stray setting
    would otherwise serve and time the oracle in place of the kernels."""
    if _BACKEND is not None:
        return _BACKEND
    on_tpu = jax.default_backend() == "tpu"
    env = os.environ.get("REPRO_KERNEL_BACKEND")
    if env:
        if env not in _BACKENDS:
            raise ValueError(f"REPRO_KERNEL_BACKEND={env!r}: expected one "
                             f"of {_BACKENDS}")
        if (on_tpu and env != "pallas"
                and "PYTEST_CURRENT_TEST" not in os.environ):
            raise RuntimeError(
                f"REPRO_KERNEL_BACKEND={env!r} on a TPU: the Pallas kernels "
                "would be replaced by the CPU-test path.  Unset it, or call "
                "ops.set_backend() where a comparison needs the oracle")
        return env
    return "pallas" if on_tpu else "ref"


def _pad_to(x: jax.Array, m0: int, m1: int, value=0) -> jax.Array:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)), constant_values=value)
    return x


# tables are lazily built + device-transferred on first kernel use (module
# import used to eagerly push both tables to device — satellite fix)
_TABLE_CACHE: dict[bool, jax.Array] = {}


def _get_table(a_signed: bool) -> jax.Array:
    """[16, 16] int32 product table (row = weight code, col = act code)."""
    t = _TABLE_CACHE.get(a_signed)
    if t is None:
        t = jnp.asarray(contraction_table(a_signed=a_signed), jnp.int32)
        # under a jit trace the constant is a tracer — never cache those
        if not isinstance(t, jax.core.Tracer):
            _TABLE_CACHE[a_signed] = t
    return t


# ---------------------------------------------------------------------------
# block-size selection (+ optional autotune sweep)
# ---------------------------------------------------------------------------

_AUTOTUNE: Optional[bool] = None
_BLOCK_CACHE: dict[tuple, tuple[int, int, int]] = {}

# (bm, bn, bk) candidates, all (8, 128, 128)-aligned; the first entry is the
# heuristic default so a disabled autotuner is a zero-cost lookup.  Wide
# N/K blocks keep the grid short: at decode (M=8) a v5e pays a fixed cost
# per grid step that dominated every kernel at 128x128 blocks.
_CANDIDATES = ((128, 512, 512), (128, 128, 128), (256, 256, 256),
               (128, 256, 256), (128, 512, 256))


def set_autotune(enabled: Optional[bool]) -> None:
    global _AUTOTUNE
    _AUTOTUNE = enabled


def autotune_enabled() -> bool:
    if _AUTOTUNE is not None:
        return _AUTOTUNE
    return os.environ.get("REPRO_LUTMUL_AUTOTUNE", "0") == "1"


def _clip_blocks(M: int, K: int, N: int, bm: int, bn: int,
                 bk: int) -> tuple[int, int, int]:
    """Shrink blocks to the (padded) problem so tiny shapes don't over-pad.

    ``bn``/``bk`` are halved (down to 128) until they divide N/K padded to
    128: a block that does not divide the weight's dims makes every call
    copy the whole weight into a padded buffer (a 1x4 shard of d_ff=18944
    is 4736 wide, which a 512 block would pad to 5120)."""
    def fit(b: int, d: int) -> int:
        d = max(128, 128 * (-(-d // 128)))
        b = min(b, d)
        while b > 128 and d % b:
            b //= 2
        return b
    bm = min(bm, max(8, 8 * (-(-M // 8))))
    return bm, fit(bn, N), fit(bk, K)


def pick_blocks(op: str, M: int, K: int, N: int, backend: str,
                bench_fn=None) -> tuple[int, int, int]:
    """Cached (bm, bn, bk) per shape; times a candidate sweep when autotuning
    is on and a ``bench_fn(bm, bn, bk) -> callable`` is supplied."""
    key = (op, M, K, N, backend)
    hit = _BLOCK_CACHE.get(key)
    if hit is not None:
        return hit
    default = _clip_blocks(M, K, N, *_CANDIDATES[0])
    if not autotune_enabled():
        _BLOCK_CACHE[key] = default
        return default
    if bench_fn is None:      # tracing: can't time; don't poison the cache
        return default
    best, best_t = default, float("inf")
    seen = set()
    for cand in _CANDIDATES:
        blocks = _clip_blocks(M, K, N, *cand)
        if blocks in seen:
            continue
        seen.add(blocks)
        try:
            run = bench_fn(*blocks)
            run()                                   # compile
            run()                                   # warm caches / frequency
            reps = []
            for _ in range(5):
                t0 = time.perf_counter()
                run()
                reps.append(time.perf_counter() - t0)
            dt = sorted(reps)[len(reps) // 2]       # median
        except Exception:
            if backend == "pallas":                 # a kernel that fails to
                raise                               # compile must surface
            continue                                # infeasible in interpret
        if dt < best_t:
            best, best_t = blocks, dt
    _BLOCK_CACHE[key] = best
    return best


# ---------------------------------------------------------------------------
# quant-mode grammar + shape validation
# ---------------------------------------------------------------------------

_TMAC_MODE = re.compile(r"^(?:w(\d+)|(ternary))_?a(\d+)(_tmac)?$")


def parse_mode(mode: str) -> tuple[str, object, int]:
    """Parse a quant-mode string -> (formulation, wbits_spec, abits).

    Legacy modes: "w4a4_mxu"/"" -> ("int", 4, 4); "w8a8" -> ("int", 8, 8);
    "w4a4_lut" -> ("onehot", 4, 4).  T-MAC family: "w{1,2,3,4}a{4,8}_tmac"
    and "ternary_a{4,8}_tmac" -> ("tmac", spec, abits).  Suffix-free
    sub-4-bit modes ("w2a4", "ternary_a4") -> ("auto", spec, abits): the
    formulation is chosen per (bits, shape) by :func:`pick_formulation`.
    """
    if mode in ("", "none", "w4a4_mxu"):
        return ("int", 4, 4)
    if mode == "w8a8":
        return ("int", 8, 8)
    if mode == "w4a4_lut":
        return ("onehot", 4, 4)
    m = _TMAC_MODE.match(mode)
    if m:
        spec = "ternary" if m.group(2) else int(m.group(1))
        validate_weight_bits(spec)
        abits = int(m.group(3))
        if abits not in (4, 8):
            raise ValueError(
                f"unsupported activation bit width a{abits} in {mode!r}: "
                "the quantizers support a4 and a8")
        return ("tmac" if m.group(4) else "auto", spec, abits)
    raise ValueError(
        f"unknown quant mode {mode!r}: expected one of w4a4_mxu | w4a4_lut | "
        "w8a8 | w{{1,2,3,4}}a{{4,8}}[_tmac] | ternary_a{{4,8}}[_tmac]")


def tmac_group_size(abits: int) -> int:
    """Activation-group width g.  a4 uses g=2 (real partial-sum tables, int8
    table entries bounded by 8g <= 32 on TPU); a8 clamps to g=1 (the
    degenerate direct-contraction path) so table entries stay in int8."""
    return 1 if abits >= 8 else 2


def _check_lut_shapes(a_codes: jax.Array, w_packed: jax.Array,
                      table: Optional[jax.Array] = None) -> None:
    K = a_codes.shape[1]
    if K % 2:
        raise ValueError(
            f"lutmul requires even K for nibble-packed weights, got K={K}; "
            "pad the contraction dim to a multiple of 2 (models do this by "
            "construction)")
    if w_packed.ndim != 2:
        raise ValueError(
            f"w_packed must be 2D [K//2, N], got shape {w_packed.shape}; "
            "3D [P, K//8, N] bitplane leaves belong to the tmac formulation "
            "(use lutmul_tmac)")
    if w_packed.shape[0] * 2 != K:
        raise ValueError(
            f"w_packed rows ({w_packed.shape[0]}) must be K//2 = {K // 2} "
            f"for activation K={K}: the weight was packed for "
            f"K={w_packed.shape[0] * 2} (mismatched quantize/packing?)")
    if table is not None and tuple(table.shape) != (16, 16):
        raise ValueError(
            f"product table must be [16, 16] (4-bit x 4-bit codes), got "
            f"{tuple(table.shape)}")


def _check_tmac_shapes(a_q: jax.Array, w_planes: jax.Array, wbits) -> None:
    validate_weight_bits(wbits)
    n_planes = plane_decomposition(wbits)[0]
    K = a_q.shape[1]
    if w_planes.ndim != 3:
        raise ValueError(
            f"tmac weights must be 3D [P, K//8, N] packed bitplanes, got "
            f"shape {w_planes.shape} (2D leaves belong to the one-hot/int "
            "formulations)")
    if w_planes.shape[0] != n_planes:
        raise ValueError(
            f"tmac weight has {w_planes.shape[0]} bitplanes but wbits="
            f"{wbits!r} decomposes into {n_planes} planes (was the leaf "
            "quantized at a different width?)")
    if K % 8:
        raise ValueError(
            f"tmac requires K % 8 == 0 for byte-packed bitplanes, got K={K}")
    if w_planes.shape[1] * 8 != K:
        raise ValueError(
            f"tmac w_planes rows ({w_planes.shape[1]}) must be K//8 = "
            f"{K // 8} for activation K={K}: the weight was packed for "
            f"K={w_planes.shape[1] * 8}")


def truncate_planes(w_planes: jax.Array, wbits, keep: int
                    ) -> tuple[jax.Array, int, int]:
    """Top-``keep`` plane suffix of a packed w{wbits} tmac stack.

    ``w_planes`` is a packed bitplane stack with the plane axis at -3
    (``[P, K//8, N]`` or stacked ``[G, P, K//8, N]``).  Returns
    ``(draft_planes, draft_wbits, scale_mult)``: the suffix slice is a
    *valid* ``w{keep}`` tmac stack (``truncate_plane_spec`` proves the
    coefficient algebra), and ``scale_mult = 2^(wbits-keep)`` must be folded
    into the leaf's ``w_scale`` so the drafter dequantizes on the target's
    code grid.  Pure slicing — the draft view shares the target's packed
    bytes, zero extra weight memory.
    """
    kept, mult = truncate_plane_spec(wbits, keep)
    n_planes = plane_decomposition(wbits)[0]
    if w_planes.ndim < 3 or w_planes.shape[-3] != n_planes:
        raise ValueError(
            f"cannot truncate: leaf has plane axis {w_planes.shape} but "
            f"wbits={wbits!r} decomposes into {n_planes} planes")
    return w_planes[..., n_planes - kept:, :, :], kept, mult


# ---------------------------------------------------------------------------
# raw integer matmuls (int32 out, no scales)
# ---------------------------------------------------------------------------

def lutmul(a_codes: jax.Array, w_packed: jax.Array, *, a_signed: bool = True,
           backend: Optional[str] = None, impl: str = "onehot") -> jax.Array:
    """LUT-based matmul on 4-bit codes. a_codes: [M,K] u8; w_packed: [K//2,N] u8."""
    _check_lut_shapes(a_codes, w_packed)
    be = backend or get_backend()
    M, K = a_codes.shape
    N = w_packed.shape[1]
    if be == "ref":
        return ref.lutmul_ref(a_codes, w_packed, a_signed)
    table = _get_table(a_signed)
    interpret = be != "pallas"

    def bench(bm, bn, bk):
        a_p = _pad_to(a_codes, bm, bk)
        w_p = _pad_to(w_packed, bk // 2, bn)
        f = jax.jit(functools.partial(
            kernel.lutmul_pallas, a_p, w_p, table, bm=bm, bn=bn, bk=bk,
            impl=impl, interpret=interpret))
        return lambda: f().block_until_ready()

    # a sweep can only time concrete arrays — under a jit trace fall back to
    # the cache (populated by a prior eager call) or the heuristic
    if isinstance(a_codes, jax.core.Tracer):
        bench = None
    bm, bn, bk = pick_blocks(f"lutmul_{impl}", M, K, N, be, bench)
    a_p = _pad_to(a_codes, bm, bk)
    w_p = _pad_to(w_packed, bk // 2, bn)
    out = kernel.lutmul_pallas(a_p, w_p, table, bm=bm, bn=bn, bk=bk,
                               impl=impl, interpret=interpret)
    return out[:M, :N]


def lutmul_gather(a_codes: jax.Array, w_packed: jax.Array, *,
                  a_signed: bool = True,
                  backend: Optional[str] = None) -> jax.Array:
    """The retained serial-gather kernel (A/B baseline for the benches)."""
    return lutmul(a_codes, w_packed, a_signed=a_signed, backend=backend,
                  impl="gather")


def int_matmul(a: jax.Array, w: jax.Array,
               backend: Optional[str] = None) -> jax.Array:
    """int8 x int8 -> int32 under the same tiling (DSP-packing analogue)."""
    be = backend or get_backend()
    if be == "ref":
        return ref.int_matmul_ref(a, w)
    M, K = a.shape
    N = w.shape[1]
    interpret = be != "pallas"

    def bench(bm, bn, bk):
        a_p = _pad_to(a, bm, bk)
        w_p = _pad_to(w, bk, bn)
        f = jax.jit(functools.partial(
            kernel.int_matmul_pallas, a_p, w_p, bm=bm, bn=bn, bk=bk,
            interpret=interpret))
        return lambda: f().block_until_ready()

    if isinstance(a, jax.core.Tracer):
        bench = None
    bm, bn, bk = pick_blocks("int_matmul", M, K, N, be, bench)
    a_p = _pad_to(a, bm, bk)
    w_p = _pad_to(w, bk, bn)
    out = kernel.int_matmul_pallas(a_p, w_p, bm=bm, bn=bn, bk=bk,
                                   interpret=interpret)
    return out[:M, :N]


def _pad_planes(w_planes: jax.Array, bk: int, bn: int) -> jax.Array:
    """Pad [P, K//8, N] packed bitplanes to (bk//8, bn) multiples.  Zero
    plane bytes select table entry 0 (= 0) so padding is exact."""
    p1 = (-w_planes.shape[1]) % (bk // 8)
    p2 = (-w_planes.shape[2]) % bn
    if p1 or p2:
        w_planes = jnp.pad(w_planes, ((0, 0), (0, p1), (0, p2)))
    return w_planes


def lutmul_tmac(a_q: jax.Array, w_planes: jax.Array, wbits, *,
                g: Optional[int] = None, abits: int = 4,
                backend: Optional[str] = None) -> jax.Array:
    """T-MAC matmul: int8 activation codes x packed weight bitplanes -> int32.

    a_q: [M, K] int8 signed codes; w_planes: [P, K//8, N] uint8 (the
    ``quantize_weights_planes`` format); wbits: spec from
    ``core.lut.WEIGHT_BITS_SPECS``.  Kernel cost is linear in the plane
    count P (module docstring of kernel.py).
    """
    _check_tmac_shapes(a_q, w_planes, wbits)
    n_planes, coeffs, const = plane_decomposition(wbits)
    if g is None:
        g = tmac_group_size(abits)
    be = backend or get_backend()
    M, K = a_q.shape
    N = w_planes.shape[-1]
    if be == "ref":
        # decoded-plane contraction: exact integer math, identical result to
        # the faithful group-table gather (ref.lutmul_tmac_ref — the fuzz
        # suite pins all three against each other)
        from repro.core.lut import unpack_bitplanes
        w = decode_planes(unpack_bitplanes(w_planes), wbits)
        return a_q.astype(jnp.int32) @ w
    interpret = be != "pallas"

    def bench(bm, bn, bk):
        a_p = _pad_to(a_q, bm, bk)
        w_p = _pad_planes(w_planes, bk, bn)
        f = jax.jit(functools.partial(
            kernel.lutmul_tmac_pallas, a_p, w_p, coeffs=coeffs, const=const,
            g=g, bm=bm, bn=bn, bk=bk, interpret=interpret))
        return lambda: f().block_until_ready()

    if isinstance(a_q, jax.core.Tracer):
        bench = None
    bm, bn, bk = pick_blocks(f"lutmul_tmac{g}_p{n_planes}", M, K, N, be,
                             bench)
    a_p = _pad_to(a_q, bm, bk)
    w_p = _pad_planes(w_planes, bk, bn)
    out = kernel.lutmul_tmac_pallas(a_p, w_p, coeffs=coeffs, const=const,
                                    g=g, bm=bm, bn=bn, bk=bk,
                                    interpret=interpret)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# fused-epilogue dispatch (kernel backends): int32 accumulate + in-kernel
# rescale, so no fp32 [M, N] intermediate is materialized
# ---------------------------------------------------------------------------

def _fused_lut(a_codes, w_packed, a_scale, w_scale, *, a_signed: bool,
               be: str, out_dtype) -> jax.Array:
    _check_lut_shapes(a_codes, w_packed)
    M, K = a_codes.shape
    N = w_packed.shape[1]
    table = _get_table(a_signed)
    interpret = be != "pallas"
    bm, bn, bk = pick_blocks("lutmul_fused", M, K, N, be)
    a_p = _pad_to(a_codes, bm, bk)
    w_p = _pad_to(w_packed, bk // 2, bn)
    as_p = _pad_to(a_scale.astype(jnp.float32), bm, 1)
    ws_p = _pad_to(w_scale.astype(jnp.float32), 1, bn)
    out = kernel.lutmul_fused_pallas(a_p, w_p, table, as_p, ws_p, bm=bm,
                                     bn=bn, bk=bk, out_dtype=out_dtype,
                                     interpret=interpret)
    return out[:M, :N]


def _fused_int(a_q, w_int, a_scale, w_scale, *, be: str,
               out_dtype) -> jax.Array:
    M, K = a_q.shape
    N = w_int.shape[1]
    interpret = be != "pallas"
    bm, bn, bk = pick_blocks("int_matmul_fused", M, K, N, be)
    a_p = _pad_to(a_q, bm, bk)
    w_p = _pad_to(w_int, bk, bn)
    as_p = _pad_to(a_scale.astype(jnp.float32), bm, 1)
    ws_p = _pad_to(w_scale.astype(jnp.float32), 1, bn)
    out = kernel.int_matmul_fused_pallas(a_p, w_p, as_p, ws_p, bm=bm, bn=bn,
                                         bk=bk, out_dtype=out_dtype,
                                         interpret=interpret)
    return out[:M, :N]


def _fused_tmac(a_q, w_planes, a_scale, w_scale, *, wbits, g: int, be: str,
                out_dtype) -> jax.Array:
    _check_tmac_shapes(a_q, w_planes, wbits)
    _, coeffs, const = plane_decomposition(wbits)
    M, K = a_q.shape
    N = w_planes.shape[-1]
    n_planes = w_planes.shape[0]
    interpret = be != "pallas"
    bm, bn, bk = pick_blocks(f"lutmul_tmac{g}_p{n_planes}_fused", M, K, N, be)
    a_p = _pad_to(a_q, bm, bk)
    w_p = _pad_planes(w_planes, bk, bn)
    as_p = _pad_to(a_scale.astype(jnp.float32), bm, 1)
    ws_p = _pad_to(w_scale.astype(jnp.float32), 1, bn)
    out = kernel.lutmul_tmac_fused_pallas(a_p, w_p, as_p, ws_p, coeffs=coeffs,
                                          const=const, g=g, bm=bm, bn=bn,
                                          bk=bk, out_dtype=out_dtype,
                                          interpret=interpret)
    return out[:M, :N]


# ---------------------------------------------------------------------------
# epilogue-variant selection (fused vs unfused dequant) — satellite fix for
# the fused-dequant regression: interpret mode pays more for the VMEM
# scratch + per-block epilogue machinery than the fusion saves (measured:
# 7.8 ms fused vs 5.2 ms unfused at 256^3), so dispatch defaults to the
# unfused epilogue there and to fused on real hardware; with autotuning on,
# a timed A/B per (op, shape) decides and the bench records the winner.
# ---------------------------------------------------------------------------

_VARIANT_CACHE: dict[tuple, str] = {}


def pick_variant(op: str, M: int, K: int, N: int, backend: str,
                 bench_fns=None) -> str:
    """Cached "fused" | "unfused" dequant-epilogue choice per (op, shape).

    ``bench_fns``: optional {"fused": fn, "unfused": fn} of nullary timed
    callables; only consulted when autotuning is enabled (the bench supplies
    them so the committed BENCH rows record which variant won).
    """
    key = (op, M, K, N, backend)
    hit = _VARIANT_CACHE.get(key)
    if hit is not None:
        return hit
    default = "fused" if backend == "pallas" else "unfused"
    if not autotune_enabled():
        _VARIANT_CACHE[key] = default
        return default
    if not bench_fns:
        return default
    best, best_t = default, float("inf")
    for name, run in bench_fns.items():
        try:
            run()
            run()
            reps = []
            for _ in range(5):
                t0 = time.perf_counter()
                run()
                reps.append(time.perf_counter() - t0)
            dt = sorted(reps)[len(reps) // 2]
        except Exception:
            if backend == "pallas":
                raise
            continue
        if dt < best_t:
            best, best_t = name, dt
    _VARIANT_CACHE[key] = best
    return best


# ---------------------------------------------------------------------------
# quantizers
# ---------------------------------------------------------------------------

def _quantize_with_scale(x2: jax.Array, a_scale: jax.Array,
                         qmax: int) -> jax.Array:
    """Symmetric round/clip to int8 codes under a precomputed scale — THE
    one copy of the formula both the full-K and the head-sharded (pmax
    scale) paths share, so they can never drift apart."""
    return jnp.clip(jnp.round(x2 / a_scale), -qmax - 1, qmax).astype(jnp.int8)


def quantize_activations(x2: jax.Array, bits: int):
    """Per-token symmetric quant: [M, K] f32 -> (int8 codes, [M, 1] scale)."""
    if bits not in (4, 8):
        raise ValueError(
            f"unsupported activation bit width {bits!r}: activations "
            "quantize to a4 or a8 (sub-4-bit widths apply to *weights* — "
            "see quantize_weights_planes)")
    qmax = 2 ** (bits - 1) - 1
    a_scale = jnp.maximum(jnp.max(jnp.abs(x2), axis=1, keepdims=True),
                          1e-8) / qmax
    return _quantize_with_scale(x2, a_scale, qmax), a_scale


@functools.partial(jax.jit, static_argnums=1)
def absmax_codes(wf: jax.Array, qmax: int):
    """Per-output-channel symmetric quant math shared by every weight
    quantizer: ([..., K, N] float codes in [-qmax-1, qmax], [..., 1, N]
    f32 scale).  Always compiled, so an eager call and one traced inside a
    larger jit give the same bits (XLA folds the division by the constant
    ``qmax`` into a reciprocal multiply only when it compiles)."""
    wf = wf.astype(jnp.float32)
    scale = jnp.maximum(
        jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / qmax, 1e-8)
    return jnp.clip(jnp.round(wf / scale), -qmax - 1, qmax), scale


@functools.partial(jax.jit, static_argnums=1)
def _meanabs_codes(wf: jax.Array, wbits) -> tuple[jax.Array, jax.Array]:
    """BitNet-b1.58 per-channel mean-|w| quant (ternary / w1), compiled for
    the same reason as :func:`absmax_codes`."""
    wf = wf.astype(jnp.float32)
    scale = jnp.maximum(jnp.mean(jnp.abs(wf), axis=-2, keepdims=True), 1e-8)
    if wbits == "ternary":
        return jnp.clip(jnp.round(wf / scale), -1, 1), scale
    return jnp.where(wf >= 0, 1, -1), scale


def quantize_weights(wf: jax.Array, bits: int, pack: bool = False):
    """Per-output-channel symmetric quant: [K, N] f32 -> (codes, [1, N] scale).

    ``bits`` must be 4 or 8 here — the nibble/int8 storage formats.  Sub-4
    widths (1, 2, 3, ternary) use the bitplane format via
    :func:`quantize_weights_planes`.  Counted by ``WEIGHT_QUANT_COUNT`` —
    cached layers must hit this once at load, never per forward call.
    """
    if bits not in (4, 8):
        raise ValueError(
            f"unsupported weight bit width {bits!r} for the nibble/int8 "
            "format: use 4 or 8, or quantize_weights_planes for the tmac "
            "bitplane family (1, 2, 3, 4, 'ternary')")
    if pack and bits != 4:
        raise ValueError("nibble packing (pack=True) is a 4-bit format; "
                         f"got bits={bits}")
    global WEIGHT_QUANT_COUNT
    WEIGHT_QUANT_COUNT += 1
    codes, w_scale = absmax_codes(wf, 2 ** (bits - 1) - 1)        # [1, N]
    w_q = codes.astype(jnp.int8)
    if pack:
        if wf.shape[0] % 2:
            raise ValueError(
                f"nibble packing needs even K, got K={wf.shape[0]}")
        w_q = pack_int4(w_q.T).T                                   # pack K
    return w_q, w_scale


def quantize_weights_planes(wf: jax.Array, wbits):
    """Per-output-channel quant to the tmac bitplane format.

    [..., K, N] f32 -> ([..., P, K//8, N] uint8 packed bitplanes,
    [..., 1, N] f32 scale) — leading stack dims (the scanned per-group
    block axis) pass through.

    Integer widths use the same absmax/round/clip formula as
    :func:`quantize_weights` (so w4 planes decode to EXACTLY the w4 nibble
    codes — the basis of the cross-formulation bit-exactness tests).
    Ternary and binary follow BitNet-b1.58: per-channel mean-|w| scale,
    codes in {-1, 0, +1} (ternary) / sign in {-1, +1} (w1).
    """
    validate_weight_bits(wbits)
    if wf.shape[-2] % 8:
        raise ValueError(
            f"tmac bitplane packing needs K % 8 == 0, got K={wf.shape[-2]}; "
            "pad the contraction dim before quantizing")
    global WEIGHT_QUANT_COUNT
    WEIGHT_QUANT_COUNT += 1
    if wbits in ("ternary", 1):
        codes, w_scale = _meanabs_codes(wf, wbits)             # [..., 1, N]
    else:
        codes, w_scale = absmax_codes(wf, 2 ** (int(wbits) - 1) - 1)
    planes = planes_from_codes(codes.astype(jnp.int32), wbits)
    return pack_bitplanes(planes), w_scale


# ---------------------------------------------------------------------------
# formulation selection: tmac vs one-hot per (bits, shape) — the serving
# quantizer consults this at load time, so the stored leaf format IS the
# formulation choice and the forward pass just follows the leaf's shape
# ---------------------------------------------------------------------------

_FORMULATION_CACHE: dict[tuple, str] = {}


def pick_formulation(wbits, abits: int, K: int, N: int,
                     backend: Optional[str] = None) -> str:
    """Cached "tmac" | "onehot" choice per (wbits, abits, K, N, backend).

    Heuristic default: tmac below 4 weight bits (its MAC count is linear in
    the plane count; one-hot is flat at 4K), one-hot at w4.  With autotuning
    enabled, the first call per shape times both dispatches on synthetic
    codes at a probe M and caches the winner.  a8 activations always take
    tmac (the one-hot product table is 4-bit x 4-bit).
    """
    validate_weight_bits(wbits)
    be = backend or get_backend()
    key = (wbits, abits, K, N, be)
    hit = _FORMULATION_CACHE.get(key)
    if hit is not None:
        return hit
    if abits >= 8:
        _FORMULATION_CACHE[key] = "tmac"
        return "tmac"
    default = "tmac" if weight_bits(wbits) < 4 else "onehot"
    if be == "ref" or not autotune_enabled():
        _FORMULATION_CACHE[key] = default
        return default
    import numpy as np
    rng = np.random.default_rng(0)
    M = 256
    a_q = jnp.asarray(rng.integers(-8, 8, size=(M, K)), jnp.int8)
    n_planes = plane_decomposition(wbits)[0]
    planes = jnp.asarray(
        rng.integers(0, 256, size=(n_planes, K // 8, N)), jnp.uint8)
    # sub-4-bit codes are valid 4-bit codes, so one-hot runs them unchanged
    # (at its flat 4K cost) — decode the planes and nibble-pack
    from repro.core.lut import unpack_bitplanes
    codes = decode_planes(unpack_bitplanes(planes), wbits).astype(jnp.int8)
    nib = pack_int4(codes.T).T
    timings = {}
    for name, fn in (
            ("tmac", jax.jit(functools.partial(
                lutmul_tmac, a_q, planes, wbits, abits=abits, backend=be))),
            ("onehot", jax.jit(functools.partial(
                lutmul, (a_q.astype(jnp.uint8)) & 0xF, nib, a_signed=True,
                backend=be)))):
        try:
            jax.block_until_ready(fn())
            reps = []
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                reps.append(time.perf_counter() - t0)
            timings[name] = sorted(reps)[len(reps) // 2]
        except Exception:
            if be == "pallas":
                raise
            continue
    best = min(timings, key=timings.get) if timings else default
    _FORMULATION_CACHE[key] = best
    return best


# ---------------------------------------------------------------------------
# pre-quantized (serving) matmul: weights already integer codes on HBM
# ---------------------------------------------------------------------------

def _unpack_w(w_q: jax.Array) -> jax.Array:
    """Packed-int4 uint8 [..., K//2, N] -> int8 [..., K, N]."""
    from repro.core.lut import unpack_int4
    return jnp.swapaxes(
        unpack_int4(jnp.swapaxes(w_q, -1, -2), signed=True), -1, -2)


def _row_parallel_prequant(x, w_q, w_scale, mode, compute_dtype, be,
                           axis: str, size: int) -> jax.Array:
    """Row-parallel (K-sharded) pre-quantized matmul under ``shard_map``.

    ``w_q`` is this device's K slice of the codes.  ``x`` is either the full
    replicated activation (classic Megatron row-parallel) or — when attention
    runs head-sharded — already this shard's K slice (the head-local
    attention output feeding ``wo``), distinguished statically by its K
    extent.  Either way the activation scale is the FULL-K per-token scale
    (identical to the single-device scale): taken directly on the replicated
    input, or recovered exactly from the local slice via a ``pmax`` of the
    per-shard maxima — max is associative and exact, so both routes yield
    the same fp32 scale bit for bit.  Each shard contracts its slice into an
    int32 partial, and ``psum`` adds the partials — int32 addition is exact,
    so the dequant epilogue sees bit-identical accumulators to the unsharded
    kernel.  The epilogue is deliberately unfused here: fusion would rescale
    *partial* sums per shard and break that exactness.
    """
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w_q.shape[-1]
    tmac = w_q.ndim == 3
    packed = w_q.dtype == jnp.uint8 and not tmac
    if tmac:
        _, wspec, bits = parse_mode(mode)
    else:
        bits = 4 if packed else 8
    rows = w_q.shape[-2]
    Kl = 8 * rows if tmac else (2 * rows if packed else rows)
    qmax = 2 ** (bits - 1) - 1
    if K == Kl * size:
        # replicated input: quantize full-K, contract the local slice
        x2 = x.reshape(-1, K).astype(jnp.float32)
        a_q, a_scale = quantize_activations(x2, bits)
        a_l = jax.lax.dynamic_slice_in_dim(
            a_q, jax.lax.axis_index(axis) * Kl, Kl, axis=1)
    elif K == Kl:
        # head-sharded input: x IS the local K slice; the full-K per-token
        # max is the max of the per-shard maxima (exact)
        x2 = x.reshape(-1, K).astype(jnp.float32)
        local_max = jnp.max(jnp.abs(x2), axis=1, keepdims=True)
        a_scale = jnp.maximum(jax.lax.pmax(local_max, axis), 1e-8) / qmax
        a_l = _quantize_with_scale(x2, a_scale, qmax)
    else:
        raise ValueError(
            f"row-parallel activation K ({K}) matches neither the full "
            f"extent ({Kl * size}) nor this shard's slice ({Kl})")
    if tmac:
        acc = lutmul_tmac(a_l, w_q, wspec, abits=bits, backend=be)
    elif packed and mode == "w4a4_lut":
        acc = lutmul(a_l.astype(jnp.uint8) & 0xF, w_q, a_signed=True,
                     backend=be)
    else:
        acc = int_matmul(a_l, _unpack_w(w_q) if packed else w_q, backend=be)
    acc = jax.lax.psum(acc, axis)
    y = acc.astype(jnp.float32) * a_scale * w_scale.reshape(1, N)
    return y.reshape(*lead, N).astype(compute_dtype)


def prequant_matmul(x: jax.Array, w_q: jax.Array, w_scale: jax.Array,
                    mode: str = "", compute_dtype=jnp.bfloat16,
                    backend: Optional[str] = None,
                    tp: Optional[str] = None) -> jax.Array:
    """x: [..., K] float; w_q: packed-int4 uint8 [K//2, N] or int8 [K, N].

    Weight bytes on HBM are the integer codes (4x/2x smaller than bf16) —
    the serving embodiment of the paper's weights-live-in-LUTs idea.  On the
    kernel backends the dequant epilogue is fused: the int32 accumulator is
    rescaled in-kernel and written as ``compute_dtype`` directly.

    ``tp`` ("col" | "head" | "row" | None) is the tensor-parallel layout of
    ``w_q`` when tracing inside an active ``dist.tp.tp_context`` (the
    sharded serving engine): column-parallel computes the local N columns
    with the unsharded math and all-gathers; head-parallel is
    column-parallel *without* the gather (QKV projections whose local
    columns are whole attention heads — the caller keeps working on local
    heads); row-parallel contracts a K slice and psums the exact int32
    accumulator (see ``_row_parallel_prequant``).  Outside the context
    ``tp`` is ignored.
    """
    from repro.dist import tp as tp_lib
    axis = tp_lib.model_axis() if tp else None
    if axis is not None and tp == "row":
        return _row_parallel_prequant(x, w_q, w_scale, mode, compute_dtype,
                                      backend or get_backend(), axis,
                                      tp_lib.model_size())
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w_q.shape[-1]
    tmac = w_q.ndim == 3                     # bitplane leaf -> tmac kernel
    packed = w_q.dtype == jnp.uint8 and not tmac
    x2 = x.reshape(-1, K).astype(jnp.float32)
    if tmac:
        _, wspec, bits = parse_mode(mode)
        g = tmac_group_size(bits)
        _check_tmac_shapes(x2, w_q, wspec)
        op = f"lutmul_tmac{g}"
    else:
        if packed:             # both fused and unfused dispatch need this
            _check_lut_shapes(x2, w_q)
        bits = 4 if packed else 8
        op = "lutmul" if (packed and mode == "w4a4_lut") else "int_matmul"
    a_q, a_scale = quantize_activations(x2, bits)
    be = backend or get_backend()
    ws_row = w_scale.reshape(1, N)
    fused = (be != "ref"
             and pick_variant(op, x2.shape[0], K, N, be) == "fused")
    if fused:
        if tmac:
            y = _fused_tmac(a_q, w_q, a_scale, ws_row, wbits=wspec, g=g,
                            be=be, out_dtype=compute_dtype)
        elif packed and mode == "w4a4_lut":
            y = _fused_lut(a_q.astype(jnp.uint8) & 0xF, w_q, a_scale, ws_row,
                           a_signed=True, be=be, out_dtype=compute_dtype)
        else:
            y = _fused_int(a_q, _unpack_w(w_q) if packed else w_q, a_scale,
                           ws_row, be=be, out_dtype=compute_dtype)
        y = y.reshape(*lead, N)
    else:
        if tmac:
            acc = lutmul_tmac(a_q, w_q, wspec, g=g, abits=bits, backend=be)
        elif packed and mode == "w4a4_lut":
            acc = lutmul((a_q.astype(jnp.uint8)) & 0xF, w_q, a_signed=True,
                         backend=be)
        else:
            acc = int_matmul(a_q, _unpack_w(w_q) if packed else w_q,
                             backend=be)
        y = (acc.astype(jnp.float32) * a_scale * ws_row) \
            .reshape(*lead, N).astype(compute_dtype)
    if axis is not None and tp == "col":     # column-parallel: N is local
        y = jax.lax.all_gather(y, axis, axis=-1, tiled=True)
    return y                                 # "head": stays head-local


# ---------------------------------------------------------------------------
# high-level quantized projection used by models/layers.linear
# ---------------------------------------------------------------------------

def quantized_matmul(x: jax.Array, w: jax.Array, mode: str = "w4a4_mxu",
                     compute_dtype=jnp.bfloat16,
                     backend: Optional[str] = None) -> jax.Array:
    """Dynamic-activation-quant matmul: x [..., K] fp, w [K, N] fp.

    Weights: symmetric per-output-channel int4 (or int8); activations:
    symmetric per-token int4/int8 (transformer hidden states are signed — the
    unsigned-uint4+threshold path of the paper applies to post-ReLU CNNs and
    is exercised by the MobileNetV2 model).

    NOTE: this path re-quantizes ``w`` on every call — models that own their
    weights should quantize once via ``models.layers.QuantizedLinear`` (or
    ``serve.quantize``) and go through :func:`prequant_matmul`.
    """
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w.shape[-1]
    x2 = x.reshape(-1, K).astype(jnp.float32)
    wf = w.astype(jnp.float32)

    form, wspec, abits = parse_mode(mode)
    if form in ("tmac", "auto") and weight_bits(wspec) < 4:
        form = "tmac"          # sub-4 bit auto: tmac is the only exact fit
    if form == "tmac":
        w_planes, w_scale = quantize_weights_planes(wf, wspec)
        a_q, a_scale = quantize_activations(x2, abits)
        be = backend or get_backend()
        g = tmac_group_size(abits)
        fused = (be != "ref" and pick_variant(
            f"lutmul_tmac{g}", x2.shape[0], K, N, be) == "fused")
        if fused:
            y = _fused_tmac(a_q, w_planes, a_scale, w_scale, wbits=wspec,
                            g=g, be=be, out_dtype=compute_dtype)
            return y.reshape(*lead, N)
        acc = lutmul_tmac(a_q, w_planes, wspec, g=g, abits=abits, backend=be)
        y = acc.astype(jnp.float32) * a_scale * w_scale
        return y.reshape(*lead, N).astype(compute_dtype)

    bits = 4 if mode.startswith("w4") else 8
    a_q, a_scale = quantize_activations(x2, bits)
    w_q, w_scale = quantize_weights(wf, bits, pack=(mode == "w4a4_lut"))
    be = backend or get_backend()

    op = "lutmul" if mode == "w4a4_lut" else "int_matmul"
    if be != "ref" and pick_variant(op, x2.shape[0], K, N, be) == "fused":
        if mode == "w4a4_lut":
            y = _fused_lut(a_q.astype(jnp.uint8) & 0xF, w_q, a_scale, w_scale,
                           a_signed=True, be=be, out_dtype=compute_dtype)
        else:
            y = _fused_int(a_q, w_q, a_scale, w_scale, be=be,
                           out_dtype=compute_dtype)
        return y.reshape(*lead, N)
    if mode == "w4a4_lut":
        acc = lutmul((a_q.astype(jnp.uint8)) & 0xF, w_q, a_signed=True,
                     backend=be)
    else:  # w4a4_mxu / w8a8 — integer dot (MXU path)
        acc = int_matmul(a_q, w_q, backend=be)
    y = acc.astype(jnp.float32) * a_scale * w_scale
    return y.reshape(*lead, N).astype(compute_dtype)
