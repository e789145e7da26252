"""Pallas kernels vs pure-jnp oracles: exact integer equality across shape
sweeps (interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.lut import pack_int4
from repro.kernels.lutmul import ops, ref

SHAPES = [(8, 32, 16), (16, 128, 128), (100, 256, 130), (128, 384, 256),
          (1, 64, 48), (257, 128, 64)]


def _rand_case(rng, M, K, N):
    a = rng.integers(-8, 8, size=(M, K)).astype(np.int8)
    w = rng.integers(-8, 8, size=(K, N)).astype(np.int8)
    a_codes = jnp.asarray(a.astype(np.uint8) & 0xF)
    w_packed = pack_int4(jnp.asarray(w).T).T
    want = a.astype(np.int32) @ w.astype(np.int32)
    return a, w, a_codes, w_packed, want


@pytest.mark.parametrize("M,K,N", SHAPES)
def test_lutmul_kernel_vs_oracle(M, K, N):
    rng = np.random.default_rng(M * 1000 + N)
    a, w, a_codes, w_packed, want = _rand_case(rng, M, K, N)
    got_ref = ref.lutmul_ref(a_codes, w_packed, a_signed=True)
    np.testing.assert_array_equal(np.asarray(got_ref), want)
    got_kernel = ops.lutmul(a_codes, w_packed, backend="interpret")
    np.testing.assert_array_equal(np.asarray(got_kernel), want)


@pytest.mark.parametrize("M,K,N", SHAPES)
def test_int_matmul_kernel_vs_oracle(M, K, N):
    rng = np.random.default_rng(M + N)
    a = rng.integers(-128, 128, size=(M, K)).astype(np.int8)
    w = rng.integers(-128, 128, size=(K, N)).astype(np.int8)
    want = a.astype(np.int32) @ w.astype(np.int32)
    got = ops.int_matmul(jnp.asarray(a), jnp.asarray(w), backend="interpret")
    np.testing.assert_array_equal(np.asarray(got), want)


@given(M=st.integers(1, 40), K=st.integers(2, 96).map(lambda k: k * 2),
       N=st.integers(1, 40))
@settings(max_examples=20, deadline=None)
def test_lutmul_property_random_shapes(M, K, N):
    rng = np.random.default_rng(M * 7 + K * 13 + N)
    a, w, a_codes, w_packed, want = _rand_case(rng, M, K, N)
    got = ops.lutmul(a_codes, w_packed, backend="ref")
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("mode", ["w4a4_lut", "w4a4_mxu", "w8a8"])
def test_quantized_matmul_accuracy(mode):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (32, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (128, 64), jnp.float32)
    y = ops.quantized_matmul(x, w, mode=mode, backend="ref",
                             compute_dtype=jnp.float32)
    rel = float(jnp.linalg.norm(y - x @ w) / jnp.linalg.norm(x @ w))
    # 4-bit dynamic quant of gaussian data: ~4.7% per-operand grid error
    # compounding over both operands -> ~17% output error pre-QAT (QAT's job
    # is to adapt the distributions; see benchmarks/qat_accuracy.py)
    assert rel < (0.02 if mode == "w8a8" else 0.20), rel
    assert np.isfinite(np.asarray(y)).all()


def test_quantized_matmul_lut_equals_mxu_int_math():
    """The LUT path and the integer-dot path share quantizers -> identical."""
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 64), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(3), (64, 32), jnp.float32)
    y1 = ops.quantized_matmul(x, w, mode="w4a4_lut", backend="ref",
                              compute_dtype=jnp.float32)
    y2 = ops.quantized_matmul(x, w, mode="w4a4_mxu", backend="ref",
                              compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-6)


@pytest.mark.slow
def test_lutmul_interpret_dtype_sweep():
    rng = np.random.default_rng(0)
    for a_signed in (True, False):
        M, K, N = 64, 128, 96
        a_vals = rng.integers(-8, 8, (M, K)) if a_signed \
            else rng.integers(0, 16, (M, K))
        w = rng.integers(-8, 8, size=(K, N)).astype(np.int8)
        a_codes = jnp.asarray(a_vals.astype(np.uint8) & 0xF)
        w_packed = pack_int4(jnp.asarray(w).T).T
        want = a_vals.astype(np.int32) @ w.astype(np.int32)
        got = ops.lutmul(a_codes, w_packed, a_signed=a_signed,
                         backend="interpret")
        np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# padding edge cases + impl agreement (onehot contraction vs gather vs ref)
# ---------------------------------------------------------------------------

PAD_SHAPES = [(5, 18, 7),       # everything under one block
              (3, 130, 5),      # K just over a block
              (129, 126, 129),  # M/N just over, K just under
              (7, 2, 1),        # M < 8, minimal K/N
              (1, 64, 48)]      # single row


@pytest.mark.parametrize("M,K,N", PAD_SHAPES)
def test_lutmul_padding_all_impls_agree(M, K, N):
    rng = np.random.default_rng(M * 31 + K * 7 + N)
    a, w, a_codes, w_packed, want = _rand_case(rng, M, K, N)
    got_ref = np.asarray(ref.lutmul_ref(a_codes, w_packed, a_signed=True))
    got_onehot = np.asarray(ops.lutmul(a_codes, w_packed,
                                       backend="interpret", impl="onehot"))
    got_gather = np.asarray(ops.lutmul(a_codes, w_packed,
                                       backend="interpret", impl="gather"))
    np.testing.assert_array_equal(got_ref, want)
    np.testing.assert_array_equal(got_onehot, want)
    np.testing.assert_array_equal(got_gather, want)


def test_lutmul_odd_k_rejected():
    a_codes = jnp.zeros((4, 7), jnp.uint8)          # odd K
    w_packed = jnp.zeros((3, 8), jnp.uint8)
    with pytest.raises(ValueError, match="even K"):
        ops.lutmul(a_codes, w_packed)
    # packed rows must be exactly K // 2
    with pytest.raises(ValueError, match="K//2"):
        ops.lutmul(jnp.zeros((4, 8), jnp.uint8), jnp.zeros((3, 8), jnp.uint8))


def test_quantized_matmul_padding_shapes():
    for (M, K, N) in [(5, 30, 7), (1, 128, 3), (100, 130, 70)]:
        x = jax.random.normal(jax.random.PRNGKey(M), (M, K), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(N), (K, N), jnp.float32)
        for mode in ("w4a4_lut", "w4a4_mxu", "w8a8"):
            y_ref = ops.quantized_matmul(x, w, mode=mode, backend="ref",
                                         compute_dtype=jnp.float32)
            y_int = ops.quantized_matmul(x, w, mode=mode, backend="interpret",
                                         compute_dtype=jnp.float32)
            # same integer accumulator, same epilogue -> bitwise identical
            np.testing.assert_array_equal(np.asarray(y_ref),
                                          np.asarray(y_int))


def test_prequant_fused_epilogue_matches_ref():
    from repro.serve.quantize import quantize_leaf
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 34), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (34, 20), jnp.float32)
    leaf = quantize_leaf(w, 4)
    for mode in ("w4a4_lut", "w4a4_mxu"):
        y_ref = ops.prequant_matmul(x, leaf["w_q"], leaf["w_scale"],
                                    mode=mode, compute_dtype=jnp.float32,
                                    backend="ref")
        y_int = ops.prequant_matmul(x, leaf["w_q"], leaf["w_scale"],
                                    mode=mode, compute_dtype=jnp.float32,
                                    backend="interpret")
        np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_int))


def test_block_autotuner_caches_winner():
    rng = np.random.default_rng(0)
    a, w, a_codes, w_packed, want = _rand_case(rng, 16, 128, 128)
    ops.set_autotune(True)
    try:
        ops._BLOCK_CACHE.clear()
        got = ops.lutmul(a_codes, w_packed, backend="interpret")
        key = ("lutmul_onehot", 16, 128, 128, "interpret")
        assert key in ops._BLOCK_CACHE
        bm, bn, bk = ops._BLOCK_CACHE[key]
        assert bm % 8 == 0 and bn % 128 == 0 and bk % 128 == 0
        np.testing.assert_array_equal(np.asarray(got), want)
        # second call is a pure cache hit (no sweep) and stays exact
        got2 = ops.lutmul(a_codes, w_packed, backend="interpret")
        np.testing.assert_array_equal(np.asarray(got2), want)
    finally:
        ops.set_autotune(None)
        ops._BLOCK_CACHE.clear()


def test_fused_kernel_matches_scaled_oracle():
    rng = np.random.default_rng(3)
    M, K, N = 10, 64, 33
    a, w, a_codes, w_packed, _ = _rand_case(rng, M, K, N)
    a_scale = jnp.asarray(rng.uniform(0.01, 1.0, (M, 1)), jnp.float32)
    w_scale = jnp.asarray(rng.uniform(0.01, 1.0, (1, N)), jnp.float32)
    want = ref.scaled_lutmul_ref(a_codes, w_packed, a_scale, w_scale)
    from repro.kernels.lutmul import kernel, ops as _ops
    a_p = _ops._pad_to(a_codes, 8, 128)
    w_p = _ops._pad_to(w_packed, 64, 128)
    as_p = _ops._pad_to(a_scale, 8, 1)
    ws_p = _ops._pad_to(w_scale, 1, 128)
    got = kernel.lutmul_fused_pallas(
        a_p, w_p, _ops._get_table(True), as_p, ws_p, bm=16, bn=128, bk=128,
        out_dtype=jnp.float32, interpret=True)[:M, :N]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prequant_malformed_packed_rejected_on_all_backends():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 8), jnp.float32)
    bad_wq = jnp.zeros((3, 8), jnp.uint8)           # rows != K//2
    w_scale = jnp.ones((1, 8), jnp.float32)
    for backend in ("ref", "interpret"):
        with pytest.raises(ValueError, match="K//2"):
            ops.prequant_matmul(x, bad_wq, w_scale, mode="w4a4_lut",
                                backend=backend)


def test_backend_env_cannot_swap_kernels_on_tpu(monkeypatch):
    """On a TPU, REPRO_KERNEL_BACKEND may select only the Pallas kernels
    outside a test; an unknown name is an error anywhere."""
    monkeypatch.setattr(ops, "_BACKEND", None)
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("PYTEST_CURRENT_TEST", raising=False)
    for name in ("interpret", "ref"):
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", name)
        with pytest.raises(RuntimeError, match="on a TPU"):
            ops.get_backend()
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    assert ops.get_backend() == "pallas"
    monkeypatch.delenv("REPRO_KERNEL_BACKEND")
    assert ops.get_backend() == "pallas"
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "Pallas")
    with pytest.raises(ValueError, match="expected one of"):
        ops.get_backend()


@pytest.mark.parametrize("M,K,N,want", [
    (8, 3584, 18944, (8, 512, 512)),      # qwen2-7b MLP up, one chip
    (8, 18944, 3584, (8, 512, 512)),      # MLP down
    (8, 3584, 4736, (8, 128, 512)),       # MLP up, one of 4 shards
    (8, 4736, 3584, (8, 512, 128)),       # MLP down, row-parallel shard
    (8, 896, 3584, (8, 512, 128)),        # wo, head-sharded over 4
    (128, 3584, 152064, (128, 512, 512)),  # head, prefill M
    (5, 100, 200, (8, 256, 128)),         # tiny: clipped to the padding
])
def test_default_blocks_divide_padded_dims(M, K, N, want):
    """The default blocks never make a call pad a weight whose dims are
    multiples of 128 (padding would copy the weight on every call)."""
    bm, bn, bk = ops._clip_blocks(M, K, N, *ops._CANDIDATES[0])
    assert (bm, bn, bk) == want
    assert (-(-N // 128) * 128) % bn == 0 and (-(-K // 128) * 128) % bk == 0
