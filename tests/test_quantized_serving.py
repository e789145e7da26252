"""Pre-quantized serving (serve/quantize.py): the deployment path of the
paper's technique — weights stored as integer codes, LUT/MXU integer matmul,
and the LUT path bit-identical to the integer-dot path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import transformer as T
from repro.serve.quantize import dequantize_weight, quantize_params_for_serving


@pytest.mark.parametrize("mode", ["w8a8", "w4a4_mxu"])
def test_roundtrip_error_bounded(mode):
    cfg = configs.get_config("qwen2-7b", smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    q = quantize_params_for_serving(params, mode=mode)
    leaf = q["blocks"][0]["attn"]["wq"]
    assert "w_q" in leaf and "w_scale" in leaf
    back = dequantize_weight(leaf, jnp.float32)
    orig = params["blocks"][0]["attn"]["wq"]["w"]
    rel = float(jnp.linalg.norm(back - orig) / jnp.linalg.norm(orig))
    assert rel < (0.02 if mode == "w8a8" else 0.15)
    # packed int4 halves the K dim
    if mode.startswith("w4"):
        assert leaf["w_q"].dtype == jnp.uint8
        assert leaf["w_q"].shape[-2] == orig.shape[-2] // 2
    # norms untouched
    assert "scale" in q["blocks"][0]["ln1"]


def test_lut_serving_identical_to_mxu_serving():
    """Same integer codes -> the table-gather path and the int-dot path must
    produce bitwise-identical logits (the kernel-equivalence property,
    end-to-end)."""
    params = T.init_params(jax.random.PRNGKey(0),
                           configs.get_config("qwen2-7b", smoke=True))
    q = quantize_params_for_serving(params, mode="w4a4_mxu")
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 512)
    cfg_mxu = configs.get_config("qwen2-7b", smoke=True, quant="w4a4_mxu")
    cfg_lut = configs.get_config("qwen2-7b", smoke=True, quant="w4a4_lut")
    l_mxu, _ = T.prefill(q, cfg_mxu, toks)
    l_lut, _ = T.prefill(q, cfg_lut, toks)
    np.testing.assert_array_equal(np.asarray(l_mxu), np.asarray(l_lut))


def test_quantized_moe_serving():
    cfg = configs.get_config("mixtral-8x22b", smoke=True, quant="w4a4_mxu")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    q = quantize_params_for_serving(params, mode="w4a4_mxu")
    assert "w_q" in q["blocks"][0]["moe"]["wi"]
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab)
    logits, _ = T.prefill(q, cfg, toks)
    assert np.isfinite(np.asarray(logits)).all()


def test_split_head_params_forward():
    cfg = configs.get_config("qwen2-7b", smoke=True)
    cfg = dataclasses.replace(cfg, compute_dtype="float32",
                              split_head_params=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0, cfg.vocab)
    full, _ = T.forward(params, cfg, toks)
    pl, _ = T.prefill(params, cfg, toks[:, :9])
    np.testing.assert_allclose(np.asarray(pl),
                               np.asarray(full[:, 8], np.float32),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("mode", ["w4a4_lut", "w4a4_tmac", "ternary_a4_tmac",
                                  "w8a8"])
def test_streamed_quantized_load_matches_quantize_after_init(mode):
    """The full-width loader (quantize each layer group as it is built)
    returns exactly ``quantize_params_for_serving(init_params(...))``, with
    one weight-quantization event per leaf either way."""
    from repro.kernels.lutmul import ops
    from repro.serve.quantize import init_quantized_params
    cfg = configs.get_config("qwen2-7b", smoke=True, quant=mode)
    key = jax.random.PRNGKey(3)
    c0 = ops.WEIGHT_QUANT_COUNT
    want = quantize_params_for_serving(T.init_params(key, cfg), mode=mode)
    c1 = ops.WEIGHT_QUANT_COUNT
    got = init_quantized_params(key, cfg, mode)
    assert ops.WEIGHT_QUANT_COUNT - c1 == c1 - c0 > 0
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree_util.tree_leaves(got)):
        assert a.dtype == b.dtype and a.shape == b.shape, path
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))
