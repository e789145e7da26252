"""The benchmark's served-weight format is the program's, and the
reference decodes it as the program does."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights
from bench.tests.tiny import REPO

CONFIGS = ["qwen2-7b.w4a4_lut", "bitnet-b1.58-3b.ternary_a8_tmac"]


def _small(name: str) -> dict:
    cfg = json.loads((REPO / f"bench/configs/{name}.json").read_text())
    return spec.arch(cfg).tiny(cfg, 3)


@pytest.mark.parametrize("name", CONFIGS)
def test_layout_matches_the_program(name):
    from bench.model import model_config
    from repro.serve.quantize import init_quantized_params
    cfg = _small(name)
    mc = model_config(cfg)
    want = jax.eval_shape(lambda k: init_quantized_params(k, mc, mc.quant),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: weights.served_params(cfg, 5))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_decodes_codes_as_the_program(name):
    from repro.serve.quantize import dequantize_weight
    cfg = _small(name)
    seed = 2 ** 33 + 7                    # wider than 32 bits
    params = weights.served_params(cfg, seed)
    key = weights.seed_key(seed)
    fmt = weights.weight_format(cfg, cfg["serving"]["format"])
    kind, = spec.arch(cfg).layout(cfg).pattern
    for g in (0, 2):
        layer = weights.layer_params(cfg, key, jnp.uint32(g), kind)
        stacked = jax.tree_util.tree_map(lambda x: x[g],
                                         params["blocks"][0])
        for group, proj in (("attn", "wq"), ("mlp", "wo")):
            mine = layer[group][proj]
            theirs = stacked[group][proj]
            np.testing.assert_array_equal(mine["w_q"], theirs["w_q"])
            np.testing.assert_array_equal(
                fmt.codes(mine).astype(np.float32) * mine["w_scale"],
                dequantize_weight(theirs, jnp.float32))
    head = params["lm_head"]
    np.testing.assert_array_equal(
        weights.weight_format(cfg, weights.HEAD_FORMAT).codes(head).astype(
            np.float32) * head["w_scale"],
        dequantize_weight(head, jnp.float32))


def test_seeds_beyond_32_bits_differ():
    a = weights.seed_key(5)
    b = weights.seed_key(5 + 2 ** 32)
    assert not np.array_equal(a, b)
