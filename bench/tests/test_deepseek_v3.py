"""The DeepSeek-V3 architecture (Moonlight-16B-A3B) in the harness: its
served leaves are the program's, its expert codes decode as the program
reads them, a tiny copy of its decode cell runs correct on the CPU with no
(token, expert) pair dropped while both controls fail, and the chip check
files this configuration and the BitNet prefill cell brought keep their
limits between their readings."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cost, spec, weights
from bench.tests import tiny
from bench.tests.tiny import REPO

NAME = "moonlight-16b-a3b.w4a4_lut"
CELL = "moonlight-16b-lut.decode"
TINY_LIMITS = {"logit_gap": 0.01, "kv_gap": 0.01, "kv_gap_layer0": 0.01}


def _small(layers: int = 3) -> dict:
    cfg = json.loads((REPO / f"bench/configs/{NAME}.json").read_text())
    return spec.arch(cfg).tiny(cfg, layers)


def test_layout_matches_the_program():
    """The served tree is the one the program builds and quantizes; the
    float leaves (router, kv_b_proj) are bfloat16 as served."""
    from bench.model import model_config
    from repro.serve.quantize import init_quantized_params
    cfg = _small()
    mc = model_config(cfg)
    want = jax.eval_shape(lambda k: init_quantized_params(k, mc, mc.quant),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: weights.served_params(cfg, 5))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(want),
                            jax.tree_util.tree_leaves(got)):
        key = jax.tree_util.keystr(path)
        assert a.shape == b.shape, key
        floating = "'router'" in key or "'wkv_b'" in key
        assert b.dtype == (jnp.bfloat16 if floating else a.dtype), key


def test_reference_decodes_expert_codes_as_the_program():
    from repro.serve.quantize import dequantize_weight
    cfg = _small()
    seed = 2 ** 33 + 7
    params = weights.served_params(cfg, seed)
    layer = weights.layer_params(cfg, weights.seed_key(seed),
                                 jnp.uint32(2), "moe")
    fmt = weights.weight_format(cfg, "nibble_experts")
    for proj in ("wi", "wo"):
        mine = layer["moe"][proj]
        theirs = jax.tree_util.tree_map(lambda x: x[1],
                                        params["blocks"][0]["moe"][proj])
        np.testing.assert_array_equal(mine["w_q"], theirs["w_q"])
        np.testing.assert_array_equal(
            fmt.codes(mine).astype(np.float32) * mine["w_scale"],
            dequantize_weight(theirs, jnp.float32))


def test_work_of_a_forward():
    """Six LUT calls a layer (three of MLA, three of the dense MLP or the
    shared experts), the head on the int8 kernel; a uniform router's
    expected experts hit bound the expert bytes."""
    cfg = json.loads((REPO / f"bench/configs/{NAME}.json").read_text())
    cfg["bench_dir"] = str(REPO / "bench")
    arch = spec.arch(cfg)
    assert len(cost.kernel_calls(cfg, "onehot")) == 6 * 27
    assert cost.kernel_calls(cfg, "int8") == [(2048, 163840, 8)]
    one, many = cost.forward(cfg, [2048]), cost.forward(cfg, [2048] * 16)
    e = arch.expert_bytes(cfg)
    assert many.bytes - one.bytes > 26 * (50 - 6) * e
    assert many.bytes - one.bytes < 26 * (64 - 6) * e + 16 * 27 * 576 * 2 \
        * 2048 + 16 * 4 * 2048
    w = arch.expert_work(cfg, pairs=96, hit=51)
    assert w.int_ops == 96 * 3 * 2 * 2048 * 1408
    assert w.bytes > 51 * e


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("bench"), layers=3,
                     limits=TINY_LIMITS)


class Counted:
    """The engine, keeping each round's MoE counters."""

    def __init__(self, eng):
        self._eng, self.moe = eng, []

    def __getattr__(self, name):
        return getattr(self._eng, name)

    def step(self, *args, **kw):
        out = self._eng.step(*args, **kw)
        self.moe.append(np.asarray(out[10]))
        return out


def test_tiny_cell_runs_correct_dropless_and_controls_fail(root):
    """A one-round window (the tiny mix's requests cannot finish in it, so
    every served request was admitted by the set-up's batched prefill)."""
    from bench import check
    seed = 2 ** 31 + 5
    eng = []
    out = tiny.run(root, CELL, seed=seed, seconds=0.01, trace=True,
                   keep_sample=True,
                   engine_hook=lambda e: eng.append(Counted(e)) or eng[0])
    assert out["correct"], out["checks"]
    assert all(c["value"] == 0.0 for c in out["checks"].values())
    assert out["metrics"]["occupancy.decode"]["value"] == 100.0
    for name in ("moe_share.decode", "mla_share.decode",
                 "expert_roofline.decode"):
        assert name not in out["metrics"]         # device readers: silent
    hit, pairs, dropped = np.sum(eng[0].moe, axis=0)
    # 2 MoE layers, top-2 of 8, 4 slots, decode_chunk forwards a round
    assert pairs == 2 * 2 * 4 * 8 * len(eng[0].moe) and dropped == 0
    assert 0 < hit <= 2 * 8 * 8 * len(eng[0].moe)
    c = spec.load_cell(root, CELL)
    caught = set()
    for control in c.check["controls"]:
        res = check.compare(c.config, seed, out["sample"], c.check,
                            int(c.traffic["serve"]["max_len"]),
                            control=control)
        caught |= {n for n, (v, lim) in res.items() if v > lim}
    assert caught == set(c.check["numbers"])


@pytest.mark.parametrize("cell", [CELL, "bitnet-3b-tmac.prefill"])
def test_chip_limits_sit_between_their_readings(cell):
    chk = json.loads((REPO / f"bench/checks/{cell}.json").read_text())
    for name, n in chk["numbers"].items():
        lower, upper = n["program_max"], n["control_min"]
        assert lower < n["limit"] < upper, name
        assert n["seeds"] >= 10
    assert {n["catches"] for n in chk["numbers"].values()} == set(
        chk["controls"])
