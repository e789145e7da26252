"""Architectures are plug-ins: a configuration names its architecture
module, a second architecture joins a tiny copy of the benchmark by new
files and entries alone and runs correct on the CPU, and the dense decoder
makes the same weights and reference numbers it made before it became the
first such module."""
from __future__ import annotations

import hashlib
import json

import jax
import numpy as np
import pytest

from bench import reference, spec, weights
from bench.tests import tiny
from bench.tests.tiny import REPO

# The dense decoder with a GeGLU MLP (tanh GELU on the gate), as Gemma's:
# one layer equation away from the dense decoder, served by the program's
# own ``mlp="geglu"`` block.
GEGLU = '''"""A dense decoder whose MLP gate is tanh-approximated GELU (GeGLU)."""
import jax

from bench.archs import dense_decoder
from bench.archs.dense_decoder import (  # noqa: F401
    cache_rows, dims, forward, kernel_calls, layer_leaves, layout,
    outer_leaves, tiny)


def model_config(cfg):
    from repro.models.transformer import BlockSpec, ModelConfig
    m = dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=m["L"], d_model=m["d"],
        n_heads=m["h"], n_kv=m["kv"], head_dim=m["hd"], d_ff=m["ff"],
        vocab=m["V"], qkv_bias=m["qkv_bias"],
        pattern=(BlockSpec(kind="attn", attn_type="global", mlp="geglu"),),
        rope_theta=m["theta"], quant=m["quant"],
        compute_dtype=cfg["serving"]["compute_dtype"])


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def layer(cfg, kind, p, x, *, mono, T, lower):
    x, rows = dense_decoder.attention(cfg, p, x, mono=mono, T=T,
                                      lower=lower)
    return x + dense_decoder.gated_mlp(cfg, p, x, lower=lower,
                                       act=gelu), rows
'''

CELL = "geglu.decode"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A tiny copy with the GeGLU architecture, a configuration, a mix, a
    check and BENCHMARK.json entries added, and nothing else changed."""
    root = tiny.make(tmp_path_factory.mktemp("bench"))
    bench = root / "bench"
    (bench / "archs/geglu_decoder.py").write_text(GEGLU)
    cfg = json.loads((bench / "configs/qwen2-7b.w4a4_lut.json").read_text())
    cfg.update(name="geglu.w4a4_lut", arch="geglu_decoder",
               hidden_act="gelu_pytorch_tanh")
    (bench / "configs/geglu.w4a4_lut.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic/decode.p128.o1024-1536.json")
                     .read_text())
    mix["clients"] = 3
    (bench / "traffic/geglu.json").write_text(json.dumps(mix))
    (bench / f"checks/{CELL}.json").write_text(json.dumps({
        "sample": 4, "controls": ["kv_fp8"],
        "numbers": {n: {"limit": 0.01} for n in ("logit_gap", "kv_gap")}}))
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({
        "name": "geglu.w4a4_lut", "source": "test",
        "file": "bench/configs/geglu.w4a4_lut.json", "reduced": [],
        "why": "test"})
    bm["workloads"].append({"name": CELL, "config": "geglu.w4a4_lut",
                            "traffic": "geglu", "chips": 1, "why": "test"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "qwen2-7b-lut.decode" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def test_an_architecture_added_by_files_alone_runs_correct(root):
    from bench import cost
    seed = 2 ** 31 + 5
    out = tiny.run(root, CELL, seed=seed, seconds=1.0, trace=True)
    assert out["correct"], out["checks"]
    assert all(c["value"] == 0.0 for c in out["checks"].values())
    # the readers that read on the CPU: counters and the program's spans;
    # the device's readers stay silent without a chip
    assert out["metrics"]["occupancy.decode"]["value"] > 0
    assert out["metrics"]["host_ms.decode"]["value"] > 0
    for name in ("kv_share.decode", "step_mfu.decode", "round_ms.decode",
                 "onehot_roofline.decode"):
        assert name not in out["metrics"]
    # the architecture's work per forward: its projections on the
    # configuration's kernel, the head on the int8 one
    c = spec.load_cell(root, CELL)
    L = c.config["num_hidden_layers"]
    assert len(cost.kernel_calls(c.config, "onehot")) == 7 * L
    assert len(cost.kernel_calls(c.config, "int8")) == 1
    assert cost.forward(c.config, [10, 20]).int_ops > 0
    out = tiny.run(root, CELL, seed=seed + 1, seconds=0.5)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "out_tok_s", "itl_p95_ms"}


@pytest.mark.parametrize("arch", [None, "", "no_such_arch"])
def test_a_configuration_must_name_a_known_architecture(tmp_path, arch):
    root = tiny.make(tmp_path)
    p = root / "bench/configs/qwen2-7b.w4a4_lut.json"
    cfg = json.loads(p.read_text())
    if arch is None:
        del cfg["arch"]
    else:
        cfg["arch"] = arch
    p.write_text(json.dumps(cfg))
    with pytest.raises(spec.SpecError):
        spec.load_cell(root, "qwen2-7b-lut.decode")


LEAD_AND_PATTERN = '''"""The dense decoder's layers laid out as one leading layer outside the
scan, then two pattern positions of different kinds."""
from bench.archs.dense_decoder import layer_leaves, outer_leaves  # noqa: F401
from bench.weights import Layout


def layout(cfg):
    return Layout(pattern=("even", "odd"), groups=2,
                  lead=((("lead", "first"), "first"),))
'''


def test_layout_places_leading_layers_and_pattern_positions(tmp_path):
    """Layer g draws its leaves from fold_in(.., g) wherever it sits: a
    leading layer at its own path, pattern position j of group i at row i
    of blocks[j], g = lead + i * positions + j."""
    root = tiny.make(tmp_path)
    (root / "bench/archs/lead_and_pattern.py").write_text(LEAD_AND_PATTERN)
    cfg = json.loads((root / "bench/configs/qwen2-7b.w4a4_lut.json")
                     .read_text())
    cfg.update(arch="lead_and_pattern", num_hidden_layers=5,
               bench_dir=str(root / "bench"))
    lay = spec.arch(cfg).layout(cfg)
    assert lay.kinds() == ["first", "even", "odd", "even", "odd"]
    params = weights.served_params(cfg, 9)
    # one layer at a time, jitted as the reference regenerates it
    layer = jax.jit(lambda g, kind: weights.layer_params(
        cfg, weights.seed_key(9), g, kind), static_argnums=1)

    def same(a, b):
        jax.tree_util.tree_map(np.testing.assert_array_equal, a, b)
    same(params["lead"]["first"], layer(jax.numpy.uint32(0), "first"))
    for g, (i, j) in zip(range(1, 5), [(0, 0), (0, 1), (1, 0), (1, 1)]):
        row = jax.tree_util.tree_map(lambda x: x[i], params["blocks"][j])
        same(row, layer(jax.numpy.uint32(g), lay.pattern[j]))


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str((a.shape, a.dtype)).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# sha256 prefixes of served_params and of reference.run's logits and K/V
# rows at tiny size (2 layers), recorded before the dense decoder moved
# into bench/archs: the move changed no bit
PINNED = {
    ("qwen2-7b.w4a4_lut", 5):
        ("f699151a12d1584c", "d72c3dd696b261ca", "c42e5e851cec7939"),
    ("qwen2-7b.w4a4_lut", 2 ** 33 + 7):
        ("9465db15a9c844de", "858ce79e83cefec6", "dd6b0c7581c0c215"),
    ("bitnet-b1.58-3b.ternary_a8_tmac", 5):
        ("559123e87e6101fa", "2e07b90140d9262b", "6e14ee0b82cdfe1f"),
    ("bitnet-b1.58-3b.ternary_a8_tmac", 2 ** 33 + 7):
        ("a444bfd83e9867a1", "f3b77d0440e85a42", "d5d2d94d45a72276"),
}


@pytest.mark.parametrize("name,seed", sorted(PINNED))
def test_dense_decoder_makes_the_same_bits(name, seed):
    cfg = json.loads((REPO / f"bench/configs/{name}.json").read_text())
    cfg = spec.arch(cfg).tiny(cfg, 2)
    params = weights.served_params(cfg, seed)
    rng = np.random.default_rng(seed % 1000)
    tokens = rng.integers(0, 512, (2, 24)).astype(np.int32)
    at = np.tile(np.arange(8, 24, dtype=np.int32), (2, 1))
    score = rng.integers(0, 512, (2, 16)).astype(np.int32)
    r = reference.run(cfg, seed, tokens, mono=8, T=64, at=at, score=score,
                      kv_rows=24)
    got = (_digest(jax.tree_util.tree_leaves(params)),
           _digest([r["best"], r["got"], r["argmax"]]),
           _digest([r["rows"]["k"], r["rows"]["v"]]))
    assert got == PINNED[(name, seed)]
