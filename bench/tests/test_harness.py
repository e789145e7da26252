"""Whole runs of tiny copies of the cells on the CPU (Pallas kernels as
their jnp oracles), with the chip check skipped: a sound run is correct
and reports its metrics; cells and metrics are found by file name; the
command refuses to run without a TPU."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import pytest

from bench.tests import tiny
from bench.tests.tiny import REPO

CELLS = ["qwen2-7b-lut.decode", "qwen2-7b-lut.prefill",
         "bitnet-3b-tmac.decode"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_reports_its_metrics(root, cell):
    out = tiny.run(root, cell, seed=2 ** 31 + 99)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) == want
    assert out["correct"], out["checks"]
    assert all(c["value"] == 0.0 for c in out["checks"].values())
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["compiles_in_window"]["value"] == 0


def test_traced_run_reports_counters(root):
    out = tiny.run(root, "qwen2-7b-lut.prefill", trace=True)
    # counters need no device; device metrics stay silent off the chip
    assert out["metrics"]["chunk_fill.prefill"]["value"] == 100.0
    assert "onehot_roofline.prefill" not in out["metrics"]
    assert out["device"]["window_s"] > 0


def test_a_cell_and_a_metric_added_by_files_alone(root):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({
        "name": "qwen2-7b-lut.dummy", "config": "qwen2-7b.w4a4_lut",
        "traffic": "dummy", "chips": 1, "why": "test"})
    spec["per_layer"].append({
        "name": "dummy_rounds", "unit": "rounds", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "out_tok_s", "workloads": ["qwen2-7b-lut.dummy"]})
    for m in spec["end_to_end"]:
        if m["name"] in ("out_tok_s", "itl_p95_ms"):
            m["workloads"].append("qwen2-7b-lut.dummy")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    mix = json.loads((root / "bench/traffic/decode.p128.o1024-1536.json")
                     .read_text())
    mix["clients"] = 2
    (root / "bench/traffic/dummy.json").write_text(json.dumps(mix))
    (root / "bench/checks/qwen2-7b-lut.dummy.json").write_text(
        (root / "bench/checks/qwen2-7b-lut.decode.json").read_text())
    (root / "bench/metrics/dummy_rounds.py").write_text(
        "def read(ctx):\n    return float(len(ctx.window.rounds))\n")
    out = tiny.run(root, "qwen2-7b-lut.dummy", trace=True)
    assert out["correct"]
    assert out["metrics"]["dummy_rounds"]["value"] >= 1
    out = tiny.run(root, "qwen2-7b-lut.dummy")
    assert set(out["metrics"]) == {"setup_s", "out_tok_s", "itl_p95_ms"}


PLANES4 = '''"""Signed int4 codes as four packed two's-complement bitplanes
(coefficients 1, 2, 4, -8): the T-MAC kernel's w4 format."""
import math

import jax
import jax.numpy as jnp

BITS = 4
KERNEL = "tmac"
CODE_RMS = math.sqrt(21.5)


def make(key, K, N):
    return {"w_q": jax.random.bits(key, (4, K // 8, N), jnp.uint8),
            "w_tmac": jnp.zeros((0,), jnp.uint8)}


def codes(leaf):
    planes = leaf["w_q"]
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape(1, 1, 8, 1)
    bits = ((planes[:, :, None, :] >> shifts) & 1).astype(jnp.int8)
    bits = bits.reshape(4, planes.shape[1] * 8, planes.shape[2])
    return bits[0] + 2 * bits[1] + 4 * bits[2] - 8 * bits[3]
'''

TIMED = '''"""Open loop: ``requests`` requests, one every ``gap_s`` seconds
of the window, lengths stepping through the ranges."""
from bench.traffic import Traffic, grid


class Timed(Traffic):
    def __init__(self, mix, seed, vocab):
        super().__init__(mix, seed, vocab)
        n = int(mix["requests"])
        self.due = list(zip([i * mix["gap_s"] for i in range(n)],
                            grid(*mix["prompt_len"], n),
                            grid(*mix["output_len"], n)))

    def first(self):
        return []

    def after_round(self, elapsed_s, finished):
        out = []
        while self.due and self.due[0][0] <= elapsed_s:
            _, p, o = self.due.pop(0)
            out.append(self.request(p, o))
        return out


def make(mix, seed, vocab):
    return Timed(mix, seed, vocab)
'''


def test_a_format_and_a_traffic_kind_added_by_files_alone(root):
    """A cell on a weight format and a traffic kind the harness has never
    seen (w4 bitplanes on the T-MAC kernel; timed arrivals), brought by
    their files, a configuration, a mix, a check and BENCHMARK.json
    entries; the served engine takes a ServeConfig key from the mix."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "qwen2-7b.w4a4_tmac", "source": "test",
        "file": "bench/configs/qwen2-7b.w4a4_tmac.json", "reduced": [],
        "why": "test"})
    spec["workloads"].append({
        "name": "qwen2-7b-tmac.timed", "config": "qwen2-7b.w4a4_tmac",
        "traffic": "timed", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "out_tok_s":
            m["workloads"].append("qwen2-7b-tmac.timed")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cfg = json.loads((root / "bench/configs/qwen2-7b.w4a4_lut.json")
                     .read_text())
    cfg["serving"].update(quant="w4a4_tmac", format="planes4")
    (root / "bench/configs/qwen2-7b.w4a4_tmac.json").write_text(
        json.dumps(cfg))
    (root / "bench/formats/planes4.py").write_text(PLANES4)
    (root / "bench/traffic_kinds/timed.py").write_text(TIMED)
    (root / "bench/traffic/timed.json").write_text(json.dumps({
        "kind": "timed", "start": "queued", "requests": 6, "gap_s": 0.05,
        "prompt_len": [6, 12], "output_len": [4, 8],
        "serve": {"slots": 4, "max_len": 96, "decode_chunk": 4,
                  "prefill_chunk": 4, "top_k": 0}}))
    (root / "bench/checks/qwen2-7b-tmac.timed.json").write_text(
        (root / "bench/checks/qwen2-7b-lut.decode.json").read_text())
    from bench import weights
    from bench.model import model_config
    from repro.serve.quantize import init_quantized_params
    mc = model_config(cfg)
    want = jax.eval_shape(lambda k: init_quantized_params(k, mc, mc.quant),
                          jax.random.PRNGKey(0))
    cfg["bench_dir"] = str(root / "bench")
    got = jax.eval_shape(lambda: weights.served_params(cfg, 3))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    out = tiny.run(root, "qwen2-7b-tmac.timed", seconds=1.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 6
    assert set(out["metrics"]) == {"setup_s", "out_tok_s"}


def test_a_compile_inside_the_window_is_not_correct(root):
    class Recompiles:
        def __init__(self, eng):
            self._eng, self._n = eng, 0

        def __getattr__(self, name):
            return getattr(self._eng, name)

        def step(self, *args, **kw):
            self._n += 1                        # a new shape every call
            jax.jit(lambda x: x + 1)(jax.numpy.zeros(self._n))
            return self._eng.step(*args, **kw)

    out = tiny.run(root, "qwen2-7b-lut.decode", seconds=0.5,
                   engine_hook=Recompiles)
    assert not out["correct"]
    assert out["checks"]["compiles_in_window"]["value"] > 0
    assert out["checks"]["compiles_in_window"]["limit"] == 0


def _command(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_host_without_a_tpu():
    p = _command(REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_command_refuses_a_tree_without_the_program(tmp_path):
    import shutil
    shutil.copytree(REPO / "bench", tmp_path / "bench")
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
