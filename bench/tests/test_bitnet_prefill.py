"""The BitNet prefill cell (``bitnet-3b-tmac.prefill``) under its committed
check file: a tiny copy runs correct on the CPU, reports the cell's
end-to-end metrics, and its ``kv_fp8`` control fails the check."""
from __future__ import annotations

import json

import pytest

from bench import check, spec
from bench.tests import tiny
from bench.tests.tiny import REPO

CELL = "bitnet-3b-tmac.prefill"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make(tmp_path_factory.mktemp("bench"))


def test_tiny_prefill_cell_is_correct_and_its_control_fails(root):
    seed = 2 ** 31 + 17
    out = tiny.run(root, CELL, seed=seed, keep_sample=True)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert set(out["metrics"]) == want
    assert out["correct"], out["checks"]
    c = spec.load_cell(root, CELL)
    assert set(out["checks"]) == set(c.check["numbers"]) | {
        "compiles_in_window"}
    assert out["attempted"] > 0 and out["failed"] == 0
    res = check.compare(c.config, seed, out["sample"], c.check,
                        int(c.traffic["serve"]["max_len"]),
                        control="kv_fp8")
    assert {n for n, (v, lim) in res.items() if v > lim} == set(
        c.check["numbers"])
