"""A tiny copy of the benchmark tree, for driving whole runs on the CPU.

Every configuration keeps its architecture and serving mode at a few
layers, shrunk as its architecture module's ``tiny`` says (width 64 for
a dense decoder); every traffic mix keeps its shape (clients, start,
closed loop) at a few slots and short prompts.  The program under test is
the repository's own ``src``.
"""
from __future__ import annotations

import json
import os
import shutil
from pathlib import Path
from unittest import mock

import jax  # noqa: F401  (read its environment before ``run`` patches it)

from bench.spec import load_module

REPO = Path(__file__).resolve().parents[2]


def make(dst: Path, layers: int = 2, limits: dict | None = None) -> Path:
    """Copy ``bench`` and ``BENCHMARK.json`` into ``dst`` at tiny sizes;
    ``limits`` ({number: limit}) overrides the check files' limits."""
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (dst / "src").symlink_to(REPO / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        p = dst / c["file"]
        cfg = json.loads(p.read_text())
        arch = load_module(dst / "bench", "archs", cfg["arch"])
        p.write_text(json.dumps(arch.tiny(cfg, layers)))
    for p in (dst / "bench" / "traffic").glob("*.json"):
        mix = json.loads(p.read_text())
        mix["clients"] = min(mix["clients"], 4)
        mix["serve"]["slots"] = min(mix["serve"]["slots"], 4)
        mix["serve"]["max_len"] = 96
        if mix["start"] == "batched":
            mix["prompt_len"], mix["output_len"] = [24, 24], [20, 40]
        else:
            mix["prompt_len"], mix["output_len"] = [12, 40], [4, 8]
        p.write_text(json.dumps(mix))
    if limits is not None:
        for p in (dst / "bench" / "checks").glob("*.json"):
            chk = json.loads(p.read_text())
            for name, n in chk["numbers"].items():
                n["limit"] = limits[name]
            p.write_text(json.dumps(chk))
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))
    return dst


def run(root: Path, workload: str, seed: int = 7, seconds: float = 2.0,
        trace: bool = False, **kw) -> dict:
    """One whole run on the CPU: the look for a chip is skipped, the CPU
    has no peaks (device metrics stay silent), and the compile cache is
    left as the test process has it (the variable the benchmark defers
    to is set, after JAX has read it)."""
    from bench import run as bench_run
    with mock.patch.object(bench_run, "_chips",
                           lambda jax, n: jax.devices()[:n]), \
            mock.patch("bench.peaks.peaks", lambda kind: None), \
            mock.patch.dict(os.environ, {
                "JAX_COMPILATION_CACHE_DIR": str(root / ".jax_cache")}):
        return bench_run.run_cell(root, workload, seed, seconds, trace,
                                  **kw)
