"""``correct`` fails where it must: the control (the reference one
precision step below the configuration, put in the program's place) and
faults planted in the timed path (a step that leaves the cache as it was,
half of the batch left out, a token altered where it is produced) all
exceed the limits.

On the CPU the tiny program matches the reference exactly (every number
reads 0), so the tiny cells hold limits of their own, set the same way as
the chip's: between the program's reading and the control's.  The chip
cells' limits are checked against the readings they were set from."""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.tests import tiny
from bench.tests.tiny import REPO

CELLS = ["qwen2-7b-lut.decode", "qwen2-7b-lut.prefill",
         "bitnet-3b-tmac.decode"]


# the tiny cells' limits: programs read 0, controls 0.03-0.4 (4 layers)
TINY_LIMITS = {"logit_gap": 0.01, "kv_gap": 0.01, "kv_gap_layer0": 0.01}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    # four layers: enough depth for a flipped code to spread
    return tiny.make(tmp_path_factory.mktemp("bench"), layers=4,
                     limits=TINY_LIMITS)


@pytest.mark.parametrize("cell", CELLS)
def test_chip_limits_sit_between_their_readings(cell):
    chk = json.loads((REPO / f"bench/checks/{cell}.json").read_text())
    for name, n in chk["numbers"].items():
        lower, upper = n["program_max"], n["control_min"]
        assert upper >= 3 * lower, name          # the control separates
        assert lower < n["limit"] < upper, name
        assert n["seeds"] >= 12
        assert n["catches"] in chk["controls"]
    # every control is caught by some number
    assert {n["catches"] for n in chk["numbers"].values()} == set(
        chk["controls"])


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limit(root, cell):
    from bench import check, spec
    out = tiny.run(root, cell, seed=11, keep_sample=True)
    assert out["correct"], out["checks"]
    c = spec.load_cell(root, cell)
    for control in c.check["controls"]:
        ctl = check.compare(c.config, 11, out["sample"], c.check,
                            int(c.traffic["serve"]["max_len"]),
                            control=control)
        assert not check.verdict(ctl), (control, ctl)


class Faulty:
    """The engine with its unified step broken in one way."""

    def __init__(self, eng, fault: str):
        self._eng, self._fault = eng, fault

    def __getattr__(self, name):
        return getattr(self._eng, name)

    def step(self, cache, entries, tok, *args, **kw):
        if self._fault == "half_batch" and entries is not None:
            entries = dict(entries)
            entries["slot"] = [s if i % 2 == 0 else -1
                               for i, s in enumerate(entries["slot"])]
        if self._fault == "altered_token" and entries is not None:
            entries = dict(entries)
            entries["tok"] = [(t + 1) % self._eng.cfg.vocab
                              for t in entries["tok"]]
        keep = None
        if self._fault == "stale_state":
            keep = jax.tree_util.tree_map(jnp.copy, cache)
        out = list(self._eng.step(cache, entries, tok, *args, **kw))
        if keep is not None:
            out[0] = keep
        toks = np.asarray(out[6]).copy()
        if self._fault == "half_batch":
            toks[1::2] = np.asarray(tok)[1::2, None]   # rows left out
        if self._fault == "altered_token":
            toks[:, toks.shape[1] // 2] += 1
        out[6] = jnp.asarray(toks % self._eng.cfg.vocab)
        return tuple(out)


@pytest.mark.parametrize("fault", ["stale_state", "half_batch",
                                   "altered_token"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_in_the_timed_path_is_not_correct(root, cell, fault):
    out = tiny.run(root, cell, seed=5,
                   engine_hook=lambda eng: Faulty(eng, fault))
    assert not out["correct"], (fault, out["checks"])
