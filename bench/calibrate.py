"""Readings that set the limits of ``correct`` (not run by the benchmark).

    python3 -m bench.calibrate --workload <cell> --seeds 1,2,3 --seconds 4

For each seed, one short run of the cell in this process (set-up compiles
once, so a dozen seeds share it), printing one JSON line: the numbers the
check compares for the program's own run and for each control -- the
reference one precision step below the configuration (the steps its check
file names) put in the program's place, read at the same prompts and
served tokens -- and, where the check compares cache rows, the program's
K and V gaps of each layer (``kv_gaps``: where a departure starts).  A
cell whose step fills the chip needs one process per seed: what one
seed's run leaves on the device can keep the next from loading.
A number's limit goes between the largest program reading and the
smallest reading of the control it catches (bench/checks/).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from bench import check, run, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            out = run.run_cell(run.ROOT, args.workload, seed, args.seconds,
                               False, t_start=t0, keep_sample=True)
        except (run.NoChip, run.SpecError) as e:
            print(f"bench.calibrate: {e}", file=sys.stderr)
            return 2
        cell = spec.load_cell(run.ROOT, args.workload)
        T = int(cell.traffic["serve"]["max_len"])
        line = {"seed": seed, "correct": out["correct"],
                "program": {k: c["value"] for k, c in out["checks"].items()}}
        t1 = time.perf_counter()
        for ctl in cell.check["controls"]:
            res = check.compare(cell.config, seed, out["sample"],
                                cell.check, T, control=ctl)
            line[f"control.{ctl}"] = {k: v for k, (v, _) in res.items()}
        line["control_s"] = time.perf_counter() - t1
        if out["sample"].slots:
            gaps = check.kv_gaps(cell.config, seed, out["sample"],
                                 int(cell.check["sample"]), T)
            for name, gap in gaps.items():
                line[f"{name}_gap_by_layer"] = [float(g) for g in gap]
        line["metrics"] = out["metrics"]
        line["wall_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
