"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``us_per_call`` is the median
wall-time of the benchmarked callable on this host (CPU); ``derived`` carries
the paper-comparable quantity (GOPS, FPS, LUT counts, accuracy, ...).

``--json PATH`` additionally writes a machine-readable record per row
(op name, median ms, GOP/s when derivable, the derived string) so successive
PRs can diff kernel baselines::

    python -m benchmarks.run --only kernel_bench --json BENCH_kernels.json

``--diff BASELINE.json`` prints per-benchmark deltas of this run against a
committed baseline (median ms and GOP/s, with new/missing rows flagged) so
later PRs can check regressions mechanically; ``--fail-on-regress PCT``
turns the diff into a gate (exit 1 on any benchmark > PCT% slower than the
baseline or missing from the run) — the CI invocation::

    python -m benchmarks.run --only kernel_bench --diff BENCH_kernels.json \
        --fail-on-regress 25
"""
from __future__ import annotations

import argparse
import json
import re
import statistics
import sys
import time


def _time_rows(rows: list, repeats: int) -> dict[str, float]:
    """us-per-call medians for every callable row, sampled ROUND-ROBIN.

    Two defenses against noisy (2-core CI) hosts, where naive per-row
    timing swings +-50%:

      * short calls are batched so each timing sample covers >= ~100ms —
        millisecond calls are otherwise dominated by scheduler jitter;
      * sample r of EVERY row is taken before sample r+1 of any, so a host
        slow phase (GC, cron, a neighbor VM) lands on the same round of
        every benchmark instead of swallowing one row's entire window; the
        per-row median then drops the bad rounds for all rows alike.
    """
    plan, samples = [], {}
    for name, fn, _ in rows:
        if not callable(fn):
            continue
        fn()                   # warmup / compile
        t0 = time.perf_counter()
        fn()
        probe = time.perf_counter() - t0
        plan.append((name, fn, max(1, min(256, int(0.1 / max(probe,
                                                             1e-9))))))
        samples[name] = []
    for _ in range(repeats):
        for name, fn, inner in plan:
            t0 = time.perf_counter()
            for _ in range(inner):
                fn()
            samples[name].append((time.perf_counter() - t0) / inner * 1e6)
    return {name: statistics.median(v) for name, v in samples.items()}


def _gops(derived: str, us: float | None):
    """GOP/s from a ``gop_per_call=X`` annotation + measured wall time."""
    m = re.search(r"gop_per_call=([0-9.eE+-]+)", derived)
    if not m or not us:
        return None
    return float(m.group(1)) / (us / 1e6)


def diff_records(records: list[dict], baseline_path: str,
                 normalize: str | None = None) -> list[dict]:
    """Per-benchmark deltas vs a committed ``--json`` baseline.

    Prints the delta CSV and returns one entry per benchmark in the union of
    run and baseline: ``{"name", "status": "ok"|"new"|"missing",
    "delta_ms_pct": float|None}``.  Benchmarks present in the baseline but
    absent from the run are reported (and returned) as ``missing`` — a
    silently dropped benchmark must never diff clean — and count as
    regressions under ``--fail-on-regress``.

    ``normalize`` rescales every baseline median by a host-speed factor
    before the delta, so uniform speed differences (CI runner vs the
    machine that committed the baseline) cancel and only *relative*
    slowdowns trip the gate.  ``"median"`` (what CI uses) takes the median
    run/baseline ratio over all shared rows — robust to any single noisy or
    genuinely-regressed row; any other value names one calibration
    benchmark whose speed is independent of the code under test (e.g. the
    plain-XLA ``kernel_bf16_matmul_baseline``).
    """
    with open(baseline_path) as f:
        base = {r["name"]: r for r in json.load(f)["rows"]}
    speed = None
    if normalize == "median":
        ratios = sorted(
            r["median_ms"] / base[r["name"]]["median_ms"] for r in records
            if r["name"] in base and base[r["name"]]["median_ms"])
        if not ratios:
            raise SystemExit("--normalize median: no benchmarks shared "
                             "between the run and the baseline")
        speed = ratios[len(ratios) // 2]
        print(f"normalizing by the median of {len(ratios)} run/baseline "
              f"ratios: this host runs {speed:.2f}x the baseline host's "
              "time", file=sys.stderr)
    elif normalize is not None:
        run_cal = next((r for r in records if r["name"] == normalize), None)
        base_cal = base.get(normalize)
        if not run_cal or not base_cal or not base_cal["median_ms"]:
            raise SystemExit(
                f"--normalize: calibration benchmark {normalize!r} must "
                "exist in both the run and the baseline")
        speed = run_cal["median_ms"] / base_cal["median_ms"]
        print(f"normalizing by {normalize}: this host runs "
              f"{speed:.2f}x the baseline host's time", file=sys.stderr)
    if speed is not None:
        # gops ~ 1/time: rescale it too so both delta columns agree
        base = {k: dict(v, median_ms=v["median_ms"] * speed,
                        gops=(v["gops"] / speed if v.get("gops") else
                              v.get("gops")))
                for k, v in base.items()}
    print(f"\ndiff vs {baseline_path}", file=sys.stderr)
    print("name,base_ms,new_ms,delta_ms_pct,base_gops,new_gops,delta_gops_pct")
    out = []
    seen = set()
    for r in records:
        seen.add(r["name"])
        b = base.get(r["name"])
        if b is None:
            print(f"{r['name']},NEW,{r['median_ms']},,,{r['gops'] or ''},")
            out.append({"name": r["name"], "status": "new",
                        "delta_ms_pct": None})
            continue
        dms = (r["median_ms"] / b["median_ms"] - 1) * 100 \
            if b["median_ms"] else float("nan")
        dg = ""
        if r.get("gops") and b.get("gops"):
            dg = f"{(r['gops'] / b['gops'] - 1) * 100:+.1f}"
        print(f"{r['name']},{b['median_ms']},{r['median_ms']},{dms:+.1f},"
              f"{b.get('gops') or ''},{r.get('gops') or ''},{dg}")
        out.append({"name": r["name"], "status": "ok", "delta_ms_pct": dms})
    for name in base:
        if name not in seen:
            print(f"{name},MISSING (in baseline, not in this run),,,,,")
            out.append({"name": name, "status": "missing",
                        "delta_ms_pct": None})
    return out


def gate_regressions(diffs: list[dict], threshold_pct: float) -> list[str]:
    """Failures under ``--fail-on-regress``: slower than the baseline by
    more than ``threshold_pct`` percent, or missing from the run entirely.
    NEW benchmarks never fail the gate (they have no baseline yet)."""
    bad = []
    for d in diffs:
        if d["status"] == "missing":
            bad.append(f"{d['name']}: missing from this run")
        elif (d["status"] == "ok" and d["delta_ms_pct"] is not None
                and d["delta_ms_pct"] == d["delta_ms_pct"]   # not NaN
                and d["delta_ms_pct"] > threshold_pct):
            bad.append(f"{d['name']}: {d['delta_ms_pct']:+.1f}% slower "
                       f"(threshold +{threshold_pct:g}%)")
    return bad


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write machine-readable results to this path")
    ap.add_argument("--diff", default=None, metavar="BASELINE.json",
                    help="print per-benchmark deltas vs a committed baseline")
    ap.add_argument("--fail-on-regress", type=float, default=None,
                    metavar="PCT",
                    help="with --diff: exit 1 when any benchmark runs more "
                         "than PCT%% slower than the baseline, or is missing "
                         "from this run (the CI kernel-bench gate)")
    ap.add_argument("--normalize", default=None, metavar="NAME|median",
                    help="with --diff: rescale baseline medians by a "
                         "host-speed factor so uniform speed differences "
                         "cancel — 'median' (CI default) uses the median "
                         "run/baseline ratio over all shared rows; any "
                         "other value names one calibration benchmark")
    ap.add_argument("--only", action="append", default=None,
                    help="run only these benchmark modules (by name)")
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args(argv)
    if args.fail_on_regress is not None and not args.diff:
        ap.error("--fail-on-regress requires --diff BASELINE.json")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (fpga_roofline, kernel_bench, lut_cost, lut_init,
                            qat_accuracy, resource_breakdown,
                            throughput_table2)
    mods = [lut_init, lut_cost, fpga_roofline, throughput_table2,
            resource_breakdown, kernel_bench, qat_accuracy]
    if args.only:
        mods = [m for m in mods if m.__name__.split(".")[-1] in args.only]
    records = []
    print("name,us_per_call,derived")
    for mod in mods:
        rows = list(mod.run())
        timed = _time_rows(rows, args.repeats)
        for name, fn, derived in rows:
            us = timed[name] if callable(fn) else float(fn)
            print(f"{name},{us:.1f},{derived}")
            sys.stdout.flush()
            records.append({
                "name": name,
                "median_ms": round(us / 1e3, 4),
                "gops": _gops(derived, us),
                "derived": derived,
            })
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": records}, f, indent=1)
        print(f"wrote {args.json} ({len(records)} rows)", file=sys.stderr)
    if args.diff:
        diffs = diff_records(records, args.diff, normalize=args.normalize)
        if args.fail_on_regress is not None:
            bad = gate_regressions(diffs, args.fail_on_regress)
            if bad:
                print("REGRESSION GATE FAILED:", file=sys.stderr)
                for line in bad:
                    print(f"  {line}", file=sys.stderr)
                sys.exit(1)
            print(f"regression gate ok (threshold "
                  f"+{args.fail_on_regress:g}%)", file=sys.stderr)


if __name__ == "__main__":
    main()
