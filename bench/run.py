"""Run one benchmark cell once on the chip and print one JSON result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix; the
run makes the served weights on the device from ``--seed``, builds the
engine with ``make_engine``, warms every program the window runs, then
drives ``Scheduler.step`` for ``--seconds`` (to the next round boundary)
under the mix's traffic.  With ``--trace 0`` it reports the cell's
end-to-end metrics (host clock), with ``--trace 1`` its per-layer metrics
(device trace and scheduler counters).  After the window it frees the
program's state and compares what the window served with the plain
reference (``bench/check.py``); a program compiled or loaded inside the
window also makes the run not correct.  The numbers compared and their
limits are the last lines on stderr and the last key of the result.

It needs a TPU: on any other platform, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import check, spec  # noqa: E402
from bench import trace as tr_mod  # noqa: E402
from bench.spec import SpecError  # noqa: E402

OUT_DIR = ".bench_out"          # traces, under the checkout (git-ignored)


class NoChip(RuntimeError):
    """The host has no TPU, or fewer chips than the cell asks for."""


class Context:
    """What a metric reader sees (``bench/metrics/<name>.py``)."""

    def __init__(self, cell, window, setup_s, trace, peaks, slots, chunk):
        from bench import cost
        self.cell = cell
        self.config = cell.config
        self.window = window            # bench.serve.Window
        self.setup_s = setup_s
        self.trace = trace              # bench.trace.Trace or None
        self.peaks = peaks
        self.slots = slots
        self.chunk = chunk
        self.cost = cost
        self.kernel_names = json.loads(
            (spec.bench_dir(cell.root) / "kernels.json").read_text())

    @property
    def elapsed_s(self) -> float:
        return self.window.t1 - self.window.t0

    def traced_rounds(self) -> list:
        """(Round, device busy seconds inside it) for every window round
        the trace holds (host spans and window rounds pair in order)."""
        from bench import trace as tr
        if self.trace is None:
            return []
        busy = tr.round_busy_ns(self.trace)
        if len(busy) != len(self.window.rounds):
            return []
        return [(r, b / 1e9) for r, b in zip(self.window.rounds, busy)]


def _init_jax(root: Path):
    """JAX, with the persistent compilation cache at a fixed path:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
    Every program is kept, however fast it compiled, so a warm set-up
    compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def _chips(jax, chips: int) -> list:
    """The cell's chips, served on the Pallas kernels; there is no CPU
    fallback."""
    from repro.kernels.lutmul import ops
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r} "
                     "(there is no CPU fallback)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX sees "
                     f"{len(devs)}")
    if ops.get_backend() != "pallas":
        raise NoChip(f"kernel backend is {ops.get_backend()!r}, not pallas")
    return devs[:chips]


class CompileCounter:
    """Compilations and compile-cache loads, counted by JAX's monitoring
    events (the listener cannot be removed, so one per process)."""
    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")
    _instance = None

    def __init__(self):
        import jax.monitoring
        self.n = 0
        self.by_event = dict.fromkeys(self.EVENTS, 0)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event in self.EVENTS:
            self.n += 1
            self.by_event[event] += 1

    @classmethod
    def get(cls):
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool, *,
             engine_hook=None, t_start: float | None = None,
             keep_sample: bool = False) -> dict:
    """One run of one cell; returns the result dict (last key ``checks``).

    ``engine_hook(engine)`` (tests) may wrap the engine before set-up;
    ``keep_sample=True`` (calibration, tests) also returns what the check
    compared under ``sample``, to read the control on it."""
    t_start = T_START if t_start is None else t_start
    root = Path(root)
    cell = spec.load_cell(root, workload)
    src = root / "src"
    if not (src / "repro").is_dir():
        raise SpecError(f"the program is missing: no {src / 'repro'}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    jax = _init_jax(root)
    devs = _chips(jax, cell.chips)
    dev = devs[0]
    from bench import peaks as peaks_mod
    peaks = peaks_mod.peaks(dev.device_kind)
    counter = CompileCounter.get()
    seen0 = dict(counter.by_event)
    phases = {"start": time.perf_counter() - t_start}

    from bench import serve, weights
    from bench.model import model_config, serve_config
    from repro.serve import make_engine

    mix = cell.traffic
    mcfg = model_config(cell.config)
    t = time.perf_counter()
    params = jax.block_until_ready(weights.served_params(cell.config, seed))
    phases["weights"] = time.perf_counter() - t
    eng = make_engine(params, mcfg, serve_config(mix, seed))
    del params
    if engine_hook is not None:
        eng = engine_hook(eng)
    t = time.perf_counter()
    sched, gen, reqs, mono = serve.set_up(spec.bench_dir(root), eng, mix,
                                          seed, mcfg.vocab)
    phases["warm_and_admit"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    # what set-up compiled and what it loaded from the compile cache
    phases["compiled"], phases["cache_loads"] = (
        counter.by_event[e] - seen0[e] for e in CompileCounter.EVENTS)

    trace_dir = root / OUT_DIR / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    compiles0 = counter.n
    win = serve.measure(sched, gen, reqs, mono, seconds)
    compiles = counter.n - compiles0
    tr = None
    if trace:
        jax.profiler.stop_trace()
        tr = tr_mod.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)

    stats = [d.memory_stats() or {} for d in devs]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    sample = check.take_sample(win, sched, cell.check, seed)
    slots, chunk = sched.n_slots, sched.chunk
    attempted = sum(1 for r in win.requests if r.tokens or r.done
                    or r.slot is not None)
    failed = sum(1 for r in win.requests
                 if r.status.value in ("failed", "timed_out", "shed"))
    # the reference runs once the program's state is gone
    del sched, eng, gen, reqs
    gc.collect()

    max_len = int(mix["serve"]["max_len"])
    t = time.perf_counter()
    results = check.compare(cell.config, seed, sample, cell.check, max_len)
    check_s = time.perf_counter() - t
    # set-up warms every program the window runs: one compiled (or loaded
    # from the cache) inside the window makes the run not correct
    results["compiles_in_window"] = (compiles, 0)
    correct = check.verdict(results)

    ctx = Context(cell, win, setup_s, tr, peaks, slots, chunk)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.load_reader(root, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": int(mem_peak)}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if tr is not None:
        lo, hi = tr.window
        device["busy_s"] = tr_mod.busy_per_device(tr) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        out["breakdown"] = tr_mod.breakdown(tr, ctx.kernel_names)
    out["setup_phases"] = phases
    out["check_s"] = check_s
    if keep_sample:
        out["sample"] = sample
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, (v, lim) in results.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(ROOT, args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except (NoChip, SpecError) as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return 2
    print(f"set-up phases: {json.dumps(out['setup_phases'])}",
          file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
