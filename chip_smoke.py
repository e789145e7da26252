#!/usr/bin/env python3
"""Smoke run of the serving main path on a TPU: qwen2-7b at its published
widths (28 layers, d=3584, GQA 28/4, d_ff=18944, vocab 152064), weights
quantized to w4a4 one-hot LUT codes (the paper's path, fused dequant), random
weights from a fixed seed.

    python chip_smoke.py              # one chip: kernel phase + model phase
    python chip_smoke.py --chips 4    # four chips: ShardedEngine on a 1x4
                                      # mesh against the single-device Engine

Kernel phase: at one qwen2-7b projection shape (K=3584, N=18944; M=8 and
M=128) every lutmul dispatch runs with ``backend="pallas"`` and is compared
with ``backend="ref"``: int32 accumulators exactly, the fused-dequant
``prequant_matmul`` outputs within one bf16 ulp.

Model phase: the quantized params are built one layer group at a time (the
float model never exists on the device), then 8 requests (32-64-token
prompts, 32 new tokens each) are served through ``make_engine`` ->
``Scheduler`` on a dense cache with ``max_len=512``.  Every request must get
its full budget and the served steps must contain the Pallas kernels
(``tpu_custom_call``).  The same params and requests are then served again
on the ``ref`` kernels: the transcripts must be bit-identical, and the
logits of one prefill through the engine's compiled prefill must agree
within ``LOGIT_REL_BOUND``.

The script runs only on a TPU with the Pallas kernels: anywhere else it
exits non-zero without a result.  Every phase raises on failure.  The
numbers it prints come from one smoke run and are not a benchmark.  The last
line of its output is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
QUANT = "w4a4_lut"
SEED = 0
N_REQUESTS = 8
PROMPT_LENS = (32, 64)                 # inclusive range of prompt lengths
NEW_TOKENS = 32
MAX_LEN = 512
# |pallas - ref| / max|ref| over the logits of one prefill of the served
# params.  The int32 accumulators agree exactly (kernel phase) and the
# engine compiles every program without excess precision
# (``engine.STEP_COMPILER_OPTIONS``), so both round alike and the logits
# should agree to float noise.  A wrong tile or accumulator, or
# one activation code flipped by a rounding difference, is amplified by 28
# layers of random int4 weights into an O(1) error (relative 0.7 was seen
# with excess precision left on), far above this bound.
LOGIT_REL_BOUND = 2e-2


def _peak_bytes(device):
    return (device.memory_stats() or {}).get("peak_bytes_in_use",
                                             "not reported")


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# kernel phase
# ---------------------------------------------------------------------------

def _exact(name, got, want):
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or got.dtype != want.dtype:
        raise AssertionError(f"{name}: pallas {got.dtype}{got.shape} vs ref "
                             f"{want.dtype}{want.shape}")
    bad = np.count_nonzero(got != want)
    if bad:
        raise AssertionError(
            f"{name}: {bad}/{got.size} int32 accumulators differ (max |d| "
            f"{np.abs(got.astype(np.int64) - want).max()})")
    log(f"  {name}: pallas == ref exactly ({got.size} int32 accumulators)")


def _within_bf16_ulp(name, got, want):
    import numpy as np
    g = np.asarray(got.astype("float32"))
    w = np.asarray(want.astype("float32"))
    # one bf16 ulp at the larger magnitude: the f32 spacing times 2^16
    ulp = np.spacing(np.maximum(np.abs(g), np.abs(w))) * 65536.0
    bad = np.count_nonzero(np.abs(g - w) > ulp)
    if bad or not np.isfinite(g).all():
        raise AssertionError(f"{name}: {bad}/{g.size} outputs differ by more "
                             f"than one bf16 ulp")
    log(f"  {name}: pallas within 1 bf16 ulp of ref ({g.size} outputs, "
        f"{np.count_nonzero(g != w)} differ)")


def kernel_phase(K: int, N: int) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.lutmul import ops
    log(f"kernel phase: K={K} N={N}")
    for M in (8, 128):
        rng = np.random.default_rng(SEED + M)
        a_u4 = jnp.asarray(rng.integers(0, 16, (M, K), dtype=np.uint8))
        a_s4 = jnp.asarray(rng.integers(-8, 8, (M, K)).astype(np.int8))
        a_s8 = jnp.asarray(rng.integers(-128, 128, (M, K)).astype(np.int8))
        w_nib = jnp.asarray(rng.integers(0, 256, (K // 2, N), dtype=np.uint8))
        w_i8 = jnp.asarray(rng.integers(-128, 128, (K, N)).astype(np.int8))
        planes = {n: jnp.asarray(rng.integers(0, 256, (n, K // 8, N),
                                              dtype=np.uint8))
                  for n in (2, 4)}
        both = lambda f: (f("pallas"), f("ref"))             # noqa: E731
        _exact(f"lutmul one-hot M={M}", *both(
            lambda be: ops.lutmul(a_u4, w_nib, a_signed=True, backend=be)))
        for label, wbits, n, g in (("w4", 4, 4, 1), ("w4", 4, 4, 2),
                                   ("ternary", "ternary", 2, 2)):
            _exact(f"lutmul_tmac {label} g={g} M={M}", *both(
                lambda be: ops.lutmul_tmac(a_s4, planes[n], wbits, g=g,
                                           backend=be)))
        _exact(f"int_matmul int8 M={M}", *both(
            lambda be: ops.int_matmul(a_s8, w_i8, backend=be)))
        x = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)
        scale = jnp.asarray(rng.uniform(1e-3, 2e-2, (1, N)), jnp.float32)
        for mode, w_q in (("w4a4_lut", w_nib), ("w4a4_tmac", planes[4]),
                          ("w8a8", w_i8)):
            _within_bf16_ulp(f"prequant_matmul {mode} fused M={M}", *both(
                lambda be: ops.prequant_matmul(x, w_q, scale, mode=mode,
                                               backend=be)))


# ---------------------------------------------------------------------------
# model phase
# ---------------------------------------------------------------------------

def make_requests(vocab: int):
    import numpy as np
    from repro.serve import Request
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [Request(prompt=rng.integers(0, vocab, int(n)).tolist(),
                    max_new_tokens=NEW_TOKENS) for n in lens]


def build_model():
    import jax
    from repro.configs import qwen2_7b
    from repro.serve.quantize import init_quantized_params
    cfg = qwen2_7b.config(quant=QUANT)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_quantized_params(jax.random.PRNGKey(SEED), cfg, QUANT))
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    log(f"  quantized params: {n_bytes} bytes built in "
        f"{time.perf_counter() - t0:.1f} s ({cfg.n_layers} layers, "
        f"d={cfg.d_model}, d_ff={cfg.d_ff}, vocab={cfg.vocab}); device 0 "
        f"peak_bytes_in_use so far: {_peak_bytes(jax.devices()[0])}")
    return cfg, params


def serve(eng, label: str):
    """Drain the fixed request set through a Scheduler; every request must
    finish with its full budget.  Returns the finished requests."""
    from repro.serve import RequestStatus, Scheduler
    reqs = make_requests(eng.cfg.vocab)
    sched = Scheduler(eng, slots=N_REQUESTS)
    t0 = time.perf_counter()
    sched.run(reqs)
    wall = time.perf_counter() - t0
    short = [i for i, r in enumerate(reqs)
             if r.status != RequestStatus.FINISHED
             or len(r.tokens) != NEW_TOKENS]
    if short:
        raise AssertionError(f"{label}: requests {short} did not get their "
                             f"{NEW_TOKENS}-token budget")
    log(f"  {label}: {len(reqs)} requests, prompts "
        f"{sorted(len(r.prompt) for r in reqs)}, "
        f"{sum(len(r.tokens) for r in reqs)} tokens served in {wall:.1f} s "
        f"wall ({sched.stats['rounds']} rounds)")
    return reqs


def record_step_args(eng) -> dict:
    """Wrap the engine's step-function builder so the shapes of the first
    call of every compiled step are kept (for lowering it again later)."""
    import jax
    if eng._step_fns:
        raise AssertionError("the engine built steps before they could be "
                             "recorded")
    seen = {}
    build = eng._build_step_fn

    def recording_build(C, chunk, greedy, spec=False):
        fn = build(C, chunk, greedy, spec)

        def call(*args):
            key = (C, chunk, greedy, spec)
            if key not in seen:
                seen[key] = (fn, jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
            return fn(*args)
        return call

    eng._build_step_fn = recording_build
    return seen


def check_served_steps(eng, steps: dict) -> None:
    """Every step the scheduler ran holds the Pallas kernels."""
    if not steps or set(steps) != set(eng._step_fns):
        raise AssertionError(f"recorded steps {sorted(steps)} are not the "
                             f"served steps {sorted(eng._step_fns)}")
    for key, (fn, sds) in steps.items():
        n = fn.lower(*sds).as_text().count("tpu_custom_call")
        if not n:
            raise AssertionError(f"served step {key} holds no Pallas kernel")
        log(f"  served step (prefill_chunk, chunk, greedy, spec)={key}: "
            f"{n} tpu_custom_call sites")


def model_phase(compile_seconds) -> None:
    import jax
    import numpy as np
    from repro.kernels.lutmul import ops
    from repro.serve import ServeConfig, make_engine
    log(f"model phase: qwen2-7b {QUANT}")
    cfg, params = build_model()
    scfg = ServeConfig(max_len=MAX_LEN, quant=QUANT)
    eng = make_engine(params, cfg, scfg)
    steps = record_step_args(eng)
    c0 = compile_seconds()
    got = serve(eng, "make_engine -> Scheduler")
    log(f"  compile seconds during serving: {compile_seconds() - c0:.1f}")
    check_served_steps(eng, steps)
    # the same params and requests on the ref kernels, through the same
    # entry points: the served programs must agree token for token
    tokens = jax.numpy.asarray([got[0].prompt], jax.numpy.int32)
    logits = {"pallas": np.asarray(eng._prefill(eng.params, tokens)[0])}
    del eng
    ops.set_backend("ref")
    try:
        ref_eng = make_engine(params, cfg, scfg)
        want = serve(ref_eng, "ref kernels, make_engine -> Scheduler")
        logits["ref"] = np.asarray(ref_eng._prefill(ref_eng.params,
                                                     tokens)[0])
    finally:
        ops.set_backend(None)
    diff = [i for i, (a, b) in enumerate(zip(got, want)) if a.tokens != b.tokens]
    log(f"  transcripts pallas vs ref bit-identical: {not diff}"
        + (f" (requests {diff} differ)" if diff else ""))
    # one prefill of the served params through the engine's own compiled
    # prefill, on the Pallas kernels against ref
    err = float(np.abs(logits["pallas"] - logits["ref"]).max())
    rel = err / float(np.abs(logits["ref"]).max())
    log(f"  prefill logits, {cfg.n_layers} layers, {tokens.shape[1]} tokens, "
        f"pallas vs ref: max abs {err:.6g}, relative {rel:.6g} (bound "
        f"{LOGIT_REL_BOUND}), argmax agree: "
        f"{bool(logits['pallas'].argmax() == logits['ref'].argmax())}")
    if not (np.isfinite(logits["pallas"]).all() and rel <= LOGIT_REL_BOUND):
        raise AssertionError(f"prefill logits disagree with the ref kernels "
                             f"(relative {rel:.6g} > {LOGIT_REL_BOUND})")
    if diff:
        raise AssertionError("served transcripts differ from the ref kernels'")


def four_chip_phase() -> None:
    import jax
    from repro.launch.mesh import make_serving_mesh
    from repro.serve import ServeConfig, make_engine
    log(f"four-chip phase: qwen2-7b {QUANT}, ShardedEngine 1x4 vs Engine")
    cfg, params = build_model()
    scfg = ServeConfig(max_len=MAX_LEN, quant=QUANT)
    sharded = make_engine(params, cfg, scfg, mesh=make_serving_mesh("1x4"))
    log(f"  head-sharded attention: {sharded.head_sharded}; tensor-parallel "
        f"leaves: {sharded.n_tp_leaves}")
    got = serve(sharded, "ShardedEngine 1x4 (make_engine mesh=)")
    del sharded
    single = make_engine(params, cfg, scfg)
    want = serve(single, "Engine (single device)")
    diff = [i for i, (a, b) in enumerate(zip(got, want))
            if a.tokens != b.tokens]
    for d in jax.devices():
        log(f"  device {d.id} peak_bytes_in_use: {_peak_bytes(d)}")
    log(f"  transcripts bit-identical: {not diff}"
        + (f" (requests {diff} differ)" if diff else ""))
    if diff:
        raise AssertionError("sharded transcripts differ from single-device")


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail("run this script from a checkout of the repo (src/repro is "
             "missing next to it)")
    sys.path.insert(0, os.path.join(ROOT, "src"))

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    import jax.monitoring
    if jax.default_backend() != "tpu":
        fail(f"needs a TPU; JAX found {jax.default_backend()!r}.  There is "
             "no CPU or interpret fallback: run the tests for those")
    from repro.kernels.lutmul import ops
    try:
        backend = ops.get_backend()
    except (RuntimeError, ValueError) as e:
        fail(str(e))
    if backend != "pallas":
        fail(f"kernel backend is {backend!r}, not 'pallas'")
    devices = jax.devices()
    if len(devices) < args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)} device(s)")

    compile_s = [0.0]

    def on_compile(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += secs
    jax.monitoring.register_event_duration_secs_listener(on_compile)

    dev = devices[0]
    log("chip_smoke: single smoke run, not a benchmark")
    log(f"  device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"compile cache: {cache_dir}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase()
    else:
        from repro.configs import qwen2_7b
        full = qwen2_7b.config()
        kernel_phase(full.d_model, full.d_ff)
        model_phase(lambda: compile_s[0])
        log(f"  peak_bytes_in_use: {_peak_bytes(dev)}")
    log(f"  compile seconds (all phases): {compile_s[0]:.1f}; wall seconds: "
        f"{time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
