"""Unit tests of the yardstick: peaks, ops and bytes, traffic, the window
arithmetic and the trace reduction.  No TPU library is used."""
from __future__ import annotations

import json
import re
import types

import numpy as np
import pytest

from bench import cost, peaks, spec, trace, traffic
from bench.serve import Round, Window
from bench.spec import load_reader
from bench.tests.tiny import REPO

QWEN = json.loads((REPO / "bench/configs/qwen2-7b.w4a4_lut.json").read_text())
BITNET = json.loads(
    (REPO / "bench/configs/bitnet-b1.58-3b.ternary_a8_tmac.json").read_text())
V5E = peaks.peaks("TPU v5 lite")


def test_peaks_v5e_and_unknown_device():
    assert V5E["int8_ops_per_s"] == 393e12
    assert V5E["bf16_flops_per_s"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert "source" in V5E
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")


def test_qwen_kernel_counts_by_hand():
    # one forward at M=16: 28 layers x 7 projections, 4-bit codes
    d, ff, q, kv, L = 3584, 18944, 3584, 512, 28
    macs = d * q + 2 * d * kv + q * d + 2 * d * ff + ff * d
    w = cost.kernel_work(QWEN, "onehot", 16)
    assert w.int_ops == 2 * 16 * macs * L
    acts = sum(16 * K + 4 * 16 + 2 * 16 * N for K, N in
               [(d, q), (d, kv), (d, kv), (q, d), (d, ff), (d, ff), (ff, d)])
    scales = 4 * (q + 2 * kv + d + 2 * ff + d)
    assert w.bytes == L * (macs / 2 + scales + acts)
    assert cost.kernel_calls(QWEN, "tmac") == []
    assert len(cost.kernel_calls(BITNET, "tmac")) == 26 * 7
    # memory-bound at decode: weight bytes over HBM bandwidth
    assert w.ideal_s(V5E) == pytest.approx(w.bytes / 819e9)


def test_qwen_step_counts_by_hand():
    d, V, L = 3584, 152064, 28
    kv_tok = L * 2 * 4 * 128 * 2
    dense = spec.arch(QWEN)
    assert dense.kv_bytes_per_token(QWEN) == 57344 == kv_tok
    body = dense.weight_bytes(QWEN)
    # 5,995,134,976 bytes of served params minus the f32 embedding,
    # which a forward gathers by row
    assert body == 5995134976 - 4 * V * d
    f = cost.forward(QWEN, [100, 200])
    n_params = L * (d * d + 2 * d * 512 + d * d + 3 * d * 18944) + d * V
    assert f.int_ops == 2 * 2 * n_params
    assert f.bf16_ops == 4 * L * 28 * 128 * 300
    assert f.bytes == body + kv_tok * 300 + 2 * 4 * d
    r = cost.decode_round(QWEN, [100, 200], 3)
    assert r.int_ops == 3 * f.int_ops
    assert r.bytes == 3 * body + kv_tok * (300 + 302 + 304) + 3 * 2 * 4 * d


def test_traffic_same_seed_same_requests_other_seed_differs():
    mix = json.loads((REPO / "bench/traffic/prefill.p512-1536.o16-64.json")
                     .read_text())
    seed = 2 ** 31 + 12345
    bench = REPO / "bench"
    a = traffic.load(bench, mix, seed, 152064)
    b = traffic.load(bench, mix, seed, 152064)
    c = traffic.load(bench, mix, seed + 1, 152064)

    def sent(t):
        reqs = t.first() + t.after_round(1.0, 8)
        return [(r["prompt"], r["max_new_tokens"]) for r in reqs]
    ra = sent(a)
    assert ra == sent(b)
    rc = sent(c)
    assert ra != rc
    # every wave of `clients` requests has the same multiset of lengths
    for reqs in (ra, rc):
        for w in (reqs[:8], reqs[8:]):
            assert sorted((len(p), n) for p, n in w) == sorted(
                (len(p), n) for p, n in ra[:8])
    assert all(512 <= len(p) <= 1536 and 16 <= n <= 64 for p, n in ra)


def _window():
    # two requests; 3 rounds of 1 s; request 0 gets 2 tokens per round,
    # request 1 starts in round 2 (first token is not a gap)
    rounds = [Round(0.0, 1.0, 0, False, 2, [10], []),
              Round(1.0, 2.0, 8, True, 3, [12], list(range(8))),
              Round(2.0, 4.0, 0, False, 4, [14, 9], [])]
    gaps = [1.0, 0.0, 1.0, 0.0, 2.0, 0.0, 2.0, 0.0]
    stats0 = {"rounds": 5, "occupancy_sum": 2.0, "prefill_tokens": 8,
              "admitted_tokens": 8}
    stats1 = {"rounds": 8, "occupancy_sum": 3.5, "prefill_tokens": 24,
              "admitted_tokens": 20}
    return Window(0.0, 4.0, rounds, gaps, [], {}, stats0, stats1, {})


def _ctx(win, tr=None):
    return types.SimpleNamespace(
        window=win, elapsed_s=win.t1 - win.t0, setup_s=12.5, trace=tr,
        config=QWEN, peaks=V5E, slots=2, chunk=8, cost=cost,
        kernel_names=KERNELS,
        traced_rounds=lambda: [])


@pytest.mark.parametrize("name,want", [
    ("out_tok_s", 9 / 4), ("prompt_tok_s", 8 / 4), ("setup_s", 12.5),
    ("itl_p95_ms", float(np.percentile([1, 0, 1, 0, 2, 0, 2, 0], 95)) * 1e3),
    ("occupancy.decode", 100 * 1.5 / 3),
    ("chunk_fill.prefill", 100 * 12 / 16)])
def test_window_arithmetic(name, want):
    assert load_reader(REPO, name)(_ctx(_window())) == pytest.approx(want)


def test_sample_keeps_slots_freed_in_the_last_round():
    """A slot whose request finished in the window's last round still holds
    that request's rows (no round ran since), so a window that closes with
    every slot free still has rows to compare; a request that left its
    slot unfinished (preempted) does not count."""
    from bench.serve import sample_slots

    def req(prompt, tokens, done):
        return types.SimpleNamespace(prompt=[1] * prompt,
                                     tokens=[2] * tokens, done=done)
    finished, decoding, preempted = req(4, 2, True), req(4, 3, False), \
        req(6, 0, False)
    sched = types.SimpleNamespace(n_slots=3, slots=[None, decoding, None],
                                  _progress=[0, 4, 0], _target=[0, 4, 0])
    win = _window()
    win.held = {0: finished, 1: decoding, 2: preempted}
    assert sample_slots(win, sched, 8, 3) == [(0, finished, 5),
                                              (1, decoding, 6)]
    sched.slots = [None] * 3
    decoding.done = True
    assert sample_slots(win, sched, 8, 3) == [(0, finished, 5),
                                              (1, decoding, 6)]


# op names as a v5e trace records them: the instruction's HLO text
ONEHOT = ("%closed_call.177 = bf16[8,3584]{1,0:T(8,128)(2,1)S(1)} custom-call("
          "u8[8,18944]{1,0:T(8,128)(4,1)S(1)} %fusion.277, u8[9472,3584]{1,0:"
          "T(8,128)(4,1)S(1)} %dynamic-slice_bitcast_fusion.68, s32[16,16]{1,0"
          ":T(8,128)S(1)} %get-tuple-element.3442), custom_call_target="
          "\"tpu_custom_call\"")
TMAC = ("%closed_call.92 = bf16[16,8704]{1,0:T(8,128)(2,1)S(1)} custom-call("
        "s8[16,3200]{1,0:T(8,128)(4,1)S(1)} %fusion.119, u8[2,400,8704]{2,1,0"
        ":T(8,128)(4,1)S(1)} %pad.133, f32[16,1]{1,0:T(8,128)S(1)} %copy.33),"
        " custom_call_target=\"tpu_custom_call\"")
COPY = ("%copy.121 = bf16[28,16,2048,4,128]{4,3,2,1,0:T(4,128)(2,1)} copy("
        "bf16[28,16,2048,4,128]{4,3,2,1,0:T(4,128)(2,1)} %get-tuple-element)")
WHILE = ("%while.46 = (s32[]{:T(128)}, bf16[28,16,2048,4,128]{4,3,2,1,0}) "
         "while((s32[]{:T(128)}, bf16[28,16,2048,4,128]{4,3,2,1,0}) %tuple)")
KERNELS = json.loads((REPO / "bench/kernels.json").read_text())


def _recorded():
    """A small trace as the reduction sees it: two rounds on one device,
    a one-hot kernel, an idle gap under the host's client bookkeeping."""
    ops = {"/device:TPU:0": [
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 100, 300),
        (ONEHOT, 300, 700),
        (ONEHOT, 650, 900),                     # overlaps: union counts once
        (COPY, 1200, 1500), (TMAC, 50, 90)]}    # the last before the window
    spans = [("bench.round", 100, 1000), ("bench.clients", 1000, 1150),
             ("bench.round", 1150, 1600)]
    return trace.Trace(ops, spans)


def test_kernel_name_map_tells_the_kernels_apart():
    kinds = {n: [k for k, rx in KERNELS.items() if re.search(rx, n)] for n in (ONEHOT, TMAC, COPY)}
    assert kinds == {ONEHOT: ["onehot"], TMAC: ["tmac"], COPY: []}
    assert trace.CONTAINER.match(WHILE)
    assert not trace.CONTAINER.match(ONEHOT)


def test_trace_busy_idle_and_kernel_time():
    t = _recorded()
    assert t.window == (100, 1600)
    assert trace.busy_per_device(t) == 800 + 300
    assert trace.round_busy_ns(t) == [800, 300]
    assert trace.kernel_ns(t, KERNELS["onehot"]) == (650, 2)
    assert trace.kernel_ns(t, KERNELS["tmac"]) == (0, 0)
    ctx = _ctx(_window(), t)
    idle = load_reader(REPO, "idle_share.decode")(ctx)
    assert idle == pytest.approx(100 * (1 - 1100 / 1500))
    b = trace.breakdown(t, KERNELS)
    assert b["device_ops"][0] == ["onehot %closed_call.177 bf16[8,3584]",
                                  650 / 1e9]
    assert b["device_ops"][1] == ["copy %copy.121 bf16[28,16,2048,4,128]",
                                  300 / 1e9]
    # the gaps: 900..1200 mostly under the clients span, 1500..1600
    assert b["idle_gaps"][0] == ["bench.clients", 300 / 1e9]
    assert b["idle_gaps"][1] == ["bench.round", 100 / 1e9]


def test_rooflines_stay_silent_without_a_trace():
    ctx = _ctx(_window())
    for name in ("onehot_roofline.decode", "step_mfu.decode",
                 "round_ms.decode", "idle_share.prefill"):
        assert load_reader(REPO, name)(ctx) is None
