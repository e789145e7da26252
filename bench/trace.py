"""Reduce a profiler trace to what the per-layer metrics read.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per HLO operation run on the chip, named by the instruction's
HLO text (``%copy.121 = bf16[...] copy(...)``).  A ``while`` or
``conditional`` event spans the ops of its body, so those containers are
dropped and only leaf ops count.  The benchmark's host spans
(``bench.round``, ``bench.clients``) sit on the host plane, on the same
clock.  ``bench.scopes.load`` reads a trace directory into a
``ScopedTrace``, this ``Trace`` with the program's own names added.
Everything here works on plain (name, start_ns, end_ns) tuples, so it is
tested on a small recorded trace without a chip.
"""
from __future__ import annotations

import dataclasses
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
CONTAINER = re.compile(r"^%?(while|conditional)[.\d]")
HOST_SPANS = ("bench.round", "bench.clients")
_INST = re.compile(r"^(%?[\w.-]+) = (\S+) ([\w-]+)\(")


@dataclasses.dataclass
class Trace:
    ops: dict            # device plane -> [(name, start_ns, end_ns)]
    spans: list          # host spans [(name, start_ns, end_ns)] in order

    @property
    def rounds(self) -> list:
        return [(a, b) for n, a, b in self.spans if n == "bench.round"]

    @property
    def window(self) -> tuple:
        r = self.rounds
        return (r[0][0], r[-1][1]) if r else (0, 0)


def union(intervals, lo: float, hi: float) -> list:
    """Merged [a, b) intervals clipped to [lo, hi)."""
    out = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(events, lo: float, hi: float) -> float:
    return float(sum(b - a for a, b in union(
        ((a, b) for _, a, b in events), lo, hi)))


def busy_per_device(trace: Trace, lo=None, hi=None) -> float:
    """Busy nanoseconds in [lo, hi) (default: the window), averaged over
    the device planes."""
    if lo is None:
        lo, hi = trace.window
    if not trace.ops:
        return 0.0
    return sum(busy_ns(ev, lo, hi) for ev in trace.ops.values()) / len(
        trace.ops)


def round_busy_ns(trace: Trace) -> list:
    """Device busy time inside each ``bench.round`` host span: the round's
    program runs between its dispatch and the host reading its tokens."""
    return [busy_per_device(trace, a, b) for a, b in trace.rounds]


def kernel_ns(trace: Trace, pattern: str, lo=None, hi=None) -> tuple:
    """(summed device time, event count) of the ops whose name matches
    ``pattern``, in [lo, hi), averaged over the device planes."""
    if lo is None:
        lo, hi = trace.window
    rx = re.compile(pattern)
    tot, cnt = 0.0, 0
    for ev in trace.ops.values():
        for name, a, b in ev:
            if rx.search(name) and a >= lo and b <= hi:
                tot += b - a
                cnt += 1
    n = max(len(trace.ops), 1)
    return tot / n, cnt / n


def label(name: str, kernels: dict) -> str:
    """A short name for an HLO op event: the kernel it is (by the name map)
    or its opcode, its instruction and its result type."""
    m = _INST.match(name)
    if not m:
        return name[:120]
    inst, typ, opcode = m.groups()
    kind = next((k for k, rx in kernels.items() if re.search(rx, name)),
                opcode)
    return f"{kind} {inst} {typ.split('{')[0]}"


def breakdown(trace: Trace, kernels: dict, top: int = 10) -> dict:
    """The device ops that took the most time in the window (summed by
    instruction, so a loop body's op sums over its iterations), and the
    longest idle gaps, each labelled by the innermost host span under its
    midpoint (``bench.scopes.idle_gaps``)."""
    from bench.scopes import idle_gaps
    lo, hi = trace.window
    by_name: dict = {}
    for ev in trace.ops.values():
        for name, a, b in ev:
            if a >= lo and b <= hi:
                key = label(name, kernels)
                by_name[key] = by_name.get(key, 0) + (b - a)
    n = max(len(trace.ops), 1)
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / n / 1e9] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle_gaps(trace, lo, hi)[:top]]}
