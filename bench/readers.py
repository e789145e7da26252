"""Helpers the metric readers in ``bench/metrics/`` share."""
from __future__ import annotations


def pct(x):
    return None if x is None else 100.0 * x


def kernel_roofline(ctx, kernel: str):
    """Summed ideal time of the window's calls of ``kernel`` over their
    summed device time.  Every forward runs the same calls at M = slots
    rows, so the calls in the trace are whole forwards of
    ``kernel_calls`` each."""
    from bench import trace as tr
    if ctx.trace is None or ctx.peaks is None:
        return None
    calls = len(ctx.cost.kernel_calls(ctx.config, kernel))
    if not calls:
        return None
    t_ns, n = tr.kernel_ns(ctx.trace, ctx.kernel_names[kernel])
    if not n or not t_ns:
        return None
    forwards = n / calls
    ideal = ctx.cost.kernel_work(ctx.config, kernel, ctx.slots).ideal_s(
        ctx.peaks)
    return pct(forwards * ideal / (t_ns / 1e9))


def rounds_of(ctx, chunk_lane: bool) -> list:
    return [(r, busy) for r, busy in ctx.traced_rounds()
            if r.chunk_lane == chunk_lane and busy > 0]


def round_ms(ctx, chunk_lane: bool):
    rs = rounds_of(ctx, chunk_lane)
    if not rs:
        return None
    return 1e3 * sum(b for _, b in rs) / len(rs)


def idle_share(ctx):
    from bench import trace as tr
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace.window
    if hi <= lo:
        return None
    return pct(1.0 - tr.busy_per_device(ctx.trace) / (hi - lo))
