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


def lane_spans(ctx, chunk_lane: bool) -> list:
    """(start, end) in trace nanoseconds of each traced round of the lane
    (chunk-lane rounds, or decode-only ones)."""
    return [span for r, _, span in ctx.traced_round_spans()
            if r.chunk_lane == chunk_lane]


def host_ms(ctx, chunk_lane: bool):
    """Mean host time a round of the lane spent in ``Scheduler.step``
    outside its one blocking read: the program's ``serve.admin``,
    ``serve.assemble``, ``serve.dispatch`` and ``serve.deliver`` spans
    inside each round, ms.  Silent where the program wrote none."""
    from bench import scopes
    spans = lane_spans(ctx, chunk_lane)
    if not spans:
        return None
    per = [sum(r[p] for p in scopes.HOST_PHASES)
           for r in scopes.phase_ms(ctx.trace, spans)]
    return sum(per) / len(per) if any(per) else None


def scope_share(ctx, chunk_lane: bool, keep):
    """% of the lane's rounds' device busy time in ops for which ``keep``
    (name, (scope path, kernel name)) holds.  Silent where no op of those
    rounds carries a scope or a kernel name (the trace's ops were not
    joined to their programs)."""
    from bench import scopes
    spans = lane_spans(ctx, chunk_lane)
    if not spans or not sum(scopes.busy_each(
            ctx.trace, lambda n, s: scopes.scoped(s), spans)):
        return None
    return scopes.share(ctx.trace, spans, keep)
