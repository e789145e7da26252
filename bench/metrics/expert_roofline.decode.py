"""The routed experts' share of their roofline in the window: the ideal
time of the work the program's counters say it did (``Scheduler.stats``
``moe_pairs`` (token, expert) pairs through three int4 projections, and
the codes of the ``experts_hit`` experts read once: the architecture's
``expert_work``) over the device time of the ops under the ``moe_experts``
scope.  Counting the experts actually hit, a kernel that skips idle
experts cannot read above 100%.  Silent without those counters."""
from bench import scopes, spec
from bench.readers import pct


def read(ctx):
    s0, s1 = ctx.window.stats0, ctx.window.stats1
    if ctx.trace is None or ctx.peaks is None or "moe_pairs" not in s1:
        return None
    pairs = s1["moe_pairs"] - s0["moe_pairs"]
    hit = s1["experts_hit"] - s0["experts_hit"]
    spans = [span for _, _, span in ctx.traced_round_spans()]
    if not pairs or not spans:
        return None
    busy = sum(scopes.busy_each(
        ctx.trace, lambda n, s: scopes.under(s, "moe_experts"), spans))
    if not busy:
        return None
    work = spec.arch(ctx.config).expert_work(ctx.config, pairs, hit)
    return pct(work.ideal_s(ctx.peaks) / (busy / 1e9))
