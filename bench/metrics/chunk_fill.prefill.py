"""Share of chunk-lane positions that carried a prompt token
(Scheduler.stats admitted_tokens / prefill_tokens over the window)."""


def read(ctx):
    s0, s1 = ctx.window.stats0, ctx.window.stats1
    lane = s1["prefill_tokens"] - s0["prefill_tokens"]
    if not lane:
        return None
    return 100.0 * (s1["admitted_tokens"] - s0["admitted_tokens"]) / lane
