"""Mean share of slots holding a request per round (Scheduler.stats)."""


def read(ctx):
    s0, s1 = ctx.window.stats0, ctx.window.stats1
    rounds = s1["rounds"] - s0["rounds"]
    if not rounds:
        return None
    return 100.0 * (s1["occupancy_sum"] - s0["occupancy_sum"]) / rounds
