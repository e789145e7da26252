"""Process start to the first timed round (build, compile or cache load,
warm-up, the traffic's own set-up)."""


def read(ctx):
    return ctx.setup_s
