"""Output tokens delivered to requests in the window over its length."""


def read(ctx):
    return sum(r.emitted for r in ctx.window.rounds) / ctx.elapsed_s
