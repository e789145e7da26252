"""Prompt tokens the chunk lane fed in the window over its length."""


def read(ctx):
    return sum(r.prefill for r in ctx.window.rounds) / ctx.elapsed_s
