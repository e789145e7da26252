"""95th percentile over every gap, in the window, between successive
tokens delivered to one request (host clock; tokens reach the host once per
round, so the gaps inside a round are 0 and the tail reads a round)."""
import numpy as np


def read(ctx):
    gaps = ctx.window.gaps
    return float(np.percentile(gaps, 95)) * 1e3 if gaps else None
