"""Device time per decode-only round (no chunk lane), from the trace."""
from bench.readers import round_ms


def read(ctx):
    return round_ms(ctx, chunk_lane=False)
