"""Device time per round that carries the chunk lane, from the trace."""
from bench.readers import round_ms


def read(ctx):
    return round_ms(ctx, chunk_lane=True)
