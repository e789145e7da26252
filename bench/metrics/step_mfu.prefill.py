"""Chunk-lane rounds' share of the chip's peaks: the ideal time of the
prompt tokens each round fed (one weight pass plus their work, from
shapes, bench/cost.py) over the rounds' device time."""
from bench.readers import pct, rounds_of


def read(ctx):
    rs = rounds_of(ctx, chunk_lane=True)
    if not rs or ctx.peaks is None:
        return None
    ideal = sum(ctx.cost.prefill_round(ctx.config,
                                       r.prompt_pos).ideal_s(ctx.peaks)
                for r, _ in rs)
    return pct(ideal / sum(b for _, b in rs)) if ideal else None
