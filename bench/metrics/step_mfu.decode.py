"""Decode-only rounds' share of the chip's peaks: the ideal time of the
model's useful work (each round's forwards of the occupied slots, from
shapes, bench/cost.py) over the rounds' device time."""
from bench.readers import pct, rounds_of


def read(ctx):
    rs = rounds_of(ctx, chunk_lane=False)
    if not rs or ctx.peaks is None:
        return None
    ideal = sum(ctx.cost.decode_round(ctx.config, r.decoding,
                                      ctx.chunk).ideal_s(ctx.peaks)
                for r, _ in rs)
    return pct(ideal / sum(b for _, b in rs)) if ideal else None
