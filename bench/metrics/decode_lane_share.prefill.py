"""Share of the chunk-lane rounds' device busy time in ops under the step
program's ``decode_lane`` scope, %: what the decode lane costs a round
that feeds prompts."""
from bench import scopes
from bench.readers import scope_share


def read(ctx):
    return scope_share(ctx, True, lambda n, s: scopes.under(s, "decode_lane"))
