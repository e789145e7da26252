"""Share of the decode-only rounds' device busy time in ops under the
program's ``mla`` scope (the absorbed latent attention: query absorption,
scores over the latent cache, readout and W_UV; cache writes stay under
``kv_write``), %."""
from bench import scopes
from bench.readers import scope_share


def read(ctx):
    return scope_share(ctx, False, lambda n, s: scopes.under(s, "mla"))
