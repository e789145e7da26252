"""Share of the decode-only rounds' device busy time spent on the KV
cache, %: ops under the program's ``kv_write`` scope, plus the copies,
slices and update-slices whose result is a whole cache leaf or one layer
of it, which the layer scan and the compiler add outside that scope
(``bench.scopes.cache_op``)."""
from bench import scopes
from bench.readers import scope_share


def read(ctx):
    return scope_share(ctx, False, scopes.cache_op(ctx.cache_shapes))
