"""Share of the decode-only rounds' device busy time in ops under the
program's MoE scopes (``moe_route``, ``moe_experts``, ``moe_combine``,
``moe_shared``), %."""
from bench import scopes
from bench.readers import scope_share

MOE_SCOPES = ("moe_route", "moe_experts", "moe_combine", "moe_shared")


def read(ctx):
    return scope_share(ctx, False, lambda n, s: any(
        scopes.under(s, name) for name in MOE_SCOPES))
