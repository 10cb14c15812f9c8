"""Device milliseconds a round under the program's ``attention/mla``
scope: MLA's projections and its flash-structured core, forward,
recomputation and gradients, in the traced window (``programtrace.py``'s
split of the round program by scope, the scope's ops alone or fused with
another scope's)."""


def read(ctx):
    return ctx.counters.get("scope_ms", {}).get("attention/mla")
