"""Device milliseconds a round under the program's ``moe/experts`` scope:
the grouped product over the held experts, its recomputation and its
gradients, in the traced window (``programtrace.py``'s split of the
round program by scope, the scope's ops alone or fused with another
scope's)."""


def read(ctx):
    return ctx.counters.get("scope_ms", {}).get("moe/experts")
