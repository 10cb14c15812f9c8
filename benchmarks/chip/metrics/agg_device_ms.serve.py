"""Device milliseconds of one FedBuff aggregation: the device time of the
program ``jax.jit(agg)`` compiles (``serve/service.py``), found in the
trace by that jit's name, over the aggregations the service counted in
the traced window."""

PROGRAM = "jit_agg"


def read(ctx):
    t, n = ctx.trace, ctx.counters.get("aggregations")
    if t is None or not n or PROGRAM not in t.module_s:
        return None
    return 1e3 * t.module_s[PROGRAM] / n
