"""Device-busy milliseconds per round: the union of the operations'
intervals in the traced window over the rounds completed in it (no
program names are read, so a rename cannot silence it)."""


def read(ctx):
    t, rounds = ctx.trace, ctx.counters.get("rounds")
    if t is None or not rounds or t.busy_s <= 0:
        return None
    return 1e3 * t.busy_s / rounds
