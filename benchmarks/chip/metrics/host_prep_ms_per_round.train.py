"""Host time before the round's program starts: from the start of each
``step`` span to the start of the longest device program inside it,
averaged over the traced rounds."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.steps:
        return None
    return 1e3 * sum(s["prep_s"] for s in t.steps) / len(t.steps)
