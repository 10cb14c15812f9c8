"""Rounds completed in the window: a reader that exists only in the
self-test fixture, to show a metric is found by its name alone."""


def read(ctx):
    return ctx.counters.get("rounds")
