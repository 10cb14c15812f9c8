"""Faults planted underneath a run, to show that ``correct`` catches them.

Each fault is a ``plant(obj)`` that breaks the program object a driver
built (a ``Federation`` for the rounds driver, a ``FederationService``
for the service driver) before any step runs; a fault that patches a
module returns the function that undoes it.  ``readings.py`` plants
them on the chip at a cell's own size; the self-tests plant them at a
CPU size and require ``correct`` to come out false.
"""
from __future__ import annotations


def rounds_unchanged(fed):
    """Every round leaves the parameters as they were."""
    eng = fed.engine
    real = eng.round

    def round_(seed=None):
        keep = _host(eng.params)
        rec = real(seed=seed)
        import jax.numpy as jnp
        eng.params = _tmap(jnp.asarray, keep)
        return rec
    eng.round = round_


def rounds_half_batch(fed):
    """Half of every minibatch is left out; the mean is taken over the
    rest (its rows are masked, so the loss and gradient see 32 of 64)."""
    import repro.core.engine as engine_mod
    real = engine_mod.stacked_round_batches

    def half(*a, **kw):
        stacked, counts = real(*a, **kw)
        p = stacked["doc_mask"].shape[-1]
        stacked["doc_mask"][..., p // 2:] = 0.0
        return stacked, counts
    engine_mod.stacked_round_batches = half
    return lambda: setattr(engine_mod, "stacked_round_batches", real)


def service_unchanged(service):
    """Every aggregation publishes the model it started from."""
    real = service._agg_fn

    def agg(params, server_state, *rest):
        real(params, server_state, *rest)
        return params, server_state
    service._agg_fn = agg


def service_half_batch(service):
    """Every aggregation folds only the first half of its buffer, the
    mean taken over those."""
    real = service._agg_fn

    def agg(params, server_state, deltas, weights, *rest):
        m = weights.shape[0]
        weights = weights.at[m // 2:].set(0.0)
        return real(params, server_state, deltas, weights, *rest)
    service._agg_fn = agg


def service_answer_altered(service):
    """Infer answers with the topic columns rotated by one."""
    real = service.infer

    def infer(*a, **kw):
        import jax.numpy as jnp
        return jnp.roll(real(*a, **kw), 1, axis=-1)
    service.infer = infer


def _tmap(f, t):
    import jax
    return jax.tree_util.tree_map(f, t)


def _host(t):
    import numpy as np
    return _tmap(lambda x: np.array(x, copy=True), t)


FAULTS = {
    "rounds": {"unchanged": rounds_unchanged,
               "half_batch": rounds_half_batch},
    "service": {"unchanged": service_unchanged,
                "half_batch": service_half_batch,
                "answer_altered": service_answer_altered},
}
