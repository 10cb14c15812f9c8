"""The names of the round's host spans and device scopes, in one list.

Host spans are ``jax.profiler.TraceAnnotation``s: they record, with their
counts as arguments, on the profiler's clock while a trace is active, and
cost one inactive ``TraceMe`` each otherwise.  Device scopes are
``jax.named_scope``s inside the fused round graphs: they change only the
ops' ``op_name`` metadata, never the ops.  The reader of these spans and
scopes in a trace (``benchmarks/chip/programtrace.py``) imports
:data:`HOST_SPANS` and :data:`DEVICE_SCOPES` from here, so the names the
program emits and the names it reads cannot drift apart.
"""
from __future__ import annotations

import jax

# vmap round, in order (core/engine.py ``_round_vmap``,
# data/federated_split.py ``stacked_round_batches``)
DRAW = "round/draw"           # the jitted cohort index draws (host fill: reads)
# the (K, E, P, ...) cohort arrays, count ``bytes``; count ``device`` 1: the
# enqueue of their gather from the clients' rows on the device, 0: their
# numpy fill on the host
GATHER = "round/gather"
# the fused call's enqueue (the copy follows); count ``scan`` 1 when the
# round scans its clients one by one, 0 when it vmaps them
DISPATCH = "round/dispatch"
FETCH = "round/fetch"         # scalar and loss reads, the round's record
HOST_SPANS = (DRAW, GATHER, DISPATCH, FETCH)

# inside the fused graphs (core/engine.py ``_build_vmap_fns``); one scope
# per registry transform (core/transforms.py ``TRANSFORMS``)
LOCAL_UPDATE = "local_update"
AGGREGATE = "aggregate"           # the Eq. (2) combine and the server step
TRANSFORM_SCOPES = tuple(f"transform/{n}"
                         for n in ("dp", "topk", "secure", "precision"))
# inside a language model's layers (models/layers/moe.py
# ``held_moe_apply``, models/layers/attention.py ``mla_full``)
MOE_ROUTE = "moe/route"       # router, top-k, the pairs' sort and combine
MOE_EXPERTS = "moe/experts"   # the grouped product over the held experts
MOE_SHARED = "moe/shared"     # the shared experts
ATTENTION_MLA = "attention/mla"
MODEL_SCOPES = (MOE_ROUTE, MOE_EXPERTS, MOE_SHARED, ATTENTION_MLA)
DEVICE_SCOPES = (LOCAL_UPDATE,) + TRANSFORM_SCOPES + (AGGREGATE,) \
    + MODEL_SCOPES


def span(name: str, **counts):
    """A host span of :data:`HOST_SPANS`, with ``counts`` as arguments."""
    if name not in HOST_SPANS:
        raise ValueError(f"{name!r} is not one of {HOST_SPANS}")
    return jax.profiler.TraceAnnotation(name, **counts)


def scope(name: str):
    """A device scope of :data:`DEVICE_SCOPES` (``transform/<name>`` for a
    registry transform)."""
    if name not in DEVICE_SCOPES:
        raise ValueError(f"{name!r} is not one of {DEVICE_SCOPES}")
    return jax.named_scope(name)
