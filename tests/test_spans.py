"""The one list of the round's host spans and device scopes
(``repro.spans``): the program emits only names it holds."""
import jax
import jax.numpy as jnp
import pytest

from repro import spans
from repro.core.transforms import TRANSFORMS


def test_every_registry_transform_has_a_scope():
    assert {f"transform/{n}" for n in TRANSFORMS} \
        == set(spans.TRANSFORM_SCOPES)


@pytest.mark.parametrize("make, name", [
    (spans.span, "round/other"), (spans.span, "local_update"),
    (spans.scope, "transform/other"), (spans.scope, "round/draw")])
def test_a_name_off_the_list_is_refused(make, name):
    with pytest.raises(ValueError, match="is not one of"):
        make(name)


def test_spans_and_scopes_leave_results_alone():
    """Outside a trace a span records nothing; a scope renames ops only."""
    def f(x):
        with spans.scope(spans.AGGREGATE):
            return jnp.sin(x).sum()
    x = jnp.arange(4.0)
    with spans.span(spans.GATHER, bytes=16):
        got = jax.jit(f)(x)
    assert float(got) == float(jnp.sin(x).sum())
    hlo = jax.jit(f).lower(x).as_text(debug_info=True)
    assert "aggregate" in hlo
