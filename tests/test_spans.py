"""The one list of the round's host spans and device scopes
(``repro.spans``): the program emits only names it holds."""
import jax
import jax.numpy as jnp
import pytest

from repro import spans
from repro.core.transforms import TRANSFORMS
from conftest import tiny_spec


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


@pytest.mark.parametrize("where, device", [
    ("one-device", 1), ("one-device-fits", 1), ("mesh", 0),
    ("one-device-too-small", 0)])
def test_resident_corpus_placement(where, device, monkeypatch, corpus8):
    """From its second round a single-device vmap engine gathers its
    cohorts on the device when the corpus, the cohort and the larger of
    the round program's and the gather's temporaries fit in what the
    device reports free (or it reports no limit); a mesh engine, or a
    device with room for only the corpus and the cohort, keeps the host
    fill.  Reading the round program's temporaries costs no trace."""
    from repro.api import Federation
    from repro.data.federated_split import row_nbytes
    spec = tiny_spec(mesh={"data": 1}) if where == "mesh" else tiny_spec()
    fed = Federation.from_spec(spec, corpus=corpus8)
    eng = fed.engine
    k_e_p = eng.scheduler.clients_per_round * eng._e_max * eng.batch_size
    row = row_nbytes(eng.clients[0].data)
    used = 1 << 30
    room = {"one-device-fits": 1 << 30,
            # the rows and the cohort (its bow, doc_mask and rng), no
            # temporaries
            "one-device-too-small":
            row * (sum(c.num_docs for c in eng.clients) + 1)
            + k_e_p * (eng.clients[0].data["bow"].itemsize
                       * eng.clients[0].data["bow"].shape[1] + 4)
            + k_e_p // eng.batch_size * 8}.get(where)
    if room is not None:
        monkeypatch.setattr(
            type(jax.devices()[0]), "memory_stats",
            lambda self: {"bytes_limit": used + room, "bytes_in_use": used})
    seen = []
    real = spans.span

    def span(name, **counts):
        if name == spans.GATHER:
            seen.append(counts["device"])
        return real(name, **counts)
    monkeypatch.setattr(spans, "span", span)
    fed.run(rounds=3)
    assert seen == [0, device, device]
    assert set(eng.trace_counts.values()) == {1}


def test_the_model_scopes_reach_the_round_graph():
    """The held-expert layer and MLA put their four scopes on the ops of
    a language model's loss and its gradient."""
    import dataclasses
    from repro.configs import get_config
    from repro.models import transformer as tfm
    base = get_config("moonlight-16b-a3b")
    cfg = dataclasses.replace(
        base.reduced(), moe=dataclasses.replace(base.reduced().moe,
                                                ep_size=2))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((1, 8), jnp.int32)

    def loss(p):
        return tfm.train_loss_sum(p, cfg, {"tokens": toks,
                                           "labels": toks})[0]
    hlo = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    assert set(spans.MODEL_SCOPES) <= set(spans.DEVICE_SCOPES)
    for name in spans.MODEL_SCOPES:
        assert name in hlo, name


@pytest.mark.parametrize("free, scan", [(None, 0), (4096, 1)])
def test_dispatch_counts_the_schedule(free, scan, monkeypatch, corpus8):
    """``round/dispatch`` carries ``scan``: 1 when the cohort does not fit
    the device and the round scans its clients, else 0."""
    from repro.api import Federation
    if free is not None:
        used = 1 << 30
        monkeypatch.setattr(
            type(jax.devices()[0]), "memory_stats",
            lambda self: {"bytes_limit": used + free, "bytes_in_use": used})
    seen = []
    real = spans.span

    def span(name, **counts):
        if name == spans.DISPATCH:
            seen.append(counts["scan"])
        return real(name, **counts)
    monkeypatch.setattr(spans, "span", span)
    Federation.from_spec(tiny_spec(), corpus=corpus8).run(rounds=2)
    assert seen == [scan, scan]
