"""The reader of the program's own spans and scopes
(``benchmarks/chip/programtrace.py``), on recorded and CPU traces."""
import glob
import json
import os
import re
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import programtrace
import tracefile
from repro import spans as program_spans

DATA = Path(__file__).resolve().parent / "data"


def trace_file(d) -> str:
    return glob.glob(os.path.join(str(d), "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]


def test_hlo_reader_on_a_recorded_tpu_trace():
    """The metadata plane of a TPU trace gives each program's HLO: the
    ops of ``jit_agg`` and ``jit__lambda`` carry their ``op_name`` and no
    program scope, and the ``copy-start``/``copy-done`` pair none at all;
    every op the device ran is found."""
    path = str(DATA / "small_tpu.xplane.pb")
    hlo = programtrace.hlo_ops(path)
    devices, _, _ = programtrace.load(path)
    ops, mods = devices[0]
    assert {m[3] for m in mods} == set(hlo)
    ran = {name for _, _, name in ops}
    assert ran <= {n for prog in hlo.values() for n in prog}
    by_name = {n: op for prog in hlo.values() for n, op in prog.items()}
    assert by_name["multiply_reduce_fusion"].op_name == "jit(agg)/reduce_sum"
    assert by_name["fusion"].op_name == "jit(<lambda>)/dot_general"
    assert by_name["copy-start"].op_name == by_name["copy-done"].op_name \
        == ""
    assert {by_name[n].label for n in ran} == {""}


def test_a_fusion_is_labelled_by_every_scope_it_fuses(tmp_path):
    """A fused op's own ``op_name`` names its root's scope alone; its
    label names every scope of the instructions it fuses."""
    def scoped_pair(x):
        with program_spans.scope(program_spans.LOCAL_UPDATE):
            y = jnp.sin(x) * 2
        with program_spans.scope(program_spans.AGGREGATE):
            return y + 1
    g = jax.jit(scoped_pair)
    x = jnp.arange(1000.0)
    jax.block_until_ready(g(x))
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(g(x))
    # the trace holds every live program's HLO; keep this one's
    hlo = programtrace.hlo_ops(trace_file(tmp_path))
    fused = [op for prog in hlo.values() for n, op in prog.items()
             if "fusion" in n
             and op.op_name.startswith("jit(scoped_pair)/")]
    assert [op.label for op in fused] == ["aggregate+local_update"]
    assert programtrace.scope_of(fused[0].op_name) == "aggregate"


@pytest.mark.parametrize("op_name, scope", [
    ("jit(fused_sync)/local_update/vmap(client_update)/while/body/mul",
     "local_update"),
    ("jit(f)/vmap(local_update)/while/body/add", "local_update"),
    ("jit(f)/local_update/transpose(jvp(client_update))/dot_general",
     "local_update"),
    ("jit(f)/transpose(jvp(vmap(transform/topk)))/mul", "transform/topk"),
    ("jit(f)/transform/topk/sort", "transform/topk"),
    ("jit(f)/transform/topk/transform/dp/add", "transform/dp"),
    ("jit(f)/aggregate/transpose(jvp(transform/dp))/mul", "transform/dp"),
    ("jit(fused_sync)/local_update/vmap()/while", "local_update"),
    ("jit(f)/local_update_x/mul", None),
    ("jit(f)/vmap(local_update_x)/mul", None),
    ("jit(f)/transform/topkx/mul", None),
    ("jit(f)/transform/mul", None),
    ("tstate['topk']['beta']", None),
    ("", None),
])
def test_scope_matching(op_name, scope):
    assert programtrace.scope_of(op_name) == scope


def test_scope_times_partition_the_busy_time():
    """An op nested in a labelled one (a ``while`` holding its body) is
    counted once: the innermost labelled op takes each instant, an
    unlabelled op inside a labelled one takes its label, and an instant
    with no labelled op running is ``unscoped``; all clipped to the
    window."""
    ops = [(0, 100, "local_update"),       # a while
           (10, 40, "local_update+transform/dp"),  # a mixed fusion
           (50, 60, ""),                   # unlabelled, inside the loop
           (120, 130, "transform/topk"),
           (125, 140, ""),                 # overlaps the top-k op
           (190, 260, "aggregate")]        # past the window's end
    got = programtrace.scope_times(ops, 0, 200)
    assert got == {"local_update": 70, "local_update+transform/dp": 30,
                   "transform/topk": 10, "unscoped": 10, "aggregate": 10}
    busy = tracefile.union([(a, b) for a, b, _ in ops], 0, 200)
    assert sum(got.values()) == sum(b - a for a, b in busy)


def synthetic_trace():
    """Two rounds of 1000 ns: draw, gather (with its bytes), dispatch,
    fetch; the round program (id 7) runs 500-800 after a 100 ns wait for
    the transfer, and a draw program (id 3) at 5-10."""
    sp = {n: [] for n in tracefile.SPANS + program_spans.HOST_SPANS}
    stats = {n: [] for n in program_spans.HOST_SPANS}
    ops, mods = [], []
    sp["window"] = [(0, 2000)]
    for r in (0, 1000):
        sp["step"].append((r, r + 1000))
        for name, a, b in [("round/draw", 2, 20), ("round/gather", 20, 380),
                           ("round/dispatch", 380, 400),
                           ("round/fetch", 400, 900)]:
            sp[name].append((r + a, r + b))
            stats[name].append({"bytes": 720.0} if name == "round/gather"
                               else {})
        mods += [(r + 5, r + 10, "jit_draw", 3),
                 (r + 500, r + 800, "jit_fused_sync", 7)]
        ops += [(r + 5, r + 10, "draw.1"),
                (r + 500, r + 700, "while.1"), (r + 520, r + 600, "f.1"),
                (r + 700, r + 750, "f.2"), (r + 750, r + 800, "copy.1")]
    hlo = {7: {"while.1": programtrace.HloOp("local_update", ""),
               "f.1": programtrace.HloOp("local_update+transform/dp", ""),
               "f.2": programtrace.HloOp("aggregate+transform/topk", ""),
               "copy.1": programtrace.HloOp("", "")}}
    return [(ops, mods)], sp, stats, hlo


def test_breakdown_of_a_synthetic_trace():
    b = programtrace.breakdown(*synthetic_trace())
    assert b.rounds == 2 and b.step_ms == pytest.approx(1000e-6)
    assert b.host_ms == pytest.approx({
        "round/draw": 18e-6, "round/gather": 360e-6,
        "round/dispatch": 20e-6, "round/fetch": 500e-6})
    assert b.host_share_of_step == pytest.approx(0.898)
    assert b.transfer_wait_ms == pytest.approx(100e-6)
    assert b.gather_gb_per_s == pytest.approx(720 / 360)
    assert b.device_busy_ms == pytest.approx(305e-6)
    assert b.round_program_ms == pytest.approx(300e-6)
    assert b.device_ms == pytest.approx({
        "local_update": 120e-6, "local_update+transform/dp": 80e-6,
        "aggregate+transform/topk": 50e-6, "unscoped": 50e-6})
    assert b.ops[0][:2] == ["jit_fused_sync/while.1", "local_update"]
    names = {n for n, _ in b.idle_gaps}
    assert names <= {"round/gather", "round/fetch", "round/draw", "step"}
    assert b.idle_gaps[0][0] == "round/gather"


def test_breakdown_leaves_the_device_split_out_without_the_hlo():
    devices, sp, stats, _ = synthetic_trace()
    b = programtrace.breakdown(devices, sp, stats, {})
    assert b.device_ms == {}
    assert b.round_program_ms == pytest.approx(300e-6)


def test_breakdown_of_a_recorded_tpu_trace():
    """The recorded trace has steps but no program spans or scopes: the
    host parts read 0 and its round program is all unscoped."""
    path = str(DATA / "small_tpu.xplane.pb")
    b = programtrace.read(path)
    s = tracefile.reduce(*tracefile.load(path))
    assert b.rounds == 3
    assert set(b.host_ms.values()) == {0.0}
    assert b.gather_gb_per_s is None
    assert b.round_program_ms == pytest.approx(
        1e3 * s.module_s["jit__lambda"] / 3, rel=0.01)
    assert b.device_ms == pytest.approx(
        {"unscoped": b.round_program_ms})


def test_gap_named_by_innermost_program_span():
    """Program spans nest in the benchmark's ``step``: a gap is named by
    the innermost span, program spans included."""
    sp = {n: [] for n in tracefile.SPANS + program_spans.HOST_SPANS}
    sp["window"] = [(0, 100)]
    sp["step"] = [(10, 60)]
    sp["round/draw"] = [(11, 12)]
    sp["round/gather"] = [(12, 40)]
    sp["round/fetch"] = [(45, 58)]
    assert tracefile.innermost(sp, 11.5) == "round/draw"
    assert tracefile.innermost(sp, 35) == "round/gather"
    assert tracefile.innermost(sp, 42) == "step"
    assert tracefile.innermost(sp, 50) == "round/fetch"
    assert tracefile.innermost(sp, 80) == "outside"


def tiny_federation(rounds=4):
    from repro.api import (DataSpec, ExecutionSpec, Federation,
                           FederationSpec, ModelSpec, ScheduleSpec,
                           TransformsSpec)
    return Federation.from_spec(FederationSpec(
        model=ModelSpec(vocab=300, topics=8, hidden=16),
        data=DataSpec(num_clients=6, docs_per_node=20, val_docs_per_node=0,
                      seed=1),
        schedule=ScheduleSpec(rounds=rounds, clients_per_round=4,
                              local_epochs=2),
        transforms=TransformsSpec(names=("dp", "topk"),
                                  dp_noise_multiplier=0.3,
                                  dp_clip_norm=0.05, compression_topk=0.01),
        execution=ExecutionSpec(exec_mode="vmap", batch_size=8,
                                learning_rate=1e-3)))


def test_program_spans_of_a_traced_round(tmp_path):
    """Rounds of a tiny sync federation traced on the CPU: each holds
    ``round/draw``, ``round/gather``, ``round/dispatch`` and
    ``round/fetch`` once, inside its ``step`` and in that order without
    overlap, and the gather's ``bytes`` are the stacked arrays' bytes."""
    from repro.data.federated_split import stacked_round_batches
    fed = tiny_federation()
    fed.step()                                     # compile outside
    eng = fed.engine
    stacked, _ = stacked_round_batches(
        [eng.clients[0].data] * 4, [eng.clients[0].num_docs] * 4,
        jax.random.PRNGKey(0), [0, 1, 2, 3], batch_size=8, local_epochs=2)
    nbytes = sum(a.nbytes for a in stacked.values())
    n = 2
    with jax.profiler.trace(str(tmp_path)):
        for r in range(n):
            with jax.profiler.TraceAnnotation("step", r=r):
                fed.step()
                jax.block_until_ready(fed.params)
    devices, sp, stats = programtrace.load(trace_file(tmp_path))
    assert devices == []                           # no TPU plane on a CPU
    steps = sorted(sp["step"])
    assert len(steps) == n
    for a, b in steps:
        inside = sorted((iv, name) for name in program_spans.HOST_SPANS
                        for iv in sp[name] if a <= iv[0] and iv[1] <= b)
        assert [name for _, name in inside] == list(program_spans.HOST_SPANS)
        for ((_, end), _), ((start, _), _) in zip(inside, inside[1:]):
            assert end <= start
    for name in program_spans.HOST_SPANS:
        assert len(sp[name]) == n
    assert [kw["bytes"] for kw in stats["round/gather"]] == [nbytes] * n


def test_fused_sync_carries_the_device_scopes():
    """The compiled round graph of a dp+top-k engine names each stage in
    its ops' ``op_name`` metadata, and the reader finds them."""
    from repro.data.federated_split import stacked_round_batches
    eng = tiny_federation().engine
    eng._build_vmap_fns()
    k = eng.scheduler.clients_per_round
    cohort = list(range(k))
    stacked, counts = stacked_round_batches(
        [eng.clients[c].data for c in cohort],
        [eng.clients[c].num_docs for c in cohort], jax.random.PRNGKey(0),
        cohort, batch_size=eng.batch_size, local_epochs=eng._e_max,
        pad_to=k)
    text = eng._fused_sync.lower(
        eng.params, eng.server_state, eng._tstate, stacked,
        np.full(k, eng._e_max, np.int32), counts.sum(axis=1),
        np.arange(k, dtype=np.int32), jax.random.PRNGKey(0),
        np.int32(0)).compile().as_text()
    found = {programtrace.scope_of(n)
             for n in re.findall(r'op_name="([^"]*)"', text)}
    assert {"local_update", "transform/dp", "transform/topk",
            "aggregate"} <= found


def test_main_adds_the_breakdown_to_a_traced_run(tmp_path, monkeypatch,
                                                 capsys):
    """The command runs the cell as ``run.py --trace 1`` does and reads
    the program's breakdown from the trace before it is removed."""
    import run
    d = tmp_path / "plugins" / "profile" / "1"
    d.mkdir(parents=True)
    shutil.copy(DATA / "small_tpu.xplane.pb", d / "t.xplane.pb")

    def execute(args):
        assert args.trace == 1
        s = tracefile.summarize(str(tmp_path))
        return {"correct": True, "busy_s": s.busy_s}
    monkeypatch.setattr(run, "execute", execute)
    monkeypatch.setattr(tracefile, "summarize", tracefile.summarize)
    assert programtrace.main(["--workload", "w", "--seed", "1",
                              "--seconds", "1"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["busy_s"] > 0 and out["program"]["rounds"] == 3
    assert out["program"]["device_ms"] == pytest.approx(
        {"unscoped": out["program"]["round_program_ms"]})
