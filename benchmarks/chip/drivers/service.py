"""The FedBuff service under open-loop load, over the HTTP server.

Set-up builds one ``FederationService`` from the configuration (the
benchmark's corpus and weights), puts it behind ``repro.net``'s server
in this process, which holds the chip, and starts the load generator
(``loadgen.py``) as a child process that never imports JAX.  The
generator warms the service (uploads until two aggregations have run,
and a few infers), then sends the window's schedule: uploads at the
mix's rate, Poisson arrivals with a fixed count per window (the same
amount of work for every seed), client ids drawn Zipf(s) over the
population, a base version lagging the newest seen by 0..lag_max, the
delta one of a pool of real client deltas; and ``/v1/infer`` pages of
held-out documents at a share of that rate.

The pool's deltas are the benchmark's own: one plain SGD step of the
reference ELBO (``reference.py``) on a client's minibatch at the
initial weights.  ``correct`` replays what the service acknowledged:
the receipts give each aggregation's buffered deltas (the service
answers an upload with the version it joined and its slot; a client's
uploads arrive in the order it sent them), the reference folds them
from the initial weights, and the live model at the end must match the
fold at its version; a sample of the infer answers, drawn from the seed,
must match the reference posterior of the model version it reports (or
of one published while it was being answered).
"""
from __future__ import annotations

import pickle
import subprocess
import sys
import time
from pathlib import Path
from typing import Any, Dict, List

import numpy as np

import compare
import loadgen
import reference
from common import check_model, derive_seeds, host, make_corpus
from harness import BenchError, Outcome, check_entry, log, percentile, span

HERE = Path(__file__).resolve().parents[1]
WARM_AGGREGATIONS = 2
WARM_INFERS = 2


def program_spec(cfg: Dict[str, Any], seed: int):
    from repro.api import (DataSpec, ExecutionSpec, FederationSpec,
                           ModelSpec, ScheduleSpec)
    from repro.api.spec import ServingSpec
    return FederationSpec(
        name=cfg["name"],
        model=ModelSpec(vocab=cfg["vocab_size"], topics=cfg["num_topics"],
                        hidden=cfg["hidden"][0]),
        data=DataSpec(num_clients=cfg["num_clients"],
                      docs_per_node=cfg["docs_per_client"],
                      val_docs_per_node=cfg["val_docs_per_client"],
                      shared_topics=cfg["corpus"]["shared_topics"],
                      seed=seed),
        schedule=ScheduleSpec(rounds=1, mode="buffered_async",
                              buffer_size=cfg["buffer_size"],
                              staleness_policy=cfg["staleness_policy"],
                              max_staleness=cfg["max_staleness"]),
        execution=ExecutionSpec(exec_mode="loop",
                                batch_size=cfg["batch_size"],
                                learning_rate=cfg["learning_rate"],
                                stochastic_loss=cfg["dropout"] > 0,
                                seed=seed),
        serving=ServingSpec(host="127.0.0.1", port=0,
                            wire_precision=cfg["wire_precision"]))


def delta_pool(cfg, corpus, params0, n: int, seed: int):
    """``n`` client deltas: one SGD step of the reference ELBO on a
    client's minibatch, clients and documents drawn from ``seed``."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    clients = rng.choice(cfg["num_clients"], n, replace=False)
    bows = np.stack([corpus.node_bows[c][rng.choice(
        cfg["docs_per_client"], cfg["batch_size"], replace=False)]
        for c in clients])[:, None]                       # (n, E=1, P, V)
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 32)), n)[:, None]

    @jax.jit
    def step(p, b, k):
        return jax.vmap(lambda bb, kk: reference.local_update(
            p, bb, kk, cfg["learning_rate"], cfg["dropout"])[0])(b, k)
    with jax.default_matmul_precision("highest"):
        deltas = step(params0, jnp.asarray(bows), keys)
    deltas = host(deltas)
    return [jax.tree_util.tree_map(lambda d, i=i: d[i], deltas)
            for i in range(n)], float(cfg["batch_size"])


def schedule(tr, cfg, seconds: float, seed: int) -> List[tuple]:
    """The window's requests, sorted by due time: a fixed count of each
    kind spread uniformly (Poisson arrivals given their number)."""
    rng = np.random.default_rng([seed, 0x5EED])
    L = cfg["num_clients"]
    n_up = int(round(tr["upload_rate"] * seconds))
    n_inf = int(round(tr["upload_rate"] * tr["infer_share"] * seconds))
    ranks = np.arange(1, L + 1, dtype=np.float64) ** -tr["zipf_s"]
    who = rng.permutation(L)                  # rank -> client id
    ups = [(float(t), "upload", int(who[r]), int(lag), int(p), -1)
           for t, r, lag, p in zip(
               rng.uniform(0, seconds, n_up),
               rng.choice(L, n_up, p=ranks / ranks.sum()),
               rng.integers(0, tr["lag_max"] + 1, n_up),
               rng.integers(0, tr["delta_pool"], n_up))]
    infs = [(float(t), "infer", -1, 0, 0, int(pg))
            for t, pg in zip(rng.uniform(0, seconds, n_inf),
                             rng.integers(0, tr["pages"], n_inf))]
    return sorted(ups + infs)


def warm_schedule(cfg, tr) -> List[tuple]:
    n = WARM_AGGREGATIONS * cfg["buffer_size"]
    ups = [(0.05 * i, "upload", i, 0, i % tr["delta_pool"], -1)
           for i in range(n)]
    infs = [(0.05 * n + 0.2 * i, "infer", -1, 0, 0, i)
            for i in range(WARM_INFERS)]
    return ups + infs


class Child:
    """The load generator process and its pickle pipe."""

    def __init__(self):
        self.proc = subprocess.Popen(
            [sys.executable, str(HERE / "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)

    def send(self, msg):
        pickle.dump(msg, self.proc.stdin)
        self.proc.stdin.flush()

    def recv(self):
        return pickle.load(self.proc.stdout)

    def close(self) -> bool:
        """Ask the child to exit; return whether it had imported JAX."""
        jax_seen = False
        try:
            self.send({"op": "exit"})
            while True:                 # skip replies nobody read
                msg = self.recv()
                if msg["op"] == "exit":
                    jax_seen = msg["jax"]
                    break
        except (EOFError, OSError, pickle.UnpicklingError):
            pass
        finally:
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        return jax_seen


def traced(fn, name):
    def wrapper(*a, **kw):
        with span(name):
            return fn(*a, **kw)
    return wrapper


def aggregations_from(records, buffer_size: int, final_version: int,
                      weight: float):
    """Each aggregation's buffered deltas, replayed from the receipts:
    an upload joined the buffer that aggregated at its receipt's version,
    in its slot, and a later upload of the same client in that buffer
    took the slot over."""
    slots: Dict[int, Dict[int, Dict[str, Any]]] = {}
    for r in records:                       # in send order
        rc = r.get("receipt") if r else None
        if not rc or not rc.get("accepted"):
            continue
        v = int(rc["version"])
        slots.setdefault(v, {})[int(rc["slot"])] = {
            "pool": r["pool"], "weight": weight,
            "age": v - r["base_version"]}
    aggs = []
    for v in range(final_version):
        got = slots.get(v, {})
        if sorted(got) != list(range(buffer_size)):
            raise BenchError(f"version {v}: slots {sorted(got)} acknowledged,"
                             f" a buffer of {buffer_size} aggregated")
        aggs.append([got[s] for s in range(buffer_size)])
    return aggs


def readings(*, cfg, tr, p0, pool, weight, records, version, live, pages,
             seed, dtype=None) -> Dict[str, Any]:
    """The live model's gap to the reference fold at its version, and the
    worst gap of a sample of the infer answers to the reference
    posterior.  With ``dtype`` the reference in that precision stands in
    the program's place (the control)."""
    import jax
    import jax.numpy as jnp
    aggs = aggregations_from(records, cfg["buffer_size"], version, weight)
    versions = reference.fedbuff_fold(p0, aggs, pool)
    low = None if dtype is None else \
        reference.fedbuff_fold(p0, aggs, pool, dtype=dtype)
    final = live if low is None else low[version]
    model_gap, info = compare.leaf_dist(final, versions[version], p0)

    answered = [r for r in records
                if r and r["kind"] == "infer" and r.get("ok")]
    rng = np.random.default_rng([seed, 0xC0DE])
    n = min(len(answered), tr["infer_checks"])
    sample = [answered[i] for i in
              sorted(rng.choice(len(answered), n, replace=False))]
    post = jax.jit(reference.posterior)
    worst = 0.0 if sample else float("inf")
    for r in sample:
        page = jnp.asarray(pages[r["page"]], jnp.float32)
        v = r["version"]
        if low is None:
            got = r["theta"]
        else:
            got = np.asarray(post(reference.tmap(
                lambda x: jnp.asarray(x, dtype), low[v]),
                page.astype(dtype)), np.float32)
        best = float("inf")
        with jax.default_matmul_precision("highest"):
            for c in range(v, min(v + 3, version + 1)):
                ref = np.asarray(post(reference.tmap(jnp.asarray,
                                                     versions[c]), page))
                best = min(best, float(np.abs(got - ref).max()))
        worst = max(worst, best)
    return {"model_gap": model_gap, "infer_gap": worst,
            "detail": {"aggregations": len(aggs), "version": version,
                       "model_leaf": info, "infers_checked": len(sample)}}


def run(ctx) -> Outcome:
    from repro.net import BackgroundServer
    from repro.serve import FederationService
    child = Child()
    try:
        return _run(ctx, child, BackgroundServer, FederationService)
    finally:
        if child.close():
            raise BenchError("the load generator imported JAX")


def setup(ctx, child, BackgroundServer, FederationService):
    """Corpus, weights, delta pool, the service behind its server, and the
    generator prepared and warmed; returns the pieces the window needs."""
    cfg, tr = ctx.cell.config, ctx.cell.traffic
    ctx.phases.mark("imports")
    s = derive_seeds(ctx.args.seed)
    corpus = make_corpus(cfg, s["corpus"])
    ctx.phases.mark("corpus")
    params0 = reference.init_params(s["weights"], cfg["vocab_size"],
                                    cfg["num_topics"], cfg["hidden"])
    p0 = host(params0)
    pool, weight = delta_pool(cfg, corpus, params0, tr["delta_pool"],
                              s["corpus"] ^ 0x9E3779B9)
    val = corpus.concat_val_bows()
    pages = [val[i * tr["page_docs"]:(i + 1) * tr["page_docs"]]
             for i in range(tr["pages"])]
    child.send({"op": "prepare", "num_clients": cfg["num_clients"],
                "pool": [{"part": loadgen.serialize_tree(d),
                          "weight": weight} for d in pool],
                "pages": [p.astype(np.uint8) for p in pages]})
    ctx.phases.mark("init")
    service = FederationService.from_spec(
        program_spec(cfg, s["program"]), corpus=corpus, init_params=params0)
    check_model(cfg, service._fed.model_cfg)
    del corpus
    service.submit = traced(service.submit, "upload")
    service.infer = traced(service.infer, "infer")
    if ctx.plant is not None:
        ctx.plant(service)
    bg = BackgroundServer(service).start()
    ctx.phases.mark("build")
    if child.recv()["op"] != "prepared":
        raise BenchError("the load generator did not prepare")
    ctx.clock.lap()
    with span("warmup"):
        child.send({"op": "warmup", "host": bg.host, "port": bg.port,
                    "schedule": warm_schedule(cfg, tr), "connections": 1})
        warm = child.recv()["records"]
    bad = [r for r in warm if not r or not r.get("ok")]
    if bad:
        bg.stop()
        raise BenchError(f"warm-up requests failed: {bad[:2]}")
    ctx.phases.mark("warmup")
    compile_s, compiles = ctx.clock.lap()
    log(f"[service] set-up {ctx.phases.marks} compile_s={compile_s:.3f} "
        f"compiles={compiles}")
    return {"service": service, "bg": bg, "p0": p0, "pool": pool,
            "weight": weight, "pages": pages, "warm": warm}


def window(ctx, child, st, sched):
    """Send ``sched``; return (records, seconds, aggregations in it)."""
    service, bg = st["service"], st["bg"]
    before = service.agg_index
    t0 = time.perf_counter()
    with span("window"):
        child.send({"op": "window", "host": bg.host, "port": bg.port,
                    "schedule": sched,
                    "connections": ctx.cell.traffic["connections"]})
        records = child.recv()["records"]
    return records, time.perf_counter() - t0, service.agg_index - before


def tails(sched, records):
    """Latency from due to reply per kind (a failed request counts as
    infinitely late), how late each was sent, and the failures."""
    lat = {"upload": [], "infer": []}
    lag, failed = [], 0
    for req, r in zip(sched, records):
        if r is not None and "sent" in r:
            lag.append(r["sent"] - r["due"])
        if r is None or "recv" not in r or not r.get("ok"):
            failed += 1
            lat[req[1]].append(float("inf"))
        else:
            lat[req[1]].append(r["recv"] - r["due"])
    return lat, lag, failed


def _run(ctx, child, BackgroundServer, FederationService) -> Outcome:
    import gc
    args, cfg, tr = ctx.args, ctx.cell.config, ctx.cell.traffic
    st = setup(ctx, child, BackgroundServer, FederationService)
    try:
        sched = schedule(tr, cfg, args.seconds, args.seed)
        ctx.setup_done()
        trace = ctx.start_trace() if args.trace else None
        records, seconds, aggregations = window(ctx, child, st, sched)
        trace_path = ctx.stop_trace(trace) if trace else None
        _, window_compiles = ctx.clock.lap()
        if window_compiles:
            log(f"[service] {window_compiles} compiles inside the window")
        version, live = st["service"].fetch_model()
        live = host(live)
        peak = ctx.memory_peak()
    finally:
        st["bg"].stop()
    st.pop("service")
    gc.collect()

    lat, lag, failed = tails(sched, records)
    if failed:
        log(f"[service] {failed} of {len(sched)} requests failed: "
            f"{[r for r in records if r is None or not r.get('ok')][:3]}")
    r = readings(cfg=cfg, tr=tr, p0=st["p0"], pool=st["pool"],
                 weight=st["weight"], records=st["warm"] + records,
                 version=version, live=live, pages=st["pages"],
                 seed=args.seed)
    log(f"[service] reference detail {r['detail']}")
    checks = {k: check_entry(r[k], ctx.cell.limits[k])
              for k in ("model_gap", "infer_gap")}
    return Outcome(
        attempted=len(sched), failed=failed,
        end_to_end={"upload_p95_ms": 1e3 * percentile(lat["upload"], 95),
                    "infer_p95_ms": 1e3 * percentile(lat["infer"], 95)},
        checks=checks,
        counters={"aggregations": aggregations,
                  "generator_lag_p95_ms": 1e3 * percentile(lag, 95),
                  "window_compiles": window_compiles,
                  "window_s": seconds},
        memory_peak_bytes=peak, trace_path=trace_path)
