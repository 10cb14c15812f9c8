#!/usr/bin/env python3
"""Drive the federation's main path once on a TPU, at ProdLDA paper widths.

    python chip_smoke.py             # one chip: train, stale, pallas, service
    python chip_smoke.py --chips 4   # only the data=4 mesh vs one-chip twins

The model is the paper's ProdLDA at its published widths (V=5000 terms,
50 topics, a 100-100 encoder; ``configs/prodlda_synthetic.py``) over a
synthetic LDA federation generated from ``--seed``; weights start from a
seeded init.  Every phase goes through the entry points a user calls
(spec -> ``Federation`` / ``FederationService`` -> engine ->
``kernels/ops.py``) and checks its own result:

  * train   — sync rounds on the fused vmap path (XLA backend), then an
              ``exec_mode="loop"`` twin from its own init: parameters
              within 1e-5, finite losses, one trace of the round graph;
  * stale   — the ``straggler_ring`` scenario: late deltas ride the
              in-graph ring buffer and are combined with staleness
              discounts, against the loop path's host-side pending list;
  * pallas  — the ``pallas-topk`` and ``pallas-secure`` scenarios at
              these widths, each within 1e-5 of its XLA-backend twin;
  * service — the ``buffered_async_net`` preset behind ``repro.net`` on
              loopback in this process: uploads until two aggregations
              have happened, then ``/v1/infer`` batches whose posterior
              rows must be finite and sum to 1;
  * mesh    — (``--chips 4`` only) ``execution.mesh={"data": 4}`` with
              L=K=8 on the XLA and Pallas backends, each within 1e-5 of
              its unsharded one-chip twin, one trace per graph.

Everything runs in this one process: a chip belongs to one process at a
time.  Compile seconds and counts come from JAX's own compile events;
the speed of a round is measured by the chip benchmark
(``benchmarks/chip/run.py``), not here.  The last line of stdout is one
JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the paper's ProdLDA widths and synthetic setup (configs/prodlda_synthetic.py)
VOCAB, TOPICS, HIDDEN = 5000, 50, 100
PAPER_DOCS_PER_NODE = 10_000
DOCS_PER_NODE = 1_000          # cut from the paper's 10,000: host time
BATCH = 64
LR = 2e-3
ROUNDS = 5                     # the first compiles; no later one may
PARITY_BOUND = 1e-5
INFER_BATCHES = 3

# XLA's own compile, and loads from the persistent cache in its place
# (trace and lowering events nest, so summing them would double-count)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class SmokeFailure(Exception):
    """A phase's result broke its check."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_tpu(chips: int):
    """The devices, or exit 1: there is no CPU fallback."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"chip_smoke: no TPU found ({e})")
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{devs[0].platform!r}); this script measures the chip "
                 "and has no CPU fallback")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX sees {len(devs)}")
    log(f"device: platform=tpu kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__}")
    return devs


class CompileClock:
    """Seconds XLA spent compiling (or loading compiled programs from the
    persistent cache) since the last :meth:`lap`, and how many backend
    compiles ran, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.events = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
            self.events += event.endswith("backend_compile_duration")

    def lap(self):
        out = (self.seconds, self.events)
        self.seconds, self.events = 0.0, 0
        return out


def paper_spec(num_clients: int, seed: int):
    from repro.api import (DataSpec, ExecutionSpec, FederationSpec,
                           ModelSpec, ScheduleSpec)
    return FederationSpec(
        name="chip-smoke",
        model=ModelSpec(vocab=VOCAB, topics=TOPICS, hidden=HIDDEN),
        data=DataSpec(num_clients=num_clients, docs_per_node=DOCS_PER_NODE,
                      val_docs_per_node=BATCH, seed=seed),
        schedule=ScheduleSpec(rounds=ROUNDS),
        execution=ExecutionSpec(exec_mode="vmap", batch_size=BATCH,
                                learning_rate=LR, seed=seed))


def run_rounds(fed, clock: CompileClock) -> dict:
    """``ROUNDS`` rounds, each ending in ``block_until_ready``; the
    compile seconds of the first, and the compiles of the rest."""
    import jax

    def one_round():
        fed.step()
        jax.block_until_ready(fed.params)

    clock.lap()
    one_round()
    compile_s, _ = clock.lap()
    for _ in range(ROUNDS - 1):
        one_round()
    return {"compile_s": compile_s, "compiles_in_window": clock.lap()[1]}


def finite_losses(fed) -> bool:
    return bool(np.isfinite([h["loss"] for h in fed.history]).all())


def run_pair(label, spec, twin_spec, corpus, clock, graph="fused_sync"):
    """The run and its reference twin, driven the same way: finite
    losses, one trace of the fused ``graph`` on each vmap side, no
    compile after the run's first round, parameters within the parity
    bound."""
    from repro.api import Federation, max_param_dev
    fed = Federation.from_spec(spec, corpus=corpus)
    stats = run_rounds(fed, clock)
    twin = Federation.from_spec(twin_spec, corpus=corpus)
    twin_stats = run_rounds(twin, clock)
    dev = max_param_dev(fed.params, twin.params)
    log(f"[{label}] run: compile_s={stats['compile_s']:.3f} "
        f"traces={fed.engine.trace_counts}")
    log(f"[{label}] twin ({twin.engine.exec_mode}, mesh "
        f"{twin.mesh_shape}): compile_s={twin_stats['compile_s']:.3f}")
    log(f"[{label}] max_param_dev={dev:.3e} (bound {PARITY_BOUND})")
    check(finite_losses(fed) and finite_losses(twin),
          f"{label}: non-finite loss")
    check(stats["compiles_in_window"] == 0,
          f"{label}: {stats['compiles_in_window']} compiles in the window")
    for f in (fed, twin):
        if f.engine.exec_mode == "vmap":
            check(f.engine.trace_counts == {graph: 1},
                  f"{label}: traces {f.engine.trace_counts}")
    check(dev <= PARITY_BOUND,
          f"{label}: max_param_dev {dev:.3e} > {PARITY_BOUND}")
    return fed


def phase_train(seed, clock):
    from repro.api import build_corpus, spec_replace
    spec = paper_spec(5, seed)
    log(f"[train] V={VOCAB} topics={TOPICS} hidden={HIDDEN}-{HIDDEN} "
        f"L=K=5 E=1 batch={BATCH} lr={LR}; docs_per_node={DOCS_PER_NODE} "
        f"(cut from the paper's {PAPER_DOCS_PER_NODE}); the spec's "
        "ModelConfig drops ProdLDA's dropout 0.2 and learned priors "
        "(FederationSpec.to_model_config)")
    t0 = time.perf_counter()
    corpus = build_corpus(spec)
    log(f"[train] corpus generated in {time.perf_counter() - t0:.3f}s")
    run_pair("train vmap vs loop", spec,
             spec_replace(spec, {"execution.exec_mode": "loop"}),
             corpus, clock)
    return spec, corpus


def phase_stale(spec, corpus, clock):
    from repro.api import scenario_spec, spec_replace
    sspec = scenario_spec("straggler_ring", base=spec)
    sch = sspec.schedule
    fed = run_pair("stale vmap vs loop", sspec,
                   spec_replace(sspec, {"execution.exec_mode": "loop"}),
                   corpus, clock, graph="fused_stale")
    log(f"[stale] straggler_prob={sch.straggler_prob} "
        f"max_staleness={sch.max_staleness} "
        f"staleness_decay={sch.staleness_decay}; per round "
        f"(arrived, in_flight)="
        f"{[(h['arrived'], h['in_flight']) for h in fed.history]}")
    check(any(h["in_flight"] > 0 for h in fed.history[:-1]),
          "stale: no delta was in flight before the last round")


def phase_pallas(spec, corpus, clock):
    from repro.api import scenario_spec, spec_replace
    for name in ("pallas-topk", "pallas-secure"):
        pspec = scenario_spec(name, base=spec)
        run_pair(f"{name} vs xla", pspec,
                 spec_replace(pspec, {"execution.kernel_backend": "xla"}),
                 corpus, clock)


def phase_service(spec, corpus, clock):
    from repro.api import scenario_spec
    from repro.net import BackgroundServer, ServiceClient
    from repro.serve import REJECT_REASONS, FederationService
    sspec = scenario_spec("buffered_async_net", base=spec)
    n = sspec.data.num_clients
    service = FederationService.from_spec(sspec, corpus=corpus)
    clock.lap()
    with BackgroundServer(service) as bg:
        client = ServiceClient(sspec, bg.host, bg.port, corpus=corpus)
        try:
            receipts = []
            for c in range(n):
                bv, delta, w = client.client_update(c)
                receipts.append(client.submit(c, delta, w, base_version=bv))
            status = client.status()
            rng = np.random.default_rng(0)
            for i in range(INFER_BATCHES):
                bow = corpus.node_bows[i % n][rng.choice(
                    len(corpus.node_bows[i % n]), BATCH, replace=False)]
                theta = client.infer(bow)
                check(theta.shape == (BATCH, TOPICS),
                      f"infer: theta shape {theta.shape}")
                check(bool(np.isfinite(theta).all()), "infer: non-finite")
                err = float(np.abs(theta.sum(axis=1) - 1.0).max())
                check(err <= 1e-5, f"infer: row sums off by {err:.2e}")
        finally:
            client.close()
    compile_s, _ = clock.lap()
    reasons = {r["reason"] for r in receipts if r["reason"] is not None}
    log(f"[service] buffer={sspec.resolved_buffer_size} uploads={n} "
        f"accepted={sum(r['accepted'] for r in receipts)} "
        f"aggregations={status['aggregations']} "
        f"version={status['version']} rejections={status['rejections']} "
        f"compile_s={compile_s:.3f} infer batches={INFER_BATCHES} "
        f"(batch {BATCH})")
    check(status["aggregations"] >= 2,
          f"service: {status['aggregations']} aggregations")
    check(status["version"] >= 2, f"service: version {status['version']}")
    check(reasons <= set(REJECT_REASONS),
          f"service: unnamed rejection reasons {reasons}")


def phase_mesh(seed, clock):
    from repro.api import build_corpus, spec_replace
    spec = paper_spec(8, seed)
    corpus = build_corpus(spec)
    log(f"[mesh] data=4 over L=K=8 at V={VOCAB} topics={TOPICS} "
        f"hidden={HIDDEN}-{HIDDEN}, each backend against its unsharded "
        "one-chip twin")
    for kb in ("xla", "pallas"):
        mspec = spec_replace(spec, {"execution.kernel_backend": kb,
                                    "execution.mesh": {"data": 4}})
        twin_spec = spec_replace(mspec, {"execution.mesh": None})
        fed = run_pair(f"mesh data=4 {kb} vs one chip", mspec, twin_spec,
                       corpus, clock)
        check(fed.mesh_shape == {"data": 4},
              f"mesh {kb}: mesh_shape {fed.mesh_shape}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="run the federation's main path once on the chip "
                    "(module docstring)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the data=4 mesh phase and its "
                         "one-chip twins")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    failed = []

    def run(name, fn, *fn_args):
        t0 = time.perf_counter()
        try:
            return fn(*fn_args)
        except Exception as e:             # report, then fail the run
            failed.append(name)
            log(f"[{name}] FAILED: {type(e).__name__}: {e}")
            if not isinstance(e, SmokeFailure):
                traceback.print_exc()
            return None
        finally:
            log(f"[{name}] phase wall {time.perf_counter() - t0:.3f}s")

    if args.chips == 4:
        run("mesh", phase_mesh, args.seed, clock)
    else:
        base = run("train", phase_train, args.seed, clock)
        if base is not None:               # later phases reuse its data
            run("stale", phase_stale, *base, clock)
            run("pallas", phase_pallas, *base, clock)
            run("service", phase_service, *base, clock)
    if failed:
        log(f"chip_smoke: failed phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
