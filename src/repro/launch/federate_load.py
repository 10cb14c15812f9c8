"""Multi-process load driver for the federation wire (repro.net).

Boots a :class:`repro.serve.FederationService` behind the asyncio
front-end (:func:`repro.net.server.run_server`) in its own process,
then hammers it with N client processes, each a
:class:`repro.net.client.ServiceClient` owning a DISJOINT shard of the
client population and replaying the same deterministic
permutation-sweep schedule as ``run_traffic`` (seeded per process, so
a rerun is the same schedule; the interleaving across processes is the
one genuinely concurrent ingredient).  The parent collects per-request
latencies and reduces them to the latency-under-load cell
(p50/p95/p99 upload + infer RTT, aggregations/s, the server's
authoritative rejection totals) that ``benchmarks/bench_load.py``
publishes as ``load_results`` and ``benchmarks/ci_gate.py`` gates.

Two regimes:

* ``run_load`` — the concurrent measurement (>= 4 processes in CI).
* ``run_anchor`` — the sync-equivalence anchor OVER THE WIRE: M=K,
  staleness 0, in-order sequential uploads from one client process;
  the final ``GET /v1/model`` params must match the sync twin's
  ``Federation.run()`` within 1e-5 (DESIGN.md §6 — the same anchor the
  in-process tests pin, now crossing encode → TCP → decode).  Server,
  client and twin all run on the CPU backend, so the comparison is of
  like with like.

One process per accelerator: the parent never initializes a JAX
backend (it only spawns, collects, and talks plain HTTP to the
server); the load cell's server is the only process on the default
backend, and every client process — a stand-in for a remote device —
is spawned with ``JAX_PLATFORMS=cpu``.

Usage:

    PYTHONPATH=src python -m repro.launch.federate_load \\
        --procs 4 --num-clients 8 --sweeps 2 --buffer-size 2 \\
        --max-staleness 4 --out experiments/load.json

Upload latency is the ``POST /v1/upload`` round trip (encode + socket
+ decode + receipt) — local jax compute is deliberately excluded, the
SLO is the wire.
"""
from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import sys
import time
from typing import Any, Dict, List

import numpy as np

SERVER_BOOT_TIMEOUT_S = 300
CLIENT_JOIN_TIMEOUT_S = 900


# ---------------------------------------------------------------------------
# subprocess entry points (top-level: spawn pickles them by name)
# ---------------------------------------------------------------------------
def _server_main(spec_dict: Dict[str, Any], conn) -> None:
    """Server process: build the service from the spec dict and serve
    until a wire-side shutdown; the bound (host, port) goes back first."""
    from repro.api.spec import FederationSpec
    from repro.launch.compile_cache import enable_compile_cache
    from repro.net.server import run_server
    from repro.serve import FederationService

    enable_compile_cache()
    spec = FederationSpec.from_dict(spec_dict)
    service = FederationService.from_spec(spec)
    run_server(service, on_bound=lambda h, p: conn.send((h, p)))


def _client_main(spec_dict: Dict[str, Any], host: str, port: int,
                 client_ids: List[int], sweeps: int, seed: int,
                 infer_every: int, infer_batch: int, conn) -> None:
    """Client process: replay ``sweeps`` permutation passes over its
    shard (the `run_traffic` schedule shape), timing each wire call."""
    from repro.api.spec import FederationSpec
    from repro.net.client import ServiceClient

    spec = FederationSpec.from_dict(spec_dict)
    client = ServiceClient(spec, host, port)
    rng = np.random.default_rng([0xFED10, int(seed)])
    vocab = spec.model.vocab
    lm = spec.model.family == "lm"
    upload_lat: List[float] = []
    infer_lat: List[float] = []
    reasons: Dict[str, int] = {}
    uploads = accepted = step = 0
    try:
        for _sweep in range(int(sweeps)):
            for c in rng.permutation(client_ids):
                step += 1
                bv, delta, w = client.client_update(int(c))
                t0 = time.perf_counter()
                receipt = client.submit(int(c), delta, w, base_version=bv)
                upload_lat.append(time.perf_counter() - t0)
                uploads += 1
                accepted += int(receipt["accepted"])
                if receipt["reason"]:
                    reasons[receipt["reason"]] = \
                        reasons.get(receipt["reason"], 0) + 1
                if infer_every and step % int(infer_every) == 0:
                    t0 = time.perf_counter()
                    if lm:
                        client.generate(
                            rng.integers(0, vocab, (infer_batch, 8))
                            .astype(np.int32), max_new=8)
                    else:
                        client.infer(rng.poisson(1.0, (infer_batch, vocab))
                                     .astype(np.float32))
                    infer_lat.append(time.perf_counter() - t0)
        conn.send({"ok": True, "uploads": uploads, "accepted": accepted,
                   "receipt_reasons": reasons, "upload_lat": upload_lat,
                   "infer_lat": infer_lat})
    except Exception as e:              # surfaced by the parent
        conn.send({"ok": False, "error": f"{type(e).__name__}: {e}"})
        raise
    finally:
        client.close()


def _anchor_client_main(spec_dict: Dict[str, Any], host: str, port: int,
                        sweeps: int, conn) -> None:
    """Anchor client process: in-order uploads over the wire, then the
    sync twin's ``Federation.run()`` on this same (CPU) backend and the
    deviation of the wire model from it."""
    from repro.api.federation import Federation, max_param_dev
    from repro.api.spec import FederationSpec
    from repro.net.client import ServiceClient
    from repro.serve import sync_twin_spec

    spec = FederationSpec.from_dict(spec_dict)
    client = ServiceClient(spec, host, port)
    try:
        upload_lat: List[float] = []
        accepted = 0
        for _sweep in range(int(sweeps)):
            for c in range(spec.data.num_clients):
                bv, delta, w = client.client_update(c)
                t0 = time.perf_counter()
                receipt = client.submit(c, delta, w, base_version=bv)
                upload_lat.append(time.perf_counter() - t0)
                accepted += int(receipt["accepted"])
        version, wire_params = client.fetch_model()
        twin = Federation.from_spec(sync_twin_spec(spec))
        twin.run()
        conn.send({"ok": True, "accepted": accepted, "version": version,
                   "upload_lat": upload_lat,
                   "final_param_dev": float(max_param_dev(
                       twin.engine.params, wire_params))})
    except Exception as e:              # surfaced by the parent
        conn.send({"ok": False, "error": f"{type(e).__name__}: {e}"})
        raise
    finally:
        client.close()


# ---------------------------------------------------------------------------
# parent-side drivers (no JAX backend here: spawn, collect, plain HTTP)
# ---------------------------------------------------------------------------
def _spawn(ctx, target, args, *, cpu: bool):
    """Start ``target(*args, child_conn)`` in a spawned process; ``cpu``
    spawns it with ``JAX_PLATFORMS=cpu`` in its environment, so it never
    contends for the accelerator (the parent's own environment is
    restored).  Returns ``(process, parent_conn)``."""
    parent_conn, child_conn = ctx.Pipe()
    proc = ctx.Process(target=target, args=(*args, child_conn), daemon=True)
    saved = os.environ.get("JAX_PLATFORMS")
    if cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        proc.start()
    finally:
        if saved is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = saved
    return proc, parent_conn


def _wire_json(host: str, port: int, method: str, path: str
               ) -> Dict[str, Any]:
    """One JSON call to the server over plain HTTP (the parent's status
    probe and shutdown — no local replica, no JAX backend)."""
    from repro.net.client import HttpClient
    http = HttpClient(host, port)
    try:
        status, resp = http.request_with_retry(method, path)
    finally:
        http.close()
    out = json.loads(resp.decode("utf-8"))
    if status != 200:
        raise RuntimeError(f"{method} {path} answered {status}: {out}")
    return out


def _collect(proc, conn, server, timeout: float) -> Dict[str, Any]:
    """A child's result, or the server torn down and an error raised."""
    proc.join(timeout)
    if not conn.poll(1):
        server.terminate()
        raise RuntimeError(f"child process pid={proc.pid} died without a "
                           f"result (exitcode {proc.exitcode})")
    r = conn.recv()
    if not r.get("ok"):
        server.terminate()
        raise RuntimeError(f"child process failed: {r.get('error')}")
    return r


def _percentiles(lat: List[float], prefix: str) -> Dict[str, float]:
    if not lat:
        return {}
    arr = np.asarray(lat, np.float64)
    return {f"{prefix}_p50_s": float(np.percentile(arr, 50)),
            f"{prefix}_p95_s": float(np.percentile(arr, 95)),
            f"{prefix}_p99_s": float(np.percentile(arr, 99))}


def _boot_server(ctx, spec_dict: Dict[str, Any], *, cpu: bool = False):
    proc, parent_conn = _spawn(ctx, _server_main, (spec_dict,), cpu=cpu)
    if not parent_conn.poll(SERVER_BOOT_TIMEOUT_S):
        proc.terminate()
        raise RuntimeError(
            f"wire server did not bind within {SERVER_BOOT_TIMEOUT_S}s")
    host, port = parent_conn.recv()
    return proc, host, port


def run_load(spec, *, procs: int, sweeps: int, infer_every: int = 4,
             infer_batch: int = 8, order_seed: int = 0) -> Dict[str, Any]:
    """The concurrent cell: ``procs`` client processes over a round-robin
    shard of the population.  Returns the ``wire-load`` stats dict."""
    if procs < 1:
        raise ValueError(f"procs must be >= 1, got {procs}")
    L = spec.data.num_clients
    if procs > L:
        raise ValueError(f"--procs {procs} exceeds data.num_clients {L}: "
                         "client processes own disjoint id shards")
    ctx = mp.get_context("spawn")       # fork is unsafe after jax init
    spec_dict = spec.to_dict()
    server, host, port = _boot_server(ctx, spec_dict)
    shards = [list(range(L))[i::procs] for i in range(procs)]
    t0 = time.perf_counter()
    workers = [
        _spawn(ctx, _client_main,
               (spec_dict, host, port, shard, sweeps, order_seed * 1000 + i,
                infer_every, infer_batch), cpu=True)
        for i, shard in enumerate(shards)]
    results = [_collect(p, conn, server, CLIENT_JOIN_TIMEOUT_S)
               for p, conn in workers]
    wall = time.perf_counter() - t0
    # authoritative server-side view, then a wire shutdown
    status = _wire_json(host, port, "GET", "/v1/status")
    _wire_json(host, port, "POST", "/v1/shutdown?drain=true")
    server.join(60)
    upload_lat = [x for r in results for x in r["upload_lat"]]
    infer_lat = [x for r in results for x in r["infer_lat"]]
    cell: Dict[str, Any] = {
        "procs": procs,
        "uploads": sum(r["uploads"] for r in results),
        "accepted": sum(r["accepted"] for r in results),
        "infer_calls": len(infer_lat),
        "aggregations": int(status["aggregations"]),
        "version": int(status["version"]),
        "rejections": dict(status["rejections"]),
        "wall_s": wall,
        "aggs_per_s": float(status["aggregations"] / wall) if wall else 0.0,
        "uploads_per_s": float(sum(r["uploads"] for r in results) / wall)
        if wall else 0.0}
    cell.update(_percentiles(upload_lat, "upload"))
    cell.update(_percentiles(infer_lat, "infer"))
    return cell


def run_anchor(spec, *, sweeps: int) -> Dict[str, Any]:
    """The anchor cell: M=K / staleness-0 / in-order uploads over the
    wire vs the sync twin's ``Federation.run()`` — ``final_param_dev``
    must stay <= 1e-5 (hard-gated)."""
    from repro.api.spec import spec_replace

    anchor_spec = spec_replace(spec, {"schedule.buffer_size": 0,
                                      "schedule.max_staleness": 0,
                                      "schedule.rounds": int(sweeps)})
    ctx = mp.get_context("spawn")
    spec_dict = anchor_spec.to_dict()
    server, host, port = _boot_server(ctx, spec_dict, cpu=True)
    proc, conn = _spawn(ctx, _anchor_client_main,
                        (spec_dict, host, port, sweeps), cpu=True)
    r = _collect(proc, conn, server, CLIENT_JOIN_TIMEOUT_S)
    status = _wire_json(host, port, "GET", "/v1/status")
    _wire_json(host, port, "POST", "/v1/shutdown?drain=false")
    server.join(60)
    cell: Dict[str, Any] = {
        "final_param_dev": r["final_param_dev"],
        "uploads": sweeps * anchor_spec.data.num_clients,
        "accepted": r["accepted"],
        "aggregations": int(status["aggregations"]),
        "version": int(r["version"]),
        "rejections": dict(status["rejections"])}
    cell.update(_percentiles(r["upload_lat"], "upload"))
    return cell


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def spec_from_args(args):
    from repro.api.spec import (DataSpec, ExecutionSpec, FederationSpec,
                                ModelSpec, ScheduleSpec, ServingSpec)
    return FederationSpec(
        name="federate-load",
        model=ModelSpec(vocab=args.vocab, topics=args.topics,
                        hidden=args.hidden),
        data=DataSpec(num_clients=args.num_clients,
                      docs_per_node=args.docs_per_node,
                      val_docs_per_node=args.val_docs),
        schedule=ScheduleSpec(mode="buffered_async",
                              buffer_size=args.buffer_size,
                              max_staleness=args.max_staleness,
                              staleness_policy=args.staleness_policy),
        execution=ExecutionSpec(exec_mode="loop", batch_size=args.batch,
                                learning_rate=args.lr, seed=args.seed),
        serving=ServingSpec(host=args.host, port=args.port,
                            wire_precision=args.wire_precision))


def main(argv=None):
    from repro.api.spec import STALENESS_POLICIES, WIRE_PRECISIONS
    ap = argparse.ArgumentParser(
        description="multi-process load driver for the federation wire "
                    "(module docstring; docs/serving.md)",
        allow_abbrev=False)
    ap.add_argument("--procs", type=int, default=4,
                    help="client processes (>= 4 for the CI SLO cell)")
    ap.add_argument("--sweeps", type=int, default=2,
                    help="passes over each process's client shard")
    ap.add_argument("--vocab", type=int, default=64)
    ap.add_argument("--topics", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=16)
    ap.add_argument("--num-clients", type=int, default=8)
    ap.add_argument("--docs-per-node", type=int, default=40)
    ap.add_argument("--val-docs", type=int, default=8)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=2e-4)
    ap.add_argument("--buffer-size", type=int, default=2)
    ap.add_argument("--max-staleness", type=int, default=4)
    ap.add_argument("--staleness-policy", default="polynomial",
                    choices=STALENESS_POLICIES)
    ap.add_argument("--infer-every", type=int, default=4,
                    help="each process runs one inference batch every N "
                         "steps (0 = train-only)")
    ap.add_argument("--infer-batch", type=int, default=8)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 = ephemeral (the driver discovers the bound "
                         "port)")
    ap.add_argument("--wire-precision", default="fp32",
                    choices=WIRE_PRECISIONS)
    ap.add_argument("--anchor-sweeps", type=int, default=3,
                    help="sweeps for the wire-sync-equivalence anchor "
                         "cell (0 = skip it)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    if argv is None:
        argv = sys.argv[1:]
    args = ap.parse_args(argv)
    spec = spec_from_args(args)

    cells = []
    if args.anchor_sweeps:
        anchor = run_anchor(spec, sweeps=args.anchor_sweeps)
        anchor["cell"] = "wire-sync-equivalence"
        cells.append(anchor)
        print(f"[anchor] dev={anchor['final_param_dev']:.3e} "
              f"({anchor['accepted']}/{anchor['uploads']} uploads, "
              f"{anchor['aggregations']} aggregations)")
    load = run_load(spec, procs=args.procs, sweeps=args.sweeps,
                    infer_every=args.infer_every,
                    infer_batch=args.infer_batch, order_seed=args.seed)
    load["cell"] = "wire-load"
    cells.append(load)
    print(f"[load] {load['procs']} procs: "
          f"{load['accepted']}/{load['uploads']} uploads accepted, "
          f"{load['aggregations']} aggregations in {load['wall_s']:.1f}s "
          f"({load['aggs_per_s']:.2f}/s), "
          f"upload p50={load.get('upload_p50_s', float('nan')):.4f}s "
          f"p99={load.get('upload_p99_s', float('nan')):.4f}s, "
          f"rejections={load['rejections']}")
    payload = {"setup": {"spec": spec.to_dict(), "procs": args.procs,
                         "sweeps": args.sweeps,
                         "anchor_sweeps": args.anchor_sweeps},
               "load_results": cells}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.out}")
    return payload


if __name__ == "__main__":
    main()
