"""Synchronous federated rounds through ``Federation.step()``.

Set-up builds one ``Federation`` from the configuration and the mix, with
the benchmark's corpus and weights, and drives it through the mix's
``checked_rounds`` first rounds: they compile every program the window
runs, and what they produce is what ``correct`` compares with the plain
reference (the loss of each round, the norm of round 1's update and of
the change after the checked rounds, leaf by leaf).  The window then
runs whole rounds, each ending in ``block_until_ready`` on the
parameters, until ``--seconds`` have passed.

``client_updates_per_s`` is the positive-weight cohort rows of every
round completed in the window over the window's seconds.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

import compare
import flops
import reference
from common import check_model, derive_seeds, host, make_corpus
from harness import Outcome, check_entry, log, span


def program_spec(cfg: Dict[str, Any], tr: Dict[str, Any], seed: int):
    from repro.api import (DataSpec, ExecutionSpec, FederationSpec,
                           ModelSpec, ScheduleSpec, TransformsSpec)
    names = tuple(tr["transforms"])
    return FederationSpec(
        name=cfg["name"],
        model=ModelSpec(vocab=cfg["vocab_size"], topics=cfg["num_topics"],
                        hidden=cfg["hidden"][0]),
        data=DataSpec(num_clients=cfg["num_clients"],
                      docs_per_node=cfg["docs_per_client"],
                      val_docs_per_node=cfg["val_docs_per_client"],
                      shared_topics=cfg["corpus"]["shared_topics"],
                      seed=seed),
        schedule=ScheduleSpec(rounds=1_000_000,
                              clients_per_round=tr["clients_per_round"],
                              local_epochs=tr["local_epochs"]),
        transforms=TransformsSpec(
            names=names,
            dp_noise_multiplier=tr["dp_noise_multiplier"]
            if "dp" in names else 0.0,
            dp_clip_norm=tr.get("dp_clip_norm", 1.0),
            compression_topk=tr["compression_topk"]
            if "topk" in names else 0.0),
        execution=ExecutionSpec(exec_mode="vmap",
                                batch_size=cfg["batch_size"],
                                learning_rate=cfg["learning_rate"],
                                stochastic_loss=cfg["dropout"] > 0,
                                kernel_backend=tr["kernel_backend"],
                                seed=seed))


def build(cell, seed: int, phases=None,
          plant: Optional[Callable] = None) -> Dict[str, Any]:
    """Corpus, weights and the Federation; ``plant(fed)`` breaks the
    program for the fault tests."""
    from repro.api import Federation
    if phases:
        phases.mark("imports")
    cfg, tr = cell.config, cell.traffic
    s = derive_seeds(seed)
    corpus = make_corpus(cfg, s["corpus"])
    if phases:
        phases.mark("corpus")
    params0 = reference.init_params(s["weights"], cfg["vocab_size"],
                                    cfg["num_topics"], cfg["hidden"])
    p0 = host(params0)
    if phases:
        phases.mark("init")
    fed = Federation.from_spec(program_spec(cfg, tr, s["program"]),
                               corpus=corpus, init_params=params0)
    check_model(cfg, fed.model_cfg)
    undo = plant(fed) if plant is not None else None
    if phases:
        phases.mark("build")
    return {"fed": fed, "corpus": corpus, "p0": p0, "seeds": s,
            "undo": undo}


def checked_rounds(state, n: int) -> None:
    """The first ``n`` rounds, through the window's own call; keeps the
    parameters after round 1 and after round n, and every loss."""
    import jax
    fed = state["fed"]
    snaps, losses = {}, []
    for r in range(n):
        with span("warmup", r=r):
            rec = fed.step()
            jax.block_until_ready(fed.params)
        losses.append(rec["loss"])
        if r == 0 or r == n - 1:
            snaps[r + 1] = host(fed.params)
    state.update(snaps=snaps, losses=losses)


def ref_config(cell) -> Dict[str, Any]:
    cfg, tr = cell.config, cell.traffic
    return {"num_clients": cfg["num_clients"],
            "clients_per_round": tr["clients_per_round"],
            "local_epochs": tr["local_epochs"], "batch": cfg["batch_size"],
            "lr": cfg["learning_rate"], "dropout": cfg["dropout"],
            "dp_clip_norm": tr["dp_clip_norm"],
            "dp_noise_multiplier": tr["dp_noise_multiplier"],
            "topk": tr["compression_topk"]}


def readings(cell, state, *, dtype=None) -> Dict[str, Any]:
    """The compared numbers: the program's checked rounds against the
    reference (or, with ``dtype``, against the reference run in that
    precision in the program's place)."""
    import jax.numpy as jnp
    n = cell.traffic["checked_rounds"]
    rcfg = ref_config(cell)
    corpus, p0, seed = state["corpus"], state["p0"], \
        state["seeds"]["program"]
    ref_p, ref_loss, grad = reference.sync_rounds(
        p0, corpus.node_bows, rcfg, seed, n)
    if dtype is None:
        prog_p, prog_loss = state["snaps"], state["losses"]
    else:
        low_p, prog_loss, _ = reference.sync_rounds(
            p0, corpus.node_bows, rcfg, seed, n, dtype=dtype)
        prog_p = {1: low_p[0], n: low_p[n - 1]}
    loss_gap = max(compare.rel_gap(a, b) for a, b in zip(prog_loss,
                                                         ref_loss))
    upd, upd_info = compare.leaf_norm_gap(
        compare.tree_sub(prog_p[1], p0), compare.tree_sub(ref_p[0], p0),
        grad)
    chg, chg_info = compare.leaf_norm_gap(
        compare.tree_sub(prog_p[n], p0), compare.tree_sub(ref_p[n - 1], p0),
        grad)
    return {"loss_gap": loss_gap, "update_norm_gap": upd,
            "change_norm_gap": chg,
            "detail": {"prog_loss": list(map(float, prog_loss)),
                       "ref_loss": ref_loss, "update_leaf": upd_info,
                       "change_leaf": chg_info,
                       "low_precision": None if dtype is None
                       else jnp.dtype(dtype).name}}


def run(ctx) -> Outcome:
    import jax
    cell, args = ctx.cell, ctx.args
    tr = cell.traffic
    state = build(cell, args.seed, ctx.phases, plant=ctx.plant)
    ctx.clock.lap()
    checked_rounds(state, tr["checked_rounds"])
    ctx.phases.mark("warmup")
    compile_s, compiles = ctx.clock.lap()
    log(f"[rounds] set-up {ctx.phases.marks} compile_s={compile_s:.3f} "
        f"compiles={compiles}")
    ctx.setup_done()

    fed = state["fed"]
    rounds = rows = 0
    trace = ctx.start_trace() if args.trace else None
    t0 = time.perf_counter()
    with span("window"):
        while True:
            with span("step", r=rounds):
                rec = fed.step()
                jax.block_until_ready(fed.params)
            rounds += 1
            rows += int(rec["participants"])
            if time.perf_counter() - t0 >= args.seconds:
                break
    t1 = time.perf_counter()
    trace_path = ctx.stop_trace(trace) if trace else None
    _, window_compiles = ctx.clock.lap()
    if window_compiles:
        log(f"[rounds] {window_compiles} compiles inside the window")
    peak = ctx.memory_peak()
    losses_finite = bool(np.isfinite([h["loss"] for h in fed.history]).all())
    del fed, rec
    state.pop("fed")
    gc.collect()

    r = readings(cell, state)
    lim = cell.limits
    checks = {k: check_entry(r[k], lim[k])
              for k in ("loss_gap", "update_norm_gap", "change_norm_gap")}
    log(f"[rounds] reference detail {r['detail']}")
    if not losses_finite:
        log("[rounds] a round's loss is not finite")
        checks["finite_losses"] = check_entry(1.0, 0.0)
    per_round = flops.round_flops(cell.config, tr["clients_per_round"],
                                  tr["local_epochs"],
                                  cell.config["batch_size"])
    return Outcome(
        attempted=rows, failed=0,
        end_to_end={"client_updates_per_s": rows / (t1 - t0)},
        checks=checks,
        counters={"rounds": rounds, "flops_per_round": per_round,
                  "window_compiles": window_compiles},
        memory_peak_bytes=peak, trace_path=trace_path)
