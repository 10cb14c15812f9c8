"""Synchronous cross-silo rounds of a published language model, cut to
one chip's share, through ``Federation.step()``.

As ``rounds.py``: set-up builds one ``Federation`` from the
configuration and the mix, with the benchmark's token corpus
(``lm_corpus.py``) and weights (``reference_lm.py``), checks that the
program runs the configuration's model, and drives the mix's
``checked_rounds`` first rounds, which compile every program the window
runs and give what ``correct`` compares with the plain reference (the
loss of each round, the norm of round 1's update and of the change after
the checked rounds, leaf by leaf).  The window runs whole rounds, each
ending in ``block_until_ready`` on the parameters.

``client_updates_per_s`` is the positive-weight cohort rows of every
round completed in the window over the window's seconds.  The traced run
also reads the trace by the program's scopes (``programtrace.py``): the
device ms of each scope, its ops alone or fused with another scope's, go
to the per-layer readers under ``counters["scope_ms"]``, with the
grouped product's FLOPs and bytes
counted from the routed pairs the rounds report (``expert_tokens``).
"""
from __future__ import annotations

import gc
import time
from typing import Any, Callable, Dict, Optional

import numpy as np

import compare
import flops_lm
import lm_corpus
import programtrace
import reference_lm
import tracefile
from common import derive_seeds, host
from harness import BenchError, Outcome, check_entry, log, span


def program_spec(cfg: Dict[str, Any], tr: Dict[str, Any], seed: int):
    from repro.api import (DataSpec, ExecutionSpec, FederationSpec,
                           ModelSpec, ScheduleSpec)
    return FederationSpec(
        name=cfg["name"],
        model=ModelSpec(family="lm", arch=cfg["arch"], published=True,
                        layers=cfg["num_hidden_layers"],
                        ep_size=cfg["router_outputs"]
                        // cfg["n_routed_experts"],
                        vocab=cfg["vocab_size"], seq_len=tr["seq_len"]),
        data=DataSpec(num_clients=cfg["num_clients"],
                      docs_per_node=cfg["docs_per_client"],
                      val_docs_per_node=0, seed=seed),
        schedule=ScheduleSpec(rounds=1_000_000,
                              clients_per_round=tr["clients_per_round"],
                              local_epochs=tr["local_epochs"]),
        execution=ExecutionSpec(exec_mode="vmap",
                                batch_size=tr["batch_size"],
                                learning_rate=cfg["learning_rate"],
                                kernel_backend=tr["kernel_backend"],
                                seed=seed))


def check_model(cfg: Dict[str, Any], m) -> None:
    """The program must run the model the configuration states."""
    held = m.moe.num_experts // m.moe.ep_size
    got = {"hidden_size": m.d_model, "num_attention_heads": m.num_heads,
           "num_key_value_heads": m.num_kv_heads,
           "qk_nope_head_dim": m.head_dim, "v_head_dim": m.head_dim,
           "qk_rope_head_dim": m.mla_rope_head_dim,
           "kv_lora_rank": m.mla_kv_lora_rank,
           "q_lora_rank": m.mla_q_lora_rank or None,
           "intermediate_size": m.dense_d_ff,
           "moe_intermediate_size": m.d_ff,
           "first_k_dense_replace": m.first_k_dense,
           "num_hidden_layers": m.num_layers,
           "router_outputs": m.moe.num_experts,
           "n_routed_experts": held,
           "experts_held": list(range(m.moe.ep_rank * held,
                                      (m.moe.ep_rank + 1) * held)),
           "num_experts_per_tok": m.moe.top_k,
           "n_shared_experts": m.moe.num_shared_experts,
           "topk_method": m.moe.routing,
           "routed_scaling_factor": m.moe.routed_scaling_factor,
           "rms_norm_eps": m.norm_eps, "rope_theta": m.rope_theta,
           "tie_word_embeddings": m.tie_embeddings,
           "vocab_size": m.vocab_size,
           "precision": [m.dtype, m.param_dtype]}
    want = {k: cfg[k] for k in got}
    if got != want:
        raise BenchError(f"the program runs {got}, the configuration "
                         f"states {want}")


def build(cell, seed: int, phases=None,
          plant: Optional[Callable] = None) -> Dict[str, Any]:
    """Corpus, weights and the Federation (the spec first: a program
    without the configuration's model fails here, before any work)."""
    from repro.api import Federation
    from repro.data.lm_data import LMCorpus
    cfg, tr = cell.config, cell.traffic
    s = derive_seeds(seed)
    spec = program_spec(cfg, tr, s["program"])
    if phases:
        phases.mark("imports")
    tokens = lm_corpus.generate(
        vocab=cfg["vocab_size"], nodes=cfg["num_clients"],
        docs_per_node=cfg["docs_per_client"], seq_len=tr["seq_len"],
        seed=s["corpus"], zipf_a=cfg["corpus"]["zipf_a"])
    corpus = LMCorpus(node_tokens=list(tokens),
                      val_tokens=np.zeros((0, tr["seq_len"] + 1), np.int32),
                      vocab_size=cfg["vocab_size"], seq_len=tr["seq_len"])
    if phases:
        phases.mark("corpus")
    params0 = reference_lm.init_params(s["weights"], cfg)
    p0 = host(params0)
    if phases:
        phases.mark("init")
    fed = Federation.from_spec(spec, corpus=corpus, init_params=params0)
    del params0
    check_model(cfg, fed.model_cfg)
    undo = plant(fed) if plant is not None else None
    if phases:
        phases.mark("build")
    return {"fed": fed, "tokens": tokens, "p0": p0, "seeds": s,
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
            "local_epochs": tr["local_epochs"], "batch": tr["batch_size"],
            "lr": cfg["learning_rate"]}


def readings(cell, state, *, dtype=None) -> Dict[str, Any]:
    """The compared numbers: the program's checked rounds against the
    reference (or, with ``dtype``, against the reference run in that
    precision in the program's place)."""
    import jax.numpy as jnp
    n = cell.traffic["checked_rounds"]
    rcfg = ref_config(cell)
    tokens, p0, seed = state["tokens"], state["p0"], \
        state["seeds"]["program"]
    t0 = time.perf_counter()
    ref_p, ref_loss, grad = reference_lm.sync_rounds(
        p0, tokens, cell.config, rcfg, seed, n)
    if dtype is None:
        prog_p, prog_loss = state["snaps"], state["losses"]
    else:
        low_p, prog_loss, _ = reference_lm.sync_rounds(
            p0, tokens, cell.config, rcfg, seed, n, dtype=dtype)
        prog_p = {1: low_p[0], n: low_p[n - 1]}
    log(f"[lm_rounds] reference replay {time.perf_counter() - t0:.1f} s")
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


def scope_readings(trace_path: str) -> Dict[str, Any]:
    """Device ms a round by the labels of the program's scopes, each
    scope's ms (its ops alone or fused with another scope's), and the
    ``round/dispatch`` counts, from the traced window."""
    path = tracefile.find_xplane(trace_path)
    devices, spans, stats = programtrace.load(path)
    b = programtrace.breakdown(devices, spans, stats,
                               programtrace.hlo_ops(path))
    scopes = {sc: sum(ms for label, ms in b.device_ms.items()
                      if sc in label.split(programtrace.MIX))
              for sc in {sc for label in b.device_ms
                         for sc in label.split(programtrace.MIX)}}
    return {"device_ms": b.device_ms, "scope_ms": scopes,
            "scan": [int(s.get("scan", 0)) for s in
                     stats.get(programtrace.DISPATCH, [])]}


def run(ctx) -> Outcome:
    import jax
    cell, args = ctx.cell, ctx.args
    cfg, tr = cell.config, cell.traffic
    state = build(cell, args.seed, ctx.phases, plant=ctx.plant)
    ctx.clock.lap()
    checked_rounds(state, tr["checked_rounds"])
    ctx.phases.mark("warmup")
    compile_s, compiles = ctx.clock.lap()
    log(f"[lm_rounds] set-up {ctx.phases.marks} compile_s={compile_s:.3f} "
        f"compiles={compiles}")
    ctx.setup_done()

    fed = state["fed"]
    rounds = rows = 0
    pairs = 0.0
    trace = ctx.start_trace() if args.trace else None
    t0 = time.perf_counter()
    with span("window"):
        while True:
            with span("step", r=rounds):
                rec = fed.step()
                jax.block_until_ready(fed.params)
            rounds += 1
            rows += int(rec["participants"])
            pairs += float(sum(rec["expert_tokens"]))
            if time.perf_counter() - t0 >= args.seconds:
                break
    t1 = time.perf_counter()
    trace_path = ctx.stop_trace(trace) if trace else None
    _, window_compiles = ctx.clock.lap()
    if window_compiles:
        log(f"[lm_rounds] {window_compiles} compiles inside the window")
    peak = ctx.memory_peak()
    losses_finite = bool(np.isfinite([h["loss"] for h in fed.history]).all())
    log(f"[lm_rounds] window losses {[h['loss'] for h in fed.history]}")
    del fed, rec
    state.pop("fed")
    gc.collect()

    counters: Dict[str, Any] = {"rounds": rounds,
                                "window_compiles": window_compiles}
    if trace_path:
        scopes = scope_readings(trace_path)
        log(f"[lm_rounds] device ms a round by label {scopes['device_ms']}; "
            f"by scope {scopes['scope_ms']}; "
            f"round/dispatch scan {scopes['scan']}")
        counters["scope_ms"] = scopes["scope_ms"]
    r = readings(cell, state)
    lim = cell.limits
    checks = {k: check_entry(r[k], lim[k])
              for k in ("loss_gap", "update_norm_gap", "change_norm_gap")}
    log(f"[lm_rounds] reference detail {r['detail']}")
    if not losses_finite:
        log("[lm_rounds] a round's loss is not finite")
        checks["finite_losses"] = check_entry(1.0, 0.0)
    per_round = pairs / max(rounds, 1)
    steps = tr["clients_per_round"] * tr["local_epochs"] * tr["batch_size"]
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    counters.update(
        expert_tokens_per_round=per_round,
        flops_per_round=flops_lm.round_flops(cfg, steps, tr["seq_len"],
                                             per_round),
        expert_flops_per_round=flops_lm.expert_flops(cfg, per_round),
        expert_bytes_per_round=flops_lm.expert_bytes(
            cfg, per_round, steps * moe_layers))
    return Outcome(
        attempted=rows, failed=0,
        end_to_end={"client_updates_per_s": rows / (t1 - t0)},
        checks=checks, counters=counters,
        memory_peak_bytes=peak, trace_path=trace_path)
