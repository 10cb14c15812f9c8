import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

DOC = """Multi-pod dry-run: prove every (arch x shape x mesh) lowers + compiles.

The two lines above MUST run before any other import (jax locks the device
count on first init) — 512 placeholder host devices back the production
meshes.  Never set that flag globally: smoke tests and benches see 1
device.

For each selected pair this driver:
  1. resolves the architecture variant for the shape (sliding-window for
     long_500k on quadratic archs),
  2. builds param/batch/cache shardings from repro.parallel rules,
  3. ``jit(step).lower(**ShapeDtypeStructs).compile()`` on the production
     mesh (16x16 single-pod, or 2x16x16 with --multi-pod),
  4. prints memory_analysis / cost_analysis and writes the roofline JSON
     consumed by benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch phi3-mini-3.8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""

import argparse
import json
import sys
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp

from repro.configs import (ARCHS, ASSIGNED_ARCHS, INPUT_SHAPES,
                           applicable_shapes, get_config, get_shape)
from repro.launch import analysis, analytic
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (input_specs, make_decode_step,
                                make_prefill_step, make_train_step,
                                resolve_arch_for_shape)
from repro.models import transformer as tfm
from repro.optim.optimizers import get_optimizer
from repro.parallel.sharding import (batch_partition_spec,
                                     cache_partition_specs,
                                     param_partition_specs, shardings_for)


def _model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N_active*D (training) or 2*N_active*D (fwd only)."""
    n = cfg.num_active_params()
    tokens = shape.global_batch * (shape.seq_len
                                   if shape.mode in ("train", "prefill")
                                   else 1)
    mult = 6 if shape.mode == "train" else 2
    return float(mult) * n * tokens


def _compile_step(cfg, shape, mesh, *, optimizer="sgd", remat="none",
                  cast_params=False):
    """Lower + compile one step for this cfg variant; return compiled."""
    specs = input_specs(cfg, shape)
    params_shape = jax.eval_shape(
        lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    p_shard = shardings_for(mesh, param_partition_specs(cfg, mesh,
                                                        params_shape))
    if shape.mode == "train":
        opt = get_optimizer(optimizer, 1e-3)
        opt_state_shape = jax.eval_shape(opt.init, params_shape)
        o_spec = param_partition_specs(cfg, mesh, opt_state_shape) \
            if jax.tree_util.tree_leaves(opt_state_shape) else opt_state_shape
        o_shard = shardings_for(mesh, o_spec)
        b_shard = shardings_for(mesh, batch_partition_spec(cfg, mesh, specs))
        step = make_train_step(cfg, opt, remat=remat,
                               cast_params=cast_params)
        jitted = jax.jit(step,
                         in_shardings=(p_shard, o_shard, b_shard, None),
                         out_shardings=(p_shard, o_shard, None),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(params_shape, opt_state_shape, specs,
                               jnp.int32(0))
    elif shape.mode == "prefill":
        b_shard = shardings_for(mesh, batch_partition_spec(cfg, mesh, specs))
        step = make_prefill_step(cfg)
        jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
        lowered = jitted.lower(params_shape, specs)
    else:  # decode
        cache_shape = specs["cache"]
        c_shard = shardings_for(mesh, cache_partition_specs(cfg, mesh,
                                                            cache_shape))
        tok_spec = specs["tokens"]
        t_shard = shardings_for(
            mesh, batch_partition_spec(cfg, mesh, {"tokens": tok_spec}))
        step = make_decode_step(cfg)
        jitted = jax.jit(step,
                         in_shardings=(p_shard, c_shard, t_shard["tokens"]),
                         out_shardings=(None, c_shard),
                         donate_argnums=(1,))
        lowered = jitted.lower(params_shape, cache_shape, tok_spec)
    return lowered.compile()


def _cost_triplet(compiled):
    """(flops, bytes, collective_bytes) per device from one compile."""
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    coll = analysis.parse_collective_bytes(compiled.as_text())
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            float(coll["total"]), coll)


def lower_pair(arch: str, shape_name: str, mesh, *, optimizer="sgd",
               remat="none", cast_params=False, mla_absorb=False,
               verbose=True, analysis_layers=True):
    """Lower + compile one (arch, shape) on ``mesh``; return the report.

    Report sources (XLA counts while-loop bodies once, so the full
    scan-over-layers program under-reports flops/bytes/collectives):
      * FULL-depth compile — the lower+compile proof and the per-device
        memory_analysis ("does it fit");
      * FLOPs + HBM bytes — the closed-form model in launch/analytic.py
        (exact for our own einsums; validated vs cost_analysis on small
        unrolled lowerings in tests/test_analytic.py);
      * collective bytes — two SHALLOW compiles (1 and 2 scan units,
        layer loop unrolled, chunk scans kept as loops: collectives live
        at layer boundaries, not inside chunk scans), extrapolated
        linearly to the real depth.
    """
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        shape = get_shape(shape_name)
        cfg = resolve_arch_for_shape(get_config(arch), shape)
        if mla_absorb:
            import dataclasses as _dc0
            cfg = _dc0.replace(cfg, mla_absorb=True)
        per_unit = 2 if (cfg.kind == "moe" and cfg.moe.moe_every > 1) else 1
        nu = cfg.num_layers // per_unit
        chips = mesh.devices.size

        t0 = time.time()
        compiled_full = _compile_step(cfg, shape, mesh, optimizer=optimizer,
                                      remat=remat, cast_params=cast_params)
        dt_full = time.time() - t0

        from repro.parallel.sharding import get_profile
        model_ways = dict(zip(mesh.axis_names,
                              mesh.devices.shape)).get("model", 1)
        param_ways = chips
        if shape.mode == "decode" and get_profile() in ("megatron", "tp"):
            # params replicate across data under these profiles' decode
            param_ways = model_ways if get_profile() == "tp" else chips
        if get_profile() == "tp":
            param_ways = model_ways
        est = analytic.estimate(cfg, shape).per_device(
            chips, param_ways=param_ways)

        if analysis_layers and nu > 2:
            import dataclasses as _dc
            t1 = time.time()
            cfg1 = _dc.replace(cfg, num_layers=per_unit, scan_layers=False)
            cfg2 = _dc.replace(cfg, num_layers=2 * per_unit,
                               scan_layers=False)
            _, _, c1, _ = _cost_triplet(
                _compile_step(cfg1, shape, mesh, optimizer=optimizer,
                              remat=remat, cast_params=cast_params))
            _, _, c2, coll2 = _cost_triplet(
                _compile_step(cfg2, shape, mesh, optimizer=optimizer,
                              remat=remat, cast_params=cast_params))
            dt_an = time.time() - t1
            coll = c1 + (c2 - c1) * (nu - 1)
            breakdown = {k: int(v * nu) for k, v in coll2.items()
                         if k != "total"}
        else:
            _, _, coll, breakdown = _cost_triplet(compiled_full)
            breakdown = dict(breakdown)
            dt_an = 0.0

        mesh_name = "x".join(str(d) for d in mesh.devices.shape)
        report = analysis.RooflineReport(
            arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
            hlo_flops=est.flops, hlo_bytes=est.bytes, collective_bytes=coll,
            collective_breakdown=breakdown,
            model_flops=_model_flops(cfg, shape),
            memory_per_device=analysis.memory_analysis_dict(compiled_full))
    if verbose:
        mem = report.memory_per_device
        print(f"  compiled full in {dt_full:.1f}s (+{dt_an:.1f}s analysis) | "
              f"per-device: args={mem.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
              f"temp={mem.get('temp_size_in_bytes', 0)/2**30:.2f}GiB")
        print(f"  flops/dev={report.hlo_flops:.3e} "
              f"bytes/dev={report.hlo_bytes:.3e} "
              f"coll_bytes/dev={report.collective_bytes:.3e}")
        print(f"  roofline: compute={report.compute_s*1e3:.2f}ms "
              f"memory={report.memory_s*1e3:.2f}ms "
              f"collective={report.collective_s*1e3:.2f}ms "
              f"-> {report.bottleneck}-bound "
              f"(useful-flops ratio {report.useful_flops_ratio:.3f})")
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=DOC)
    ap.add_argument("--arch", default=None, choices=sorted(ARCHS))
    ap.add_argument("--shape", default=None, choices=sorted(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true",
                    help="every assigned (arch x applicable shape)")
    ap.add_argument("--multi-pod", action="store_true",
                    help="2x16x16 (512 chips) instead of 16x16")
    ap.add_argument("--optimizer", default="sgd")
    ap.add_argument("--remat", default="none",
                    choices=["none", "full", "dots", "layer"])
    ap.add_argument("--mla-absorb", action="store_true",
                    help="MLA decode weight absorption (perf pair C)")
    ap.add_argument("--cast-params", action="store_true",
                    help="bf16 parameter all-gathers (mixed precision)")
    ap.add_argument("--profile", default="megatron",
                    choices=["megatron", "fsdp", "tp"],
                    help="sharding profile (EXPERIMENTS.md §Perf)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    from repro.parallel.sharding import set_profile
    set_profile(args.profile)
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    mesh_name = "x".join(str(d) for d in mesh.devices.shape)
    os.makedirs(args.out, exist_ok=True)

    if args.all:
        pairs = [(a, s) for a in ASSIGNED_ARCHS
                 for s in applicable_shapes(get_config(a))]
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        pairs = [(args.arch, args.shape)]

    failures = []
    for arch, shape in pairs:
        tag = f"{arch}__{shape}__{mesh_name}"
        if args.optimizer != "sgd" or args.remat != "none" \
                or args.profile != "megatron" or args.cast_params \
                or args.mla_absorb:
            tag += f"__{args.optimizer}_{args.remat}_{args.profile}" \
                + ("_bf16agg" if args.cast_params else "") \
                + ("_mlaabsorb" if args.mla_absorb else "")
        path = os.path.join(args.out, tag + ".json")
        if args.skip_existing and os.path.exists(path):
            print(f"[skip] {tag}")
            continue
        print(f"[dryrun] {arch} x {shape} on {mesh_name} "
              f"({mesh.devices.size} chips)")
        try:
            report = lower_pair(arch, shape, mesh,
                                optimizer=args.optimizer, remat=args.remat,
                                cast_params=args.cast_params,
                                mla_absorb=args.mla_absorb)
            with open(path, "w") as f:
                json.dump(report.to_dict(), f, indent=2)
        except Exception as e:  # noqa: BLE001 — record, keep sweeping
            failures.append((arch, shape, repr(e)))
            print(f"  FAILED: {e}")
            traceback.print_exc()
    print(f"\n{len(pairs) - len(failures)}/{len(pairs)} combinations "
          f"lowered+compiled on {mesh_name}")
    if failures:
        for a, s, e in failures:
            print(f"  FAIL {a} x {s}: {e}")
        sys.exit(1)


if __name__ == "__main__":
    main()
