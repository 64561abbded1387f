import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds abstract (ShapeDtypeStruct, zero-allocation)
parameters/optimizer state/inputs with their production shardings, lowers
the jitted step, compiles it for the 16x16 (single-pod) and 2x16x16
(multi-pod) meshes, and records:

  * ``memory_analysis``  — per-device buffer footprint (proves it fits)
  * ``cost_analysis``    — per-device HLO FLOPs / bytes (roofline inputs)
  * collective bytes by kind (parsed from compiled HLO; roofline input)

Artifacts go to ``benchmarks/artifacts/dryrun/<cell>.json`` and are read
by ``benchmarks/roofline.py`` and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh both]
"""

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_NAMES, SHAPES, applicable, get_config, \
    input_specs
from repro.core.hlo_inspect import (collective_bytes_by_stride,
                                    loop_aware_analysis, parse_hlo)
from repro.core import telemetry
from repro.core.autotune import autotune_stats
from repro.core.comm import unified_stats
from repro.core.plan import plan_cache_entries, plan_cache_stats
from repro.launch.mesh import make_production_mesh
from repro.models import build_model, make_serve_step, make_train_step
from repro.models.common import abstract_params
from repro.models.transformer import cache_logical_axes
from repro.optim import AdamW, AdamWConfig, cosine_with_warmup
from repro.parallel.sharding import ShardingRules, resolve_spec

ARTIFACTS = Path(__file__).resolve().parents[3] / "benchmarks" / \
    "artifacts" / "dryrun"


def _sharded_sds(shape, dtype, logical, mesh, rules):
    sh = NamedSharding(mesh, resolve_spec(shape, logical, mesh, rules))
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)


def _abstract_opt_state(p_abs):
    mu = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                       sharding=s.sharding), p_abs)
    return {"mu": mu, "nu": mu,
            "step": jax.ShapeDtypeStruct((), jnp.int32)}


def _abstract_batch(cfg, shape_cell, mesh, rules):
    specs = input_specs(cfg, shape_cell)
    out = {}
    for k, v in specs.items():
        if not hasattr(v, "shape"):
            continue
        logical = ("batch",) + (None,) * (len(v.shape) - 1)
        out[k] = _sharded_sds(v.shape, v.dtype, logical, mesh, rules)
    return out


def _abstract_caches(model, cfg, B, W, mesh, rules):
    shapes = jax.eval_shape(lambda: model.init_caches(B, W))
    logical = cache_logical_axes(cfg) if not cfg.encoder_layers else None
    if logical is None:
        # enc-dec: states {k,v,slot_pos} stacked over decoder layers
        kv = (None, "batch", "kv_heads", "seq_sp", None)
        logical = {"states": {"k": kv, "v": kv,
                              "slot_pos": (None, "batch", "seq_sp")},
                   "pos": ("batch",)}
    return jax.tree.map(
        lambda s, ax: _sharded_sds(s.shape, s.dtype, ax, mesh, rules),
        shapes, logical,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct) or
        (isinstance(x, tuple) and all(isinstance(a, (str, type(None)))
                                      for a in x)))


def _parse_override(kv: str):
    k, v = kv.split("=", 1)
    if v in ("true", "True", "false", "False"):
        v = v in ("true", "True")
    elif v == "none":
        v = None
    else:
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                pass
    return k, v


def apply_overrides(cfg, rules, overrides):
    """``--set key=value`` config overrides; ``rules.<logical>=axis1+axis2``
    (or ``rules.<logical>=`` for replicated) rewires the sharding rules."""
    rule_kw, cfg_kw = {}, {}
    for kv in overrides or ():
        k, v = _parse_override(kv)
        if k.startswith("rules."):
            axes = tuple(a for a in str(v or "").split("+") if a)
            rule_kw[k[len("rules."):]] = axes
        else:
            cfg_kw[k] = v
    if cfg_kw:
        cfg = cfg.replace(**cfg_kw)
    if rule_kw:
        rules = (rules or ShardingRules()).override(**rule_kw)
    return cfg, rules


def build_lowered(arch: str, shape_name: str, mesh_kind: str,
                  rules: ShardingRules | None = None, overrides=None):
    """Lower one cell; returns (cfg, model, lowered) or raises.
    Shared by the dry-run driver and benchmarks.dissect."""
    cfg = get_config(arch)
    cfg, rules = apply_overrides(cfg, rules, overrides)
    shape_cell = SHAPES[shape_name]
    ok, reason = applicable(cfg, shape_cell)
    if not ok:
        raise ValueError(f"skipped: {reason}")
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rules = rules or ShardingRules()
    model = build_model(cfg)
    p_abs = abstract_params(model.specs(), cfg.pdtype, mesh, rules)
    if shape_cell.kind == "train":
        opt = AdamW(AdamWConfig(lr=cosine_with_warmup(3e-4, 100, 10000)))
        step = make_train_step(model, opt, mesh, rules)
        o_abs = _abstract_opt_state(p_abs)
        b_abs = _abstract_batch(cfg, shape_cell, mesh, rules)
        return cfg, model, jax.jit(step).lower(p_abs, o_abs, b_abs)
    if shape_cell.kind == "prefill":
        from repro.models.model_api import make_prefill_fn
        prefill = make_prefill_fn(model, mesh, rules)
        b_abs = _abstract_batch(cfg, shape_cell, mesh, rules)
        args = [p_abs, b_abs["tokens"]]
        if "frontend_embeds" in b_abs:
            args.append(b_abs["frontend_embeds"])
        return cfg, model, jax.jit(prefill).lower(*args)
    spec = input_specs(cfg, shape_cell)
    B, W = spec["batch"], spec["cache_len"]
    serve = make_serve_step(model, mesh, rules)
    c_abs = _abstract_caches(model, cfg, B, W, mesh, rules)
    t_abs = _sharded_sds((B, 1), jnp.int32, ("batch", None), mesh, rules)
    args = [p_abs, c_abs, t_abs]
    if cfg.encoder_layers:
        m_abs = _sharded_sds((B, cfg.n_frontend_tokens, cfg.d_model),
                             cfg.cdtype, ("batch", None, None), mesh,
                             rules)
        args.append(m_abs)
    return cfg, model, jax.jit(serve).lower(*args)


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             rules: ShardingRules | None = None, verbose=True,
             overrides=None):
    cfg = get_config(arch)
    cfg, rules = apply_overrides(cfg, rules, overrides)
    shape_cell = SHAPES[shape_name]
    ok, reason = applicable(cfg, shape_cell)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rules = rules or ShardingRules()
    model = build_model(cfg)
    t0 = time.time()
    plans_before = {id(pl) for pl in plan_cache_entries()}
    autotune_before = autotune_stats()

    p_abs = abstract_params(model.specs(), cfg.pdtype, mesh, rules)

    if shape_cell.kind == "train":
        opt = AdamW(AdamWConfig(lr=cosine_with_warmup(3e-4, 100, 10000)))
        step = make_train_step(model, opt, mesh, rules)
        o_abs = _abstract_opt_state(p_abs)
        b_abs = _abstract_batch(cfg, shape_cell, mesh, rules)
        lowered = jax.jit(step).lower(p_abs, o_abs, b_abs)
    elif shape_cell.kind == "prefill":
        from repro.models.model_api import make_prefill_fn
        prefill = make_prefill_fn(model, mesh, rules)
        b_abs = _abstract_batch(cfg, shape_cell, mesh, rules)
        args = [p_abs, b_abs["tokens"]]
        if "frontend_embeds" in b_abs:
            args.append(b_abs["frontend_embeds"])
        lowered = jax.jit(prefill).lower(*args)
    else:  # decode
        spec = input_specs(cfg, shape_cell)
        B, W = spec["batch"], spec["cache_len"]
        serve = make_serve_step(model, mesh, rules)
        c_abs = _abstract_caches(model, cfg, B, W, mesh, rules)
        t_abs = _sharded_sds((B, 1), jnp.int32, ("batch", None), mesh,
                             rules)
        args = [p_abs, c_abs, t_abs]
        if cfg.encoder_layers:
            m_abs = _sharded_sds((B, cfg.n_frontend_tokens, cfg.d_model),
                                 cfg.cdtype, ("batch", None, None), mesh,
                                 rules)
            args.append(m_abs)
        lowered = jax.jit(serve).lower(*args)

    t_lower = time.time() - t0
    with telemetry.get_tracer().span("dryrun.compile", cat="dryrun",
                                     arch=arch, shape=shape_name,
                                     mesh=mesh_kind):
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    text = compiled.as_text()
    rep = parse_hlo(text)
    # Loop-aware accounting: while (scan) bodies weighted by trip count —
    # XLA's cost analysis counts them once, understating a 64-layer model
    # by ~64x.  See core/hlo_inspect.loop_aware_analysis.
    la = loop_aware_analysis(text)
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "status": "ok",
        "n_devices": mesh.devices.size,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops_per_device": la["flops"],
        "bytes_accessed_per_device": la["bytes_proxy"],
        "collective_bytes_per_device": la["collective_bytes"],
        "collective_bytes_by_kind": la["collective_bytes_by_kind"],
        "flops_per_device_loop_once": cost.get("flops", -1.0),
        "bytes_accessed_loop_once": cost.get("bytes accessed", -1.0),
        "collective_bytes_loop_once": rep.collective_bytes(),
        "collective_bytes_by_stride": {
            f"{k}@{s}": v for (k, s), v in
            collective_bytes_by_stride(text).items()},
        "collective_bytes_by_span": {
            f"{k}@{s}": v for (k, s), v in
            collective_bytes_by_stride(text, use_span=True).items()},
        "collective_op_counts": {
            k: v for k, v in rep.op_counts.items()
            if any(k.startswith(c) for c in
                   ("all-", "reduce-", "collective-", "ragged-"))},
        "memory_analysis": _mem_dict(mem),
        "params_total": model_param_count(model),
        "params_active": active_param_count(cfg),
        # A2APlans resolved while tracing this cell (MoE dispatch/combine,
        # Ulysses re-shards): the introspectable record of which backend /
        # chunk count / round order was chosen per collective —
        # describe() includes tuned_from ("measured" when a tuning-DB
        # record drove the choice, "model" for the analytic default) and
        # the measured candidate table for DB-hit plans.  Ragged plans
        # (dropless MoE, --set capacity_factor=none) appear here too with
        # kind="ragged", sparse-neighborhood plans with kind="sparse".
        "a2a_plans": (new_plans := [pl.describe()
                                    for pl in plan_cache_entries()
                                    if id(pl) not in plans_before]),
        # Per-cell bucket-occupancy stats for the ragged plans: the
        # expected useful fraction of each bucketed exchange's traffic
        # (avg_count / bucket) — the padding price dropless mode pays, the
        # quantity tuning.predict_ragged charges.
        "a2a_ragged_occupancy": [
            {"axis_names": d["axis_names"], "bucket": d["bucket"],
             "max_count": d["max_count"], "avg_count": d["avg_count"],
             "expected_occupancy": d["expected_occupancy"],
             "backend": d["backend"], "tuned_from": d["tuned_from"]}
            for d in new_plans if d.get("kind") == "ragged"],
        # Sparse-neighborhood plans (dropless MoE below the density
        # crossover): the plan-time density estimate the tuner priced
        # plus the last analyzed traffic stats (None in a dry run — the
        # compile-only path never sees a real count matrix).
        "a2a_sparse": [
            {"axis_names": d["axis_names"], "bucket": d["bucket"],
             "max_count": d["max_count"], "avg_count": d["avg_count"],
             "expected_density": d["expected_density"],
             "density": d["density"],
             "skipped_rounds": d["skipped_rounds"],
             "combined_messages": d["combined_messages"]}
            for d in new_plans if d.get("kind") == "sparse"],
        # KV-migration plans (prefill/decode disaggregated serving): the
        # serving-topology split each plan binds plus the inner exchange
        # the cost model resolved it to — what batcher.stats() reports
        # per serving comm at run time.
        "a2a_kv_migration": [
            {"axis_names": d["axis_names"], "bucket": d["bucket"],
             "max_count": d["max_count"],
             "n_prefill": d["n_prefill"], "n_decode": d["n_decode"],
             "expected_density": d["expected_density"],
             "inner_kind": d["inner_kind"], "backend": d["backend"],
             "tuned_from": d["tuned_from"]}
            for d in new_plans if d.get("kind") == "kv_migrate"],
        # Pencil-transpose plans (workloads.fft / spectral long-conv):
        # the re-shard geometry each stage resolved plus the inner dense
        # backend and the alpha-beta prediction — one entry per FFT
        # transpose stage the cell's data path built.
        "a2a_transpose": [
            {"axis_names": d["axis_names"], "dims": d["dims"],
             "in_shape": d["in_shape"], "out_shape": d["out_shape"],
             "split_axis": d["split_axis"], "concat_axis": d["concat_axis"],
             "backend": d["backend"], "pencil_bytes": d["pencil_bytes"],
             "predicted_seconds": d["predicted_seconds"],
             "tuned_from": d["tuned_from"]}
            for d in new_plans if d.get("kind") == "transpose"],
        "a2a_plan_cache": plan_cache_stats(),
        # Tuning-DB traffic for the cell (delta over the cell, like the
        # a2a_plans snapshot above): under a2a_backend="autotune"
        # db_hits/db_misses show whether measured records covered the
        # plans; timing_executions must stay 0 in a dry run (compile-only
        # paths never measure).
        "a2a_autotune": {k: v - autotune_before[k]
                         for k, v in autotune_stats().items()},
        # The TorusComm-unified view of the same state (factorization /
        # plan / autotune / tuning-DB / comm registries in one dict) —
        # what a single comm.stats() call reports at serving time.
        "a2a_comm_stats": unified_stats(),
        # Per-cell telemetry snapshot: the merged metrics registry (every
        # registered stats provider under its namespace), tracer state,
        # and the measured-vs-model drift summary.  In a dry run the
        # drift table is empty (compile-only paths never execute), but
        # the snapshot documents the cell's cache/plan traffic the same
        # way a production process would export it.
        "a2a_telemetry": {
            "metrics": telemetry.metrics_snapshot(),
            "tracer": telemetry.get_tracer().stats(),
            "drift": telemetry.drift_detector().summary(),
        },
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: "
              f"compile {t_compile:.1f}s, "
              f"flops/dev {record['flops_per_device']:.3g}, "
              f"coll B/dev {record['collective_bytes_per_device']:.3g}")
        print("  memory_analysis:", record["memory_analysis"])
    return record


def _mem_dict(mem):
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        if hasattr(mem, attr):
            out[attr] = getattr(mem, attr)
    if not out:
        out["repr"] = str(mem)
    return out


def model_param_count(model) -> int:
    from repro.models.common import param_count
    return param_count(model.specs())


def active_param_count(cfg) -> int:
    return cfg.param_count_estimate(active_only=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="recompute existing artifacts")
    ap.add_argument("--set", action="append", dest="overrides",
                    metavar="KEY=VALUE",
                    help="config override (e.g. remat_policy=dots, "
                         "a2a_backend=direct, rules.act_embed=)")
    ap.add_argument("--tag", default="",
                    help="artifact suffix for variant runs")
    args = ap.parse_args()

    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = ARCH_NAMES if args.all or not args.arch else [args.arch]
    shapes = tuple(SHAPES) if args.all or not args.shape else [args.shape]

    failures = []
    tag = f"__{args.tag}" if args.tag else ""
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                out = ARTIFACTS / f"{arch}__{shape}__{mesh_kind}{tag}.json"
                if out.exists() and not args.force:
                    print(f"[dryrun] cached {out.name}")
                    continue
                try:
                    rec = run_cell(arch, shape, mesh_kind,
                                   overrides=args.overrides)
                    if args.tag:
                        rec["tag"] = args.tag
                        rec["overrides"] = args.overrides
                except Exception as e:  # noqa: BLE001 - record and continue
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape,
                           "mesh": mesh_kind, "status": "failed",
                           "error": f"{type(e).__name__}: {e}"}
                    failures.append(out.name)
                out.write_text(json.dumps(rec, indent=1))
    if failures:
        print(f"FAILED cells: {failures}")
        raise SystemExit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
