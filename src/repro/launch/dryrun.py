import os
import sys

if "--smoke-exec" not in sys.argv:
    # the production-mesh dry-run wants 512 fake devices; the smoke-exec
    # gate runs real steps on one CPU device (flag must be set pre-import)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware: the sharding composition
(ZeRO-3 x TP/CP/EP) is coherent on the production mesh, the program
partitions (collectives resolve), and it yields the compiled artifact from
which EXPERIMENTS.md's roofline terms are derived.

``--smoke-exec`` instead executes a few real steps through the
InfinityExecutor on a local mesh (the tier-1 CI layer-scheduler gate): with
``--offload-param nvme`` it asserts ``peak_resident_param_bytes`` stays
strictly below the total parameter bytes — params never fully reside on
device.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --mesh pod1 --arch smollm-135m
  PYTHONPATH=src python -m repro.launch.dryrun --all   # every cell, cached
  PYTHONPATH=src python -m repro.launch.dryrun --smoke-exec --engine zero3 \
      --arch smollm-135m --offload-param nvme --prefetch-layers 2
  PYTHONPATH=src python -m repro.launch.dryrun --smoke-exec --plan auto \
      --hw-device-mem 1e6 --hw-host-mem 2e6   # planner-derived tiers + gate
"""

import argparse
import json
import time
import traceback

import jax

from repro import configs
from repro import plan as plan_mod
from repro.config import (RunConfig, ParallelConfig, OffloadConfig, SHAPES,
                          ShapeConfig)
from repro.core import model_math
from repro.core.engine import ZeroInfinityEngine
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import common as cm
from repro.models import registry
from repro.roofline import analysis
from repro.runtime import trace

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")


def cell_skip_reason(arch: str, shape_name: str) -> str | None:
    cfg = configs.get(arch)
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        return ("full-attention arch: 512k dense-KV decode is quadratic by "
                "definition — skipped per assignment (see DESIGN.md)")
    return None


def model_flops_for(bundle, shape) -> float:
    n = bundle.n_params_active()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return model_math.model_flops(n, tokens)
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return model_math.decode_model_flops(n, shape.global_batch)  # 1 new token/seq


def cell_result_path(out_dir: str, mesh_name: str, arch: str,
                     shape_name: str, tag: str = "") -> str:
    """The one place the per-cell result filename is built — the sweep's
    cached-cell check and run_cell's cache short-circuit must agree."""
    return os.path.join(out_dir, f"{mesh_name}__{arch}__{shape_name}{tag}.json")


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             parallel: ParallelConfig, offload: OffloadConfig,
             out_dir: str, force: bool = False, tag: str = "",
             model_overrides: dict | None = None, plan=None) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    path = cell_result_path(out_dir, mesh_name, arch, shape_name, tag)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    skip = cell_skip_reason(arch, shape_name)
    if skip:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "status": "skipped", "reason": skip}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_name == "pod2"))
    n_chips = 1
    for a in mesh.axis_names:
        n_chips *= mesh.shape[a]
    cfg = configs.get(arch)
    if model_overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **model_overrides)
    shape = SHAPES[shape_name]
    run = RunConfig(model=cfg, parallel=parallel, offload=offload)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "n_chips": n_chips, "parallel": parallel.__dict__ | {},
           "status": "error"}
    if plan is not None:  # record WHY this cell's config was chosen
        rec["plan"] = json.loads(plan.to_json())
    t0 = time.time()
    try:
        with cm.attention_paths() as tally:  # the step trace's calls
            if parallel.engine == "zero3":
                from repro.core.zero import ExplicitZero3Engine

                zeng = ExplicitZero3Engine(run, mesh)
                if shape.kind != "train":
                    raise ValueError("explicit zero3 engine: train shapes only")
                lowered = zeng.lower_train(shape)

                class _B:  # bundle stand-in for flops accounting
                    pass

                eng = _B()
                eng.bundle = __import__("repro.models.registry", fromlist=["registry"]).build(cfg)
            else:
                eng = ZeroInfinityEngine(run, mesh)
                lowered = eng.lower(shape)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mf = model_flops_for(eng.bundle, shape)
        roof = analysis.analyze(compiled, arch=arch, shape=shape_name,
                                mesh_name=mesh_name, n_chips=n_chips,
                                model_flops_total=mf)
        print(compiled.memory_analysis())   # proves it fits
        cost = compiled.cost_analysis() or {}
        print(cost)  # FLOPs/bytes for §Roofline
        rec.update(status="ok", lower_s=t_lower, compile_s=t_compile,
                   n_params=eng.bundle.n_params(),
                   n_params_active=eng.bundle.n_params_active(),
                   memory_analysis=str(compiled.memory_analysis()),
                   cost_analysis={k: float(v) for k, v in
                                  cost.items()
                                  if isinstance(v, (int, float))},
                   roofline=roof.to_dict(),
                   attention_paths=dict(tally))
    except Exception as e:  # record the failure — these are bugs to fix
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    rec["wall_s"] = time.time() - t0
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=float)
    return rec


def _trace_gate(args, ex, metrics, plan, *, param_nvme: bool,
                cfg=None, shape=None) -> None:
    """The trace smoke gate (tier-1 CI): export the Perfetto trace and the
    stall report, then assert the instrumentation is real — nonzero
    slow-tier read spans, attribution fractions that cover the step wall
    time, and spans from every major subsystem on the layered path."""
    if args.trace:
        trace.export_chrome(args.trace)
        print(f"trace: wrote {args.trace} "
              f"({len(trace.TRACER.events())} spans)")
    atts = list(ex.trace_attributions)
    predictions = plan.predictions if plan is not None else None
    if predictions is None and cfg is not None and shape is not None:
        # Manual mode carries no plan, but the report should still show
        # measured-vs-predicted: derive a shadow plan from the same flags
        # purely for its Eq. 6 predictions (never applied to the run).
        try:
            shadow = plan_mod.plan_run(
                cfg, shape, plan_mod.hardware_from_args(args),
                overrides=plan_mod.overrides_from_argv(args))
            predictions = shadow.predictions
            metrics.setdefault("plan_efficiency",
                               predictions.get("efficiency"))
        except Exception:
            predictions = None
    report = trace.format_report(atts, predictions=predictions,
                                 tracer=trace.TRACER)
    if args.trace_report:
        print(report)
    frac = float(metrics.get("trace_attr_frac_sum", 0.0))
    if not 0.95 <= frac <= 1.05:
        raise SystemExit(
            f"trace gate: attribution fractions sum to {frac:.3f}, outside "
            "1±0.05 — compute_s + io_wait_s + other_s does not cover the "
            "step wall time")
    meff = metrics.get("trace_measured_efficiency")
    peff = metrics.get("plan_efficiency")
    print(f"trace gate: measured_efficiency="
          f"{meff if meff is None else f'{meff:.3f}'} "
          f"predicted_efficiency={peff if peff is None else f'{peff:.3f}'} "
          f"overlap_frac={metrics.get('trace_overlap_frac', 0.0):.3f} "
          f"attr_frac_sum={frac:.3f}")
    if param_nvme:
        names = trace.TRACER.span_names()
        if not names.get("nvme_read"):
            raise SystemExit(
                "trace gate: no nvme_read spans recorded with "
                "param_tier=nvme — store I/O is not instrumented")
        systems = trace.TRACER.subsystems()
        if len(systems) < 4:
            raise SystemExit(
                f"trace gate: spans cover only subsystems {systems} — "
                "expected >= 4 of (sched, store, compute, optim, ...)")
        print(f"trace gate: subsystems={systems} "
              f"nvme_read_spans={names['nvme_read']}")


def smoke_exec(args) -> None:
    """Tier-1 CI gate: run real steps with the configured tiers on the smoke
    config and, for NVMe-resident params, assert the layer scheduler keeps
    peak residency strictly below total param bytes. With ``--plan auto``
    the tiers come from the planner instead of flags and the gate
    additionally asserts the emitted plan is feasible for the (detected or
    ``--hw-*``-overridden) hardware and that measured peak residency stays
    at or below the planner's prediction."""
    import dataclasses
    import tempfile

    import jax
    import jax.numpy as jnp

    from repro.config import RunConfig, TrainConfig, make_offload, make_parallel
    from repro.core.executor import InfinityExecutor
    from repro.launch.mesh import make_local_mesh

    cfg = dataclasses.replace(configs.smoke(args.arch or "smollm-135m"),
                              n_layers=args.exec_layers)
    nvme_dir = tempfile.mkdtemp(prefix="repro_smoke_nvme")
    tc = TrainConfig(lr=3e-3, warmup_steps=2)
    shape = ShapeConfig("smoke-exec", 16, 2, "train")
    plan = plan_mod.resolve_plan(args, cfg, shape, nvme_dir=nvme_dir)
    if plan is not None:
        run = plan.to_run_config(train=tc, nvme_dir=nvme_dir)
    else:
        run = RunConfig(
            model=cfg, parallel=make_parallel(args.engine, remat="none"),
            offload=make_offload(opt_tier=args.offload,
                                 param_tier=args.offload_param,
                                 grad_tier=args.offload_grad,
                                 nvme_dir=nvme_dir,
                                 prefetch_layers=args.prefetch_layers,
                                 param_quant=args.param_quant,
                                 param_read_ahead=args.read_ahead,
                                 nvme_workers=args.nvme_workers,
                                 expert_hot_mb=args.expert_hot_mb),
            train=tc)
    mesh = make_local_mesh(1, 1)

    def _run_steps(run_cfg, run_plan=None):
        ex = InfinityExecutor(run_cfg, mesh, plan=run_plan)
        state = ex.init_state(jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((2, 16), jnp.int32),
                 "labels": jnp.ones((2, 16), jnp.int32)}
        step = ex.make_train_step()
        metrics, losses = {}, []
        for _ in range(args.exec_steps):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        return ex, metrics, losses

    ex, metrics, losses = _run_steps(run, plan)
    if trace.enabled():
        _trace_gate(args, ex, metrics, plan,
                    param_nvme=run.offload.param_tier == "nvme",
                    cfg=cfg, shape=shape)
    peak = int(metrics.get("peak_resident_param_bytes", -1))
    total = ex.total_param_bytes
    engine = run.parallel.engine
    param_tier = run.offload.param_tier
    print(f"smoke-exec: engine={engine} param_tier={param_tier} "
          f"loss={float(metrics['loss']):.4f} "
          f"peak_resident_param_bytes={peak} total_param_bytes={total} "
          f"prefetch_hit_rate={metrics.get('prefetch_hit_rate')} "
          f"evictions={metrics.get('evictions')}")
    if plan is not None:
        if not plan.feasible:
            raise SystemExit("plan gate: emitted plan is INFEASIBLE for the "
                             "specified hardware: " + "; ".join(plan.warnings))
        pred = plan.predictions["peak_resident_param_bytes"]
        if peak >= 0 and peak > pred:
            raise SystemExit(
                f"plan gate: measured peak residency {peak} exceeds the "
                f"planner's prediction {pred:.0f}")
        print(f"plan gate: feasible=True measured_peak={peak} "
              f"predicted_peak={pred:.0f} "
              f"residency_ok={metrics.get('plan_residency_ok', 'n/a')}")
    quant = run.offload.param_quant
    if quant != "none":
        if param_tier != "nvme":
            print(f"smoke-exec: param_quant={quant} only shapes the slow-tier "
                  "wire — no nvme param store here, quant gate skipped")
        else:
            import numpy as np

            wire = int(metrics["param_in_wire_bytes"])
            logical = int(metrics["param_in_bytes"])
            if not 0 < wire < logical:
                raise SystemExit(
                    f"quant gate: wire traffic {wire} not strictly below "
                    f"logical {logical} — {quant} rows are not compressed "
                    "on the wire")
            if wire > 0.6 * logical:
                raise SystemExit(
                    f"quant gate: wire/logical ratio {wire / logical:.3f} "
                    f"exceeds 0.6 — {quant} encode is not paying for itself")
            base_run = run.replace(offload=dataclasses.replace(
                run.offload, param_quant="none",
                nvme_dir=tempfile.mkdtemp(prefix="repro_smoke_nvme_bf16")))
            _, _, base_losses = _run_steps(base_run)
            if not np.allclose(losses, base_losses, rtol=5e-2, atol=5e-2):
                raise SystemExit(
                    f"quant gate: {quant} loss trajectory {losses} diverged "
                    f"from the bf16 baseline {base_losses} beyond 5e-2")
            print(f"quant gate: {quant} wire/logical="
                  f"{wire / logical:.3f} (<=0.6) "
                  f"max_loss_delta="
                  f"{max(abs(a - b) for a, b in zip(losses, base_losses)):.2e}")
    if param_tier == "nvme":
        if engine != "zero3":
            # the pjit engine's scheduler bounds host *staging* only — its
            # jit step still assembles every leaf on device, so the strict
            # device-residency bound is a zero3 (layered-epoch) claim
            print("smoke-exec: pjit engine — host-staging bound only "
                  f"(peak {peak} <= total {total}: {peak <= total})")
            if peak > total:
                raise SystemExit("host staging exceeded total param bytes")
            return
        # strictly below total whenever the window is smaller than the model
        # (a 1-layer model's window necessarily equals full residency);
        # bound against the model the executor actually ran (a loaded plan
        # embeds its own ModelConfig)
        nl = run.model.n_layers
        window = run.offload.prefetch_layers or nl - 1
        bound = total if min(window, nl) >= nl else total - 1
        if not 0 <= peak <= bound:
            raise SystemExit(
                f"layer scheduler violated the residency bound: peak {peak} "
                f"exceeds {bound} (total {total})")
        if getattr(ex, "is_moe", False):
            # expert-paging gate: expert rows are independent schedule units
            # — only router-selected waves (+ the hot cache) ever reside,
            # and the popularity/backward prefetch must actually land hits
            epeak = int(metrics["expert_peak_resident_bytes"])
            etotal = int(metrics["expert_total_bytes"])
            ehit = float(metrics["expert_prefetch_hit_rate"])
            edrop = float(metrics["moe_dropped_token_fraction"])
            print(f"expert gate: peak_resident={epeak} total={etotal} "
                  f"prefetch_hit_rate={ehit:.3f} dropped_frac={edrop:.4f}")
            if not 0 < epeak < etotal:
                raise SystemExit(
                    f"expert gate: peak resident expert bytes {epeak} not "
                    f"strictly below total expert bytes {etotal} — expert "
                    "rows are not paging independently")
            if not ehit > 0.0:
                raise SystemExit(
                    "expert gate: expert prefetch hit rate is zero — "
                    "selected-set/popularity prefetch is not overlapping "
                    "expert reads with compute")
            if not 0.0 <= edrop <= 1.0:
                raise SystemExit(
                    f"expert gate: moe_dropped_token_fraction={edrop} is not "
                    "a fraction")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES), help="shape (default: all)")
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2", "both"])
    ap.add_argument("--all", action="store_true", help="all archs x shapes")
    ap.add_argument("--force", action="store_true", help="ignore cache")
    ap.add_argument("--out", default=os.path.abspath(OUT_DIR))
    ap.add_argument("--zero-stage", type=int, default=3)
    ap.add_argument("--zero-scope", default="global", choices=["global", "pod"])
    ap.add_argument("--remat", default="full", choices=["full", "dots", "none"])
    ap.add_argument("--tiling", type=int, default=1)
    ap.add_argument("--pure-dp", action="store_true",
                    help="paper-faithful: no tensor slicing, dp over all axes")
    ap.add_argument("--moe-zero-stage", type=int, default=3)
    ap.add_argument("--engine", default="pjit", choices=["pjit", "zero3"],
                    help="zero3 = explicit shard_map collective schedule")
    ap.add_argument("--prefetch", type=int, default=1)
    ap.add_argument("--score-dtype", default="float32", choices=["float32", "bfloat16"])
    ap.add_argument("--attn-chunk", type=int, default=256)
    ap.add_argument("--moe-combine-dtype", default="float32", choices=["float32", "bfloat16"])
    ap.add_argument("--offload", default="device", choices=["device", "host", "nvme"],
                    help="optimizer-state tier (nvme lowers the grads-only step)")
    ap.add_argument("--offload-param", default="device",
                    choices=["device", "host", "nvme"],
                    help="compute-parameter tier for the lowered step")
    ap.add_argument("--offload-grad", default="device",
                    choices=["device", "host", "nvme"],
                    help="gradient-drain tier (host/nvme lower grads-only)")
    ap.add_argument("--prefetch-layers", type=int, default=0,
                    help="layer-scheduler window for slow-tier params "
                         "(0 = bandwidth-aware auto)")
    ap.add_argument("--param-quant", default="none",
                    choices=["none", "q8", "q4"],
                    help="block-quantized wire format for slow-tier param "
                         "rows; under --smoke-exec also runs a bf16 baseline "
                         "and gates on trajectory parity + wire < logical")
    ap.add_argument("--read-ahead", type=int, default=2,
                    help="slow-tier param reads in flight beyond the window")
    ap.add_argument("--expert-hot-mb", type=int, default=0,
                    help="hot-expert cache budget in MiB for MoE expert "
                         "paging (0 = two waves of top_k rows)")
    ap.add_argument("--nvme-workers", type=int, default=2,
                    help="worker threads per slow-tier store")
    ap.add_argument("--smoke-exec", action="store_true",
                    help="execute real steps on a local mesh and check the "
                         "scheduler residency bound (tier-1 CI gate)")
    ap.add_argument("--exec-steps", type=int, default=2,
                    help="steps to run under --smoke-exec")
    ap.add_argument("--exec-layers", type=int, default=4,
                    help="layer count override under --smoke-exec (must "
                         "exceed the window for a strict residency bound)")
    ap.add_argument("--tag", default="", help="suffix for the result file")
    ap.add_argument("--trace", nargs="?", const="trace.json", default=None,
                    metavar="OUT.json",
                    help="enable the span tracer and write a Chrome/Perfetto "
                         "trace-event JSON (default name trace.json)")
    ap.add_argument("--trace-report", action="store_true",
                    help="enable the tracer and print the per-step stall-"
                         "attribution report (top stall sources, per-tier "
                         "busy/idle, measured vs predicted efficiency)")
    plan_mod.add_plan_args(ap)
    args = ap.parse_args()
    enable_compile_cache()

    if args.trace or args.trace_report:
        trace.enable()
    if args.smoke_exec:
        smoke_exec(args)
        return

    archs = [args.arch] if args.arch else list(configs.ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["pod1", "pod2"] if args.mesh == "both" else [args.mesh]
    parallel = ParallelConfig(zero_stage=args.zero_stage, zero_scope=args.zero_scope,
                              remat=args.remat, tiling_factor=args.tiling,
                              pure_dp=args.pure_dp, moe_zero_stage=args.moe_zero_stage,
                              engine=args.engine, prefetch=args.prefetch)
    offload = OffloadConfig(param_tier=args.offload_param,
                            grad_tier=args.offload_grad,
                            opt_tier=args.offload,
                            prefetch_layers=args.prefetch_layers,
                            param_quant=args.param_quant,
                            param_read_ahead=args.read_ahead,
                            nvme_workers=args.nvme_workers)
    overrides = {}
    if args.score_dtype != "float32":
        overrides["score_dtype"] = args.score_dtype
    if args.moe_combine_dtype != "float32":
        overrides["moe_combine_dtype"] = args.moe_combine_dtype
    if args.attn_chunk != 256:
        overrides["attn_chunk"] = args.attn_chunk

    n_ok = n_skip = n_err = 0
    # one hardware probe for the whole sweep, not one per cell
    plan_hw = (plan_mod.hardware_from_args(args)
               if args.plan == "auto" else None)
    for mesh_name in meshes:
        for arch in archs:
            for shape_name in shapes:
                cell_parallel, cell_offload, cell_plan = parallel, offload, None
                cell_path = cell_result_path(args.out, mesh_name, arch,
                                             shape_name, args.tag)
                cached = os.path.exists(cell_path) and not args.force
                # cached cells short-circuit in run_cell: don't plan for
                # them, and never let a plan error clobber a cached record
                if args.plan != "manual" and not cached:
                    # per-cell plan: the tiers/engine/window/remat come from
                    # the hardware arithmetic; non-plan parallelism knobs
                    # (zero scope/stage, tiling, MoE) stay CLI-driven. Plan
                    # on the SAME model the cell will run (incl. overrides).
                    import dataclasses as _dc
                    cell_cfg = configs.get(arch)
                    if overrides:
                        cell_cfg = _dc.replace(cell_cfg, **overrides)
                    try:
                        cell_plan = plan_mod.resolve_plan(
                            args, cell_cfg, SHAPES[shape_name],
                            quiet=True, hardware=plan_hw)
                    except ValueError as e:
                        # an override this cell cannot honor (e.g. a forced
                        # zero3 engine on a non-dense arch) is a per-cell
                        # error, not a sweep abort
                        rec = {"arch": arch, "shape": shape_name,
                               "mesh": mesh_name, "status": "error",
                               "error": f"plan: {e}"}
                        os.makedirs(args.out, exist_ok=True)
                        with open(cell_path, "w") as f:
                            json.dump(rec, f, indent=1)
                        n_err += 1
                        print(f"[{mesh_name}] {arch:24s} {shape_name:12s} "
                              f"error    {rec['error'][:120]}", flush=True)
                        continue
                    rc = cell_plan.to_run_config()
                    cell_parallel = _dc.replace(
                        rc.parallel, zero_stage=args.zero_stage,
                        zero_scope=args.zero_scope,
                        tiling_factor=args.tiling,
                        moe_zero_stage=args.moe_zero_stage,
                        prefetch=args.prefetch,
                        pure_dp=args.pure_dp or rc.parallel.pure_dp)
                    cell_offload = rc.offload
                    for w in cell_plan.warnings:
                        print(f"[{mesh_name}] {arch} {shape_name} "
                              f"PLAN WARNING: {w}")
                rec = run_cell(arch, shape_name, mesh_name,
                               parallel=cell_parallel,
                               offload=cell_offload, out_dir=args.out,
                               force=args.force, tag=args.tag,
                               model_overrides=overrides or None,
                               plan=cell_plan)
                st = rec["status"]
                n_ok += st == "ok"
                n_skip += st == "skipped"
                n_err += st == "error"
                extra = ""
                if st == "ok":
                    r = rec["roofline"]
                    extra = (f"flops/chip={r['flops']:.3e} "
                             f"bottleneck={r['bottleneck']} "
                             f"roofline={r['roofline_fraction']:.3f} "
                             f"attention={rec.get('attention_paths')} "
                             f"[{rec['wall_s']:.0f}s]")
                elif st == "error":
                    extra = rec["error"][:120]
                print(f"[{mesh_name}] {arch:24s} {shape_name:12s} {st:8s} {extra}",
                      flush=True)
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if args.trace:
        trace.export_chrome(args.trace)
        print(f"trace: wrote {args.trace}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
