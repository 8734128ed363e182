"""End-to-end training driver: data pipeline -> InfinityExecutor ->
checkpoints, with fault injection / restart, straggler monitoring, and the
three-tier (device / host / NVMe) optimizer placement for BOTH engines.

Examples (CPU, reduced configs):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --steps 50 --batch 8 --seq 128
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --steps 30 --offload-opt nvme          # streamed NVMe optimizer
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --smoke \
      --engine zero3 --offload-opt nvme      # explicit collectives + NVMe
  REPRO_FAIL_AT_STEP=7 REPRO_FAIL_MARKER=/tmp/m PYTHONPATH=src \
      python -m repro.launch.train ... --resume auto   # restart drill
"""
from __future__ import annotations

import argparse
import time

import jax

from repro import configs
from repro import plan as plan_mod
from repro.checkpoint.manager import CheckpointManager
from repro.config import (RunConfig, ShapeConfig, TrainConfig, make_offload,
                          make_parallel)
from repro.core.executor import InfinityExecutor
from repro.data.pipeline import PrefetchLoader, SyntheticStream
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, maybe_init_distributed
from repro.runtime import trace
from repro.runtime.elastic import wire_straggler
from repro.runtime.fault import FailureInjector, StragglerMonitor, retry_loop
from repro.runtime.metrics import MetricsLogger, elastic_step_metrics


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--engine", default="pjit", choices=["pjit", "zero3"],
                    help="pjit = GSPMD-native; zero3 = explicit collectives")
    ap.add_argument("--zero-stage", type=int, default=3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--offload-opt", default="device", choices=["device", "host", "nvme"],
                    help="optimizer-state (fp32 master/m/v) tier")
    ap.add_argument("--offload-param", default="device", choices=["device", "host", "nvme"],
                    help="bf16 compute-parameter tier (host = pinned memory_kind, "
                         "nvme = per-rank flat shards streamed with read-ahead)")
    ap.add_argument("--offload-grad", default="device", choices=["device", "host", "nvme"],
                    help="reduce-scattered gradient drain tier")
    ap.add_argument("--nvme-dir", default="/tmp/repro_nvme")
    ap.add_argument("--no-overlap", action="store_true", help="disable NVMe overlap")
    ap.add_argument("--prefetch-layers", type=int, default=0,
                    help="layer-scheduler window for slow-tier params "
                         "(0 = bandwidth-aware auto from the paper's model)")
    ap.add_argument("--param-quant", default="none",
                    choices=["none", "q8", "q4"],
                    help="block-quantized wire format for slow-tier param "
                         "rows (core/qformat.py): shrinks NVMe traffic and "
                         "pinned staging by the compression ratio")
    ap.add_argument("--grad-compress", default="none",
                    choices=["none", "int8"],
                    help="int8 + error-feedback wire format on the zero3 "
                         "replicated-grad reduce (optim/compression.py)")
    ap.add_argument("--read-ahead", type=int, default=2,
                    help="slow-tier param reads in flight beyond the window")
    ap.add_argument("--nvme-workers", type=int, default=2,
                    help="worker threads per slow-tier store")
    ap.add_argument("--pinned-buffer-mb", type=int, default=64,
                    help="shared pinned buffer-pool budget (all stores)")
    plan_mod.add_plan_args(ap)
    ap.add_argument("--elastic", action="store_true",
                    help="run under the ElasticSupervisor "
                         "(runtime/elastic.py): membership changes trigger "
                         "re-plan -> re-shard -> resume instead of a full "
                         "restart; implies plan-driven config (legacy flags "
                         "become planner overrides)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="membership-event injection for --elastic, e.g. "
                         "'fail@3' or 'fail:2,3@5;revive@9' "
                         "(kind[:ranks]@step, ';'-joined; each event fires "
                         "once)")
    ap.add_argument("--straggler-factor", type=float, default=3.0,
                    help="flag a step as a straggler when its wall time "
                         "exceeds this multiple of the running median")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="restart budget for crash recovery")
    ap.add_argument("--recovery-budget", type=float, default=60.0,
                    help="max cumulative recovery wall-clock seconds before "
                         "giving up")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", default="no", choices=["no", "auto"])
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", nargs="?", const="trace.json", default=None,
                    metavar="OUT.json",
                    help="record spans and write a Chrome/Perfetto trace "
                         "(runtime/trace.py); per-step stall attribution "
                         "lands in the step metrics as trace_* fields")
    return ap


def make_run(args):
    """(RunConfig, Optional[InfinityPlan]). With ``--plan auto`` the planner
    derives every offload/engine knob from the (detected) hardware and the
    legacy flags only act as explicit per-field overrides; ``--plan manual``
    (default) keeps the hand-tuned path byte-for-byte."""
    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    tc = TrainConfig(lr=args.lr, steps=args.steps, checkpoint_dir=args.ckpt_dir,
                     checkpoint_every=args.ckpt_every, seed=args.seed)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    plan = plan_mod.resolve_plan(args, cfg, shape, nvme_dir=args.nvme_dir)
    if plan is not None:
        import dataclasses

        run = plan.to_run_config(train=tc, nvme_dir=args.nvme_dir,
                                 overlap=not args.no_overlap)
        # non-plan parallelism knobs stay CLI-driven under --plan auto
        par_kw = {"zero_stage": args.zero_stage}
        if args.grad_compress != "none":
            par_kw["grad_compression"] = args.grad_compress
        run = run.replace(parallel=dataclasses.replace(run.parallel, **par_kw))
        return run, plan
    run = RunConfig(
        model=cfg,
        parallel=make_parallel(args.engine, zero_stage=args.zero_stage,
                               grad_accum=args.grad_accum,
                               grad_compression=args.grad_compress),
        offload=make_offload(opt_tier=args.offload_opt,
                             param_tier=args.offload_param,
                             grad_tier=args.offload_grad, nvme_dir=args.nvme_dir,
                             overlap=not args.no_overlap,
                             prefetch_layers=args.prefetch_layers,
                             param_quant=args.param_quant,
                             param_read_ahead=args.read_ahead,
                             nvme_workers=args.nvme_workers,
                             pinned_buffer_mb=args.pinned_buffer_mb),
        train=tc,
    )
    return run, None


def train_elastic(args) -> dict:
    """The ``--elastic`` path: the ElasticSupervisor owns the loop. Config
    is always plan-derived here (re-planning against the surviving hardware
    is the point), with explicitly-passed legacy flags as overrides — the
    same contract as ``--plan auto``."""
    from repro.runtime.elastic import (ChaosSchedule, ClusterMembership,
                                       ElasticConfig, ElasticSupervisor)

    assert args.model_mesh == 1, "--elastic supports data-parallel meshes"
    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    tc = TrainConfig(lr=args.lr, steps=args.steps, checkpoint_dir=args.ckpt_dir,
                     checkpoint_every=args.ckpt_every, seed=args.seed)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    membership = ClusterMembership(
        devices=jax.devices()[:args.data_mesh],
        hardware=plan_mod.hardware_from_args(args, nvme_dir=args.nvme_dir))
    parallel_kw = {"zero_stage": args.zero_stage}
    if args.grad_compress != "none":
        parallel_kw["grad_compression"] = args.grad_compress
    supervisor = ElasticSupervisor(
        model=cfg, shape=shape, train=tc, membership=membership,
        ckpt=CheckpointManager(tc.checkpoint_dir, keep=tc.keep_checkpoints),
        chaos=ChaosSchedule.from_spec(args.chaos),
        injector=FailureInjector(),
        straggler=StragglerMonitor(factor=args.straggler_factor),
        objective=args.objective,
        overrides=plan_mod.overrides_from_argv(args),
        parallel_kw=parallel_kw, nvme_dir=args.nvme_dir,
        overlap=not args.no_overlap,
        config=ElasticConfig(max_restarts=args.max_restarts,
                             recovery_budget_s=args.recovery_budget),
        resume=args.resume == "auto", log_every=args.log_every)
    return supervisor.run()


def train(args) -> dict:
    maybe_init_distributed()
    if getattr(args, "elastic", False):
        return train_elastic(args)
    run, plan = make_run(args)
    mesh = make_local_mesh(args.data_mesh, args.model_mesh)
    executor = InfinityExecutor(run, mesh, plan=plan)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")

    ckpt = CheckpointManager(run.train.checkpoint_dir, keep=run.train.keep_checkpoints)
    injector = FailureInjector()
    straggler = wire_straggler(
        StragglerMonitor(factor=getattr(args, "straggler_factor", 3.0)))
    retry_stats = {"restarts": 0, "recovery_s": 0.0}
    # step_s: wall seconds per step, device work included (the loss is
    # pulled to the host inside the timed region); step 0 compiles
    history = {"losses": [], "step_s": [], "restarts": 0}

    def run_once():
        resuming = args.resume == "auto" and ckpt.latest_step() is not None
        # a resume re-seeds the slow-tier stores from the restored state, so
        # skip the (full-model-write) seeding from the throwaway random init
        state = executor.init_state(jax.random.PRNGKey(run.train.seed),
                                    seed_stores=not resuming)
        start_step = 0
        if resuming:
            try:
                restored, extra = ckpt.restore(state, shardings=None)
            except KeyError:
                # tier migration: the checkpoint was written under a
                # different offload config — restore the tier-independent
                # leaves and rebuild this tier's state around them
                portable, extra = ckpt.restore(executor.portable_state(state))
                start_step = extra["next_step"]
                state = executor.adopt_state(portable, step=start_step)
            else:
                # elastic restore: checkpoints hold logical layouts — place
                # them back onto this mesh's shardings (any dp degree)
                state = jax.device_put(restored, executor.state_shardings())
                start_step = extra["next_step"]
                state = executor.reseed(state, step=start_step)
            print(f"resumed from checkpoint at step {start_step}")

        step_fn = executor.make_train_step()
        stream = SyntheticStream(executor.input_specs(shape), run.model.vocab_size,
                                 seed=run.train.seed)
        loader = PrefetchLoader(stream, start_step, run.train.steps,
                                executor.batch_shardings(shape))
        logger = MetricsLogger()
        tokens = shape.global_batch * shape.seq_len
        metrics = None

        with jax.set_mesh(mesh):
            for step, batch in loader:
                straggler.start()
                injector.maybe_fail(step)
                state, metrics = step_fn(state, batch)
                loss = float(metrics["loss"])
                dt = straggler.stop(step)
                history["losses"].append(loss)
                history["step_s"].append(dt)
                if step % args.log_every == 0:
                    extras = elastic_step_metrics(
                        restarts=retry_stats["restarts"],
                        recovery_s=retry_stats["recovery_s"],
                        n_alive=len(mesh.devices.flat))
                    extras.update(straggler.step_metrics())
                    logger.log(step, loss, tokens, dt, **extras)
                if run.train.checkpoint_every and (step + 1) % run.train.checkpoint_every == 0:
                    # slow-tier-resident params are materialized from the
                    # store for the snapshot (the carried leaf is a struct)
                    ckpt.save(step + 1, executor.checkpoint_state(state),
                              {"next_step": step + 1})
        ckpt.wait()
        history["final_state"] = state
        history["last_metrics"] = metrics
        stats = executor.bandwidth_stats()
        if stats:
            history["nvme_stats"] = stats

    history["restarts"] = retry_loop(
        run_once, max_restarts=args.max_restarts,
        recovery_budget_s=args.recovery_budget, stats=retry_stats,
        on_restart=lambda n, e: print(f"restart #{n} after: {e}"))
    history["recovery_s"] = retry_stats["recovery_s"]
    executor.close()
    if straggler.flagged:
        print(f"straggler steps flagged: {straggler.flagged}")
    return history


def main() -> None:
    args = build_argparser().parse_args()
    enable_compile_cache()
    if getattr(args, "trace", None):
        trace.enable()
    t0 = time.time()
    hist = train(args)
    losses = hist["losses"]
    print(f"done in {time.time()-t0:.1f}s | first loss {losses[0]:.4f} | "
          f"last loss {losses[-1]:.4f} | restarts {hist['restarts']}")
    if "elastic" in hist:
        e = hist["elastic"]
        print(f"elastic: restarts={e['elastic_restarts']} "
              f"replans={e['elastic_replans']} "
              f"resizes={e['elastic_resizes']} "
              f"recovery_s={e['elastic_recovery_s']} "
              f"n_alive={e['elastic_n_alive']}")
    if "nvme_stats" in hist:
        s = hist["nvme_stats"]
        print(f"nvme: read {s['read_gbps']:.2f} GB/s, write {s['write_gbps']:.2f} GB/s, "
              f"pinned peak {s['pinned_peak_bytes']>>20} MiB")
    if getattr(args, "trace", None):
        trace.export_chrome(args.trace)
        print(f"trace: wrote {args.trace} "
              f"({len(trace.TRACER.events())} spans)")
    return hist


if __name__ == "__main__":
    main()
