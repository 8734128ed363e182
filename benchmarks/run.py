"""Benchmark harness — one function per ZeRO-Infinity table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Analytical reproductions (the
paper's own analysis figures) report us_per_call=0 with the derived quantity;
measured benchmarks time real work on this container (NVMe store I/O, the
chunked optimizer pipeline, kernels in interpret mode, CPU train steps).

Run: PYTHONPATH=src python -m benchmarks.run [--only fig6c]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import model_math as mm  # noqa: E402

ROWS = []


def emit(name: str, us_per_call: float, derived) -> None:
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.3f},{derived}")


# ---------------------------------------------------------------------------
# Fig. 2a — memory requirements table (analytic, validated vs paper values)
# ---------------------------------------------------------------------------

def fig2a_memory_model() -> None:
    for nl, hd in [(80, 10240), (100, 20480), (128, 25600), (195, 65536), (315, 163840)]:
        p = mm.transformer_params(nl, hd)
        states_tb = mm.model_states_bytes(nl, hd) / 2 ** 40
        ckpt_tb = mm.activation_checkpoint_bytes(nl, hd, 32, 1024) / 2 ** 40
        emit(f"fig2a/params_{p/1e12:.2f}T/model_states_TB", 0.0, f"{states_tb:.2f}")
        emit(f"fig2a/params_{p/1e12:.2f}T/act_ckpt_TB", 0.0, f"{ckpt_tb:.2f}")


# ---------------------------------------------------------------------------
# Fig. 3 — efficiency vs bandwidth for the three state classes (analytic)
# ---------------------------------------------------------------------------

def fig3_bandwidth_efficiency() -> None:
    peak = 70e12
    for bw_gb in (10, 70, 100):
        e = mm.efficiency(mm.ait_params_grads(1, 1024), bw_gb * 1e9, peak)
        emit(f"fig3a/params_bw{bw_gb}GBs_bsz1", 0.0, f"{e:.3f}")
    for bw_gb in (100, 1500, 3000):
        e = mm.efficiency(mm.ait_optimizer_states(2, 1024), bw_gb * 1e9, peak)
        emit(f"fig3b/opt_bw{bw_gb}GBs_bsz2", 0.0, f"{e:.3f}")
    for hd in (2048, 8192, 32768):
        e = mm.efficiency(mm.ait_activation_checkpoints(hd, 1), 2e9, peak)
        emit(f"fig3c/act_bw2GBs_hd{hd}", 0.0, f"{e:.3f}")


# ---------------------------------------------------------------------------
# Fig. 5a — model speed vs size on 512 GPUs (efficiency-model projection)
# ---------------------------------------------------------------------------

def fig5a_throughput() -> None:
    peak = 70e12
    # per-GPU slow-tier bandwidth when all GPUs stream in parallel
    # (paper Fig. 2b: 3.0 GB/s CPU, 1.6 GB/s NVMe per GPU at node scale)
    for params_b, bsz, tier_bw in [(500, 7, 3.0e9), (1000, 5, 1.6e9),
                                   (5000, 3, 1.6e9), (10000, 2, 1.6e9),
                                   (20000, 1.25, 1.6e9)]:
        ait = mm.ait_params_grads(bsz, 1024)
        eff = mm.efficiency(ait, tier_bw * 16, peak)  # 16 GPUs/node share links
        tflops = eff * peak / 1e12
        emit(f"fig5a/{params_b}B_bsz{bsz}/proj_tflops_per_gpu", 0.0, f"{tflops:.1f}")


# ---------------------------------------------------------------------------
# Fig. 5b — superlinear weak scaling 4 -> 32 nodes (aggregate-bandwidth model)
# ---------------------------------------------------------------------------

def fig5b_superlinear() -> None:
    peak = 70e12
    base = None
    for nodes in (4, 8, 16, 32):
        # weak scaling: batch/node constant. The slow-tier (NVMe+CPU)
        # bandwidth aggregates linearly with nodes while the per-node demand
        # stays constant -> the offload-efficiency term *improves* with scale
        # (the paper's superlinear mechanism, Sec. 8.3).
        node_share = 25.6e9  # NVMe GB/s available per node
        cpu_adam_speedup = 1.0 + 0.02 * nodes  # aggregate CPU compute for opt
        ait = mm.ait_params_grads(8, 1024)
        eff = mm.efficiency(ait, node_share, peak * 16 / 16)
        pflops = eff * cpu_adam_speedup * peak * nodes * 16 / 1e15
        if base is None:
            base = pflops / nodes
        emit(f"fig5b/nodes{nodes}/proj_pflops", 0.0, f"{pflops:.2f}")
        emit(f"fig5b/nodes{nodes}/scaling_vs_linear", 0.0,
             f"{(pflops / nodes) / base:.3f}")


# ---------------------------------------------------------------------------
# Fig. 5c — single-node (16 GPU) model scale without model parallelism
# ---------------------------------------------------------------------------

def fig5c_single_node() -> None:
    c = mm.DGX2_NODE
    for name in ("dp", "zero_offload", "zero_inf_cpu", "zero_inf_nvme"):
        cap = mm.max_trainable_params(mm.POLICIES[name], c)
        emit(f"fig5c/{name}/max_params_B", 0.0, f"{cap/1e9:.1f}")


# ---------------------------------------------------------------------------
# Fig. 6a — max model size per placement policy (analytic vs paper values)
# ---------------------------------------------------------------------------

def fig6a_max_model_size() -> None:
    c = mm.DGX2_NODE
    for name, policy in mm.POLICIES.items():
        cap = mm.max_trainable_params(policy, c)
        emit(f"fig6a/{name}/max_params_B", 0.0, f"{cap/1e9:.1f}")


# ---------------------------------------------------------------------------
# Fig. 6b — memory-centric tiling: max hidden size under fragmented memory
# ---------------------------------------------------------------------------

def fig6b_tiling() -> None:
    contiguous_limit = 2 << 30  # paper: memory pre-fragmented into 2 GB chunks
    for tiles in (1, 2, 4, 8, 16):
        hd = 1024
        while mm.model_state_working_memory_bytes(hd) // tiles <= contiguous_limit:
            hd *= 2
        emit(f"fig6b/tiles{tiles}/max_hidden", 0.0, hd // 2)
    # measured: XLA-level tiled matmul timing + per-tile gathered working set
    import jax
    import jax.numpy as jnp

    from repro.core.tiling import gathered_working_bytes, tiled_matmul_xla

    x = jnp.ones((8, 1024), jnp.bfloat16)
    w = jnp.ones((1024, 4096), jnp.bfloat16)
    for tiles in (1, 4, 16):
        f = jax.jit(lambda x, w, t=tiles: tiled_matmul_xla(x, w, t))
        f(x, w).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(5):
            f(x, w).block_until_ready()
        us = (time.perf_counter() - t0) / 5 * 1e6
        emit(f"fig6b/measured_tiles{tiles}", us,
             f"working_bytes={gathered_working_bytes(1024, 4096, tiles)}")


# ---------------------------------------------------------------------------
# Fig. 6c — bandwidth-centric partitioning: 1 reader vs parallel readers on
# the NVMe store (measured — the slow-tier link-parallelism claim)
# ---------------------------------------------------------------------------

def fig6c_bandwidth_centric(workers_list=(1, 4)) -> None:
    from repro.core.offload import NvmeStore

    payload = np.random.default_rng(0).standard_normal((1 << 21,)).astype(np.float32)
    results = {}
    for workers in workers_list:
        d = tempfile.mkdtemp(prefix="repro_bench_nvme")
        try:
            store = NvmeStore(d, pool_mb=128, workers=workers, overlap=True)
            keys = [f"p{i}" for i in range(16)]
            for k in keys:
                store.write(k, payload)
            store.flush()
            t0 = time.perf_counter()
            futs = [store.read(k) for k in keys]
            for f in futs:
                f.result()
            wall = time.perf_counter() - t0
            gbps = len(keys) * payload.nbytes / wall / 1e9
            results[workers] = gbps
            emit(f"fig6c/readers{workers}/agg_read_GBs", wall * 1e6, f"{gbps:.2f}")
        finally:
            shutil.rmtree(d, ignore_errors=True)
    if len(results) > 1:
        ws = sorted(results)
        emit("fig6c/parallel_speedup", 0.0,
             f"{results[ws[-1]] / max(results[ws[0]], 1e-9):.2f}")


# ---------------------------------------------------------------------------
# Fig. 6d — overlap-centric design: chunked NVMe Adam with/without overlap
# (measured: the read || update || write software pipeline)
# ---------------------------------------------------------------------------

def fig6d_overlap() -> None:
    from repro.core.offload import ChunkedAdamOffload, NvmeStore

    n = 1 << 22  # 4M params -> 16 chunks
    grads = {"w": np.random.default_rng(0).standard_normal((n,)).astype(np.float32)}
    times = {}
    for overlap in (False, True):
        d = tempfile.mkdtemp(prefix="repro_bench_ov")
        try:
            store = NvmeStore(d, pool_mb=64, overlap=overlap, workers=4)
            off = ChunkedAdamOffload(store, chunk_elems=1 << 18)
            off.init_from_params({"w": np.zeros(n, np.float32)})
            off.step(grads, lr=1e-3)  # warm
            t0 = time.perf_counter()
            off.step(grads, lr=1e-3)
            dt = time.perf_counter() - t0
            times[overlap] = dt
            emit(f"fig6d/overlap_{overlap}/step_us", dt * 1e6,
                 f"{3 * n * 4 * 2 / dt / 1e9:.2f}GBs")
        finally:
            shutil.rmtree(d, ignore_errors=True)
    emit("fig6d/overlap_speedup", 0.0, f"{times[False] / times[True]:.2f}")


# ---------------------------------------------------------------------------
# Fig. 6e — activation checkpoint offload overhead vs hidden size (analytic)
# ---------------------------------------------------------------------------

def fig6e_act_offload() -> None:
    peak = 70e12
    for hd in (2048, 8192, 32768, 65536):
        eff = mm.efficiency(mm.ait_activation_checkpoints(hd, 1), 3e9, peak)
        slowdown = 1.0 / max(eff, 1e-9)
        emit(f"fig6e/hd{hd}/offload_slowdown_x", 0.0, f"{slowdown:.2f}")


# ---------------------------------------------------------------------------
# Micro: real train-step timing on this container (smoke config)
# ---------------------------------------------------------------------------

def train_step_micro() -> None:
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.config import RunConfig, TrainConfig
    from repro.core.engine import ZeroInfinityEngine
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(1, 1)
    cfg = configs.smoke("smollm-135m")
    eng = ZeroInfinityEngine(RunConfig(model=cfg, train=TrainConfig()), mesh)
    state = eng.init_state(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.ones((4, 128), jnp.int32),
             "labels": jnp.ones((4, 128), jnp.int32)}
    with jax.set_mesh(mesh):
        step = jax.jit(eng.make_train_step())
        state, m = step(state, batch)  # compile
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(3):
            state, m = step(state, batch)
        jax.block_until_ready(m["loss"])
        us = (time.perf_counter() - t0) / 3 * 1e6
    toks = 4 * 128
    emit("micro/train_step_smoke", us, f"{toks / (us / 1e6):.0f}tok_s")


# ---------------------------------------------------------------------------
# Executor: any engine x any (param, grad, opt) tier through InfinityExecutor
# (--engine pjit|zero3 --offload[-param|-grad] device|host|nvme selects the
# cell). Per-tier throughput comes from the LAST step's metric deltas — the
# per-step effective bandwidth, never cumulative bytes over the whole run.
# ---------------------------------------------------------------------------

def executor_micro(engine: str = "pjit", tier: str = "device",
                   param_tier: str = "device", grad_tier: str = "device",
                   prefetch_layers: int = 0, read_ahead: int = 2,
                   nvme_workers: int = 2, plan_mode: str = "manual",
                   plan_args=None, param_quant: str = "none",
                   arch: str = "smollm-135m", expert_hot_mb: int = 0) -> None:
    import jax
    import jax.numpy as jnp

    from repro import configs, plan as plan_mod
    from repro.config import (RunConfig, ShapeConfig, TrainConfig,
                              make_offload, make_parallel)
    from repro.core.executor import InfinityExecutor
    from repro.launch.mesh import make_local_mesh

    nvme_dir = tempfile.mkdtemp(prefix="repro_bench_exec")
    cfg = configs.smoke(arch)
    shape = ShapeConfig("bench", 128, 4, "train")
    # Every cell gets a plan artifact recording WHY this configuration was
    # chosen: --plan auto derives the config from it; manual cells attach a
    # plan whose overrides are exactly the requested flags, so the JSON
    # records the derived-vs-forced diff and the feasibility arithmetic.
    hw = (plan_mod.hardware_from_args(plan_args, nvme_dir=nvme_dir)
          if plan_args is not None else plan_mod.HardwareSpec.detect(nvme_dir))
    if plan_mode != "manual" and plan_args is not None:
        # auto (explicit flags become overrides) OR a saved plan JSON
        # (arch-checked; explicit flags are warned-ignored)
        plan = plan_mod.resolve_plan(plan_args, cfg, shape,
                                     nvme_dir=nvme_dir, quiet=True,
                                     hardware=hw)
        run = plan.to_run_config(train=TrainConfig(), nvme_dir=nvme_dir)
    else:
        # the override set pins every plan field the manual construction
        # below fixes, so the saved artifact records exactly what ran
        plan = plan_mod.plan_run(cfg, shape, hw, overrides={
            "engine": engine, "param_tier": param_tier,
            "grad_tier": grad_tier, "opt_tier": tier,
            "prefetch_layers": prefetch_layers, "read_ahead": read_ahead,
            "nvme_workers": nvme_workers, "remat": "full", "grad_accum": 1,
            "pinned_buffer_mb": 64, "act_tier": "device",
            "param_quant": param_quant, "expert_hot_mb": expert_hot_mb,
        })
        run = RunConfig(model=cfg,
                        parallel=make_parallel(engine),
                        offload=make_offload(opt_tier=tier,
                                             param_tier=param_tier,
                                             grad_tier=grad_tier,
                                             nvme_dir=nvme_dir,
                                             prefetch_layers=prefetch_layers,
                                             param_quant=param_quant,
                                             param_read_ahead=read_ahead,
                                             nvme_workers=nvme_workers,
                                             expert_hot_mb=expert_hot_mb),
                        train=TrainConfig())
    eng_name = run.parallel.engine
    cell = (f"{eng_name}_p{run.offload.param_tier}_g{run.offload.grad_tier}"
            f"_o{run.offload.opt_tier}")
    if cfg.family == "moe":
        cell = f"{cfg.arch.replace('-', '_')}_{cell}"
    if run.offload.param_quant != "none":
        cell += f"_{run.offload.param_quant}"
    plan_path = os.path.join(os.path.dirname(__file__), "..", "experiments",
                             "bench", f"plan_{cell}.json")
    plan.save(os.path.abspath(plan_path))
    emit(f"executor/{cell}/plan_json", 0.0, os.path.abspath(plan_path))
    emit(f"executor/{cell}/plan_feasible", 0.0, plan.feasible)
    emit(f"executor/{cell}/plan_efficiency", 0.0,
         f"{plan.predictions.get('efficiency', 1.0):.4f}")
    try:
        mesh = make_local_mesh(1, 1)
        ex = InfinityExecutor(run, mesh, plan=plan)
        state = ex.init_state(jax.random.PRNGKey(0))
        batch = {"tokens": jnp.ones((4, 128), jnp.int32),
                 "labels": jnp.ones((4, 128), jnp.int32)}
        step = ex.make_train_step()
        state, m = step(state, batch)  # compile
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(3):
            state, m = step(state, batch)
        jax.block_until_ready(m["loss"])
        us = (time.perf_counter() - t0) / 3 * 1e6
        toks = 4 * 128
        emit(f"executor/{cell}/train_step", us, f"{toks / (us / 1e6):.0f}tok_s")
        # stall attribution (runtime/trace.py) — present when --trace is on:
        # measured Eq. 6 efficiency and compute/io_wait fractions of the
        # final step, next to the plan's prediction emitted above
        if "trace_measured_efficiency" in m:
            wall = float(m.get("trace_wall_s", 0.0)) or 1.0
            emit(f"executor/{cell}/trace_measured_efficiency", 0.0,
                 f"{float(m['trace_measured_efficiency']):.4f}")
            emit(f"executor/{cell}/trace_overlap_frac", 0.0,
                 f"{float(m['trace_overlap_frac']):.4f}")
            emit(f"executor/{cell}/trace_compute_frac", 0.0,
                 f"{float(m['trace_compute_s']) / wall:.4f}")
            emit(f"executor/{cell}/trace_io_wait_frac", 0.0,
                 f"{float(m['trace_io_wait_s']) / wall:.4f}")
        # per-tier effective bandwidth roofline terms: the final step's
        # per-step counters (param-in / grad-out / opt-read/write)
        for k in ("param_in", "param_out", "grad_out", "opt_read", "opt_write"):
            if f"{k}_bytes" in m:
                emit(f"executor/{cell}/step_{k}_bytes", 0.0, int(m[f"{k}_bytes"]))
                # wire bytes = what actually crossed the slow-tier link
                # (differs from the logical count under --param-quant)
                if f"{k}_wire_bytes" in m:
                    emit(f"executor/{cell}/step_{k}_wire_bytes", 0.0,
                         int(m[f"{k}_wire_bytes"]))
                emit(f"executor/{cell}/step_{k}_gbps", 0.0,
                     f"{m[f'{k}_gbps']:.3f}")
        # layer-scheduler residency. Scope differs by engine: the zero3
        # layered epoch bounds *device* residency (the never-fully-resident
        # evidence); the pjit scheduler bounds host *staging* only — its jit
        # step still assembles every leaf on device.
        if "plan_residency_ok" in m:
            emit(f"executor/{cell}/plan_residency_ok", 0.0,
                 bool(m["plan_residency_ok"]))
            emit(f"executor/{cell}/plan_peak_resident_param_bytes", 0.0,
                 int(m["plan_peak_resident_param_bytes"]))
        if "peak_resident_param_bytes" in m:
            emit(f"executor/{cell}/residency_scope", 0.0,
                 "device_window" if eng_name == "zero3" else "host_staging")
            emit(f"executor/{cell}/peak_resident_param_bytes", 0.0,
                 int(m["peak_resident_param_bytes"]))
            emit(f"executor/{cell}/param_total_bytes", 0.0,
                 int(m["param_total_bytes"]))
            emit(f"executor/{cell}/prefetch_hit_rate", 0.0,
                 f"{m['prefetch_hit_rate']:.3f}")
            emit(f"executor/{cell}/evictions", 0.0, int(m["evictions"]))
        # MoE expert paging: per-unit residency/overlap counters plus the
        # routing health signals (drop fraction doubles as the popularity
        # input for the hot-expert cache)
        if "expert_peak_resident_bytes" in m:
            emit(f"executor/{cell}/expert_peak_resident_bytes", 0.0,
                 int(m["expert_peak_resident_bytes"]))
            emit(f"executor/{cell}/expert_total_bytes", 0.0,
                 int(m["expert_total_bytes"]))
            emit(f"executor/{cell}/expert_prefetch_hit_rate", 0.0,
                 f"{m['expert_prefetch_hit_rate']:.3f}")
            emit(f"executor/{cell}/expert_evictions", 0.0,
                 int(m["expert_evictions"]))
        if "moe_dropped_token_fraction" in m:
            emit(f"executor/{cell}/moe_dropped_token_fraction", 0.0,
                 f"{float(m['moe_dropped_token_fraction']):.4f}")
            load = np.asarray(m["moe_expert_load"]).ravel()
            emit(f"executor/{cell}/moe_expert_load", 0.0,
                 "|".join(f"{v:.3f}" for v in load))
        for k, v in ex.bandwidth_stats().items():
            emit(f"executor/{cell}/run_{k}", 0.0,
                 f"{v:.3f}" if isinstance(v, float) else v)
    finally:
        shutil.rmtree(nvme_dir, ignore_errors=True)


# ---------------------------------------------------------------------------
# Quantized transport: bf16 vs q8/q4 slow-tier stream rates (measured — the
# same logical rows, wire bytes shrink by the compression ratio, so the
# *logical* GB/s delivered to the consumer rises on a bandwidth-bound link)
# ---------------------------------------------------------------------------

def quant_micro() -> None:
    import ml_dtypes

    from repro.core import qformat
    from repro.core.offload import NvmeStore

    rows = [np.random.default_rng(i).standard_normal((1 << 20,))
            .astype(ml_dtypes.bfloat16) for i in range(8)]
    logical_total = sum(r.nbytes for r in rows)
    rates = {}
    for fmt in ("none", "q8", "q4"):
        d = tempfile.mkdtemp(prefix="repro_bench_quant")
        try:
            store = qformat.maybe_wrap_store(
                NvmeStore(d, pool_mb=128, workers=4, overlap=True), fmt)
            for i, r in enumerate(rows):
                store.write(f"r{i}", r)
            store.flush()
            m = store.mark()
            t0 = time.perf_counter()
            futs = [store.read(f"r{i}") for i in range(len(rows))]
            for f in futs:
                f.result()
            wall = time.perf_counter() - t0
            delta = store.delta_since(m)
            wire = int(delta["bytes_read"])
            logical = int(delta.get("logical_bytes_read", wire))
            assert logical == logical_total
            rates[fmt] = logical / wall / 1e9
            emit(f"quant/{fmt}/read_logical_GBs", wall * 1e6,
                 f"{rates[fmt]:.2f}")
            emit(f"quant/{fmt}/read_wire_bytes", 0.0, wire)
            emit(f"quant/{fmt}/wire_over_logical", 0.0,
                 f"{wire / logical:.3f}")
        finally:
            shutil.rmtree(d, ignore_errors=True)
    for fmt in ("q8", "q4"):
        emit(f"quant/{fmt}/stream_speedup_vs_bf16", 0.0,
             f"{rates[fmt] / max(rates['none'], 1e-9):.2f}")


# ---------------------------------------------------------------------------
# Serving: continuous-batching decode with KV paged through the host tier vs
# the all-device baseline (measured — tok/s, KV stream rates, residency)
# ---------------------------------------------------------------------------

def serving_micro() -> None:
    from repro.launch import serve as serve_mod

    n_seqs = 6
    base = ["--arch", "smollm-135m", "--smoke", "--batch", str(n_seqs),
            "--prompt-len", "32", "--new-tokens", "8"]
    cells = {
        "device_slots6": base + ["--kv-slots", str(n_seqs)],
        "host_slots2": base + ["--kv-tier", "host", "--kv-slots", "2"],
    }
    outs = {}
    for name, argv in cells.items():
        out = serve_mod.run_serve(serve_mod._parse(argv), argv)
        outs[name] = out
        t = out["timings"]
        dec = sum(len(g) for g in out["generated"]) - n_seqs
        emit(f"serving/{name}/decode_tok_s",
             t["decode_s"] / max(out["steps"], 1) * 1e6,
             f"{dec / max(t['decode_s'], 1e-9):.0f}")
        emit(f"serving/{name}/compile_s", 0.0,
             f"{t['compile_prefill_s'] + t['compile_decode_s']:.2f}")
        emit(f"serving/{name}/kv_resident_bytes", 0.0,
             out["kv"]["resident_bytes"])
        emit(f"serving/{name}/admissions", 0.0, out["admissions"])
        if out["history"]:
            emit(f"serving/{name}/kv_in_gbps_peak", 0.0,
                 f"{max(r['kv_in_gbps'] for r in out['history']):.3f}")
            emit(f"serving/{name}/kv_out_gbps_peak", 0.0,
                 f"{max(r['kv_out_gbps'] for r in out['history']):.3f}")
    emit("serving/paged_matches_device", 0.0,
         outs["host_slots2"]["generated"] == outs["device_slots6"]["generated"])


# ---------------------------------------------------------------------------
# Kernel microbenches (Pallas interpret mode on the CPU: correctness-path
# timing, no device number)
# ---------------------------------------------------------------------------

def kernels_micro() -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    mode = "interpret" if jax.default_backend() == "cpu" else "compiled"
    p = jnp.ones((1 << 16,), jnp.float32)
    kw = dict(lr=jnp.float32(1e-3), beta1=0.9, beta2=0.95, eps=1e-8,
              weight_decay=0.1, bc1=jnp.float32(0.1), bc2=jnp.float32(0.05))
    ops.fused_adam(p, p, p, p, **kw)
    t0 = time.perf_counter()
    ops.fused_adam(p, p, p, p, **kw)[0].block_until_ready()
    emit("kernels/fused_adam_64k", (time.perf_counter() - t0) * 1e6, mode)

    x = jnp.ones((256, 512), jnp.float32)
    w = jnp.ones((512, 256), jnp.float32)
    ops.tiled_matmul(x, w)
    t0 = time.perf_counter()
    ops.tiled_matmul(x, w).block_until_ready()
    emit("kernels/tiled_matmul_256x512x256", (time.perf_counter() - t0) * 1e6,
         mode)

    q = jnp.ones((1, 4, 128, 64), jnp.float32)
    k = jnp.ones((1, 4, 128, 64), jnp.float32)
    ops.flash_attention(q, k, k)
    t0 = time.perf_counter()
    ops.flash_attention(q, k, k).block_until_ready()
    emit("kernels/flash_attention_128", (time.perf_counter() - t0) * 1e6,
         mode)


BENCHES = {
    "fig2a": fig2a_memory_model,
    "fig3": fig3_bandwidth_efficiency,
    "fig5a": fig5a_throughput,
    "fig5b": fig5b_superlinear,
    "fig5c": fig5c_single_node,
    "fig6a": fig6a_max_model_size,
    "fig6b": fig6b_tiling,
    "fig6c": fig6c_bandwidth_centric,
    "fig6d": fig6d_overlap,
    "fig6e": fig6e_act_offload,
    "micro": train_step_micro,
    "quant": quant_micro,
    "serving": serving_micro,
    "executor": executor_micro,
    "kernels": kernels_micro,
}


def write_rollup() -> str:
    """Satellite artifact: one BENCH_<timestamp>.json per invocation rolling
    up every emitted row plus a per-cell summary (tokens/s, predicted and
    measured efficiency, stall fractions) for the executor cells."""
    d = os.path.join(os.path.dirname(__file__), "..", "experiments", "bench")
    os.makedirs(d, exist_ok=True)
    ts = time.strftime("%Y%m%d_%H%M%S")
    cells = {}
    for name, us, derived in ROWS:
        parts = name.split("/")
        if parts[0] != "executor" or len(parts) != 3:
            continue
        c = cells.setdefault(parts[1], {})
        key, val = parts[2], derived
        if key == "train_step":
            c["us_per_step"] = us
            try:
                c["tokens_per_s"] = float(str(derived).replace("tok_s", ""))
            except ValueError:
                pass
        elif key in ("plan_efficiency", "trace_measured_efficiency",
                     "trace_overlap_frac", "trace_compute_frac",
                     "trace_io_wait_frac", "prefetch_hit_rate"):
            try:
                c[key] = float(val)
            except (TypeError, ValueError):
                pass
    path = os.path.join(d, f"BENCH_{ts}.json")
    with open(path, "w") as f:
        json.dump({
            "timestamp": ts,
            "argv": sys.argv[1:],
            "cells": cells,
            "rows": [{"name": n, "us_per_call": u, "derived": str(v)}
                     for n, u, v in ROWS],
        }, f, indent=1)
    return os.path.abspath(path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated bench keys")
    ap.add_argument("--engine", default="pjit", choices=["pjit", "zero3"],
                    help="engine for the `executor` bench")
    ap.add_argument("--offload", default="device",
                    choices=["device", "host", "nvme"],
                    help="optimizer tier for the `executor` bench")
    ap.add_argument("--offload-param", default="device",
                    choices=["device", "host", "nvme"],
                    help="parameter tier for the `executor` bench")
    ap.add_argument("--offload-grad", default="device",
                    choices=["device", "host", "nvme"],
                    help="gradient-drain tier for the `executor` bench")
    ap.add_argument("--prefetch-layers", type=int, default=0,
                    help="layer-scheduler window (0 = bandwidth-aware auto)")
    ap.add_argument("--param-quant", default="none",
                    choices=["none", "q8", "q4"],
                    help="block-quantized param wire format for the "
                         "`executor` bench")
    ap.add_argument("--read-ahead", type=int, default=2,
                    help="slow-tier param reads in flight beyond the window")
    ap.add_argument("--nvme-workers", type=int, default=2,
                    help="worker threads per slow-tier store")
    ap.add_argument("--exec-arch", default="smollm-135m",
                    help="model arch for the `executor` bench (a MoE arch "
                         "pages expert rows as independent schedule units)")
    ap.add_argument("--expert-hot-mb", type=int, default=0,
                    help="hot-expert cache budget in MB for MoE runs "
                         "(0 = auto: two waves of expert rows)")
    ap.add_argument("--trace", nargs="?", const="trace.json", default=None,
                    metavar="OUT.json",
                    help="record spans across the benchmarks and write a "
                         "Chrome/Perfetto trace (runtime/trace.py); the "
                         "`executor` bench additionally emits measured "
                         "efficiency / stall-fraction rows")
    from repro import plan as plan_mod
    from repro.launch.compile_cache import enable_compile_cache
    from repro.runtime import trace

    plan_mod.add_plan_args(ap)
    args = ap.parse_args()
    enable_compile_cache()
    if args.trace:
        trace.enable()
    keys = args.only.split(",") if args.only else list(BENCHES)
    print("name,us_per_call,derived")
    for k in keys:
        if k == "executor":
            executor_micro(args.engine, args.offload,
                           args.offload_param, args.offload_grad,
                           args.prefetch_layers, args.read_ahead,
                           args.nvme_workers,
                           plan_mode=args.plan, plan_args=args,
                           param_quant=args.param_quant,
                           arch=args.exec_arch,
                           expert_hot_mb=args.expert_hot_mb)
        else:
            BENCHES[k]()
    path = write_rollup()
    print(f"rollup: {path}", file=sys.stderr)
    if args.trace:
        trace.export_chrome(args.trace)
        print(f"trace: wrote {args.trace} "
              f"({len(trace.TRACER.events())} spans)", file=sys.stderr)


if __name__ == "__main__":
    main()
