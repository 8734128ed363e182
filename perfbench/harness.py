"""One run of one benchmark cell: set-up, the measured window, the check.

The cell's files are found by the names in ``BENCHMARK.json``:
``perfbench/configs/<config>.json`` (the model as it is run),
``perfbench/traffic/<traffic>.json`` (engine, tiers, mesh, batch, optimizer),
``perfbench/limits/<workload>.json`` (what ``correct`` allows) and
``perfbench/metrics/<metric>.py`` (one reader per metric). A reference model
is named by the configuration and lives in ``perfbench/references/``.

The window drives the system's own training step: ``InfinityExecutor`` built
from the cell's files, its ``make_train_step()`` in a closed loop, one loss
read back per step as the trainer does. Set-up makes the weights on the
device from the seed, runs the first three steps through that same step and
feed (the first compiles), and reads what the check needs from the state.
After the window the program is freed and the reference retrains those three
steps from the same weights and rows.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

CHECKED_STEPS = 3


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    root: Path
    bench: dict
    workload: dict
    config: dict
    traffic: dict
    limits: dict

    @property
    def name(self) -> str:
        return self.workload["name"]

    def metrics(self, section: str) -> list:
        """The section's metrics that this cell reports."""
        return [m for m in self.bench[section]
                if "workloads" not in m or self.name in m["workloads"]]


def load_cell(root: Path, name: str) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    pb = root / "perfbench"
    return Cell(root=root, bench=bench, workload=w,
                config=load_json(root / configs[w["config"]]["file"]),
                traffic=load_json(pb / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(pb / "limits" / f"{name}.json"))


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_chips(n: int):
    """The devices of a TPU host with at least ``n`` chips, or exit."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"perfbench: needs a TPU; JAX found {len(devs)} "
                         f"{devs[0].platform} device(s) "
                         f"({devs[0].device_kind}). There is no CPU fallback.")
    if len(devs) < n:
        raise SystemExit(f"perfbench: the cell needs {n} chips, JAX found "
                         f"{len(devs)} {devs[0].device_kind}")
    return devs[:n]


def seed_key(seed: int):
    import jax

    if seed < 0:
        raise SystemExit("--seed must be a non-negative whole number")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def enable_cache(root: Path) -> None:
    """The program's persistent compile cache, every program kept in it."""
    import jax

    sys.path.insert(0, str(root / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def program_model(cfg: dict):
    """The program's ModelConfig for the configuration file, or an error
    for a setting the program cannot run."""
    from repro import configs

    fixed = {"hidden_act": "silu", "rms_norm_eps": 1e-6,
             "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
             "logits_scaling": 1.0,
             "attention_multiplier": cfg["head_dim"] ** -0.5}
    if cfg.get("num_local_experts"):
        fixed["router_group"] = 1024
    for key, value in fixed.items():
        if cfg.get(key, value) != value:
            raise SystemExit(f"{cfg['name']}: the program runs {key}={value}, "
                             f"the configuration asks {cfg[key]}")
    E = cfg.get("num_local_experts", 0)
    return dataclasses.replace(
        configs.get(cfg["arch"]), n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"], n_experts=E,
        top_k=cfg.get("num_experts_per_tok", 0),
        capacity_factor=cfg.get("capacity_factor", 1.25))


def program_params(w: dict, cfg: dict, padded_vocab: int) -> dict:
    """Canonical weights -> the program's parameter tree (traceable)."""
    import jax.numpy as jnp

    tok = jnp.pad(w["embed"], ((0, padded_vocab - cfg["vocab_size"]), (0, 0)))
    blocks = {"ln1": {"scale": w["ln1"]}, "ln2": {"scale": w["ln2"]},
              "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")}}
    mlp = {"w_in": w["w_up"], "w_gate": w["w_gate"], "w_out": w["w_down"]}
    if cfg.get("num_local_experts"):
        blocks["moe"] = dict(mlp, router=w["router"])
    else:
        blocks["mlp"] = mlp
    return {"embed": {"tok": tok}, "blocks": blocks,
            "ln_f": {"scale": w["ln_f"]}}


def canonical_leaves(tree: dict) -> dict:
    """The program's parameter tree (or a tree shaped like it) by canonical
    leaf name; the inverse of ``program_params``' renaming."""
    b = tree["blocks"]
    mlp = b.get("moe", b.get("mlp"))
    out = {"embed": tree["embed"]["tok"], "ln_f": tree["ln_f"]["scale"],
           "ln1": b["ln1"]["scale"], "ln2": b["ln2"]["scale"],
           "w_up": mlp["w_in"], "w_gate": mlp["w_gate"],
           "w_down": mlp["w_out"], **b["attn"]}
    if "router" in mlp:
        out["router"] = mlp["router"]
    return out


class Program:
    """The executor, its compiled step and the feed, built from the cell."""

    def __init__(self, cell: Cell, devices, reference):
        import jax
        import jax.numpy as jnp

        from repro.config import (RunConfig, ShapeConfig, TrainConfig,
                                  make_offload, make_parallel)
        from repro.core.executor import InfinityExecutor
        from repro.optim import adam

        t, cfg = cell.traffic, cell.config
        if t["engine"] != "pjit":
            raise SystemExit(f"traffic {cell.workload['traffic']}: the "
                             f"harness builds pjit-engine state only")
        self.model = program_model(cfg)
        opt = t["optimizer"]
        self.hp = opt
        run = RunConfig(
            model=self.model,
            parallel=make_parallel("pjit", zero_stage=t["zero_stage"]),
            offload=make_offload(opt_tier=t["tiers"]["opt"],
                                 param_tier=t["tiers"]["param"],
                                 grad_tier=t["tiers"]["grad"]),
            train=TrainConfig(lr=opt["lr"], beta1=opt["beta1"],
                              beta2=opt["beta2"], eps=opt["eps"],
                              weight_decay=opt["weight_decay"],
                              warmup_steps=opt["warmup_steps"]))
        dp = t["data_mesh"]
        self.mesh = jax.make_mesh(
            (dp, 1), ("data", "model"), devices=list(devices)[:dp],
            axis_types=(jax.sharding.AxisType.Auto,) * 2)
        self.executor = InfinityExecutor(run, self.mesh)
        self.shape = ShapeConfig("bench", t["seq_len"], t["global_batch"],
                                 "train")
        shardings = self.executor.state_shardings()
        pv = self.model.padded_vocab()

        def init(key):
            params = program_params(reference.init_weights(cfg, key), cfg, pv)
            return {"params": params, "opt": adam.init_state(params)}

        self.init = jax.jit(init, out_shardings=shardings)
        self.params0 = jax.jit(
            lambda key: program_params(reference.init_weights(cfg, key), cfg,
                                       pv),
            out_shardings=shardings["params"])
        B, S, V = t["global_batch"], t["seq_len"], cfg["vocab_size"]
        if t["tokens"] != "uniform":
            raise SystemExit(f"unknown token distribution {t['tokens']!r}")

        def batch(key, i):
            toks = jax.random.randint(jax.random.fold_in(key, i), (B, S), 0, V,
                                      jnp.int32)
            return {"tokens": toks, "labels": toks}

        self.feed = jax.jit(batch, out_shardings=self.executor.batch_shardings(
            self.shape))
        self.jitted = self.executor.make_train_step()
        self.step = self.jitted
        self.tokens_per_step = B * S

    def step_temp_bytes(self, state, batch) -> int:
        """Scratch of the compiled step that the window drives, per chip:
        device memory that the allocator's statistics leave out."""
        compiled = self.jitted.lower(state, batch).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)

    def leaf_norms(self, tree, minus=None) -> dict:
        """Norms of each canonical leaf of a parameter-shaped ``tree`` (or of
        ``tree - minus``): one per layer for stacked leaves. One leaf at a
        time, so that what this adds to the device's peak memory does not
        depend on how far the host runs ahead."""
        import jax

        base = canonical_leaves(minus) if minus is not None else {}
        out = {}
        for name, x in canonical_leaves(tree).items():
            if x.sharding.memory_kind not in (None, "device"):
                x = jax.device_put(x, x.sharding.with_memory_kind("device"))
            stacked = name not in ("embed", "ln_f")
            out[name] = jax.device_get(_norm_fn(stacked)(x, base.get(name)))
            del x
        return {k: v.tolist() for k, v in out.items()}


@functools.cache
def _norm_fn(stacked: bool):
    import jax
    import jax.numpy as jnp

    def norm(a, b=None):
        a = a.astype(jnp.float32)
        if b is not None:
            a = a - b.astype(jnp.float32)
        axes = tuple(range(1, a.ndim)) if stacked else None
        return jnp.sqrt(jnp.sum(a * a, axis=axes))

    return jax.jit(norm)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


class CompileCounter:
    """Backend compilations since ``reset`` (nothing may compile in the
    measured window)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def reset(self) -> None:
        self.n = 0


def reference_module(cell: Cell):
    return load_module(cell.root / "perfbench" / "references" /
                       f"{cell.config['reference']}.py")


def checked_steps(prog: Program, key):
    """Make the state from ``key`` and run the checked steps through the
    window's own step and feed; return the state and what the check needs
    from it: each step's loss, the first gradient as the optimizer got it
    (m_1 = (1 - b1) g_1) and the params' change over the steps."""
    import jax

    state = prog.init(key)
    readings, extra = {"losses": []}, {}
    b1 = prog.hp["beta1"]
    for i in range(1, CHECKED_STEPS + 1):
        state, metrics = prog.step(state, prog.feed(key, i))
        readings["losses"].append(float(metrics["loss"]))
        if i == 1:
            m1 = prog.leaf_norms(state["opt"].m)
            readings["grad_norms"] = {
                k: ([x / (1 - b1) for x in v] if isinstance(v, list)
                    else v / (1 - b1)) for k, v in m1.items()}
        for k, v in metrics.items():
            if k.startswith("moe_dropped"):
                extra.setdefault(k, []).append(float(v))
    readings["update_norms"] = prog.leaf_norms(
        state["opt"].master, minus=prog.params0(key))
    jax.block_until_ready(state)
    return state, readings, extra


def reference_run(cell: Cell, ref_mod, devices, key, batches, mode="f32",
                  **options):
    """The reference's checked steps from the seed's weights."""
    import jax

    cfg = cell.config
    weights = jax.jit(lambda k: ref_mod.init_weights(cfg, k))(
        jax.device_put(key, devices[0]))
    return ref_mod.Reference(cfg, devices, mode=mode, **options).train(
        weights, batches, cell.traffic["optimizer"])


def window(prog, key, state, seconds: float, counter: CompileCounter):
    """Whole steps, closed loop, for ``seconds``; returns the last state,
    the non-finite losses, the window's seconds and each step's end."""
    import math

    from jax.profiler import TraceAnnotation

    i, bad, ends = CHECKED_STEPS, 0, []
    counter.reset()
    with TraceAnnotation("window"):
        t0 = time.perf_counter()
        while True:
            i += 1
            with TraceAnnotation("make_batch"):
                batch = prog.feed(key, i)
            with TraceAnnotation("dispatch_step"):
                state, metrics = prog.step(state, batch)
            with TraceAnnotation("read_loss"):
                loss = float(metrics["loss"])
            ends.append(time.perf_counter() - t0)
            bad += not math.isfinite(loss)
            if ends[-1] >= seconds:
                break
    return state, bad, ends[-1], ends


def peak_bytes(devices, temp_bytes: int) -> int:
    """The fullest chip's peak: the allocator's peak of live arrays (state,
    outputs, batches) plus the compiled step's scratch, which the allocator
    does not count."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) + temp_bytes


def run(root: Path, name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, devices=None) -> dict:
    """One run of workload ``name``; returns the result line's object.
    ``devices`` skips the look for a chip (tests on the CPU)."""
    import jax
    import numpy as np

    from perfbench import compare, flops, trace_reduce

    cell = load_cell(root, name)
    if devices is None:
        devices = require_chips(cell.workload["chips"])
    enable_cache(root)
    counter = CompileCounter()
    ref_mod = reference_module(cell)
    prog = Program(cell, devices, ref_mod)
    key = seed_key(seed)
    state, readings, extra = checked_steps(prog, key)
    temp = prog.step_temp_bytes(state, prog.feed(key, CHECKED_STEPS + 1))
    setup_s = time.perf_counter() - t_start
    trace_dir = root / ".perfbench" / "trace" / name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    state, bad, window_s, ends = window(prog, key, state, seconds, counter)
    steps = len(ends)
    if trace:
        jax.profiler.stop_trace()
    in_window = counter.n
    peak = peak_bytes(devices, temp)
    feed, tokens_per_step = prog.feed, prog.tokens_per_step
    prog.executor.close()
    del state, prog
    gc.collect()
    log(f"setup_s {setup_s:.3f} window_s {window_s:.3f} steps {steps} "
        f"compiles_in_window {in_window} peak_bytes {peak} "
        f"step_temp_bytes {temp}")
    log("memory_stats " + json.dumps([d.memory_stats() for d in devices]))
    log("step_s " + json.dumps([round(b - a, 4) for a, b in
                                zip([0.0] + ends, ends)]))
    if extra:
        log("program step metrics (checked steps): " + json.dumps(extra))

    cfg = cell.config
    chip = devices[0]
    ctx = {"setup_s": setup_s, "window_s": window_s, "steps": steps,
           "tokens": steps * tokens_per_step, "peak_bytes": peak,
           "chips": len(devices), "seq_len": cell.traffic["seq_len"],
           "model_flops_per_token": flops.model_flops_per_token(
               cfg, cell.traffic["seq_len"]),
           "trace": None}
    result_device = {"platform": chip.platform, "kind": chip.device_kind,
                     "count": len(devices), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        from perfbench import peaks

        ctx["peak_flops"] = peaks.peaks_for(chip.device_kind).flops
        summary = trace_reduce.reduce_dir(trace_dir, len(devices))
        ctx["trace"] = summary
        result_device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = summary.breakdown()
        shutil.rmtree(trace_dir, ignore_errors=True)

    t_ref = time.perf_counter()
    batches = [np.asarray(feed(key, i)["tokens"])
               for i in range(1, CHECKED_STEPS + 1)]
    ref_out = reference_run(cell, ref_mod, devices, key, batches)
    numbers = compare.gaps(readings, ref_out)
    log(f"reference_s {time.perf_counter() - t_ref:.3f}")
    log("losses program " + json.dumps(readings["losses"]) + " reference "
        + json.dumps(ref_out["losses"]))
    log(f"worst leaves: grad {numbers['grad_gap_leaf']} "
        f"update {numbers['update_gap_leaf']}")
    correct, rows = compare.judge(numbers, cell.limits)
    correct = correct and bad == 0

    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics(section):
        reader = load_module(root / "perfbench" / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": steps, "failed": bad,
              "metrics": metrics, "device": result_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    for n, v, lim in rows:
        log(f"{n} {v!r} limit {lim!r}")
    return result


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
