"""One run of one benchmark cell: set-up, the measured window, the check.

The cell's files are found by the names in ``BENCHMARK.json``:
``perfbench/configs/<config>.json`` (the model as it is run),
``perfbench/traffic/<traffic>.json`` (engine, tiers, mesh, batch, optimizer),
``perfbench/limits/<workload>.json`` (what ``correct`` allows) and
``perfbench/metrics/<metric>.py`` (one reader per metric). The reference
model is ``perfbench/references/<reference>.py``, named by the
configuration.

Nothing here knows an architecture. Of a configuration the harness reads
``name``, ``reference``, ``vocab_size`` (the token ids), ``reduced`` and the
``program`` block: the program's ModelConfig (``program_model``) and where
each of the reference's weights sits in the program's parameter tree
(``leaf_map``); every other key is the reference's. A reference module
exports ``init_weights(cfg, key)`` (the canonical weights, by leaf name),
``Reference(cfg, devices, mode=...)`` whose ``train(weights, batches, hp)``
returns each step's loss and each leaf's norms, and the counts
``matmul_params_per_token(cfg)`` and ``attention_flops_per_token(cfg,
seq_len)``. A metric reader's ``read(ctx)`` gets the window (``setup_s``,
``window_s``, ``steps``, ``tokens``, ``chips``, ``seq_len``, ``peak_bytes``),
the cell's ``config`` and ``reference`` module, ``model_flops_per_token``,
and in a traced run ``trace`` (``trace_reduce.Summary``), ``peak_flops`` and
``step_text``, the compiled step's HLO text (else ``None``).

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
import math
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
    """The program's persistent compile cache, every program kept in it.
    Keyed with the programs' metadata, so that the step's text that the
    readers map to regions carries this checkout's scope names even where
    a cache holds the same program compiled from another checkout."""
    import jax

    sys.path.insert(0, str(root / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def program_model(cfg: dict):
    """The program's ModelConfig from the configuration's ``program`` block:
    the registered ``arch`` with its ``fields`` replaced. An unknown field is
    refused, and so is a ``fixed`` value (one the program cannot change) that
    the configuration runs otherwise, or that its source states otherwise
    without the key in ``reduced``."""
    from repro import configs

    block, name = cfg["program"], cfg["name"]
    base = configs.get(block["arch"])
    unknown = sorted(set(block["fields"]) - {
        f.name for f in dataclasses.fields(base)})
    if unknown:
        raise SystemExit(f"{name}: the program's ModelConfig has no field "
                         f"{', '.join(unknown)}")
    for key, value in block.get("fixed", {}).items():
        if cfg.get(key, value) != value:
            raise SystemExit(f"{name}: the program runs {key}={value}, "
                             f"the configuration asks {cfg[key]}")
        source = cfg.get("published", {}).get(key, value)
        if source != value and key not in cfg.get("reduced", []):
            raise SystemExit(f"{name}: the program runs {key}={value}, the "
                             f"source states {source}: list {key} in "
                             f"'reduced'")
    return dataclasses.replace(base, **block["fields"])


@dataclasses.dataclass(frozen=True)
class Leaf:
    """One canonical leaf of the reference and where the program keeps it."""

    name: str
    path: tuple  # keys into the program's parameter tree
    stacked: bool  # by layer on its leading axis: one norm per layer
    shape: tuple  # the reference's
    program_shape: tuple  # the program's: the same size, or zero-padded

    def place(self, tree: dict, x) -> None:
        """Put canonical ``x`` into the program's ``tree`` (traceable)."""
        import jax.numpy as jnp

        if math.prod(self.shape) == math.prod(self.program_shape):
            x = x.reshape(self.program_shape)
        else:
            x = jnp.pad(x, [(0, b - a) for a, b in
                            zip(self.shape, self.program_shape)])
        for key in self.path[:-1]:
            tree = tree.setdefault(key, {})
        tree[self.path[-1]] = x

    def find(self, tree: dict):
        """The program's leaf in a program-shaped ``tree``."""
        for key in self.path:
            tree = tree[key]
        return tree

    def canonical(self, x):
        """The program's leaf ``x`` as the reference holds it: the inverse
        of ``place``."""
        if self.shape == self.program_shape:
            return x
        if math.prod(self.shape) == math.prod(self.program_shape):
            return x.reshape(self.shape)
        return x[tuple(slice(0, a) for a in self.shape)]


def leaf_map(cfg: dict, canonical: dict, defs) -> list:
    """The configuration's ``program.leaves`` checked against the
    reference's weights (``canonical``: name -> shape and dtype) and the
    program's parameter definitions ``defs``. Each entry is ``{"path":
    "a/b/c", "stacked": bool, "pad": [axis, ...]}``: the program's leaf holds
    the canonical one reshaped where their sizes match, or zero-padded along
    the ``pad`` axes, where the program's is larger (a padded vocabulary)."""
    import jax

    from repro.core.partition import ParamDef

    name = cfg["name"]
    flat, _ = jax.tree_util.tree_flatten_with_path(
        defs, is_leaf=lambda x: isinstance(x, ParamDef))
    program = {"/".join(k.key for k in path): d for path, d in flat}
    entries = cfg["program"]["leaves"]
    filled = [e["path"] for e in entries.values()]
    for what, names in (
            ("the reference's leaves with no path in program.leaves",
             set(canonical) - set(entries)),
            ("program.leaves names leaves the reference does not have",
             set(entries) - set(canonical)),
            ("no leaf of the reference fills the program's",
             set(program) - set(filled))):
        if names:
            raise SystemExit(f"{name}: {what}: {', '.join(sorted(names))}")
    out = []
    for leaf, e in sorted(entries.items()):
        if e["path"] not in program or filled.count(e["path"]) > 1:
            raise SystemExit(f"{name}: {leaf!r} maps to {e['path']!r}, which "
                             f"is not one program leaf of its own")
        d, ref = program[e["path"]], canonical[leaf]
        shape, pshape = tuple(ref.shape), tuple(d.shape)
        larger = [i for i, (a, b) in enumerate(zip(shape, pshape)) if a != b]
        fits = math.prod(shape) == math.prod(pshape) or (
            len(shape) == len(pshape) and set(larger) <= set(e.get("pad", []))
            and all(shape[i] < pshape[i] for i in larger))
        if not fits:
            raise SystemExit(f"{name}: {leaf!r} {shape} does not fit the "
                             f"program's {e['path']!r} {pshape}")
        if str(ref.dtype) != d.dtype:
            raise SystemExit(f"{name}: {leaf!r} is {ref.dtype}, the program "
                             f"keeps {e['path']!r} in {d.dtype}")
        out.append(Leaf(leaf, tuple(e["path"].split("/")),
                        e.get("stacked", False), shape, pshape))
    return out


def program_params(w: dict, leaves: list) -> dict:
    """Canonical weights -> the program's parameter tree (traceable)."""
    tree = {}
    for leaf in leaves:
        leaf.place(tree, w[leaf.name])
    return tree


class Program:
    """The executor, its compiled step and the feed, built from the cell."""

    def __init__(self, cell: Cell, devices, reference):
        import jax
        import jax.numpy as jnp

        from repro.config import (RunConfig, ShapeConfig, TrainConfig,
                                  make_offload, make_parallel)
        from repro.core.executor import InfinityExecutor
        from repro.models import registry
        from repro.optim import adam

        t, cfg = cell.traffic, cell.config
        if t["engine"] != "pjit":
            raise SystemExit(f"traffic {cell.workload['traffic']}: the "
                             f"harness builds pjit-engine state only")
        self.model = program_model(cfg)
        self.leaves = leaf_map(
            cfg, jax.eval_shape(functools.partial(reference.init_weights, cfg),
                                jax.random.PRNGKey(0)),
            registry.build(self.model).defs)
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

        def params(key):
            return program_params(reference.init_weights(cfg, key),
                                  self.leaves)

        def init(key):
            p = params(key)
            return {"params": p, "opt": adam.init_state(p)}

        self.init = jax.jit(init, out_shardings=shardings)
        self.params0 = jax.jit(params, out_shardings=shardings["params"])
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

    def leaf_norms(self, tree, minus=None) -> dict:
        """Norms of each canonical leaf of a parameter-shaped ``tree`` (or of
        ``tree - minus``): one per layer for stacked leaves. One leaf at a
        time, so that what this adds to the device's peak memory does not
        depend on how far the host runs ahead."""
        import jax

        def leaf_of(t, leaf):
            x = leaf.find(t)
            if x.sharding.memory_kind not in (None, "device"):
                x = jax.device_put(x, x.sharding.with_memory_kind("device"))
            return leaf.canonical(x)

        out = {}
        for leaf in self.leaves:
            x = leaf_of(tree, leaf)
            base = None if minus is None else leaf_of(minus, leaf)
            out[leaf.name] = jax.device_get(_norm_fn(leaf.stacked)(x, base))
            del x, base
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


def model_flops_per_token(ref, cfg: dict, seq_len: int) -> float:
    """Model FLOPs of one token of a training step, from the counts of the
    configuration's reference ``ref``: forward and backward are three times
    the forward's multiply-adds, two FLOPs each, over the weights a token
    multiplies with, plus causal attention."""
    return 6 * ref.matmul_params_per_token(cfg) + \
        ref.attention_flops_per_token(cfg, seq_len)


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
            if k != "loss":
                extra.setdefault(k, []).append(jax.device_get(v).tolist())
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

    from perfbench import compare, trace_reduce

    cell = load_cell(root, name)
    if devices is None:
        devices = require_chips(cell.workload["chips"])
    enable_cache(root)
    counter = CompileCounter()
    ref_mod = reference_module(cell)
    prog = Program(cell, devices, ref_mod)
    key = seed_key(seed)
    state, readings, extra = checked_steps(prog, key)
    # the step the window drives, from the cache: its scratch, which the
    # allocator's statistics leave out, and for the readers its text
    compiled = prog.jitted.lower(
        state, prog.feed(key, CHECKED_STEPS + 1)).compile()
    temp = int(compiled.memory_analysis().temp_size_in_bytes)
    step_text = compiled.as_text() if trace else None
    del compiled
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
           "config": cfg, "reference": ref_mod, "step_text": step_text,
           "model_flops_per_token": model_flops_per_token(
               ref_mod, cfg, cell.traffic["seq_len"]),
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
