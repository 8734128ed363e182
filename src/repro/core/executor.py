"""InfinityExecutor: one interface over both ZeRO engines x three tiers.

The paper's claim (Secs. 5-6) is a *single* engine that simultaneously
exploits GPU/TPU HBM, pinned host DRAM, and NVMe with an overlap-centric
schedule — for *all* model states, not just the optimizer. This module is
that unification point for the repo's two engines:

  * ``ZeroInfinityEngine`` (core/engine.py) — GSPMD-native; XLA places the
    ZeRO collectives from shardings.
  * ``ExplicitZero3Engine`` (core/zero.py) — paper-faithful explicit
    collectives in shard_map.

Both satisfy ``EngineProtocol`` (init_state / make_train_step /
jit_train_step / state_shardings / lower_train); ``make_engine`` selects one from
``RunConfig.parallel.engine``. ``InfinityExecutor`` then drives the
configured placement, independently per state class
(``offload.param_tier`` / ``grad_tier`` / ``opt_tier``):

  * in-graph tiers (device, and host via ``memory_kind``) — one jitted
    step; host-tier params/optimizer states stream HBM<->host in-graph.
  * out-of-graph tiers (``opt_offgraph``: NVMe optimizer states and/or
    host/NVMe gradient drains) — the jitted step computes reduce-scattered
    grads; gradients drain into the grad store, and master/m/v stream
    through the opt store with ``ChunkedAdamOffload``'s
    read(k+1) || update(k) || write(k-1) pipeline.
  * ``param_tier="nvme"`` — bf16 params are slow-tier resident and the
    *layer scheduler* (``core/schedule.py``) owns the step's movement. On
    the explicit engine the monolithic step is replaced by a layered epoch:
    each rank's per-layer row (the paper's per-worker NVMe partition, keyed
    ``rank<r>/c<layer>``) is prefetched inside a bounded window, materialized
    just-in-time for its gather, and evicted immediately after use — forward
    order, then reversed for the backward — so peak device residency of the
    flat params is O(window), not O(L), and the carried ``flat`` leaf is
    dropped between steps. The GSPMD engine streams its parameter leaves
    through the same scheduler (per-leaf window) before each jitted step.
    Scheduler step metrics: ``peak_resident_param_bytes``,
    ``prefetch_hit_rate``, ``evictions``.

Every store shares one ``PinnedBufferPool`` (the paper's fixed pinned-
memory supply), and per-step metrics surface per-tier bandwidth counters:
``param_in_*`` / ``param_out_*``, ``grad_out_*``, ``opt_read_*`` /
``opt_write_*`` — per-step deltas, so the benchmark harness can report an
effective-bandwidth roofline per tier.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from repro.config import RunConfig, ShapeConfig
from repro.core import qformat
from repro.core import schedule as sched_mod
from repro.core.engine import ZeroInfinityEngine
from repro.core.offload import (ArrayStore, ChunkedAdamOffload, HostArrayStore,
                                NvmeStore, ParamStreamer, PinnedBufferPool)
from repro.core.zero import ExplicitZero3Engine
from repro.optim import adam as adam_mod
from repro.runtime import trace


@runtime_checkable
class EngineProtocol(Protocol):
    """The contract both ZeRO engines implement."""

    def init_state(self, rng: jax.Array): ...

    def make_train_step(self, *, grads_only: bool = False): ...

    def jit_train_step(self, *, grads_only: bool = False): ...

    def state_shardings(self): ...

    def lower_train(self, shape: ShapeConfig, *, grads_only: bool = False): ...


def make_engine(run: RunConfig, mesh) -> EngineProtocol:
    """RunConfig.parallel.engine -> engine instance ('pjit' | 'zero3')."""
    if run.parallel.engine == "zero3":
        return ExplicitZero3Engine(run, mesh)
    return ZeroInfinityEngine(run, mesh)


def _flatten_with_paths(tree) -> Dict[str, np.ndarray]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path): leaf for path, leaf in flat}


def _unflatten_like(like, flat: Dict[str, np.ndarray]):
    leaves, _ = jax.tree_util.tree_flatten_with_path(like)
    vals = [jnp.asarray(flat[jax.tree_util.keystr(path)]).astype(leaf.dtype)
            for path, leaf in leaves]
    return jax.tree.unflatten(jax.tree.structure(like), vals)


class InfinityExecutor:
    """Drives an engine through the configured three-tier placement.

    ``train_step(state, batch)`` is a host-level callable with one signature
    for every (engine, param/grad/opt tier) combination; per-step metrics
    always include loss/grad_norm/lr and, for every slow-tier state class,
    that tier's measured per-step bandwidth counters.
    """

    def __init__(self, run: RunConfig, mesh, *,
                 engine: Optional[EngineProtocol] = None, plan=None):
        self.run = run
        self.mesh = mesh
        # optional repro.plan.InfinityPlan: its predictions are cross-checked
        # against the measured counters and reported in step metrics
        self.plan = plan
        self.engine = engine if engine is not None else make_engine(run, mesh)
        self.is_explicit = isinstance(self.engine, ExplicitZero3Engine)
        # explicit-engine MoE: expert rows are independent schedule units
        self.is_moe = bool(getattr(self.engine, "is_moe", False))
        off = run.offload
        self.offgraph = run.opt_offgraph
        self.param_nvme = off.param_tier == "nvme"
        self.grad_offload = off.grad_tier != "device"
        # layered epoch: the explicit engine's rows iterate through the
        # scheduler's window instead of ever assembling the (L, P) flat
        self.layered = self.is_explicit and self.param_nvme
        if self.layered and run.parallel.partition_mode != "allgather":
            # fail at construction, not mid-training: the layered epoch
            # assumes the bandwidth-centric row layout (every rank holds a
            # slice of every layer); the broadcast baseline stores whole
            # layers per owner rank and has no per-rank row to stream
            raise ValueError(
                "param_tier='nvme' on the explicit engine requires "
                "partition_mode='allgather' (the layer scheduler streams "
                "per-rank rows); broadcast is the non-scaling contrast "
                "baseline — keep params on the device/host tier for it")
        if self.layered and run.parallel.grad_compression != "none":
            raise ValueError(
                "grad_compression='int8' applies to the monolithic step's "
                "replicated-grad reduce; the layered epoch "
                "(param_tier='nvme' + zero3) reduce-scatters rows through "
                "the all-gather transpose and is not compressed")
        # shared pinned staging budget across all of this executor's stores
        self._pool = PinnedBufferPool(off.pinned_buffer_mb << 20)
        self.opt_store: Optional[ArrayStore] = None
        self.grad_store: Optional[ArrayStore] = None
        self.param_store: Optional[ArrayStore] = None
        self.offload: Optional[ChunkedAdamOffload] = None
        self.param_stream: Optional[ParamStreamer] = None
        self._rank_of = {d: r for r, d in enumerate(np.asarray(mesh.devices).flat)}
        self._step_fn = None
        self._param_shardings_cache = None
        self._param_shard_by_name = None
        # scheduler state (param_tier=nvme): working-set accounting shared by
        # both engines' streaming paths; plan/prefetcher built lazily (the
        # bandwidth-aware default window needs the batch token count)
        self._ws = sched_mod.WorkingSetManager()
        self._sched: Optional[sched_mod.LayerSchedule] = None
        self._pe: Optional[sched_mod.PrefetchEngine] = None
        self._pe_stream: Optional[ParamStreamer] = None
        self._sched_tokens: Optional[int] = None
        self._layer_fns = None
        self._param_template = None  # struct tree for dropped carried leaves
        self._eflat_template = None
        # dynamic expert paging (MoE layered epoch): its own PrefetchEngine
        # over ("x", layer, expert) units sharing the working-set manager,
        # plus the hot-expert cache and the popularity predictor
        self._pe_x: Optional[sched_mod.PrefetchEngine] = None
        self._pe_x_stream: Optional[ParamStreamer] = None
        self._hot: Optional[sched_mod.HotUnitCache] = None
        self._pop: Optional[sched_mod.ExpertPopularity] = None
        # per-step stall attribution (populated when the tracer is enabled):
        # each step appends its attribute_window() dict, so CLI surfaces can
        # format the run-level report without re-deriving from raw spans
        self._trace_t0: Optional[float] = None
        self._trace_tid: Optional[int] = None
        self.trace_attributions: list = []

    def close(self) -> None:
        """Flush and shut down the slow-tier stores (worker threads, pinned
        staging). The elastic supervisor tears an incarnation's executor
        down with this before building a replacement over the surviving
        membership; a closed executor must not step again."""
        for store in (self.param_store, self.grad_store, self.opt_store):
            if store is not None:
                store.close()
        self.param_store = self.grad_store = self.opt_store = None
        self.param_stream = self.offload = None
        self._step_fn = None

    # ------------------------------------------------------------------
    # state
    # ------------------------------------------------------------------

    def init_state(self, rng: jax.Array, *, seed_stores: bool = True):
        """Engine init + slow-tier store seeding. Pass ``seed_stores=False``
        when a checkpoint restore (which re-seeds from the restored state)
        immediately follows — it skips a throwaway full-model store write.
        With slow-tier-resident params and ``seed_stores=True`` the returned
        state carries placeholder structs for the param leaves (the store is
        authoritative; the device never holds the assembled copy)."""
        state = self.engine.init_state(rng)
        if seed_stores:
            state = self.reseed(state)
        return state

    def _make_store(self, tier: str, name: str) -> ArrayStore:
        """Slow-tier store for one state class; NVMe stores get their own
        subdirectory (key namespaces never collide across classes) and all
        stores share the executor's pinned pool and worker-thread count.
        With ``offload.param_quant`` set, the *param* store is wrapped in
        ``QuantizedArrayStore``: rows cross the tier (and occupy the pinned
        staging pool) in block-quantized wire bytes, decoded on read."""
        off = self.run.offload
        if tier == "nvme":
            store = NvmeStore(os.path.join(off.nvme_dir, name), pool=self._pool,
                              overlap=off.overlap, workers=off.nvme_workers)
        else:
            store = HostArrayStore(pool=self._pool, overlap=off.overlap,
                                   workers=off.nvme_workers)
        store.trace_cls = name  # tag this class's I/O spans for attribution
        if name == "param":
            store = qformat.maybe_wrap_store(store, off.param_quant)
        return store

    def reseed(self, state, step: int = 0):
        """(Re)populate the slow-tier stores from ``state`` — called by
        ``init_state`` and after a checkpoint restore (m/v restart at zero,
        matching an optimizer-state-free checkpoint). Returns the carried
        state: with slow-tier-resident params the param leaves are dropped
        to placeholder structs (peak resident param bytes stays O(window)
        between steps, not O(L))."""
        off = self.run.offload
        erows = None
        if self.is_explicit and (self.offgraph or self.param_nvme):
            assert not isinstance(state["flat"], jax.ShapeDtypeStruct), (
                "reseed needs materialized params; use materialized state "
                "(portable_state / checkpoint_state) to re-enter")
            # A checkpoint-restored flat may live on one device — re-shard
            # first so the rank partition matches the mesh.
            flat = jax.device_put(state["flat"],
                                  self.engine.state_shardings()["flat"])
            if self.is_moe:
                assert not isinstance(state["eflat"], jax.ShapeDtypeStruct)
                eflat = jax.device_put(state["eflat"],
                                       self.engine.state_shardings()["eflat"])
                erows = self._rank_arrays(eflat)  # {rank: (L*E, Pe/dp)}
        if self.offgraph:
            # stores are reused across reseeds (restart/restore re-enters
            # here): their worker threads and cumulative counters persist,
            # only the contents are rewritten
            if self.opt_store is None:
                self.opt_store = self._make_store(off.opt_tier, "opt")
            self.offload = ChunkedAdamOffload(self.opt_store)
            if self.layered:
                # per-layer per-rank key namespaces, inserted in backward
                # (production) order so the streamed update consumes grads
                # as the reversed pass emits them; MoE expert rows
                # ("xrank<r>/l<layer*E+e>") precede their layer's dense row —
                # the backward waves emit expert grads before the attn vjp
                rows = self._rank_arrays(flat)
                seed: Dict[str, np.ndarray] = {}
                for li in range(rows[next(iter(rows))].shape[0] - 1, -1, -1):
                    if erows is not None:
                        E = self.engine.n_experts
                        for e in range(E):
                            for r in sorted(erows):
                                seed[f"xrank{r}/l{li * E + e}"] = \
                                    erows[r][li * E + e].astype(np.float32)
                    for r in sorted(rows):
                        seed[f"rank{r}/l{li}"] = rows[r][li].astype(np.float32)
                self.offload.init_from_params(seed)
            elif self.is_explicit:
                # seed per-rank key namespaces with the f32 view of each
                # rank's (L, P/dp) bf16 shard (exact: bf16 -> f32 is
                # lossless) — the paper's per-worker slow-tier partition.
                self.offload.init_from_params(self._rank_shards(flat))
            else:
                self.offload.init_from_params(
                    {k: np.asarray(v) for k, v in
                     _flatten_with_paths(state["params"]).items()})
            self.offload.step_count = step
        if self.grad_offload and self.grad_store is None:
            self.grad_store = self._make_store(off.grad_tier, "grad")
        if self.param_nvme:
            if self.param_store is None:
                self.param_store = self._make_store("nvme", "param")
            self.param_stream = ParamStreamer(self.param_store,
                                              read_ahead=off.param_read_ahead)
            if self.is_explicit:
                named = {f"rank{r}": a for r, a in
                         self._rank_arrays(flat).items()}
                if erows is not None:
                    named.update({f"xrank{r}": a for r, a in erows.items()})
                self.param_stream.seed(named, row_split=True)
            else:
                self.param_stream.seed(
                    {k: np.asarray(v) for k, v in
                     _flatten_with_paths(state["params"]).items()},
                    row_split=False)
            state = self._drop_param_leaves(state)
        return state

    # ------------------------------------------------------------------
    # slow-tier-resident param leaves: placeholders + on-demand assembly
    # ------------------------------------------------------------------

    def _param_placeholder(self):
        """Struct tree standing in for the dropped param leaves (shape /
        dtype / sharding preserved so checkpoint templates still match)."""
        if self._param_template is None:
            if self.is_explicit:
                sh = self.engine.state_shardings()["flat"]
                L, Pl = self.engine.n_layers, self.engine.layout.padded
                self._param_template = jax.ShapeDtypeStruct(
                    (L, Pl), jnp.bfloat16, sharding=sh)
            else:
                self._param_template = self.engine.param_specs()
        return self._param_template

    def _eflat_placeholder(self):
        if self._eflat_template is None:
            eng = self.engine
            self._eflat_template = jax.ShapeDtypeStruct(
                (eng.n_layers * eng.n_experts, eng.elayout.padded),
                jnp.bfloat16, sharding=eng.state_shardings()["eflat"])
        return self._eflat_template

    def _drop_param_leaves(self, state):
        state = dict(state)
        key = "flat" if self.is_explicit else "params"
        state[key] = self._param_placeholder()
        if self.is_moe:
            state["eflat"] = self._eflat_placeholder()
        return state

    @staticmethod
    def _is_dropped(leaf_or_tree) -> bool:
        leaves = jax.tree.leaves(leaf_or_tree)
        return bool(leaves) and isinstance(leaves[0], jax.ShapeDtypeStruct)

    @property
    def total_param_bytes(self) -> int:
        """Global bytes of the scheduler-managed (windowed) parameters —
        the denominator of the never-fully-resident claim."""
        if not self.param_nvme:
            return 0
        tpl = [self._param_placeholder()]
        if self.is_moe:
            tpl.append(self._eflat_placeholder())
        return sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize
                   for t in tpl for l in jax.tree.leaves(t))

    @property
    def expert_total_bytes(self) -> int:
        """Global bytes of all expert rows — the denominator of the
        expert-paging claim (peak resident expert bytes << this)."""
        if not (self.param_nvme and self.is_moe):
            return 0
        l = self._eflat_placeholder()
        return int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize

    def _materialize_rows(self):
        """Assemble the full row sets from the param store — checkpoint path
        only; the training step never calls this. Returns (flat, eflat|None).
        """
        loaded = self.param_stream.load_all()
        flat = self._flat_from_ranks(
            {int(k[len("rank"):]): v for k, v in loaded.items()
             if k.startswith("rank")},
            like=self._param_placeholder())
        eflat = None
        if self.is_moe:
            eflat = self._flat_from_ranks(
                {int(k[len("xrank"):]): v for k, v in loaded.items()
                 if k.startswith("xrank")},
                like=self._eflat_placeholder())
        return flat, eflat

    def _materialize_flat(self):
        return self._materialize_rows()[0]

    def _materialize_params(self, like_tree):
        """GSPMD engine: assemble the parameter pytree from the store."""
        loaded = self.param_stream.load_all()
        if self._param_shardings_cache is None:
            self._param_shardings_cache = self.engine.state_shardings()["params"]
            self._param_shard_by_name = _flatten_with_paths(
                self._param_shardings_cache)
        return jax.device_put(_unflatten_like(like_tree, loaded),
                              self._param_shardings_cache)

    def checkpoint_state(self, state) -> dict:
        """``state`` with any dropped param leaves materialized from the
        store — what the full-state checkpoint path should persist."""
        if not self.param_nvme:
            return state
        state = dict(state)
        if self.is_explicit and self._is_dropped(state["flat"]):
            state["flat"], eflat = self._materialize_rows()
            if eflat is not None:
                state["eflat"] = eflat
        elif not self.is_explicit and self._is_dropped(state["params"]):
            state["params"] = self._materialize_params(state["params"])
        return state

    def state_shardings(self):
        return self.engine.state_shardings()

    def input_specs(self, shape: ShapeConfig):
        eng = self.engine
        return (eng.bundle.input_specs(shape) if hasattr(eng, "bundle")
                else eng.input_specs(shape))

    def batch_shardings(self, shape: ShapeConfig):
        return {k: self.engine.batch_sharding(v)
                for k, v in self.input_specs(shape).items()}

    def n_params_active(self) -> int:
        eng = self.engine
        return (eng.bundle.n_params_active() if hasattr(eng, "bundle")
                else eng.n_params_active())

    # ------------------------------------------------------------------
    # tier-independent checkpoint views
    # ------------------------------------------------------------------

    def portable_state(self, state) -> dict:
        """The tier-independent subtree of ``state`` — the leaves whose
        presence/layout does not depend on the offload configuration, so a
        checkpoint of it restores into an executor at *any* tier. Dropped
        slow-tier param leaves are materialized from the store on the way
        out (a full assembly, but only on the checkpoint path)."""
        state = self.checkpoint_state(state)
        if self.is_explicit:
            keys = ("flat", "other", "other_opt", "step")
            if self.is_moe:
                keys += ("eflat",)
            return {k: state[k] for k in keys}
        return {"params": state["params"]}

    def adopt_state(self, portable: dict, *, step: int = 0):
        """Portable leaves -> a full state for this executor's tiers.

        Streamed/in-graph optimizer moments restart at zero (the portable
        checkpoint is optimizer-state-free for the big shards; the small
        replicated 'other_opt' rides along on the explicit engine), and the
        slow-tier stores are reseeded from the restored params.
        """
        shardings = self.engine.state_shardings()
        if self.is_explicit:
            state = dict(portable)
            state = jax.device_put(
                state, {k: shardings[k] for k in state})
            if getattr(self.engine, "grad_compress", False):
                # residuals restart at zero (rank-local quantization error
                # is not portable across tier/topology changes)
                state["g_err"] = self.engine.init_g_err()
            if not self.offgraph:
                flat32 = state["flat"].astype(jnp.float32)
                state["master"] = jax.device_put(flat32, shardings["master"])
                state["m"] = jax.device_put(jnp.zeros_like(flat32), shardings["m"])
                state["v"] = jax.device_put(jnp.zeros_like(flat32), shardings["v"])
        else:
            params = jax.device_put(portable["params"], shardings["params"])
            state = {"params": params}
            if not self.offgraph:
                master = jax.tree.map(lambda p: p.astype(jnp.float32), params)
                zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                     params)
                opt = adam_mod.AdamState(jnp.asarray(step, jnp.int32), master,
                                         zeros, zeros)
                state["opt"] = jax.device_put(opt, shardings["opt"])
        return self.reseed(state, step=step)

    # ------------------------------------------------------------------
    # the unified train step
    # ------------------------------------------------------------------

    def make_train_step(self):
        """The step, numbered and under the profiler's step span
        (``trace.StepFn``); ``lower`` reaches the jitted step where there
        is one."""
        if self._step_fn is None:
            self._step_fn = trace.StepFn(self._build_train_step())
        return self._step_fn

    def _build_train_step(self):
        if self.layered:
            # scheduler-driven layered epoch: no monolithic jitted step at
            # all — per-layer fns iterate rows through the prefetch window
            return (self._layered_moe_step() if self.is_moe
                    else self._layered_step())
        jit_step = self.engine.jit_train_step(grads_only=self.offgraph)

        if not self.offgraph and not self.param_nvme:
            return jit_step  # fully in-graph (device/host tiers)
        if not self.offgraph:
            # GSPMD in-graph update; only params stream (scheduler-fed)
            inner = jit_step
        else:
            inner = (self._explicit_offgraph_step(jit_step)
                     if self.is_explicit
                     else self._gspmd_offgraph_step(jit_step))
        return self._instrumented(inner)

    def train_step(self, state, batch):
        return self.make_train_step()(state, batch)

    def lower_train(self, shape: ShapeConfig):
        return self.engine.lower_train(shape, grads_only=self.offgraph)

    # ------------------------------------------------------------------
    # slow-tier step variants
    # ------------------------------------------------------------------

    def _instrumented(self, inner):
        """Wrap a step with param streaming (slow-tier resident params) and
        per-step per-tier bandwidth metrics."""

        def step(state, batch):
            self._trace_step_begin()
            marks = {name: s.mark() for name, s in self._active_stores()}
            if self.param_nvme:
                self._ws.begin_step()
                state = self._load_params(state)
            with trace.span("jit_step", sys="compute", attr="compute"):
                new_state, metrics = inner(state, batch)
                if trace.enabled():
                    # jit dispatch is async; land the device work inside the
                    # compute span so attribution sees it on the main thread
                    jax.block_until_ready(metrics)
            if self.param_nvme:
                self._save_params(new_state)
                new_state = self._drop_param_leaves(new_state)
            if self.grad_store is not None:
                self.grad_store.flush()  # retire this step's drain futures
            return new_state, self._with_tier_metrics(metrics, marks)

        return step

    def _explicit_offgraph_step(self, jit_step):
        tc = self.run.train

        def step(state, batch):
            new_state, g32, metrics = jit_step(state, batch)
            gflat = self._rank_shards(g32)
            if self.grad_offload:
                gflat = self._drain_grads(gflat)
            new_master = self.offload.step(
                gflat, lr=float(metrics["lr"]),
                beta1=tc.beta1, beta2=tc.beta2, eps=tc.eps,
                weight_decay=tc.weight_decay)
            new_state = dict(new_state)
            new_state["flat"] = self._assemble_flat(new_master, like=state["flat"])
            return new_state, metrics

        return step

    def _gspmd_offgraph_step(self, jit_step):
        tc = self.run.train
        param_host = self.run.offload.param_tier == "host"
        # sharding pytree built once, not per step (it's a full tree walk)
        param_shardings = (self.engine.state_shardings()["params"]
                           if param_host else None)

        def step(state, batch):
            grads, metrics = jit_step(state, batch)
            gflat = {k: np.asarray(v).astype(np.float32)
                     for k, v in _flatten_with_paths(grads).items()}
            if self.grad_offload:
                gflat = self._drain_grads(gflat)
            lr = float(adam_mod.lr_at(tc, jnp.int32(self.offload.step_count + 1)))
            new_flat = self.offload.step(gflat, lr=lr, beta1=tc.beta1,
                                         beta2=tc.beta2, eps=tc.eps,
                                         weight_decay=tc.weight_decay)
            new_state = dict(state)
            params = _unflatten_like(state["params"], new_flat)
            if param_host:
                # keep the configured pinned-host residency after the
                # host-side rebuild (plain jnp arrays land in device memory)
                params = jax.device_put(params, param_shardings)
            new_state["params"] = params
            return new_state, dict(metrics, lr=lr)

        return step

    # ------------------------------------------------------------------
    # gradient drain (host/NVMe tier)
    # ------------------------------------------------------------------

    def _drain_grads(self, gflat: Dict[str, np.ndarray]) -> Dict[str, object]:
        """Drain reduce-scattered fp32 grad shards to the grad tier. Each
        leaf becomes a write-then-read ``roundtrip`` future resolving to the
        store-resident copy; ``ChunkedAdamOffload.step`` resolves a leaf only
        when its first chunk reaches the update stage, so later leaves'
        drains overlap earlier leaves' read/update/write pipeline work."""
        return {k: self.grad_store.roundtrip(f"{k}/g", g)
                for k, g in gflat.items()}

    # ------------------------------------------------------------------
    # slow-tier resident parameters (scheduler-driven)
    # ------------------------------------------------------------------

    def _ensure_row_scheduler(self, batch):
        """Plan + prefetcher over the explicit engine's per-layer rows.
        Rebuilt whenever ``reseed`` swapped the underlying streamer or — for
        the bandwidth-aware auto window (``prefetch_layers=0``, the paper's
        Sec. 3-4 model) — whenever the batch token count changes."""
        off = self.run.offload
        tokens = int(np.prod(batch["tokens"].shape))
        stale = (self._sched is None or self._pe_stream is not self.param_stream
                 or (not off.prefetch_layers and tokens != self._sched_tokens))
        if stale:
            L = self.engine.n_layers
            window = off.prefetch_layers
            if not window:
                window = sched_mod.default_prefetch_layers(
                    L, self.engine.layout.padded, tokens,
                    compression_ratio=qformat.compression_ratio(
                        off.param_quant))
            self._sched_tokens = tokens
            ranks = sorted(self._rank_of.values())
            stream = self.param_stream

            def fetch(layer):
                return [stream.read_row(f"rank{r}", layer) for r in ranks]

            self._sched = sched_mod.LayerSchedule(
                L, window, read_ahead=off.param_read_ahead)
            self._pe = sched_mod.PrefetchEngine(fetch, self._ws,
                                                trace_cls="param")
            self._pe_stream = stream
        return self._sched, self._pe

    def _ensure_leaf_scheduler(self):
        """GSPMD engine: the same scheduler over whole parameter leaves —
        at most ``window`` leaves staged in host memory at once while the
        rest are still in flight or already handed to the device."""
        if self._sched is None or self._pe_stream is not self.param_stream:
            off = self.run.offload
            names = self.param_stream.names()
            window = off.prefetch_layers or max(2, off.param_read_ahead)
            stream = self.param_stream

            def fetch(i):
                return [stream.read_row(names[i], 0)]

            self._sched = sched_mod.LayerSchedule(
                len(names), window, read_ahead=off.param_read_ahead)
            self._pe = sched_mod.PrefetchEngine(fetch, self._ws,
                                                trace_cls="param")
            self._pe_stream = stream
        return self.param_stream.names(), self._sched, self._pe

    def _load_params(self, state):
        """Materialize params from the param store through the scheduler —
        per-leaf prefetch window, each leaf device_put as it lands and its
        host staging copy evicted immediately (the store copy, not the
        carried state leaf, feeds the step)."""
        names, sched, pe = self._ensure_leaf_scheduler()
        if self._param_shardings_cache is None:  # one tree walk, cached
            self._param_shardings_cache = self.engine.state_shardings()["params"]
            self._param_shard_by_name = _flatten_with_paths(
                self._param_shardings_cache)
        shard_by_name = self._param_shard_by_name
        host: Dict[int, np.ndarray] = {}
        on_device: Dict[str, jax.Array] = {}

        def use(i):
            name = names[i]
            on_device[name] = jax.device_put(host[i], shard_by_name[name])

        pe.run_events(sched.forward(),
                      on_materialize=lambda i, vals: host.__setitem__(i, vals[0]),
                      on_use=use,
                      on_evict=lambda i: host.pop(i, None))
        state = dict(state)
        leaves, _ = jax.tree_util.tree_flatten_with_path(state["params"])
        state["params"] = jax.tree.unflatten(
            jax.tree.structure(state["params"]),
            [on_device[jax.tree_util.keystr(path)] for path, _ in leaves])
        return state

    def _save_params(self, new_state) -> None:
        """Write the step's updated params back to the param store."""
        with trace.span("param_writeback", sys="optim", attr="io_wait",
                        cls="param"):
            self.param_stream.save_all(
                {k: np.asarray(v) for k, v in
                 _flatten_with_paths(new_state["params"]).items()})

    # ------------------------------------------------------------------
    # the layered epoch (explicit engine, param_tier=nvme)
    # ------------------------------------------------------------------

    def _device_row(self, vals, sharding):
        """Per-rank host rows (rank order) -> global (P,) device row."""
        with trace.span("h2d_row", sys="store", cls="param"):
            devices = list(np.asarray(self.mesh.devices).flat)
            pieces = [jax.device_put(vals[self._rank_of[d]], d)
                      for d in devices]
            shape = (sum(int(v.shape[0]) for v in vals),)
            return jax.make_array_from_single_device_arrays(
                shape, sharding, pieces)

    def _layered_step(self):
        """One train step as two scheduler-driven passes over the layers.

        Forward materializes each layer's row just-in-time inside the
        prefetch window and evicts it right after the layer's compute; the
        backward pass re-materializes in reverse (the paper's "loaded one
        additional time" with per-layer recompute), reduce-scatters each
        layer's gradient shard, and hands it — optionally via the grad-tier
        drain — to the streamed per-layer Adam, whose updated bf16 rows are
        written straight back to the store. The full (L, P) flat array is
        never assembled on device or host, so ``peak_resident_param_bytes``
        is O(window), not O(L).
        """
        eng = self.engine
        tc = self.run.train

        def step(state, batch):
            self._trace_step_begin()
            marks = {name: s.mark() for name, s in self._active_stores()}
            if self._layer_fns is None:
                self._layer_fns = eng.make_layer_fns()
            fns = self._layer_fns
            sched, pe = self._ensure_row_scheduler(batch)
            self._ws.begin_step()
            row_sh = eng.layer_row_sharding()
            rows: Dict[int, jax.Array] = {}

            def run_pass(events, use_fn):
                pe.run_events(
                    events,
                    on_materialize=lambda l, vals: rows.__setitem__(
                        l, self._device_row(vals, row_sh)),
                    on_use=use_fn,
                    # evict: drop the device row the moment use ends
                    on_evict=lambda l: rows.pop(l, None))

            # ---- forward ----
            x = fns["embed_fwd"](state["other"], batch["tokens"])
            acts: Dict[int, jax.Array] = {}

            def fwd_use(layer):
                nonlocal x
                acts[layer] = x  # the layer's input (its recompute seed)
                x = fns["layer_fwd"](x, rows[layer])

            run_pass(sched.forward(), fwd_use)

            # ---- head + reversed layer pass ----
            loss, dx, g_head = fns["head"](x, state["other"], batch["labels"])
            gdict: Dict[str, object] = {}
            # grad-norm sum-of-squares accumulates ON DEVICE: one psum per
            # layer folded into a carried scalar, consumed directly by the
            # jitted `finish` — no per-layer host-float synchronization
            sumsq = jnp.zeros((), jnp.float32)

            def bwd_use(layer):
                nonlocal dx, sumsq
                dx, g_row = fns["layer_vjp"](acts.pop(layer), rows[layer], dx)
                sumsq = fns["accum_sumsq"](sumsq, g_row)
                # hand the store the *device* shards: the host pull runs on
                # the store worker (or lazily at the opt step), so the next
                # layer's vjp dispatches immediately
                for r, g in self._rank_device(g_row).items():
                    key = f"rank{r}/l{layer}"
                    gdict[key] = (self.grad_store.roundtrip(f"{key}/g", g)
                                  if self.grad_offload else g)

            run_pass(sched.backward(), bwd_use)

            g_emb = fns["embed_vjp"](state["other"], batch["tokens"], dx)
            new_other, new_other_opt, new_step, fm = fns["finish"](
                state["other"], state["other_opt"], state["step"],
                g_head, g_emb, sumsq)

            # pulling lr to host synchronizes on `finish` — and transitively
            # on the whole dispatched forward/backward: this is where the
            # step's device compute lands on the critical path
            with trace.span("device_sync", sys="compute", attr="compute"):
                lr_host = float(fm["lr"])

            # streamed per-layer Adam; updated bf16 rows go straight back
            new_master = self.offload.step(
                gdict, lr=lr_host, beta1=tc.beta1, beta2=tc.beta2,
                eps=tc.eps, weight_decay=tc.weight_decay)
            with trace.span("param_writeback", sys="optim", cls="param"):
                for key, m32 in new_master.items():
                    rank, layer = key.split("/")  # "rank<r>/l<i>"
                    self.param_stream.write_row(
                        rank, int(layer[1:]), m32.astype(ml_dtypes.bfloat16))
                self.param_stream.flush()
            if self.grad_store is not None:
                self.grad_store.flush()

            new_state = {"flat": self._param_placeholder(), "other": new_other,
                         "other_opt": new_other_opt, "step": new_step}
            metrics = {"loss": loss, "grad_norm": fm["grad_norm"],
                       "lr": fm["lr"]}
            return new_state, self._with_tier_metrics(metrics, marks)

        return step

    # ------------------------------------------------------------------
    # the MoE layered epoch: dynamic expert schedule units
    # ------------------------------------------------------------------

    def _ensure_expert_paging(self):
        """Dynamic-unit machinery over ``("x", layer, expert)`` rows: a
        second ``PrefetchEngine`` (class tag ``expert``) sharing the
        working-set manager, the byte-budgeted hot-expert cache, and the
        popularity EMA that predicts prefetches before the router runs.
        Rebuilt when ``reseed`` swapped the underlying streamer."""
        if self._pe_x is not None and self._pe_x_stream is self.param_stream:
            return self._pe_x, self._hot, self._pop
        if self._hot is not None:
            self._hot.clear()
        eng = self.engine
        ranks = sorted(self._rank_of.values())
        stream = self.param_stream
        E = eng.n_experts

        def fetch(unit):
            _, l, e = unit
            return [stream.read_row(f"xrank{r}", l * E + e) for r in ranks]

        self._pe_x = sched_mod.PrefetchEngine(fetch, self._ws, cls="expert")
        budget = sched_mod.resolve_expert_hot_bytes(
            self.run.offload.expert_hot_mb, eng.top_k, eng.elayout.padded * 2)
        self._hot = sched_mod.HotUnitCache(budget, self._pe_x)
        self._pop = sched_mod.ExpertPopularity()
        self._pe_x_stream = stream
        return self._pe_x, self._hot, self._pop

    @staticmethod
    def _expert_waves(sel, W):
        """Selected expert ids -> fixed-width waves (real_ids, padded ids
        array, mask array). Fixed width keeps the wave fns at one jit
        signature; padding repeats a real id with a zero mask (exactly zero
        output/gradient, see models/moe.py)."""
        waves = []
        for i in range(0, len(sel), W):
            wave = sel[i:i + W]
            pad = W - len(wave)
            ids = np.asarray(wave + [wave[-1]] * pad, np.int32)
            mask = np.asarray([1.0] * len(wave) + [0.0] * pad, np.float32)
            waves.append((wave, ids, mask))
        return waves

    def _layered_moe_step(self):
        """One MoE train step where a layer expands into heterogeneous
        schedule units: its dense row (ln1+attn+ln2) follows the static
        layer plan, while its expert rows page dynamically — the router's
        counts (one small host sync per layer) pick the selected set, which
        streams through fixed-width waves of ``top_k`` rows; evict-bound
        rows are offered to the hot-expert cache and predicted-hot rows
        prefetch alongside the static plan's horizon. Peak expert residency
        is O(wave + hot budget), never O(E)."""
        eng = self.engine
        tc = self.run.train
        E = eng.n_experts
        W = max(1, eng.top_k)
        L = eng.n_layers

        def step(state, batch):
            self._trace_step_begin()
            marks = {name: s.mark() for name, s in self._active_stores()}
            if self._layer_fns is None:
                self._layer_fns = eng.make_layer_fns()
            fns = self._layer_fns
            sched, pe = self._ensure_row_scheduler(batch)
            pe_x, hot, pop = self._ensure_expert_paging()
            self._ws.begin_step()
            row_sh = eng.layer_row_sharding()
            ranks = sorted(self._rank_of.values())
            rows: Dict[int, jax.Array] = {}
            router = state["other"]["router"]
            sel_by_layer: Dict[int, list] = {}
            drop_fracs, loads = [], []

            def run_pass(events, use_fn, predict_fn):
                # piggyback predicted expert prefetches on the static plan's
                # horizon: when layer l's dense row enters the window, the
                # predicted-hot (forward) or known-selected (backward) expert
                # rows start reading too
                def on_prefetch(l):
                    for e in predict_fn(l):
                        u = ("x", l, e)
                        if u not in hot:
                            pe_x.prefetch(u)

                pe.run_events(
                    events,
                    on_materialize=lambda l, vals: rows.__setitem__(
                        l, self._device_row(vals, row_sh)),
                    on_use=use_fn,
                    on_evict=lambda l: rows.pop(l, None),
                    on_prefetch=on_prefetch)

            def wave_rows(l, wave):
                """Materialize one wave's device rows (hot hits are free)."""
                fresh, rws = [], []
                for e in wave:
                    u = ("x", l, e)
                    payload = hot.get(u)
                    if payload is None:
                        payload = self._device_row(pe_x.materialize(u), row_sh)
                        fresh.append((u, payload))
                    rws.append(payload)
                while len(rws) < W:
                    rws.append(rws[-1])
                return jnp.stack(rws), fresh

            def retire(l, fresh):
                for u, payload in fresh:
                    if not hot.offer(u, payload,
                                     nbytes=eng.elayout.padded * 2,
                                     popularity=pop.score(l, u[2])):
                        pe_x.evict(u)  # idempotent if offer already dropped

            def start_reads(l, sel):
                for e in sel:
                    u = ("x", l, e)
                    if u not in hot:
                        pe_x.prefetch(u)

            # ---- forward ----
            x = fns["embed_fwd"](state["other"], batch["tokens"])
            acts: Dict[int, jax.Array] = {}

            def fwd_use(l):
                nonlocal x
                acts[l] = x
                x_mid, counts_e, dropped, routed = fns["moe_attn"](
                    x, rows[l], router[l])
                # the one per-layer host sync: wave dispatch needs the routed
                # set (the units only the router knows)
                counts = np.asarray(counts_e)
                sel = [int(e) for e in np.nonzero(counts > 0)[0]]
                sel_by_layer[l] = sel
                routed_f = max(float(routed), 1.0)
                drop_fracs.append(float(dropped) / routed_f)
                load = counts / routed_f
                loads.append(load)
                pop.update(l, load)
                start_reads(l, sel)
                out = x_mid
                for wave, ids, mask in self._expert_waves(sel, W):
                    erows, fresh = wave_rows(l, wave)
                    out = out + fns["moe_wave_fwd"](
                        x_mid, rows[l], router[l], erows, ids, mask)
                    retire(l, fresh)
                x = out

            run_pass(sched.forward(), fwd_use, lambda l: pop.top(l, W))

            # ---- head + reversed pass ----
            loss, dx, g_head = fns["head"](x, state["other"], batch["labels"])
            gdict: Dict[str, object] = {}
            g_router = [None] * L
            sumsq = jnp.zeros((), jnp.float32)

            def drain(key, g):
                gdict[key] = (self.grad_store.roundtrip(f"{key}/g", g)
                              if self.grad_offload else g)

            def bwd_use(l):
                nonlocal dx, sumsq
                x_in = acts.pop(l)
                x_mid = fns["moe_xmid"](x_in, rows[l])
                sel = sel_by_layer[l]
                start_reads(l, sel)
                dxmid = dx
                g_row = None
                g_rt = None
                for wave, ids, mask in self._expert_waves(sel, W):
                    erows, fresh = wave_rows(l, wave)
                    dxm, g_row_w, g_rt_w, g_er = fns["moe_wave_vjp"](
                        x_mid, rows[l], router[l], erows, ids, mask, dx)
                    dxmid = dxmid + dxm
                    g_row = g_row_w if g_row is None else g_row + g_row_w
                    g_rt = g_rt_w if g_rt is None else g_rt + g_rt_w
                    sumsq = fns["accum_sumsq2"](sumsq, g_er)
                    shards = self._rank_device(g_er)
                    for i, e in enumerate(wave):
                        for r in ranks:
                            drain(f"xrank{r}/l{l * E + e}", shards[r][i])
                    retire(l, fresh)
                dx_new, g_row_attn = fns["moe_attn_vjp"](x_in, rows[l], dxmid)
                g_row = g_row_attn if g_row is None else g_row + g_row_attn
                g_router[l] = g_rt
                sumsq = fns["accum_sumsq"](sumsq, g_row)
                dx = dx_new
                for r, g in self._rank_device(g_row).items():
                    drain(f"rank{r}/l{l}", g)

            run_pass(sched.backward(), bwd_use,
                     lambda l: sel_by_layer.get(l, []))

            # unrouted experts update from known-zero grads fed directly to
            # the streamed Adam (their m/v decay exactly as the all-resident
            # baseline's) — no slow-tier grad traffic scales with E
            zero_row = np.zeros(eng.elayout.padded // max(len(ranks), 1),
                                np.float32)
            for l in range(L):
                selset = set(sel_by_layer[l])
                for e in range(E):
                    if e not in selset:
                        for r in ranks:
                            gdict[f"xrank{r}/l{l * E + e}"] = zero_row

            g_emb = fns["embed_vjp"](state["other"], batch["tokens"], dx)
            zeros_rt = jnp.zeros_like(router[0])
            g_head = dict(g_head)
            g_head["router"] = g_head["router"] + jnp.stack(
                [g if g is not None else zeros_rt for g in g_router])
            new_other, new_other_opt, new_step, fm = fns["finish"](
                state["other"], state["other_opt"], state["step"],
                g_head, g_emb, sumsq)

            with trace.span("device_sync", sys="compute", attr="compute"):
                lr_host = float(fm["lr"])
            new_master = self.offload.step(
                gdict, lr=lr_host, beta1=tc.beta1, beta2=tc.beta2,
                eps=tc.eps, weight_decay=tc.weight_decay)
            with trace.span("param_writeback", sys="optim", cls="param"):
                for key, m32 in new_master.items():
                    rank, layer = key.split("/")  # "[x]rank<r>/l<i>"
                    self.param_stream.write_row(
                        rank, int(layer[1:]), m32.astype(ml_dtypes.bfloat16))
                # refresh hot-cached rows from the just-written masters so
                # next step's hot hits serve the updated parameters (host->
                # device put only — the saved traffic is the slow-tier read)
                for u in hot.units():
                    _, l, e = u
                    vals = [new_master[f"xrank{r}/l{l * E + e}"].astype(
                        ml_dtypes.bfloat16) for r in ranks]
                    hot.replace(u, self._device_row(vals, row_sh))
                self.param_stream.flush()
            if self.grad_store is not None:
                self.grad_store.flush()

            new_state = {"flat": self._param_placeholder(),
                         "eflat": self._eflat_placeholder(),
                         "other": new_other, "other_opt": new_other_opt,
                         "step": new_step}
            metrics = {"loss": loss, "grad_norm": fm["grad_norm"],
                       "lr": fm["lr"],
                       "moe_dropped_token_fraction": float(np.mean(drop_fracs)),
                       "moe_expert_load": np.mean(np.stack(loads), axis=0),
                       "expert_total_bytes": self.expert_total_bytes}
            return new_state, self._with_tier_metrics(metrics, marks)

        return step

    # ------------------------------------------------------------------
    # rank-shard plumbing (explicit engine)
    # ------------------------------------------------------------------

    def _rank_arrays(self, arr) -> Dict[int, np.ndarray]:
        """Global (L, P) array -> {rank: local (L, P/dp) ndarray} (own dtype)."""
        return {self._rank_of[s.device]: np.asarray(s.data)
                for s in arr.addressable_shards}

    def _rank_device(self, arr) -> Dict[int, jax.Array]:
        """Global array -> {rank: local shard as a *device* array} — no host
        sync on the caller. The device->host copy happens on the consuming
        store's worker thread (``ArrayStore.write``/``roundtrip`` convert
        inside the submitted closure) or lazily when the streamed Adam
        resolves the leaf — so issuing a layer's gradient drain never blocks
        dispatch of the next layer's vjp."""
        return {self._rank_of[s.device]: s.data for s in arr.addressable_shards}

    def _rank_shards(self, arr) -> Dict[str, np.ndarray]:
        """Global (L, P) array -> {'rank<r>/flat': f32 local (L, P/dp)}."""
        return {f"rank{r}/flat": a.astype(np.float32)
                for r, a in self._rank_arrays(arr).items()}

    def _assemble_flat(self, new_master: Dict[str, np.ndarray], *, like):
        """Per-rank f32 masters -> global bf16 flat array sharded like ``like``."""
        return self._flat_from_ranks(
            {r: new_master[f"rank{r}/flat"]
             for r in self._rank_of.values()}, like=like)

    def _flat_from_ranks(self, by_rank: Dict[int, np.ndarray], *, like):
        """{rank: (L, P/dp) ndarray} -> global bf16 array placed like
        ``like`` (an array or a ShapeDtypeStruct) — including its memory
        kind: the shards are assembled in device memory first, then streamed
        to a pinned-host target sharding (per-device assembly cannot target
        a non-default memory kind)."""
        sh = like.sharding
        asm_sh = sh
        if sh.memory_kind not in (None, "device"):
            asm_sh = sh.with_memory_kind("device")
        pieces = []
        for d in np.asarray(self.mesh.devices).flat:
            piece = np.asarray(by_rank[self._rank_of[d]]).astype(
                ml_dtypes.bfloat16)
            pieces.append(jax.device_put(piece, d))
        arr = jax.make_array_from_single_device_arrays(like.shape, asm_sh, pieces)
        if asm_sh is not sh:
            arr = jax.device_put(arr, sh)
        return arr

    # ------------------------------------------------------------------
    # per-tier bandwidth metrics
    # ------------------------------------------------------------------

    def _active_stores(self):
        out = []
        if self.param_store is not None:
            out.append(("param", self.param_store))
        if self.grad_store is not None:
            out.append(("grad", self.grad_store))
        if self.opt_store is not None:
            out.append(("opt", self.opt_store))
        return out

    # ------------------------------------------------------------------
    # per-step stall attribution (tracer-backed)
    # ------------------------------------------------------------------

    def _trace_step_begin(self) -> None:
        """Mark the step's wall-clock window for stall attribution."""
        if trace.enabled():
            self._trace_t0 = time.perf_counter()
            self._trace_tid = threading.get_ident()

    def _with_trace_attribution(self, out: dict) -> dict:
        """Partition the finished step's wall time from the recorded spans
        and surface the buckets as ``trace_*`` metrics next to the plan's
        predicted ``plan_efficiency`` — the measured side of Eq. 6."""
        if not (trace.enabled() and self._trace_t0 is not None):
            return out
        att = trace.TRACER.attribute_window(
            self._trace_t0, time.perf_counter(), main_tid=self._trace_tid)
        self._trace_t0 = None
        self.trace_attributions.append(att)
        out.update(trace.flatten_attribution(att))
        return out

    def _with_tier_metrics(self, metrics, marks) -> dict:
        """Per-step, per-tier counters: param-in (store->device), param-out
        (write-back), grad-out (drain), opt-read/opt-write (the streamed
        Adam pipeline). All values are this step's deltas — never cumulative
        totals — plus the legacy ``nvme_*`` aggregate over NVMe-backed
        stores for run summaries.

        Each class reports two byte counts: ``<class>_*_bytes`` is *logical*
        traffic (the full-precision arrays the engine moved) and
        ``<class>_*_wire_bytes`` is what actually crossed the tier link —
        identical on plain stores, smaller under a quantized wire format
        (``offload.param_quant``). The ``*_gbps`` rates are wire rates (the
        link speed the hardware delivers)."""
        out = dict(metrics)
        nvme = {"bytes_read": 0, "bytes_written": 0}
        for name, store in self._active_stores():
            d = store.delta_since(marks[name])
            wire_r, wire_w = d["bytes_read"], d["bytes_written"]
            logical_r = d.get("logical_bytes_read", wire_r)
            logical_w = d.get("logical_bytes_written", wire_w)
            if name == "param":
                out["param_in_bytes"] = logical_r
                out["param_in_wire_bytes"] = wire_r
                out["param_in_gbps"] = d["read_gbps"]
                out["param_out_bytes"] = logical_w
                out["param_out_wire_bytes"] = wire_w
                out["param_out_gbps"] = d["write_gbps"]
            elif name == "grad":
                out["grad_out_bytes"] = logical_w
                out["grad_out_wire_bytes"] = wire_w
                out["grad_out_gbps"] = d["write_gbps"]
            else:
                out["opt_read_bytes"] = logical_r
                out["opt_read_wire_bytes"] = wire_r
                out["opt_read_gbps"] = d["read_gbps"]
                out["opt_write_bytes"] = logical_w
                out["opt_write_wire_bytes"] = wire_w
                out["opt_write_gbps"] = d["write_gbps"]
            if store.kind == "nvme":
                # the aggregate counts wire bytes — what the device saw
                nvme["bytes_read"] += wire_r
                nvme["bytes_written"] += wire_w
        out["nvme_bytes_read"] = nvme["bytes_read"]
        out["nvme_bytes_written"] = nvme["bytes_written"]
        # resident (outstanding + cached) — what the fixed supply bounds
        out["nvme_pinned_peak_bytes"] = self._pool.peak_resident
        if self.param_nvme:  # scheduler residency / overlap effectiveness
            out.update(self._ws.stats())
            out["param_total_bytes"] = self.total_param_bytes
        return self._with_plan_crosscheck(self._with_trace_attribution(out))

    def _with_plan_crosscheck(self, out: dict) -> dict:
        """Predicted-vs-measured: when this executor was built from an
        ``InfinityPlan``, surface the plan's predictions next to the step's
        measured counters so drift is visible in every metrics row. The
        residency claim is directional — measured peak must stay at or below
        what the planner budgeted — so it also gets a pass/fail flag."""
        if self.plan is None:
            return out
        pred = self.plan.predictions
        pp = pred.get("peak_resident_param_bytes")
        if pp is not None:
            out["plan_peak_resident_param_bytes"] = pp
            if "peak_resident_param_bytes" in out:
                out["plan_residency_ok"] = bool(
                    out["peak_resident_param_bytes"] <= pp)
        if "efficiency" in pred:
            out["plan_efficiency"] = pred["efficiency"]
        for cls_, measured_keys in (
                ("param", ("param_in_bytes", "param_out_bytes")),
                ("grad", ("grad_out_bytes",)),
                ("opt", ("opt_read_bytes", "opt_write_bytes"))):
            pred_rw = [pred.get(f"{cls_}_step_read_bytes"),
                       pred.get(f"{cls_}_step_write_bytes")]
            total_pred = sum(v for v in pred_rw if v is not None)
            if total_pred and any(k in out for k in measured_keys):
                out[f"plan_{cls_}_step_bytes"] = total_pred
            pred_wire = [pred.get(f"{cls_}_step_read_wire_bytes"),
                         pred.get(f"{cls_}_step_write_wire_bytes")]
            total_wire = sum(v for v in pred_wire if v is not None)
            if total_wire and any(k in out for k in measured_keys):
                out[f"plan_{cls_}_step_wire_bytes"] = total_wire
        return out

    def bandwidth_stats(self) -> dict:
        """Cumulative (whole-run) aggregate over every slow-tier store, per
        state class and combined — the run-summary counterpart of the
        per-step metrics."""
        stores = self._active_stores()
        if not stores:
            return {}
        out = {}
        tot_r = tot_w = 0
        tot_rt = tot_wt = 0.0
        for name, store in stores:
            s = store.bandwidth_stats()  # one locked snapshot per store
            out[f"{name}_bytes_read"] = s["bytes_read"]
            out[f"{name}_bytes_written"] = s["bytes_written"]
            out[f"{name}_read_gbps"] = s["read_gbps"]
            out[f"{name}_write_gbps"] = s["write_gbps"]
            out[f"{name}_logical_bytes_read"] = s.get(
                "logical_bytes_read", s["bytes_read"])
            out[f"{name}_logical_bytes_written"] = s.get(
                "logical_bytes_written", s["bytes_written"])
            tot_r += s["bytes_read"]
            tot_w += s["bytes_written"]
            tot_rt += s["read_time"]
            tot_wt += s["write_time"]
        out["bytes_read"] = tot_r
        out["bytes_written"] = tot_w
        out["read_gbps"] = tot_r / max(tot_rt, 1e-9) / 1e9
        out["write_gbps"] = tot_w / max(tot_wt, 1e-9) / 1e9
        out["pinned_peak_bytes"] = self._pool.peak_resident
        return out
