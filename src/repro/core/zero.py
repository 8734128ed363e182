"""Explicit ZeRO-3 engine: the paper-faithful collective schedule.

Where ``core/engine.py`` lets GSPMD place the ZeRO collectives, this engine
issues them by hand inside ``jax.shard_map`` so every knob from the paper is
a real, controllable code path:

  * **bandwidth-centric partitioning** (Sec. 6.1): each layer's parameters
    are flattened to one 1-D buffer and split across *all* dp ranks
    (``partition_mode="allgather"``); materialization is a single
    ``lax.all_gather`` in which every rank's memory link is active. The
    contrast baseline (``"broadcast"``) stores whole layers on one owner
    rank (layers round-robined) and broadcasts on use — the paper's
    ZeRO-Offload-style single-link pattern.
  * **overlap-centric design** (Sec. 6.2): ``prefetch>=1`` double-buffers
    the gather — the scan carry holds layer i's gathered params while the
    gather for i+1 is issued *before* the block compute, so it has no data
    dependence on compute(i) and XLA's latency-hiding scheduler overlaps
    them. ``prefetch=0`` chains gather->compute serially.
  * **ZeRO grad semantics**: the gather sits inside the autodiff region, so
    its transpose is exactly the paper's ``reduce-scatter`` of gradients
    into the owner shard (and with remat, parameters are re-gathered for
    the backward pass — the paper's "loaded one additional time").
  * **partitioned Adam** (Sec. 5.2.2): optimizer states live as local
    (L, P/dp) shards and the update runs shard-locally, embarrassingly
    parallel across ranks.

This engine is pure data-parallel (mp=1), matching the paper's headline
configurations ("up to 1T parameters on a DGX-2 *without model
parallelism*"); the GSPMD engine covers TP/CP/EP compositions. Families:
dense transformer, and MoE via the layered epoch only — an MoE layer's
attention+norm leaves flatten into one *dense row* per layer while each
expert's weights flatten into their own independently paged *expert row*
(``eflat``, one row per (layer, expert)); the router is a small replicated
f32 'other' state so its master stays full precision. ``make_layer_fns``
exposes the MoE layer as schedulable pieces: ``moe_attn`` (attention +
routing counts), then fixed-width *waves* of router-selected expert rows
(``moe_wave_fwd`` / ``moe_wave_vjp``) whose sum reproduces the all-resident
computation exactly (an expert with no routed tokens contributes zero
output and zero gradient).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import RunConfig, ShapeConfig
from repro.core import partition as pt
from repro.models import common as cm
from repro.models import moe as moe_mod
from repro.models import transformer
from repro.optim import adam as adam_mod
from repro.optim import compression
from repro.runtime import trace


def _all_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def _trace_wrap_fns(fns: dict) -> dict:
    """Wrap the layered epoch's jitted pieces in compute spans. jit calls
    are async dispatch, so a piece's span measures time on the dispatching
    thread; the executor's ``device_sync`` span captures where the device
    work actually lands on the critical path."""
    return {name: trace.wrap(name, fn, sys="compute", attr="compute")
            for name, fn in fns.items()}


@dataclasses.dataclass
class _FlatLayout:
    treedef: object
    shapes: list
    dtypes: list
    sizes: list
    padded: int  # per-layer flat length (padded to dp multiple)


class ExplicitZero3Engine:
    """Paper-faithful engine with full three-tier (Infinity) placement.

    Every model-state class has its own tier knob in ``run.offload``:

      * ``opt_tier=device`` — master/m/v live in HBM as local (L, P/dp)
        shards; the partitioned Adam update runs in-graph.
      * ``opt_tier=host``   — same layout, placed with the backend's host
        memory kind (``pinned_host``); the step streams them HBM<->host
        around the compute. On the CPU, whose jit cannot place host memory
        kinds, this is device placement (``partition.host_memory_kind``).
      * ``param_tier=host`` — the bf16 (L, P/dp) compute shards live in
        pinned host memory and are streamed to HBM ahead of the prefetched
        per-layer all-gathers (same degrade rule on CPU).
      * NVMe tiers / slow-tier gradients (``opt_offgraph``) — those states
        never enter the graph: the step computes the reduce-scattered grad
        shards only, and the executor (``core/executor.py``) streams params,
        grads, and optimizer states through its ``ArrayStore`` tiers with
        the read(k+1) || update(k) || write(k-1) pipeline.
      * ``param_tier=nvme`` — the monolithic step is replaced entirely by
        the scheduler-driven layered epoch (``make_layer_fns`` +
        ``core/schedule.py``): per-layer rows are materialized just-in-time
        inside a prefetch window and evicted after use, so peak device
        residency of the flat params is O(window), not O(L).
    """

    def __init__(self, run: RunConfig, mesh: Mesh):
        assert run.model.family in ("dense", "moe"), (
            "explicit engine: dense and moe families only")
        self.is_moe = run.model.family == "moe"
        if self.is_moe and run.offload.param_tier != "nvme":
            raise ValueError(
                "explicit-engine MoE requires param_tier='nvme': expert rows "
                "page through the layered scheduler; use the pjit engine for "
                "all-resident MoE")
        self.run = run
        self.mesh = mesh
        self.dp = 1
        for a in mesh.axis_names:
            self.dp *= mesh.shape[a]
        self.axis = _all_axes(mesh)
        self.rules = pt.AxisRules(table=())  # pure dp: no TP constraints
        if self.is_moe:
            self.block_fn = None  # MoE layers run as make_layer_fns pieces
            self.defs = moe_mod.param_defs(run.model)
        else:
            self.block_fn = transformer.make_block_fn(run.model, self.rules,
                                                      run.parallel)
            self.defs = transformer.param_defs(run.model)
        self.opt_tier = run.offload.opt_tier
        self.offgraph = run.opt_offgraph
        hk = (pt.host_memory_kind(mesh)
              if "host" in (self.opt_tier, run.offload.param_tier) else None)
        self.opt_host_kind = (hk if self.opt_tier == "host" and not self.offgraph
                              else None)
        self.param_host_kind = hk if run.offload.param_tier == "host" else None
        self._build_layout()

    # ------------------------------------------------------------------
    # flat bandwidth-centric layout
    # ------------------------------------------------------------------

    def _dense_blocks(self, blocks):
        """The per-layer leaves that flatten into the dense row. For MoE the
        expert weights and router page/update separately."""
        if self.is_moe:
            return {k: v for k, v in blocks.items() if k != "moe"}
        return blocks

    def _build_layout(self):
        cfg = self.run.model
        blocks = self._dense_blocks(self.defs["blocks"])
        leaf = lambda x: isinstance(x, pt.ParamDef)
        leaves, treedef = jax.tree.flatten(blocks, is_leaf=leaf)
        shapes = [l.shape[1:] for l in leaves]  # strip layer dim
        dtypes = [l.dtype for l in leaves]
        sizes = [int(jnp.prod(jnp.array(s))) if s else 1 for s in shapes]
        total = sum(sizes)
        padded = total + ((-total) % self.dp)
        self.layout = _FlatLayout(treedef, shapes, dtypes, sizes, padded)
        self.n_layers = cfg.n_layers
        if self.is_moe:
            # expert rows: one flat buffer per (layer, expert), same
            # bandwidth-centric split over all ranks as the dense rows
            rdefs = moe_mod.expert_row_defs(cfg)
            eleaves, etreedef = jax.tree.flatten(rdefs, is_leaf=leaf)
            eshapes = [l.shape for l in eleaves]
            edtypes = [l.dtype for l in eleaves]
            esizes = [int(jnp.prod(jnp.array(s))) if s else 1 for s in eshapes]
            etotal = sum(esizes)
            epadded = etotal + ((-etotal) % self.dp)
            self.elayout = _FlatLayout(etreedef, eshapes, edtypes, esizes,
                                       epadded)
            self.n_experts = cfg.n_experts
            self.top_k = cfg.top_k

    def _flatten_blocks(self, blocks, dtype) -> jax.Array:
        leaves = jax.tree.leaves(self._dense_blocks(blocks))
        flat = jnp.concatenate(
            [l.astype(dtype).reshape(self.n_layers, -1) for l in leaves], axis=1)
        pad = self.layout.padded - flat.shape[1]
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        return flat  # (L, P)

    def _flatten_experts(self, moe_params, dtype=jnp.bfloat16) -> jax.Array:
        """moe subtree (leaves (L, E, ...)) -> (L*E, Pe) expert-row buffer;
        row index l * n_experts + e."""
        LE = self.n_layers * self.n_experts
        sub = {n: moe_params[n] for n in moe_mod.expert_leaf_names(self.run.model)}
        leaves = jax.tree.leaves(sub)  # dict order matches elayout treedef
        flat = jnp.concatenate(
            [l.astype(dtype).reshape(LE, -1) for l in leaves], axis=1)
        pad = self.elayout.padded - flat.shape[1]
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        return flat  # (L*E, Pe)

    @staticmethod
    def _unflatten_row(flat: jax.Array, layout: _FlatLayout, dtype=None):
        out = []
        off = 0
        for shape, dt, size in zip(layout.shapes, layout.dtypes, layout.sizes):
            piece = jax.lax.dynamic_slice_in_dim(flat, off, size, 0).reshape(shape)
            out.append(piece.astype(dtype or dt))
            off += size
        return jax.tree.unflatten(layout.treedef, out)

    def _unflatten_layer(self, flat: jax.Array, dtype=None):
        """flat: (P,) gathered one-layer buffer -> block param pytree."""
        return self._unflatten_row(flat, self.layout, dtype)

    def _unflatten_expert(self, flat: jax.Array, dtype=None):
        """flat: (Pe,) gathered one-expert buffer -> per-expert weight dict."""
        return self._unflatten_row(flat, self.elayout, dtype)

    # ------------------------------------------------------------------
    # state
    # ------------------------------------------------------------------

    @property
    def grad_compress(self) -> bool:
        """int8 + error-feedback wire format on the replicated-grad reduce
        (``optim/compression.py``) — carried as a rank-stacked residual."""
        return self.run.parallel.grad_compression == "int8"

    def _other_defs(self) -> dict:
        """Defs of the small replicated ('other') states: embeddings, final
        norm, and — for MoE — the stacked (L, d, E) router, kept out of the
        bf16 rows so its Adam master stays full precision."""
        out = {"embed": self.defs["embed"], "ln_f": self.defs["ln_f"]}
        if self.is_moe:
            out["router"] = self.defs["blocks"]["moe"]["router"]
        return out

    def _g_err_zeros(self):
        """Fresh rank-local error-feedback residuals: one fp32 copy of each
        'other' grad leaf per rank, stacked on a leading dp dim so each
        rank's residual stays its own across steps (the residual is the
        rank's private quantization error, never reduced)."""
        other_defs = self._other_defs()
        leaf = lambda x: isinstance(x, pt.ParamDef)
        return jax.tree.map(
            lambda d: jnp.zeros((self.dp,) + tuple(d.shape), jnp.float32),
            other_defs, is_leaf=leaf)

    def init_g_err(self):
        """Zero residual tree placed on its sharding (restore path)."""
        sh = {"g_err": self.state_shardings()["g_err"]}
        return jax.device_put({"g_err": self._g_err_zeros()}, sh)["g_err"]

    def init_state(self, rng: jax.Array):
        params = pt.init_tree(rng, self.defs)
        flat = self._flatten_blocks(params["blocks"], jnp.bfloat16)  # (L, P)
        other = {"embed": params["embed"], "ln_f": params["ln_f"]}
        if self.is_moe:
            other["router"] = params["blocks"]["moe"]["router"].astype(
                jnp.float32)
        state = {
            "flat": flat,  # bf16 compute shards
            "other": other,
            "other_opt": adam_mod.init_state(other),
            "step": jnp.zeros((), jnp.int32),
        }
        if self.is_moe:
            state["eflat"] = self._flatten_experts(params["blocks"]["moe"])
        if self.grad_compress:
            state["g_err"] = self._g_err_zeros()
        if not self.offgraph:  # offgraph: master/m/v live in the ArrayStore
            flat32 = flat.astype(jnp.float32)
            state.update(master=flat32, m=jnp.zeros_like(flat32),
                         v=jnp.zeros_like(flat32))
        return jax.device_put(state, self.state_shardings())

    def _flat_spec(self) -> P:
        if self.run.parallel.partition_mode == "broadcast":
            # owner layout: whole layers on one rank each (layers round-robin)
            assert self.n_layers % self.dp == 0, (
                "broadcast (owner) mode needs n_layers % dp == 0 — and that is "
                "the point: single-owner placement does not scale; use "
                "partition_mode='allgather' (bandwidth-centric) at scale.")
            return P(self.axis, None)
        return P(None, self.axis)  # bandwidth-centric: every param split over all dp

    def state_shardings(self):
        mesh = self.mesh
        flat_spec = self._flat_spec()
        sh = lambda spec: NamedSharding(mesh, spec)

        def rep_tree(defs):
            return jax.tree.map(lambda d: sh(P()), defs,
                                is_leaf=lambda x: isinstance(x, pt.ParamDef))

        other = {k: rep_tree(d) for k, d in self._other_defs().items()}
        other_opt = adam_mod.AdamState(
            sh(P()),
            jax.tree.map(lambda _: sh(P()), other),
            jax.tree.map(lambda _: sh(P()), other),
            jax.tree.map(lambda _: sh(P()), other))
        flat_sh = sh(flat_spec)
        if self.param_host_kind:  # bf16 compute shards resident in host DRAM
            flat_sh = flat_sh.with_memory_kind(self.param_host_kind)
        out = {
            "flat": flat_sh,
            "other": other, "other_opt": other_opt,
            "step": sh(P()),
        }
        if self.is_moe:
            out["eflat"] = sh(P(None, self.axis))  # expert rows rank-split
        if self.grad_compress:
            # rank-stacked residuals: leading dp dim split over all axes
            out["g_err"] = jax.tree.map(lambda _: sh(P(self.axis)), other)
        if not self.offgraph:
            opt_sh = sh(flat_spec)
            if self.opt_host_kind:  # optimizer states resident in pinned host DRAM
                opt_sh = opt_sh.with_memory_kind(self.opt_host_kind)
            out.update(master=opt_sh, m=opt_sh, v=opt_sh)
        return out

    # ------------------------------------------------------------------
    # data interface (mirrors ZeroInfinityEngine for the launch drivers)
    # ------------------------------------------------------------------

    def input_specs(self, shape: ShapeConfig):
        B, S = shape.global_batch, shape.seq_len
        return {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
                "labels": jax.ShapeDtypeStruct((B, S), jnp.int32)}

    def batch_sharding(self, spec: jax.ShapeDtypeStruct):
        axes = (self.axis,) + (None,) * (len(spec.shape) - 1)
        return NamedSharding(self.mesh, P(*axes))

    def n_params_active(self) -> int:
        blocks = sum(self.layout.sizes) * self.n_layers
        leaves = jax.tree.leaves(self._other_defs(),
                                 is_leaf=lambda x: isinstance(x, pt.ParamDef))
        other = sum(int(jnp.prod(jnp.array(d.shape))) if d.shape else 1
                    for d in leaves)
        if self.is_moe:
            # MoE convention: only the top_k routed experts are active
            blocks += sum(self.elayout.sizes) * self.top_k * self.n_layers
        return blocks + other

    def _rep_specs(self):
        """Replicated PartitionSpec trees for the small non-flat states."""
        rep = P()
        leaf = lambda x: isinstance(x, pt.ParamDef)
        other = {k: jax.tree.map(lambda d: rep, defs_k, is_leaf=leaf)
                 for k, defs_k in self._other_defs().items()}
        opt = adam_mod.AdamState(
            rep,
            jax.tree.map(lambda _: rep, other),
            jax.tree.map(lambda _: rep, other),
            jax.tree.map(lambda _: rep, other),
        )
        return other, opt

    # ------------------------------------------------------------------
    # train step
    # ------------------------------------------------------------------

    def make_train_step(self, *, grads_only: bool = None):
        """Build the sharded step.

        ``grads_only=None`` (default) resolves from the configured tiers:
        out-of-graph placements (NVMe optimizer states, slow-tier gradient
        drains) compute grad shards in-graph and leave the Adam update to
        the host-side pipeline (see ``InfinityExecutor``); in-graph tiers
        run partitioned Adam inside the step. The grads-only step still
        advances ``step`` and the small replicated 'other' params so only
        the flat (L, P/dp) shards are deferred to the executor.
        """
        if grads_only is None:
            grads_only = self.offgraph
        if self.is_moe:
            raise NotImplementedError(
                "explicit-engine MoE has no monolithic step: expert rows page "
                "through the layered epoch (param_tier='nvme' + "
                "make_layer_fns)")
        run = self.run
        cfg = run.model
        tc = run.train
        pc = run.parallel
        L = self.n_layers
        dp = self.dp
        axis = self.axis
        block_fn = self.block_fn
        unflatten = self._unflatten_layer
        rules = self.rules
        mode = pc.partition_mode
        prefetch = pc.prefetch

        def gather_layer(flat_local, i):
            """Materialize layer i's full parameter buffer on every rank."""
            if mode == "allgather":
                # flat_local: (L, P/dp) -> all_gather over all links (tiled)
                piece = jax.lax.dynamic_index_in_dim(flat_local, i, 0, keepdims=False)
                return jax.lax.all_gather(piece, axis, tiled=True)  # (P,)
            # broadcast baseline: owner rank holds whole layers; emulate a
            # bcast as a masked psum (only the owner contributes).
            lpr = L // dp  # layers per rank
            rank = jax.lax.axis_index(axis)
            owner = i // lpr
            local_row = jnp.clip(i - rank * lpr, 0, lpr - 1)
            piece = jax.lax.dynamic_index_in_dim(flat_local, local_row, 0, keepdims=False)
            piece = jnp.where(rank == owner, piece, jnp.zeros_like(piece))
            return jax.lax.psum(piece, axis)

        def local_loss(flat_local, other, batch_local):
            tokens = batch_local["tokens"]
            x = cm.embed(other["embed"], tokens, cfg, rules)
            B, S, _ = x.shape
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

            def body_core(x, gathered):
                blk = unflatten(gathered, jnp.bfloat16)
                return block_fn(x, blk, positions)

            if pc.remat != "none":
                body_core = jax.checkpoint(
                    body_core, policy=transformer._remat_policy(pc), prevent_cse=False)

            if prefetch >= 1:
                g0 = gather_layer(flat_local, 0)

                def body(carry, i):
                    x, g_cur = carry
                    # prefetch: issue gather(i+1) before compute(i) — no data
                    # dependence, so it overlaps under latency hiding
                    g_next = gather_layer(flat_local, jnp.minimum(i + 1, L - 1))
                    x = body_core(x, g_cur)
                    return (x, g_next), ()

                (x, _), _ = jax.lax.scan(body, (x, g0), jnp.arange(L))
            else:
                def body(x, i):
                    return body_core(x, gather_layer(flat_local, i)), ()

                x, _ = jax.lax.scan(body, x, jnp.arange(L))

            x = cm.norm(x, other["ln_f"], cfg.norm_kind)
            lg = cm.logits(other["embed"], x, cfg, rules)
            return cm.lm_loss(lg[:, :-1], batch_local["labels"][:, 1:], cfg.vocab_size)

        def sharded_step(state, batch_local):
            flat_local, other = state["flat"], state["other"]

            def scaled(flat_local, other):
                return local_loss(flat_local, other, batch_local) / dp

            loss_scaled, (g_flat, g_other) = jax.value_and_grad(scaled, argnums=(0, 1))(
                flat_local, other)
            loss = jax.lax.psum(loss_scaled, axis)
            # g_flat is already the reduce-scattered local shard (transpose of
            # all_gather); g_other needs the explicit dp reduction:
            new_g_err = None
            if pc.grad_compression == "int8":
                # int8 wire format + error feedback on the replicated-grad
                # reduce (optim/compression.py). psum_compressed returns the
                # MEAN over ranks; scale by dp to recover psum semantics.
                # Each rank's residual (its private quantization error) rides
                # in the (dp, ...)-stacked g_err state leaf, local slice [0].
                flat_g, tdef = jax.tree.flatten(g_other)
                flat_e = jax.tree.leaves(state["g_err"])
                red, errs = [], []
                for g, e in zip(flat_g, flat_e):
                    r, ne = compression.psum_compressed(g, axis, e[0])
                    red.append((r.astype(jnp.float32) * dp).astype(g.dtype))
                    errs.append(ne.astype(jnp.float32)[None])
                g_other = jax.tree.unflatten(tdef, red)
                new_g_err = jax.tree.unflatten(tdef, errs)
            else:
                g_other = jax.tree.map(lambda g: jax.lax.psum(g, axis), g_other)

            step = state["step"] + 1
            lr = adam_mod.lr_at(tc, step)
            g32 = g_flat.astype(jnp.float32)
            gnorm = jnp.sqrt(jax.lax.psum(jnp.sum(g32 ** 2), axis)
                             + sum(jnp.sum(x.astype(jnp.float32) ** 2)
                                   for x in jax.tree.leaves(g_other)))
            metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
            new_other, new_other_opt = adam_mod.apply_updates(
                g_other, state["other_opt"], tc, params_prev=other)

            if grads_only:
                # NVMe tier: flat shards updated out-of-graph by the executor
                new_state = {
                    "flat": flat_local,
                    "other": new_other, "other_opt": new_other_opt,
                    "step": step,
                }
                if new_g_err is not None:
                    new_state["g_err"] = new_g_err
                return new_state, g32, metrics

            # --- partitioned Adam on local shards (shard-parallel) ---
            b1, b2, eps, wd = tc.beta1, tc.beta2, tc.eps, tc.weight_decay
            c1 = 1.0 - b1 ** step.astype(jnp.float32)
            c2 = 1.0 - b2 ** step.astype(jnp.float32)
            m = b1 * state["m"] + (1 - b1) * g32
            v = b2 * state["v"] + (1 - b2) * g32 * g32
            master = state["master"] - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                             + wd * state["master"])
            new_state = {
                "flat": master.astype(jnp.bfloat16),
                "master": master, "m": m, "v": v,
                "other": new_other, "other_opt": new_other_opt,
                "step": step,
            }
            if new_g_err is not None:
                new_state["g_err"] = new_g_err
            return new_state, metrics

        flat_spec = self._flat_spec()
        rep = P()
        other_specs, opt_specs = self._rep_specs()
        state_specs = {
            "flat": flat_spec,
            "other": other_specs, "other_opt": opt_specs, "step": rep,
        }
        if self.grad_compress:
            state_specs["g_err"] = jax.tree.map(lambda _: P(axis), other_specs)
        if not grads_only:
            state_specs.update(master=flat_spec, m=flat_spec, v=flat_spec)
        batch_spec = {"tokens": P(self.axis, None), "labels": P(self.axis, None)}
        metric_spec = {"loss": rep, "grad_norm": rep, "lr": rep}
        out_specs = ((state_specs, flat_spec, metric_spec) if grads_only
                     else (state_specs, metric_spec))

        step_fn = jax.shard_map(
            sharded_step, mesh=self.mesh,
            in_specs=(state_specs, batch_spec),
            out_specs=out_specs,
            check_vma=False,
        )
        # Host tiers: params and/or optimizer states resident in pinned host
        # DRAM are streamed to HBM ahead of the sharded step (the params
        # arrive before their per-layer all-gathers) and back after — the
        # in-graph device_puts lower to async copies XLA can overlap.
        stream_keys = []
        if self.param_host_kind:
            stream_keys.append("flat")
        if not grads_only and self.opt_host_kind:
            stream_keys += ["master", "m", "v"]
        if not stream_keys:
            return step_fn

        host_shardings = self.state_shardings()

        def to_kind(state, kind):
            out = dict(state)
            for k in stream_keys:
                s = host_shardings[k].with_memory_kind(kind) if kind else host_shardings[k]
                out[k] = jax.device_put(state[k], s)
            return out

        def host_tier_step(state, batch):
            res = step_fn(to_kind(state, "device"), batch)
            if grads_only:
                new_state, g32, metrics = res
                return to_kind(new_state, None), g32, metrics
            new_state, metrics = res
            return to_kind(new_state, None), metrics

        return host_tier_step

    # ------------------------------------------------------------------
    # per-layer pieces for the scheduler-driven layered epoch
    # ------------------------------------------------------------------

    def layer_row_sharding(self) -> NamedSharding:
        """Global (P,) one-layer row: each rank holds its (P/dp) slice —
        the bandwidth-centric layout of a single materialized layer."""
        return NamedSharding(self.mesh, P(self.axis))

    def expert_rows_sharding(self) -> NamedSharding:
        """Global (W, Pe) wave of expert rows: each rank holds (W, Pe/dp)."""
        return NamedSharding(self.mesh, P(None, self.axis))

    def params_from_state(self, state) -> dict:
        """Rebuild the bundle-shaped parameter pytree from engine state —
        the eval/parity path (prefill with the pjit bundle's fns after a
        layered training run)."""
        blocks = jax.vmap(lambda r: self._unflatten_layer(r))(state["flat"])
        if self.is_moe:
            etree = jax.vmap(lambda r: self._unflatten_expert(r))(state["eflat"])
            L, E = self.n_layers, self.n_experts
            moe_p = jax.tree.map(
                lambda a: a.reshape((L, E) + a.shape[1:]), etree)
            moe_p["router"] = state["other"]["router"].astype(jnp.float32)
            blocks = dict(blocks)
            blocks["moe"] = moe_p
        return {"embed": state["other"]["embed"], "blocks": blocks,
                "ln_f": state["other"]["ln_f"]}

    def make_layer_fns(self):
        """Jitted per-layer pieces consumed by the layer scheduler
        (``param_tier=nvme``): the executor iterates (L, P/dp) rows through
        the prefetch window — forward order, reversed for backward — so the
        full flat array is never assembled on device. ``layer_vjp`` runs the
        layer's forward again inside ``jax.vjp`` (the paper's "parameters
        are loaded one additional time" with recompute) and its row
        cotangent is exactly the reduce-scattered local gradient shard (the
        transpose of the all-gather). All small replicated states update in
        ``finish`` with the same partitioned-Adam math as the in-graph step.
        """
        assert self.run.parallel.partition_mode == "allgather", (
            "layered epochs need the bandwidth-centric (allgather) row "
            "layout; the broadcast baseline stores whole layers per owner")
        assert not self.grad_compress, (
            "grad_compression='int8' wires into the monolithic step's "
            "replicated-grad reduce; the layered epoch's per-row reduce-"
            "scatter is implicit in the all-gather transpose and is not "
            "compressed — run it with grad_compression='none'")
        cfg = self.run.model
        tc = self.run.train
        axis, dp = self.axis, self.dp
        rules = self.rules
        block_fn = self.block_fn
        unflatten = self._unflatten_layer
        mesh = self.mesh
        rep = P()
        xspec = P(axis, None, None)
        bspec = P(axis, None)
        rowspec = P(axis)
        other_specs, _ = self._rep_specs()

        def smap(f, in_specs, out_specs):
            fn = jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                                  out_specs=out_specs, check_vma=False)
            with jax.set_mesh(mesh):
                return jax.jit(fn)

        def _gather_blk(row):
            return unflatten(jax.lax.all_gather(row, axis, tiled=True),
                             jnp.bfloat16)

        def _block(x, row):
            blk = _gather_blk(row)
            B, S = x.shape[0], x.shape[1]
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
            return block_fn(x, blk, positions)

        def _embed_fwd(other, tokens):
            return cm.embed(other["embed"], tokens, cfg, rules)

        def _layer_fwd(x, row):
            return _block(x, row)

        def _layer_vjp(x, row, dy):
            _, vjp = jax.vjp(_block, x, row)
            dx, drow = vjp(dy)
            return dx, drow.astype(jnp.float32)

        def _head(x, other, labels):
            def f(x, other):
                h = cm.norm(x, other["ln_f"], cfg.norm_kind)
                lg = cm.logits(other["embed"], h, cfg, rules)
                return cm.lm_loss(lg[:, :-1], labels[:, 1:], cfg.vocab_size) / dp

            loss_s, vjp = jax.vjp(f, x, other)
            dx, g_other = vjp(jnp.ones_like(loss_s))
            loss = jax.lax.psum(loss_s, axis)
            g_other = jax.tree.map(lambda g: jax.lax.psum(g, axis), g_other)
            return loss, dx, g_other

        def _accum_sumsq(acc, row):
            # device-side grad-norm accumulation: the layered backward adds
            # each row's global sum-of-squares into a carried device scalar
            # (one psum per layer) instead of pulling a host float per layer
            # — the accumulation stays async until `finish` consumes it.
            return acc + jax.lax.psum(
                jnp.sum(row.astype(jnp.float32) ** 2), axis)

        def _embed_vjp(other, tokens, dx0):
            _, vjp = jax.vjp(
                lambda o: cm.embed(o["embed"], tokens, cfg, rules), other)
            (g,) = vjp(dx0)
            return jax.tree.map(lambda g_: jax.lax.psum(g_, axis), g)

        def _finish(other, other_opt, step, g_head, g_emb, sumsq_flat):
            g_other = jax.tree.map(jnp.add, g_head, g_emb)
            new_step = step + 1
            lr = adam_mod.lr_at(tc, new_step)
            gnorm = jnp.sqrt(sumsq_flat
                             + sum(jnp.sum(x.astype(jnp.float32) ** 2)
                                   for x in jax.tree.leaves(g_other)))
            new_other, new_other_opt = adam_mod.apply_updates(
                g_other, other_opt, tc, params_prev=other)
            return new_other, new_other_opt, new_step, \
                {"grad_norm": gnorm, "lr": lr}

        with jax.set_mesh(mesh):
            finish = jax.jit(_finish)
        fns = {
            "embed_fwd": smap(_embed_fwd, (other_specs, bspec), xspec),
            "accum_sumsq": smap(_accum_sumsq, (rep, rowspec), rep),
            "head": smap(_head, (xspec, other_specs, bspec),
                         (rep, xspec, other_specs)),
            "embed_vjp": smap(_embed_vjp, (other_specs, bspec, xspec),
                              other_specs),
            "finish": finish,
        }
        if not self.is_moe:
            fns["layer_fwd"] = smap(_layer_fwd, (xspec, rowspec), xspec)
            fns["layer_vjp"] = smap(_layer_vjp, (xspec, rowspec, xspec),
                                    (xspec, rowspec))
            return _trace_wrap_fns(fns)

        # ---- MoE layer pieces: attention part + fixed-width expert waves --
        # A layer materializes as 1 dense row (ln1+attn+ln2) plus, per wave,
        # `W` expert rows gathered as a (W, Pe) buffer. Summing the wave
        # outputs over a partition of the selected experts reproduces the
        # all-resident moe_ffn exactly (see models/moe.py), and each wave's
        # vjp yields the reduce-scattered expert-row gradient shards through
        # the same all-gather transpose as the dense rows.
        group = 1024  # token group for sorted dispatch (moe_ffn default)
        espec = P(None, axis)
        unflatten_e = self._unflatten_expert

        def _xmid(x, row):
            blk = _gather_blk(row)
            B, S = x.shape[0], x.shape[1]
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
            a, _ = cm.attention_block(
                blk["attn"], cm.norm(x, blk["ln1"], cfg.norm_kind),
                positions, cfg, rules, causal=True)
            return x + a

        def _moe_attn(x, row, router_l):
            x_mid = _xmid(x, row)
            blk = _gather_blk(row)
            xn = cm.norm(x_mid, blk["ln2"], cfg.norm_kind)
            counts = moe_mod.moe_counts(router_l, xn, cfg, group=group)
            cap = moe_mod._capacity(cfg, min(group, x.shape[1]))
            # global routing view: which experts need paging in, plus the S1
            # drop/load accounting — one small psum each, replicated out
            counts_e = jax.lax.psum(jnp.sum(counts, axis=0), axis)
            dropped = jax.lax.psum(jnp.sum(jnp.maximum(counts - cap, 0)), axis)
            routed = jax.lax.psum(jnp.sum(counts), axis)
            return x_mid, counts_e, dropped, routed

        def _wave_fwd(x_mid, row, router_l, erows, sel_ids, sel_mask):
            blk = _gather_blk(row)
            xn = cm.norm(x_mid, blk["ln2"], cfg.norm_kind)
            rows_g = jax.lax.all_gather(erows, axis, axis=1, tiled=True)
            rtree = jax.vmap(lambda r: unflatten_e(r, jnp.bfloat16))(rows_g)
            return moe_mod.moe_ffn_selected(router_l, rtree, xn, sel_ids,
                                            sel_mask, cfg, rules, group=group)

        def _wave_vjp(x_mid, row, router_l, erows, sel_ids, sel_mask, dy):
            def f(x_mid, row, router_l, erows):
                return _wave_fwd(x_mid, row, router_l, erows, sel_ids,
                                 sel_mask)

            _, vjp = jax.vjp(f, x_mid, row, router_l, erows)
            dxm, drow, drt, der = vjp(dy)
            # row/expert cotangents are the reduce-scattered local shards
            # (all-gather transpose); the replicated router needs the psum
            drt = jax.lax.psum(drt.astype(jnp.float32), axis)
            return dxm, drow.astype(jnp.float32), drt, der.astype(jnp.float32)

        def _moe_attn_vjp(x, row, dxmid):
            _, vjp = jax.vjp(_xmid, x, row)
            dx, drow = vjp(dxmid)
            return dx, drow.astype(jnp.float32)

        def _accum_sumsq2(acc, rows):
            return acc + jax.lax.psum(
                jnp.sum(rows.astype(jnp.float32) ** 2), axis)

        fns.update({
            "moe_xmid": smap(_xmid, (xspec, rowspec), xspec),
            "moe_attn": smap(_moe_attn, (xspec, rowspec, rep),
                             (xspec, rep, rep, rep)),
            "moe_wave_fwd": smap(_wave_fwd,
                                 (xspec, rowspec, rep, espec, rep, rep),
                                 xspec),
            "moe_wave_vjp": smap(_wave_vjp,
                                 (xspec, rowspec, rep, espec, rep, rep, xspec),
                                 (xspec, rowspec, rep, espec)),
            "moe_attn_vjp": smap(_moe_attn_vjp, (xspec, rowspec, xspec),
                                 (xspec, rowspec)),
            "accum_sumsq2": smap(_accum_sumsq2, (rep, espec), rep),
        })
        return _trace_wrap_fns(fns)

    def state_structs(self):
        """ShapeDtypeStruct tree matching ``init_state`` for the active tier."""
        shardings = self.state_shardings()
        mesh = self.mesh
        sh = lambda spec: NamedSharding(mesh, spec)
        L, Pl = self.n_layers, self.layout.padded
        other_specs = pt.shape_struct_tree(
            self._other_defs(), pt.AxisRules(table=()), mesh)
        opt_specs = adam_mod.AdamState(
            jax.ShapeDtypeStruct((), jnp.int32, sharding=sh(P())),
            jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=s.sharding), other_specs),
            jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=s.sharding), other_specs),
            jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=s.sharding), other_specs),
        )
        state = {
            "flat": jax.ShapeDtypeStruct((L, Pl), jnp.bfloat16, sharding=shardings["flat"]),
            "other": other_specs,
            "other_opt": opt_specs,
            "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=sh(P())),
        }
        if self.is_moe:
            state["eflat"] = jax.ShapeDtypeStruct(
                (L * self.n_experts, self.elayout.padded), jnp.bfloat16,
                sharding=shardings["eflat"])
        if self.grad_compress:
            state["g_err"] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    (self.dp,) + tuple(s.shape), jnp.float32,
                    sharding=sh(P(self.axis))),
                other_specs)
        if not self.offgraph:
            state.update({k: jax.ShapeDtypeStruct((L, Pl), jnp.float32,
                                                  sharding=shardings[k])
                          for k in ("master", "m", "v")})
        return state

    def jit_train_step(self, *, grads_only: bool = None):
        """``make_train_step`` under jit, its state outputs placed like
        ``state_shardings``, memory kind included: the state a step returns
        is laid out exactly as the next step takes it."""
        if grads_only is None:
            grads_only = self.offgraph
        sh = self.state_shardings()
        if grads_only:  # master/m/v stay with the executor's stores
            sh = {k: v for k, v in sh.items() if k not in ("master", "m", "v")}
            out = (sh, None, None)
        else:
            out = (sh, None)
        return jax.jit(self.make_train_step(grads_only=grads_only),
                       out_shardings=out)

    def lower_train(self, shape: ShapeConfig, *, grads_only: bool = None):
        if self.is_moe:
            raise NotImplementedError(
                "explicit-engine MoE runs only as the layered epoch; there "
                "is no single lowered step to inspect")
        mesh = self.mesh
        sh = lambda spec: NamedSharding(mesh, spec)
        state = self.state_structs()
        B, S = shape.global_batch, shape.seq_len
        batch = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=sh(P(self.axis, None))),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=sh(P(self.axis, None))),
        }
        with jax.set_mesh(self.mesh):
            return self.jit_train_step(grads_only=grads_only).lower(state, batch)
