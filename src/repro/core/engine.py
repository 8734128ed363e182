"""ZeroInfinityEngine: RunConfig + mesh -> sharded train_step / serve fns.

This is the GSPMD-native engine: ZeRO stage-3 parameter/grad/optimizer
partitioning is expressed through shardings (see core/partition.py), so XLA
emits the paper's collective schedule (per-layer all-gather fwd/bwd,
reduce-scatter for grads) inside the scanned layer loop. The paper-faithful
explicit-collective engine (controllable prefetch depth,
broadcast-vs-allgather modes) lives in core/zero.py.

Offload tiers:
  * "device"  — everything in HBM.
  * "host"    — optimizer states (and/or bf16 params) live in pinned host
                memory (`memory_kind="pinned_host"`); the train step streams
                them HBM<->host with in-graph device_put (async copies).
  * "nvme"    — optimizer states live in the NvmeStore; the jit step computes
                grads only and the host loop runs the chunked, overlapped
                optimizer step (see core/offload.py + launch/train.py).
                NVMe-resident *params* are streamed per-leaf through the
                layer scheduler (core/schedule.py): the executor prefetches
                each leaf inside a bounded window, device_puts it as it
                lands, and evicts the host staging copy immediately; the
                in-graph optimizer update stays viable (params are fully
                assembled for the jit step on this engine).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import RunConfig, ShapeConfig
from repro.core import partition as pt
from repro.models import registry
from repro.optim import adam


def _tree_shardings(defs, rules, mesh, memory_kind=None):
    return pt.sharding_tree(defs, rules, mesh, memory_kind)


def _device_put_tree(tree, shardings):
    return jax.tree.map(jax.device_put, tree, shardings)


class ZeroInfinityEngine:
    def __init__(self, run: RunConfig, mesh: Mesh):
        self.run = run
        self.mesh = mesh
        mc, pc = run.model, run.parallel
        self.act_rules = pt.make_rules(mc, mesh, pc, for_state="act")
        self.param_rules = pt.make_rules(mc, mesh, pc, for_state="param")
        self.grad_rules = pt.make_rules(mc, mesh, pc, for_state="grad")
        self.opt_rules = pt.make_rules(mc, mesh, pc, for_state="opt")
        self.bundle = registry.build(mc, self.act_rules, pc)
        self.opt_defs = adam.state_defs(self.bundle.defs)
        host = "host" in (run.offload.param_tier, run.offload.opt_tier)
        self.host_kind = pt.host_memory_kind(mesh) if host else None

    # ------------------------------------------------------------------
    # shardings & specs
    # ------------------------------------------------------------------

    def _tier_kind(self, tier: str) -> Optional[str]:
        if tier == "host":
            return self.host_kind
        return None  # device, nvme (nvme states never enter the graph)

    def param_shardings(self):
        return _tree_shardings(self.bundle.defs, self.param_rules, self.mesh,
                               self._tier_kind(self.run.offload.param_tier))

    def opt_shardings(self):
        return _tree_shardings(self.opt_defs, self.opt_rules, self.mesh,
                               self._tier_kind(self.run.offload.opt_tier))

    def grad_shardings(self):
        return _tree_shardings(self.bundle.defs, self.grad_rules, self.mesh)

    def param_specs(self):
        return pt.shape_struct_tree(self.bundle.defs, self.param_rules, self.mesh,
                                    self._tier_kind(self.run.offload.param_tier))

    def opt_specs(self):
        return pt.shape_struct_tree(self.opt_defs, self.opt_rules, self.mesh,
                                    self._tier_kind(self.run.offload.opt_tier))

    def state_specs(self):
        if self.run.opt_offgraph:
            return {"params": self.param_specs()}
        return {"params": self.param_specs(), "opt": self._opt_state_from(self.opt_specs())}

    def state_shardings(self):
        """Sharding tree matching ``init_state`` (EngineProtocol)."""
        if self.run.opt_offgraph:
            return {"params": self.param_shardings()}
        return {"params": self.param_shardings(),
                "opt": self._opt_state_from(self.opt_shardings())}

    @staticmethod
    def _opt_state_from(tree) -> adam.AdamState:
        return adam.AdamState(tree["step"], tree["master"], tree["m"], tree["v"])

    def batch_sharding(self, spec: jax.ShapeDtypeStruct):
        dp = (tuple(self.mesh.axis_names) if self.run.parallel.pure_dp
              else pt.dp_axes(self.mesh))
        # divisibility guard: a global batch smaller than dp (e.g. the
        # long_500k single-sequence decode) replicates over the surplus axes
        if dp and spec.shape:
            deg = 1
            usable = []
            for a in dp:
                if spec.shape[0] % (deg * self.mesh.shape[a]) == 0:
                    usable.append(a)
                    deg *= self.mesh.shape[a]
            dp = tuple(usable)
        axes = [dp if dp else None] + [None] * (len(spec.shape) - 1)
        while axes and axes[-1] is None:
            axes.pop()
        return NamedSharding(self.mesh, P(*axes))

    def batch_specs(self, shape: ShapeConfig):
        specs = self.bundle.input_specs(shape)
        return {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=self.batch_sharding(v))
                for k, v in specs.items()}

    def cache_specs(self, shape: ShapeConfig):
        defs = self.bundle.cache_defs(shape.global_batch, shape.seq_len)
        return pt.shape_struct_tree(defs, self.act_rules, self.mesh)

    # ------------------------------------------------------------------
    # init (real allocation — small configs / CPU)
    # ------------------------------------------------------------------

    def init_state(self, rng: jax.Array):
        shardings = self.param_shardings()

        def _init(rng):
            params = pt.init_tree(rng, self.bundle.defs)
            return params

        with jax.set_mesh(self.mesh):
            params = jax.jit(_init, out_shardings=shardings)(rng)
            if self.run.opt_offgraph:
                # master/m/v never enter device memory: they live in the
                # executor's ArrayStore (seeded from these params)
                return {"params": params}
            opt = jax.jit(adam.init_state,
                          out_shardings=self._opt_state_from(self.opt_shardings()))(params)
        return {"params": params, "opt": opt}

    # ------------------------------------------------------------------
    # train step
    # ------------------------------------------------------------------

    def make_train_step(self, *, grads_only: bool = False):
        run = self.run
        tc = run.train
        pc = run.parallel
        bundle = self.bundle
        grad_shardings = self.grad_shardings()
        opt_host = (run.offload.opt_tier == "host" and self.host_kind
                    and not grads_only)
        param_host = run.offload.param_tier == "host" and self.host_kind
        param_shardings = self.param_shardings() if param_host else None

        # families with routing/step statistics (moe) expose loss_stats: the
        # grad pass threads the aux dict out so drop/load counters land in
        # step metrics without a second forward
        loss_f, has_aux = bundle.loss, False
        if bundle.loss_stats is not None:
            loss_f, has_aux = bundle.loss_stats, True

        def grads_of(params, batch):
            accum = pc.grad_accum
            if accum <= 1:
                loss, grads = jax.value_and_grad(loss_f, has_aux=has_aux)(params, batch)
                if has_aux:
                    loss, aux = loss
                    return loss, grads, aux
                return loss, grads, {}
            # microbatch over the leading batch dim
            micro = jax.tree.map(lambda x: x.reshape(accum, x.shape[0] // accum, *x.shape[1:]),
                                 batch)

            def step(carry, mb):
                loss_acc, g_acc = carry
                loss, g = jax.value_and_grad(loss_f, has_aux=has_aux)(params, mb)
                aux = {}
                if has_aux:
                    loss, aux = loss
                g = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (loss_acc + loss, g), aux

            zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), auxs = jax.lax.scan(step, (jnp.zeros(()), zeros), micro)
            inv = 1.0 / accum
            aux = jax.tree.map(lambda a: jnp.mean(a, axis=0), auxs) if has_aux else {}
            return loss * inv, jax.tree.map(lambda g: g * inv, grads), aux

        def train_step(state, batch):
            params, opt = state["params"], state.get("opt")  # no opt offgraph
            if param_host:  # stream bf16 params host -> HBM ahead of the
                # per-layer all-gathers (async copies under latency hiding)
                params = jax.tree.map(
                    lambda x, s: jax.device_put(x, s.with_memory_kind("device")),
                    params, param_shardings)
            if opt_host:  # stream optimizer states host -> HBM for the update
                opt = jax.tree.map(
                    lambda x, s: jax.device_put(x, s.with_memory_kind("device")),
                    opt, self._opt_state_from(self.opt_shardings()))
            loss, grads, aux = grads_of(params, batch)
            # ZeRO grad partitioning: force reduce-scatter placement
            grads = jax.tree.map(
                lambda g, s: jax.lax.with_sharding_constraint(g, s), grads, grad_shardings)
            if grads_only:
                gnorm = _global_norm(grads)
                return grads, {"loss": loss, "grad_norm": gnorm, **aux}
            new_params, new_opt = adam.apply_updates(grads, opt, tc, params_prev=params)
            # metrics read the device-side states: a value computed from a
            # leaf already moved to host would leave the step in host memory
            metrics = {"loss": loss, "grad_norm": _global_norm(grads),
                       "lr": adam.lr_at(tc, new_opt.step), **aux}
            if param_host:  # updated bf16 params return to pinned host memory
                new_params = jax.tree.map(
                    lambda x, s: jax.device_put(x, s), new_params, param_shardings)
            if opt_host:  # stream updated states back to pinned host memory
                new_opt = jax.tree.map(
                    lambda x, s: jax.device_put(x, s), new_opt,
                    self._opt_state_from(self.opt_shardings()))
            return {"params": new_params, "opt": new_opt}, metrics

        return train_step

    def jit_train_step(self, *, grads_only: bool = False, donate: bool = False):
        """``make_train_step`` under jit, its state outputs placed like
        ``state_shardings``, memory kind included: the state a step returns
        is laid out exactly as the next step takes it, host tiers in
        ``pinned_host``."""
        step = self.make_train_step(grads_only=grads_only)
        out = (None, None) if grads_only else (self.state_shardings(), None)
        kw = {"donate_argnums": (0,)} if donate and not grads_only else {}
        return jax.jit(step, out_shardings=out, **kw)

    def lower_train(self, shape: ShapeConfig, *, grads_only: Optional[bool] = None,
                    donate: bool = True):
        if grads_only is None:  # resolve from the configured tiers
            grads_only = self.run.opt_offgraph
        step = self.jit_train_step(grads_only=grads_only, donate=donate)
        with jax.set_mesh(self.mesh):
            return step.lower(self.state_specs(), self.batch_specs(shape))

    # ------------------------------------------------------------------
    # serve steps
    # ------------------------------------------------------------------

    def lower_prefill(self, shape: ShapeConfig):
        with jax.set_mesh(self.mesh):
            return jax.jit(self.bundle.prefill).lower(self.param_specs(), self.batch_specs(shape))

    def lower_decode(self, shape: ShapeConfig):
        batch = self.batch_specs(shape)
        cache = self.cache_specs(shape)
        with jax.set_mesh(self.mesh):
            return jax.jit(self.bundle.decode_step).lower(self.param_specs(), cache, batch)

    def lower(self, shape: ShapeConfig):
        if shape.kind == "train":
            return self.lower_train(shape)
        if shape.kind == "prefill":
            return self.lower_prefill(shape)
        return self.lower_decode(shape)


@jax.named_scope("grad_norm")
def _global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))
