"""Bandwidth-centric partitioning: logical axes -> mesh shardings.

The paper's key memory insight (Sec. 6.1): partition *every* model-state
tensor across *all* data-parallel workers so that (a) no worker holds a
redundant copy and (b) when a tensor must be materialized, every worker's
memory link participates in the gather (allgather), instead of one owner
broadcasting over a single link.

In JAX this is a sharding policy: each parameter leaf carries logical dim
names; ``AxisRules`` maps logical dims to mesh axes. ZeRO stages 0-3
(paper Table 2) are different rule sets for params / grads / optimizer
states. XLA-SPMD then materializes exactly the paper's collective schedule:
per-layer ``all-gather`` of the fp16/bf16 params before fwd/bwd use, and
``reduce-scatter`` of grads into the owner shard.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig, ParallelConfig

# ---------------------------------------------------------------------------
# Parameter definitions with logical axes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ParamDef:
    """Shape + dtype + logical axis names (one per dim) + init scale."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    dtype: str = "bfloat16"
    init: str = "normal"  # normal | zeros | ones | lru_lambda
    init_scale: float = 0.02

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def initialize(rng: jax.Array, d: ParamDef) -> jax.Array:
    dtype = jnp.dtype(d.dtype)
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    if d.init == "lru_lambda":
        # RG-LRU forget-gate params: init so a = exp(-8*softplus(L)*r) spans
        # (0.9, 0.999) per the Griffin paper.
        u = jax.random.uniform(rng, d.shape, jnp.float32, 0.9, 0.999)
        lam = jnp.log(jnp.expm1(-jnp.log(u) / 8.0))  # inverse softplus
        return lam.astype(dtype)
    fan_in = d.shape[0] if len(d.shape) > 1 else d.shape[-1]
    scale = d.init_scale if d.init == "normal" else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(rng, d.shape, jnp.float32) * scale).astype(dtype)


def init_tree(rng: jax.Array, defs) -> dict:
    leaves, treedef = jax.tree.flatten(defs, is_leaf=lambda x: isinstance(x, ParamDef))
    keys = jax.random.split(rng, len(leaves))
    return jax.tree.unflatten(treedef, [initialize(k, d) for k, d in zip(keys, leaves)])


# ---------------------------------------------------------------------------
# Axis rules
# ---------------------------------------------------------------------------

MeshAxes = Optional[Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """logical axis name -> mesh axes (or None = replicated)."""

    table: Tuple[Tuple[str, MeshAxes], ...]
    mesh_sizes: Tuple[Tuple[str, int], ...] = ()  # for divisibility guards

    def lookup(self, name: Optional[str]) -> MeshAxes:
        if name is None:
            return None
        for k, v in self.table:
            if k == name:
                return v
        return None

    def _degree(self, mesh_axes: Tuple[str, ...]) -> int:
        sizes = dict(self.mesh_sizes)
        n = 1
        for a in mesh_axes:
            n *= sizes.get(a, 1)
        return n

    def spec(self, axes: Sequence[Optional[str]], shape: Sequence[int] = None) -> P:
        entries = []
        used: set = set()
        for i, name in enumerate(axes):
            mesh_axes = self.lookup(name)
            if mesh_axes is None:
                entries.append(None)
                continue
            # a mesh axis may appear only once per spec
            mesh_axes = tuple(a for a in mesh_axes if a not in used)
            if not mesh_axes:
                entries.append(None)
                continue
            # divisibility guard: drop sharding for non-divisible dims
            if shape is not None and self.mesh_sizes:
                if shape[i] % self._degree(mesh_axes) != 0:
                    entries.append(None)
                    continue
            used.update(mesh_axes)
            entries.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)


def _filter_axes(axes: Tuple[str, ...], mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in axes if a in mesh.axis_names)


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes that constitute data parallelism (pod + data)."""
    return _filter_axes(("pod", "data"), mesh)


def dp_degree(mesh: Mesh) -> int:
    return int(jnp.prod(jnp.array([mesh.shape[a] for a in dp_axes(mesh)])).item()) if dp_axes(mesh) else 1


def _divisible(dim: int, mesh: Mesh, axes: Tuple[str, ...]) -> bool:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return dim % n == 0


def choose_attn_strategy(cfg: ModelConfig, mesh: Mesh, parallel: ParallelConfig) -> str:
    """'tp' (shard heads over model axis) or 'cp' (shard sequence)."""
    if parallel.attn_strategy != "auto":
        return parallel.attn_strategy
    tp = mesh.shape.get("model", 1)
    if cfg.n_heads and cfg.n_heads % tp == 0:
        return "tp"
    return "cp"


def make_rules(
    cfg: ModelConfig,
    mesh: Mesh,
    parallel: ParallelConfig,
    *,
    for_state: str = "param",  # param | opt | grad | act
) -> AxisRules:
    """Build the logical->mesh mapping implementing the ZeRO stage + TP/CP.

    ``for_state`` selects which ZeRO partitioning applies:
      * "param"/"grad": sharded over dp iff stage >= 3 / >= 2 respectively
      * "opt": sharded over dp iff stage >= 1
      * "act": batch/seq sharding for activations
    """
    # pure_dp (paper-faithful, Sec. 8.4 "without model parallelism"): every
    # mesh axis is data parallelism; ZeRO-3 partitions across all of them.
    if parallel.pure_dp:
        dp = tuple(mesh.axis_names)
        tp_avail = False
    else:
        dp = dp_axes(mesh)
        tp_avail = "model" in mesh.axis_names
    stage = parallel.zero_stage

    # Which dp axes participate in ZeRO partitioning (paper: all of them;
    # hierarchical 'pod' scope = beyond-paper MiCS-style variant).
    if parallel.zero_scope == "pod":
        zero_ax = tuple(a for a in dp if a != "pod")
    else:
        zero_ax = dp

    sharded = {
        "param": stage >= 3,
        "grad": stage >= 2,
        "opt": stage >= 1,
        "act": False,
    }[for_state]
    fsdp: MeshAxes = zero_ax if (sharded and zero_ax) else None
    e_stage = parallel.moe_zero_stage
    e_sharded = {
        "param": e_stage >= 3, "grad": e_stage >= 2, "opt": e_stage >= 1,
        "act": False,
    }[for_state]
    fsdp_e: MeshAxes = zero_ax if (e_sharded and zero_ax) else None

    attn = "dp" if parallel.pure_dp else choose_attn_strategy(cfg, mesh, parallel)
    tp = mesh.shape.get("model", 1)
    heads_tp = tp_avail and attn == "tp"
    kv_tp = heads_tp and cfg.n_kv_heads and cfg.n_kv_heads % tp == 0

    table = [
        # ---- parameter storage dims ----
        ("embed", fsdp),                       # ZeRO-3 partitioning dim
        ("embed_e", fsdp_e),                   # expert weights' ZeRO dim
        ("mlp", ("model",) if tp_avail else None),
        ("heads", ("model",) if heads_tp else None),
        ("kv_heads", ("model",) if kv_tp else None),
        ("head_dim", None),
        ("vocab", ("model",) if tp_avail else None),
        ("experts", ("model",) if tp_avail else None),
        ("inner", ("model",) if tp_avail else None),  # ssm d_inner / lru_width
        ("state", None),
        ("conv", None),
        ("layers", None),
        # ---- activation dims ----
        ("batch", dp if dp else None),
        ("seq", ("model",) if (tp_avail and attn == "cp") else None),
        ("kv_seq", None),          # gathered KV inside attention
        ("cache_seq", ("model",) if tp_avail else None),  # decode KV cache: flash-decode sharding
        ("act_embed", None),
        ("act_mlp", ("model",) if tp_avail else None),
        ("act_heads", ("model",) if heads_tp else None),
    ]
    return AxisRules(tuple(table), tuple(sorted(mesh.shape.items())))


def spec_tree(defs, rules: AxisRules):
    """Pytree of ParamDef -> pytree of PartitionSpec."""
    return jax.tree.map(
        lambda d: rules.spec(d.axes, d.shape),
        defs,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def host_memory_kind(mesh: Mesh) -> Optional[str]:
    """The memory kind that places a state in host DRAM on ``mesh``.

    ``pinned_host`` on TPU and GPU. None on the CPU, whose device memory
    already is host DRAM and whose jit cannot lower host placements: there
    the host tier is device placement. Read from the mesh's own devices, so
    a mesh of described (compile-only) TPU devices gets the TPU's answer.
    """
    dev = mesh.devices.flat[0]
    if dev.platform == "cpu":
        return None
    kinds = {m.kind for m in dev.addressable_memories()}
    if "pinned_host" not in kinds:
        raise ValueError(
            f"{dev.device_kind} exposes memory kinds {sorted(kinds)}, no "
            "pinned_host: the host tier cannot be placed on this backend")
    return "pinned_host"


def sharding_tree(defs, rules: AxisRules, mesh: Mesh, memory_kind: Optional[str] = None):
    def mk(d: ParamDef):
        spec = rules.spec(d.axes, d.shape)
        if memory_kind is None:
            return NamedSharding(mesh, spec)
        return NamedSharding(mesh, spec, memory_kind=memory_kind)

    return jax.tree.map(mk, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def shape_struct_tree(defs, rules: AxisRules, mesh: Mesh, memory_kind: Optional[str] = None,
                      dtype_override: Optional[str] = None):
    """Allocation-free parameter stand-ins for the dry-run (paper Sec. 7.2:
    the full model is never materialized unsharded)."""
    shardings = sharding_tree(defs, rules, mesh, memory_kind)
    return jax.tree.map(
        lambda d, s: jax.ShapeDtypeStruct(
            d.shape, jnp.dtype(dtype_override or d.dtype), sharding=s
        ),
        defs,
        shardings,
        is_leaf=lambda x: isinstance(x, ParamDef),
    )


def constrain(x: jax.Array, rules: AxisRules, axes: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint via logical axes (no-op off-mesh)."""
    try:
        return jax.lax.with_sharding_constraint(x, rules.spec(axes, x.shape))
    except (ValueError, RuntimeError):
        return x


# ---------------------------------------------------------------------------
# Flat (1-D) bandwidth-centric partitioning — the paper-literal layout used by
# the explicit zero3 engine: each layer's params are flattened into one
# contiguous buffer and split evenly across all dp ranks, so gathers use
# every link regardless of tensor shapes.
# ---------------------------------------------------------------------------


def flatten_layer(params: dict) -> Tuple[jax.Array, list]:
    """Flatten a pytree of same-dtype arrays into one 1-D buffer + layout."""
    leaves, treedef = jax.tree.flatten(params)
    layout = [(l.shape, l.dtype) for l in leaves]
    flat = jnp.concatenate([l.reshape(-1) for l in leaves]) if leaves else jnp.zeros((0,))
    return flat, (treedef, layout)


def unflatten_layer(flat: jax.Array, meta) -> dict:
    treedef, layout = meta
    leaves = []
    off = 0
    for shape, dtype in layout:
        n = int(jnp.prod(jnp.array(shape))) if shape else 1
        leaves.append(flat[off : off + n].reshape(shape).astype(dtype))
        off += n
    return jax.tree.unflatten(treedef, leaves)


def pad_to_multiple(x: jax.Array, m: int) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % m
    if pad:
        x = jnp.pad(x, (0, pad))
    return x
