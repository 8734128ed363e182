"""Partitioned mixed-precision AdamW (paper Secs. 2, 3).

Model-state layout matches the paper's 20-bytes/param accounting:
  * bf16 parameters (compute copy)   — 2 B
  * bf16 gradients (transient)       — 2 B
  * fp32 master params + m + v       — 12 B (optimizer states)
All optimizer-state leaves carry the same ZeRO sharding as their parameter
(stage >= 1 partitions them across dp), so the update is embarrassingly
parallel across shards — the property the paper exploits to hit the 1.5 TB/s
optimizer-state bandwidth requirement with aggregate memory bandwidth.

``use_fused=True`` routes the elementwise update through the Pallas
fused-Adam kernel (one HBM pass) on TPU; the jnp path is the oracle.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.config import TrainConfig


class AdamState(NamedTuple):
    step: jax.Array  # int32 scalar
    master: dict  # fp32 params
    m: dict
    v: dict


def init_state(params) -> AdamState:
    master = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    zeros = lambda: jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return AdamState(jnp.zeros((), jnp.int32), master, zeros(), zeros())


def state_defs(param_defs):
    """ParamDef tree for the optimizer state (dry-run specs, fp32)."""
    from repro.core.partition import ParamDef

    f32 = lambda: jax.tree.map(
        lambda d: ParamDef(d.shape, d.axes, "float32", "zeros"),
        param_defs, is_leaf=lambda x: isinstance(x, ParamDef))
    return {"step": ParamDef((), (), "int32", "zeros"),
            "master": f32(), "m": f32(), "v": f32()}


def lr_at(tc: TrainConfig, step: jax.Array) -> jax.Array:
    warm = jnp.minimum(step.astype(jnp.float32) / max(tc.warmup_steps, 1), 1.0)
    return tc.lr * warm


@jax.named_scope("optimizer")
def apply_updates(grads, state: AdamState, tc: TrainConfig, *, params_prev=None,
                  use_fused: bool = False):
    """Returns (new compute-dtype params, new AdamState). grads: bf16/f32 tree.
    ``params_prev`` supplies per-leaf compute dtypes (default bf16)."""
    step = state.step + 1
    lr = lr_at(tc, step)
    b1, b2, eps, wd = tc.beta1, tc.beta2, tc.eps, tc.weight_decay
    c1 = 1.0 - b1 ** step.astype(jnp.float32)
    c2 = 1.0 - b2 ** step.astype(jnp.float32)

    if use_fused:
        from repro.kernels import ops as kops

        def upd(g, p32, m, v):
            return kops.fused_adam(p32, g.astype(jnp.float32), m, v,
                                   lr=lr, beta1=b1, beta2=b2, eps=eps,
                                   weight_decay=wd, bc1=c1, bc2=c2)
    else:
        def upd(g, p32, m, v):
            g = g.astype(jnp.float32)
            m = b1 * m + (1.0 - b1) * g
            v = b2 * v + (1.0 - b2) * g * g
            mh = m / c1
            vh = v / c2
            p32 = p32 - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p32)
            return p32, m, v

    flat_g, td = jax.tree.flatten(grads)
    flat_p = td.flatten_up_to(state.master)
    flat_m = td.flatten_up_to(state.m)
    flat_v = td.flatten_up_to(state.v)
    out = [upd(g, p, m, v) for g, p, m, v in zip(flat_g, flat_p, flat_m, flat_v)]
    master = td.unflatten([o[0] for o in out])
    m = td.unflatten([o[1] for o in out])
    v = td.unflatten([o[2] for o in out])
    if params_prev is not None:
        params = jax.tree.map(lambda p32, p: p32.astype(p.dtype), master, params_prev)
    else:
        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), master)
    return params, AdamState(step, master, m, v)
