"""Fused Adam update — Pallas TPU kernel.

The optimizer step is the paper's most bandwidth-hungry phase (Sec. 4.1:
AIT = seq*bsz/4; Sec. 5.2.2: needs ~1.5 TB/s). On TPU the states live in HBM
and the update is purely memory-bound, so the win is doing ONE fused HBM pass
over (p32, m, v, g) -> (p32, m, v, p_bf16) instead of the ~10 separate
elementwise HLO ops (each a full read+write). BlockSpec streams row-blocks
through VMEM; hyperparameters ride in SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
DEFAULT_BLOCK_ROWS = 256  # (256, 128) f32 tiles: 4 inputs + 3 outputs ~ 0.9 MB VMEM


def _adam_kernel(scalars_ref, p_ref, g_ref, m_ref, v_ref,
                 p_out_ref, m_out_ref, v_out_ref, pbf_out_ref):
    lr = scalars_ref[0]
    b1 = scalars_ref[1]
    b2 = scalars_ref[2]
    eps = scalars_ref[3]
    wd = scalars_ref[4]
    c1 = scalars_ref[5]
    c2 = scalars_ref[6]

    g = g_ref[...]
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    mh = m / c1
    vh = v / c2
    p = p_ref[...]
    p = p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
    p_out_ref[...] = p
    m_out_ref[...] = m
    v_out_ref[...] = v
    pbf_out_ref[...] = p.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def fused_adam_flat(p32, g32, m, v, scalars, *, interpret: bool,
                    block_rows: int = DEFAULT_BLOCK_ROWS):
    """All arrays (R, 128) f32; scalars (7,) f32 = [lr,b1,b2,eps,wd,c1,c2].

    Returns (p32, m, v, p_bf16).
    """
    R = p32.shape[0]
    bi = min(block_rows, R)
    grid = (pl.cdiv(R, bi),)
    bs = pl.BlockSpec((bi, LANE), lambda i: (i, 0))
    return pl.pallas_call(
        _adam_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            bs, bs, bs, bs,
        ],
        out_specs=[bs, bs, bs,
                   pl.BlockSpec((bi, LANE), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((R, LANE), jnp.float32),
            jax.ShapeDtypeStruct((R, LANE), jnp.float32),
            jax.ShapeDtypeStruct((R, LANE), jnp.float32),
            jax.ShapeDtypeStruct((R, LANE), jnp.bfloat16),
        ],
        interpret=interpret,
    )(scalars, p32, g32, m, v)
