"""Causal GQA flash attention — Pallas TPU kernel.

Online-softmax attention with BlockSpec VMEM tiling: the (Sq, Sk) score
matrix never materializes in HBM (peak VMEM = bq*bk scores + running
(m, l, acc) scratch). The sequential last grid axis walks KV blocks;
causality is enforced with an in-kernel mask (out-of-range blocks are
masked, not skipped). GQA maps q-head h -> kv-head h // (H // KV) in the
BlockSpec index maps, so K/V tiles are fetched once per group.

This is the TPU perf path for train/prefill attention; the pure-jnp oracle
is kernels/ref.py:attention_ref (and models/common.chunked_attention is the
XLA-level equivalent used in lowering).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, bq: int, bk: int, causal: bool, sk_valid: int,
                  q_offset: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0]  # (bq, D)
    k = k_ref[0, 0]  # (bk, D)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (bq, bk)

    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = k_pos < sk_valid
    if causal:
        # decode-style alignment: the last query attends the last key
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + q_offset
        valid = valid & (k_pos <= q_pos)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]  # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(v_ref.dtype), v_ref[0, 0], preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, interpret: bool, causal: bool = True,
                    bq: int = 256, bk: int = 256):
    """q: (B, H, Sq, D), k/v: (B, KV, Sk, D) with H % KV == 0 -> (B, H, Sq, D)."""
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    assert H % KV == 0
    n_rep = H // KV
    scale = D ** -0.5
    bq, bk = min(bq, Sq), min(bk, Sk)
    # pad sequences to whole blocks; padded K positions are masked out via
    # -inf scores (k_valid), padded Q rows are sliced away after the call.
    pq, pk_ = (-Sq) % bq, (-Sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk_:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk_), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk_), (0, 0)))
    Sqp, Skp = Sq + pq, Sk + pk_
    grid = (B, H, Sqp // bq, Skp // bk)
    kernel = functools.partial(_flash_kernel, scale=scale, bq=bq, bk=bk,
                               causal=causal, sk_valid=Sk, q_offset=Sk - Sq)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // n_rep, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // n_rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sqp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :Sq]
