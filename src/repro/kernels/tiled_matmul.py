"""Memory-centric tiled matmul — Pallas TPU kernel (paper Sec. 5.1.3 at the
kernel level).

The XLA-level tiling (core/tiling.py) bounds the *gathered HBM* working set;
this kernel bounds the *VMEM* working set explicitly: W streams through VMEM
in (bk, bn) tiles, so an arbitrarily large operator (e.g. nemotron's
18432x73728 up-projection, 162 MiB/bf16 per TP shard — bigger than VMEM)
runs with a fixed small footprint. Accumulation in an f32 VMEM scratch over
the sequential k grid axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mm_kernel(x_ref, w_ref, o_ref, acc_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _qmm_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, qblock):
    """Fused dequant-matmul: the weight tile arrives as int8 quants +
    per-block scales (the q8 wire layout, blocks along N) and is
    dequantized in VMEM right before the MXU dot — the full-precision W
    never exists in HBM."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bk, bn = q_ref.shape
    nb = bn // qblock
    # scales arrive transposed, (nb, bk); a 0/1 expansion matmul spreads
    # block b's scale over its qblock columns as a (bk, bn) tile. Mosaic
    # cannot split the lane dim (bn -> nb x qblock) with a reshape, and
    # HIGHEST precision keeps the expanded scales exact.
    expand = (jax.lax.broadcasted_iota(jnp.int32, (nb, bn), 0)
              == jax.lax.broadcasted_iota(jnp.int32, (nb, bn), 1) // qblock)
    scale = jax.lax.dot_general(
        s_ref[...], expand.astype(jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    w = q_ref[...].astype(jnp.float32) * scale
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def tiled_matmul(x, w, *, interpret: bool, bm: int = 256, bn: int = 256,
                 bk: int = 512):
    """x: (M, K) @ w: (K, N) -> (M, N). VMEM per step ~ bm*bk + bk*bn + bm*bn."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    # pad to whole blocks (zeros contribute nothing to the contraction)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    Mp, Kp, Np = M + pm, K + pk, N + pn
    grid = (Mp // bm, Np // bn, Kp // bk)
    out = pl.pallas_call(
        _mm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w)
    return out[:M, :N]


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def quantized_matmul(x, q, scales, *, interpret: bool, bm: int = 256,
                     bn: int = 256, bk: int = 512):
    """x: (M, K) @ dequant(q: (K, N) int8, scales: (K, N//qblock)) -> (M, N).

    ``q``/``scales`` are the q8 wire layout of ``core/qformat.py``
    (``wire_matmul_operands`` / ``quantize_q8_jnp``): absmax/127 fp16 scales
    over blocks of consecutive N elements. Only wire-sized bytes transit to
    the kernel; each (bk, bn) weight tile dequantizes in VMEM scratch-free
    right before its MXU dot. N must be a multiple of the quant block."""
    M, K = x.shape
    K2, N = q.shape
    assert K == K2
    Kb, nb = scales.shape
    assert Kb == K and nb * (N // nb) == N and N % nb == 0
    qblock = N // nb
    bm, bk = min(bm, M), min(bk, K)
    bn = max(qblock, min(bn, N) // qblock * qblock)  # whole quant blocks
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        # zero scales on padding decode to zero weights — the contraction
        # is unchanged
        q = jnp.pad(q, ((0, pk), (0, pn)))
        scales = jnp.pad(scales, ((0, pk), (0, pn // qblock)))
    Mp, Kp, Np = M + pm, K + pk, N + pn
    grid = (Mp // bm, Np // bn, Kp // bk)
    # (Np/qblock, Kp) f32: a (bn/qblock, bk) scale block meets the TPU's
    # (8, 128) block tiling where a (bk, bn/qblock) one cannot, and v5e
    # has no fp16 vector loads
    scales = scales.T.astype(jnp.float32)
    out = pl.pallas_call(
        functools.partial(_qmm_kernel, qblock=qblock),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bn // qblock, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, q, scales)
    return out[:M, :N]
