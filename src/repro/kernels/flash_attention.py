"""Causal GQA flash attention: Pallas TPU kernels, forward and backward.

Forward: online softmax over KV blocks with BlockSpec VMEM tiling, so the
(Sq, Sk) score matrix never reaches HBM (peak VMEM = bq*bk scores + running
(m, l, acc) scratch). It saves only the output and the per-row log-sum-exp
(f32, ``(B, H, 1, Sq)``, a row per head so that it is lane-dense in HBM).

Backward (``jax.custom_vjp``): one fused kernel, ``flash_attention_bwd``,
where its dQ accumulator fits in VMEM (``fused_backward``); else the split
pair of the usual flash structure.

- Fused: the grid walks a kv head's KV blocks, and inner sequential axes
  walk the blocks of query heads that share the KV head and the q blocks;
  the q-side index maps hand each step its heads. It computes in the
  transposed frame (``k q^T``), where the log-sum-exp and ``D_i`` rows
  broadcast down the sublanes. Each visited block recomputes ``p`` and
  ``dS`` once and runs five block matmuls: the scores, ``dP``, and dV, dK
  and dQ. dK/dV accumulate in VMEM over the kv block's q blocks and heads;
  dQ accumulates in a VMEM scratch of the kv head's ``H // KV`` query heads
  over the whole padded sequence (f32, lanes padded to 128: 3 MiB at
  GQA 9/3, S 2048, head dim 64), written once, as the input dtype, after
  the kv head's last block; the kernel's scoped VMEM is raised by that
  scratch and its output block's buffers (``dq_resident_bytes``). So the
  KV-block axis is sequential; batch and kv heads stay parallel. No dQ
  partials reach HBM.
- Split, where that scratch and its output block outgrow
  ``VMEM_DQ_BYTES`` (long sequences, wide heads, many heads per kv head):
  a dK/dV kernel on the fused kernel's grid, and a dQ kernel whose grid
  walks q blocks with an inner sequential axis over KV blocks. Both
  recompute the scores, ``p`` and ``dS``: seven block matmuls to the fused
  kernel's five.

Every backward kernel recomputes ``s = q k^T * scale`` and
``p = exp(s - lse)`` in VMEM and uses ``D_i = rowsum(dO * O)``, computed
once outside the kernels. Matmul operands stay in the input dtype (bf16 in
the model) with f32 accumulation; the softmax statistics and dS stay f32
until the operand cast.

Causality: blocks wholly above the diagonal are skipped in compute
(``pl.when``) and in DMA (each index map clamps to the last block its row
or column needs, so a skipped step fetches nothing new). Only blocks that
the diagonal crosses, or that hold padded keys, are masked. GQA: a grid
step takes a block of query heads that share one KV head (all ``H // KV``
of them where VMEM allows, ``heads_per_step``), so a K/V tile is fetched
once for the block.

``models/common.attention`` routes causal self-attention here on a TPU;
``kernels/ref.py:attention_ref`` is the oracle.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
VMEM_HEAD_ROWS = 1 << 18  # q-block elements of the heads one step takes
VMEM_SCOPED = 16 << 20  # a v5e's default scoped VMEM, which the split
# kernels fit; the fused backward is granted its resident dQ on top of it
# (dq_resident_bytes), up to VMEM_DQ_BYTES, so its limit stays within half
# of a v5e's 128 MiB of VMEM. The v5e compiler takes the fused kernel up to
# GQA 28/2 at S 8192 and head dim 128 (168 MiB as counted here) and refuses
# 32/2 (192 MiB) and 8/1 at S 32768 (384 MiB)
VMEM_DQ_BYTES = 48 << 20
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def block_size(s: int, cap: int) -> int:
    """The block for a sequence of ``s``: the largest power of two from 128
    up to ``cap`` that divides it; else ``s`` itself when it is shorter
    than 128; else 128 (the sequence is then padded to whole blocks)."""
    b = cap
    while b > LANES and s % b:
        b //= 2
    if s % b == 0:
        return b
    return s if s < LANES else LANES


def blocks(sq: int, sk: int, d: int) -> tuple:
    """((bq, bk) of the forward, (bq, bk) of the backward) from the shapes.
    At head_dim 64 and S 2048 on a v5e the forward is fastest at 512 x 1024
    and the backward at 512 x 512 (PERF.md); a wider head halves them, to
    keep the score blocks of a query-head group in VMEM."""
    wide = d > 64
    fwd = (block_size(sq, 256 if wide else 512),
           block_size(sk, 512 if wide else 1024))
    bwd = (block_size(sq, 256 if wide else 512),
           block_size(sk, 256 if wide else 512))
    return fwd, bwd


def heads_per_step(n_rep: int, bq: int, d: int) -> int:
    """How many of the ``n_rep`` query heads that share a kv head one grid
    step takes: the most that divide ``n_rep`` with their q blocks within
    VMEM_HEAD_ROWS (a v5e compiles 7 heads x 256 x 128 and refuses
    12 x 256 x 192)."""
    return max(g for g in range(1, n_rep + 1)
               if n_rep % g == 0 and (g == 1 or g * bq * d <= VMEM_HEAD_ROWS))


def dq_resident_bytes(n_rep: int, sq: int, d: int) -> int:
    """VMEM the fused backward holds for dQ: the f32 accumulator of a kv
    head's ``n_rep`` query heads over ``sq`` padded rows and the output
    block's two buffers, counted at 4 B (lanes padded to 128)."""
    return 3 * n_rep * sq * (-(-d // LANES) * LANES) * 4


def fused_backward(q_shape, k_shape) -> bool:
    """Whether a call with q ``(B, H, Sq, D)`` and k ``(B, KV, Sk, D)``
    runs the fused backward (else the split pair)."""
    return plans(q_shape, k_shape, causal=True, interpret=False)[1].fused


@dataclasses.dataclass(frozen=True)
class _Plan:
    """Static shape of one kernel call: blocks, masking, grid extents."""

    scale: float
    bq: int
    bk: int
    nq: int
    nk: int
    group: int  # query heads one grid step takes, all of one kv head's
    n_sub: int  # such groups per kv head: H // KV // group
    causal: bool
    sk_valid: int  # keys at or past it are padding
    q_offset: int  # absolute position of q row 0 relative to key 0
    interpret: bool
    fused: bool  # backward: one kernel with dQ resident in VMEM

    def last_k(self, qi):
        """The last kv block that q block ``qi`` needs."""
        if not self.causal:
            return self.nk - 1
        last = (qi * self.bq + self.bq - 1 + self.q_offset) // self.bk
        return jnp.clip(last, 0, self.nk - 1)

    def first_q(self, ki):
        """The first q block that needs kv block ``ki``."""
        if not self.causal:
            return 0
        return jnp.clip((ki * self.bk - self.q_offset) // self.bq, 0,
                        self.nq - 1)

    def needed(self, qi, ki):
        """Whether block (qi, ki) holds any unmasked score."""
        if not self.causal:
            return True
        return ki * self.bk <= qi * self.bq + self.bq - 1 + self.q_offset

    def crossed(self, qi, ki):
        """Whether block (qi, ki) holds any masked score: the diagonal
        crosses it, or it holds padded keys."""
        c = (ki + 1) * self.bk > self.sk_valid
        if self.causal:
            c = jnp.logical_or(
                c, (ki + 1) * self.bk - 1 > qi * self.bq + self.q_offset)
        return c

    def valid(self, qi, ki, shape, q_axis: int):
        """The (q, k) positions of a score block that attend."""
        k_pos = ki * self.bk + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                        1 - q_axis)
        ok = k_pos < self.sk_valid
        if self.causal:
            q_pos = (qi * self.bq + self.q_offset
                     + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis))
            ok = jnp.logical_and(ok, k_pos <= q_pos)
        return ok

    def run(self, qi, ki, body):
        """``body(masked)`` for block (qi, ki): skipped above the diagonal,
        masked only where the block holds masked scores. (Running the
        blocks the diagonal crosses in halves, each skipped or masked on its
        own, measured slower on a v5e: PERF.md.)"""
        need, cross = self.needed(qi, ki), self.crossed(qi, ki)
        pl.when(jnp.logical_and(need, cross))(lambda: body(True))
        pl.when(jnp.logical_and(need, jnp.logical_not(cross)))(
            lambda: body(False))


def _row(col):
    """(n, 1) -> (1, n) through an aligned (n, LANES) transpose."""
    return jnp.broadcast_to(col, (col.shape[0], LANES)).T[:1]


def _col(row):
    """(1, n) -> (n, 1) through an aligned (LANES, n) transpose."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T[:, :1]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, plan: _Plan):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def body(masked):
        k, v = k_ref[0, 0], v_ref[0, 0]
        for r in range(plan.group):  # this step's heads, all of one kv head
            s = jax.lax.dot_general(q_ref[0, r], k, _NT,
                                    preferred_element_type=jnp.float32
                                    ) * plan.scale  # (bq, bk)
            if masked:
                s = jnp.where(plan.valid(qi, ki, s.shape, 0), s, NEG_INF)
            m_prev = m_ref[r]  # (bq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[r] = l_ref[r] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[r] = acc_ref[r] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[r] = m_new

    plan.run(qi, ki, body)

    @pl.when(ki == plan.nk - 1)
    def _done():
        for r in range(plan.group):
            l = jnp.maximum(l_ref[r], 1e-30)
            o_ref[0, r] = (acc_ref[r] / l).astype(o_ref.dtype)
            lse_ref[0, r] = _row(m_ref[r] + jnp.log(l))


def _forward(q, k, v, plan: _Plan):
    B, H, Sq, D = q.shape
    bq, bk, G, n_sub = plan.bq, plan.bk, plan.group, plan.n_sub

    def q_map(b, h, i, j):  # h: a block of G query heads
        return b, h, i, 0

    def kv_map(b, h, i, j):
        return b, h // n_sub, jnp.minimum(j, plan.last_k(i)), 0

    return pl.pallas_call(
        functools.partial(_fwd_kernel, plan=plan),
        grid=(B, H // G, plan.nq, plan.nk),
        in_specs=[
            pl.BlockSpec((1, G, bq, D), q_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, G, bq, D), q_map),
            pl.BlockSpec((1, G, 1, bq), lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, Sq), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((G, bq, 1), jnp.float32),
            pltpu.VMEM((G, bq, 1), jnp.float32),
            pltpu.VMEM((G, bq, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=plan.interpret,
        name="flash_attention_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               acc_ref, lse_col, di_col, *, plan: _Plan):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for r in range(plan.group):
            lse_col[r] = _col(lse_ref[0, r])
            di_col[r] = _col(di_ref[0, r])

    def body(masked):
        k, v = k_ref[0, 0], v_ref[0, 0]
        for r in range(plan.group):
            s = jax.lax.dot_general(q_ref[0, r], k, _NT,
                                    preferred_element_type=jnp.float32
                                    ) * plan.scale  # (bq, bk)
            if masked:
                s = jnp.where(plan.valid(qi, ki, s.shape, 0), s, NEG_INF)
            p = jnp.exp(s - lse_col[r])
            dp = jax.lax.dot_general(do_ref[0, r], v, _NT,
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - di_col[r])
            acc_ref[r] += jnp.dot(ds.astype(k.dtype), k,
                                  preferred_element_type=jnp.float32)

    plan.run(qi, ki, body)

    @pl.when(ki == plan.nk - 1)
    def _done():
        dq_ref[0] = (acc_ref[...] * plan.scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, plan: _Plan):
    ki, sub, qi = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when(jnp.logical_and(sub == 0, qi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked):
        k, v = k_ref[0, 0], v_ref[0, 0]
        for r in range(plan.group):  # query heads of this kv head
            q, do = q_ref[0, r], do_ref[0, r]
            st = jax.lax.dot_general(k, q, _NT,
                                     preferred_element_type=jnp.float32
                                     ) * plan.scale  # (bk, bq)
            if masked:
                st = jnp.where(plan.valid(qi, ki, st.shape, 1), st, NEG_INF)
            pt = jnp.exp(st - lse_ref[0, r])
            dv_acc[...] += jnp.dot(pt.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(v, do, _NT,
                                      preferred_element_type=jnp.float32)
            dst = pt * (dpt - di_ref[0, r])
            dk_acc[...] += jnp.dot(dst.astype(q.dtype), q,
                                   preferred_element_type=jnp.float32)

    plan.run(qi, ki, body)

    @pl.when(jnp.logical_and(sub == plan.n_sub - 1, qi == plan.nq - 1))
    def _done():
        dk_ref[0, 0] = (dk_acc[...] * plan.scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def backward_dq(q, k, v, do, lse, di, plan: _Plan):
    """dQ: (B, H, Sq, D), from the forward's log-sum-exp and ``D_i`` rows
    (both ``(B, H, 1, Sq)`` f32)."""
    B, H, Sq, D = q.shape
    bq, bk, G, n_sub = plan.bq, plan.bk, plan.group, plan.n_sub

    def q_map(b, h, i, j):  # h: a block of G query heads
        return b, h, i, 0

    def row_map(b, h, i, j):
        return b, h, 0, i

    def kv_map(b, h, i, j):
        return b, h // n_sub, jnp.minimum(j, plan.last_k(i)), 0

    return pl.pallas_call(
        functools.partial(_dq_kernel, plan=plan),
        grid=(B, H // G, plan.nq, plan.nk),
        in_specs=[
            pl.BlockSpec((1, G, bq, D), q_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, G, bq, D), q_map),
            pl.BlockSpec((1, G, 1, bq), row_map),
            pl.BlockSpec((1, G, 1, bq), row_map),
        ],
        out_specs=pl.BlockSpec((1, G, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, bq, D), jnp.float32),
            pltpu.VMEM((G, bq, 1), jnp.float32),
            pltpu.VMEM((G, bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=plan.interpret,
        name="flash_attention_dq",
    )(q, k, v, do, lse, di)


def _kv_major(k, plan: _Plan):
    """(grid, in_specs, kv-block spec) of a backward kernel whose grid walks
    kv blocks, with inner sequential axes over the ``n_sub`` blocks of
    ``group`` query heads that share the kv head and over the q blocks; the
    inputs are ``backward_dq``'s arguments."""
    B, KV, _, D = k.shape
    bq, bk, G, n_sub = plan.bq, plan.bk, plan.group, plan.n_sub

    def q_map(b, g, j, sub, i):
        return b, g * n_sub + sub, jnp.maximum(i, plan.first_q(j)), 0

    def row_map(b, g, j, sub, i):
        return b, g * n_sub + sub, 0, jnp.maximum(i, plan.first_q(j))

    kv = pl.BlockSpec((1, 1, bk, D), lambda b, g, j, sub, i: (b, g, j, 0))
    q_block = pl.BlockSpec((1, G, bq, D), q_map)
    row = pl.BlockSpec((1, G, 1, bq), row_map)
    return ((B, KV, plan.nk, n_sub, plan.nq),
            [q_block, kv, kv, q_block, row, row], kv)


def backward_dkv(q, k, v, do, lse, di, plan: _Plan):
    """(dK, dV): (B, KV, Sk, D) each; the arguments as ``backward_dq``'s.

    Each kv block accumulates over the q blocks and over the ``n_sub``
    blocks of ``group`` query heads that share its kv head, both inner
    sequential grid axes."""
    grid, in_specs, kv = _kv_major(k, plan)
    return pl.pallas_call(
        functools.partial(_dkv_kernel, plan=plan),
        grid=grid,
        in_specs=in_specs,
        out_specs=[kv, kv],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((plan.bk, k.shape[3]), jnp.float32),
            pltpu.VMEM((plan.bk, k.shape[3]), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=plan.interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, di)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dk_ref,
                dv_ref, dq_acc, dk_acc, dv_acc, *, plan: _Plan):
    ki, sub, qi = pl.program_id(2), pl.program_id(3), pl.program_id(4)
    first = jnp.logical_and(sub == 0, qi == 0)
    last = jnp.logical_and(sub == plan.n_sub - 1, qi == plan.nq - 1)

    @pl.when(jnp.logical_and(ki == 0, first))
    def _init_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(first)
    def _init_dkv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(masked):
        k, v = k_ref[0, 0], v_ref[0, 0]
        rows = pl.ds(pl.multiple_of(qi * plan.bq, plan.bq), plan.bq)
        for r in range(plan.group):  # query heads of this kv head
            q, do = q_ref[0, r], do_ref[0, r]
            st = jax.lax.dot_general(k, q, _NT,
                                     preferred_element_type=jnp.float32
                                     ) * plan.scale  # (bk, bq)
            if masked:
                st = jnp.where(plan.valid(qi, ki, st.shape, 1), st, NEG_INF)
            pt = jnp.exp(st - lse_ref[0, r])
            dv_acc[...] += jnp.dot(pt.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(v, do, _NT,
                                      preferred_element_type=jnp.float32)
            dst = (pt * (dpt - di_ref[0, r])).astype(q.dtype)
            dk_acc[...] += jnp.dot(dst, q, preferred_element_type=jnp.float32)
            dq_acc[sub * plan.group + r, rows, :] += jax.lax.dot_general(
                dst, k, _TN, preferred_element_type=jnp.float32)  # (bq, D)

    plan.run(qi, ki, body)

    @pl.when(last)
    def _done_kv():
        dk_ref[0, 0] = (dk_acc[...] * plan.scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(ki == plan.nk - 1, last))
    def _done_q():
        for h in range(dq_acc.shape[0]):  # a head at a time: no full temp
            dq_ref[0, h] = (dq_acc[h] * plan.scale).astype(dq_ref.dtype)


def backward_fused(q, k, v, do, lse, di, plan: _Plan):
    """(dQ, dK, dV) in one kernel; the arguments as ``backward_dq``'s.

    The grid is ``backward_dkv``'s; dQ of the kv head's query heads stays
    resident in VMEM across its KV blocks, so that axis is sequential."""
    _, H, Sq, D = q.shape
    n_rep = H // k.shape[1]
    grid, in_specs, kv = _kv_major(k, plan)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, plan=plan),
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((1, n_rep, Sq, D),
                                lambda b, g, j, sub, i: (b, g, 0, 0)),
                   kv, kv],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[
            pltpu.VMEM((n_rep, Sq, D), jnp.float32),
            pltpu.VMEM((plan.bk, D), jnp.float32),
            pltpu.VMEM((plan.bk, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_SCOPED + dq_resident_bytes(n_rep, Sq, D)),
        interpret=plan.interpret,
        name="flash_attention_bwd",
    )(q, k, v, do, lse, di)


def _backward(q, k, v, o, lse, do, plan: _Plan):
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                 axis=-1)[:, :, None, :]  # (B, H, 1, Sq)
    if plan.fused:
        return backward_fused(q, k, v, do, lse, di, plan)
    dk, dv = backward_dkv(q, k, v, do, lse, di, plan)
    return backward_dq(q, k, v, do, lse, di, plan), dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _attention(q, k, v, fwd: _Plan, bwd: _Plan):
    return _forward(q, k, v, fwd)[0]


def _attention_fwd(q, k, v, fwd: _Plan, bwd: _Plan):
    o, lse = _forward(q, k, v, fwd)
    return o, (q, k, v, o, lse)


def _attention_bwd(fwd: _Plan, bwd: _Plan, res, do):
    return _backward(*res, do, bwd)


_attention.defvjp(_attention_fwd, _attention_bwd)


def plans(q_shape, k_shape, *, causal: bool, interpret: bool,
          bq: int | None = None, bk: int | None = None) -> tuple:
    """(forward plan, backward plan) of a call with q ``(B, H, Sq, D)`` and
    k ``(B, KV, Sk, D)``, over sequences padded to whole blocks of both.
    ``bq``/``bk`` set every kernel's blocks; by default ``blocks`` chooses
    them from the shapes. The backward is fused where its resident dQ fits
    ``VMEM_DQ_BYTES``."""
    _, H, Sq, D = q_shape
    KV, Sk = k_shape[1], k_shape[2]
    if H % KV:
        raise ValueError(f"{H} query heads do not group over {KV} kv heads")
    fwd, bwd = blocks(Sq, Sk, D)
    if bq or bk:
        fwd = bwd = (min(bq or fwd[0], Sq), min(bk or fwd[1], Sk))
    # blocks are powers of two or the sequence itself, so the larger of
    # the two kernels' blocks pads for both
    sq = Sq + (-Sq) % max(fwd[0], bwd[0])
    sk = Sk + (-Sk) % max(fwd[1], bwd[1])

    def plan(bq, bk, fused=False):
        group = heads_per_step(H // KV, bq, D)
        return _Plan(scale=D ** -0.5, bq=bq, bk=bk, nq=sq // bq, nk=sk // bk,
                     group=group, n_sub=H // KV // group, causal=causal,
                     sk_valid=Sk, q_offset=Sk - Sq, interpret=interpret,
                     fused=fused)

    fused = dq_resident_bytes(H // KV, sq, D) <= VMEM_DQ_BYTES
    return plan(*fwd), plan(*bwd, fused)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, interpret: bool, causal: bool = True,
                    bq: int | None = None, bk: int | None = None):
    """q: (B, H, Sq, D), k/v: (B, KV, Sk, D) with H % KV == 0 -> (B, H, Sq, D).

    Differentiable (its own backward kernels); blocks as ``plans``.
    Causal alignment is decode-style: the last query attends the last key."""
    fwd, bwd = plans(q.shape, k.shape, causal=causal, interpret=interpret,
                     bq=bq, bk=bk)
    Sq, Sk = q.shape[2], k.shape[2]
    # padded K positions are masked out (sk_valid), padded Q rows are
    # sliced away after the call
    pq, pk_ = fwd.nq * fwd.bq - Sq, fwd.nk * fwd.bk - Sk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk_:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk_), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk_), (0, 0)))
    return _attention(q, k, v, fwd, bwd)[:, :, :Sq]
