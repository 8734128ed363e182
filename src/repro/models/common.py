"""Shared model layers: norms, RoPE, attention (TP-heads / context-parallel),
MLP variants, embeddings.

Pure-jnp, sharding-agnostic math; distribution enters only through
``partition.constrain`` annotations so the same code runs on 1 CPU device
(smoke tests) and on the 512-chip production mesh (dry-run). Causal
self-attention in training and prefill runs through the Pallas flash kernel
(``kernels/flash_attention.py``, its own backward) where the program compiles
for a TPU and the kernel computes exactly the same maths (``flash_path``);
everything else runs ``chunked_attention``, online softmax over KV blocks, so
peak activation memory is O(chunk^2) not O(seq^2).
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.core import partition as pt
from repro.kernels import flash_attention as fa
from repro.kernels import ops

NEG_INF = -1e30

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(dt)


@jax.named_scope("norm")
def norm(x: jax.Array, p: dict, kind: str) -> jax.Array:
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


def norm_defs(d: int, kind: str) -> dict:
    if kind == "rmsnorm":
        return {"scale": pt.ParamDef((d,), ("embed",), "float32", "zeros")}
    return {
        "scale": pt.ParamDef((d,), ("embed",), "float32", "ones"),
        "bias": pt.ParamDef((d,), ("embed",), "float32", "zeros"),
    }


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float,
         heads_first: bool = False) -> jax.Array:
    """x: (..., seq, heads, head_dim), or (..., heads, seq, head_dim) when
    ``heads_first``; positions: (..., seq).

    Rotates the halves of each head: ``[x1 cos - x2 sin, x2 cos + x1 sin]``,
    written ``x * [cos, cos] + (x @ R) * [sin, sin]`` with R the signed swap
    of the halves, so that no op writes a half-width (lane-sparse) tensor;
    the products and sums are the same f32 operations."""
    head_dim = x.shape[-1]
    half = head_dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freq  # (..., seq, half)
    angles = jnp.concatenate([angles, angles], axis=-1)
    angles = jnp.expand_dims(angles, -3 if heads_first else -2)  # over heads
    eye = jnp.eye(half, dtype=x.dtype)
    zero = jnp.zeros_like(eye)
    swap = jnp.block([[zero, eye], [-eye, zero]])  # [x1, x2] -> [-x2, x1]
    rotated = jnp.einsum("...d,de->...e", x, swap,
                         precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32)
    return (x * jnp.cos(angles) + rotated * jnp.sin(angles)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def attn_defs(cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "wq": pt.ParamDef((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": pt.ParamDef((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": pt.ParamDef((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": pt.ParamDef((h, hd, d), ("heads", "head_dim", "embed")),
    }


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def chunked_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, KV, D)
    v: jax.Array,
    *,
    causal: bool,
    q_offset: int = 0,  # absolute position of q[0] relative to k[0]
    window: int = 0,  # local attention window (0 = global)
    q_chunk: int = 256,
    kv_chunk: int = 256,
    softcap: float = 0.0,
    score_dtype=jnp.float32,
) -> jax.Array:
    """Memory-efficient attention: sequential scan over KV chunks with online
    softmax; Q chunks live in a BATCHED dim (nq). Peak score tensor =
    (B, nq, H, q_chunk, kv_chunk).

    Sharding note: nq is a plain batch dim, so a `seq`->`model`
    (context-parallel) sharding on Q survives into the loop — a lax.map over
    q-chunks would force the scanned dim to replicate across the mesh (XLA
    cannot shard a sequential loop counter), costing a model-axis-fold of
    redundant compute. Found via the roofline parser; see EXPERIMENTS.md.
    """
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    n_rep = H // KV
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)
    scale = D ** -0.5

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Sk)
    nq, nk = -(-Sq // q_chunk), -(-Sk // kv_chunk)
    # pad to whole chunks
    q = jnp.pad(q, ((0, 0), (0, nq * q_chunk - Sq), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, nk * kv_chunk - Sk), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, nk * kv_chunk - Sk), (0, 0), (0, 0)))

    q_pos = (q_offset + jnp.arange(nq * q_chunk)).reshape(nq, q_chunk)
    k_pos = jnp.arange(nk * kv_chunk).reshape(nk, kv_chunk)
    k_valid = (jnp.arange(nk * kv_chunk) < Sk).reshape(nk, kv_chunk)

    qc = q.reshape(B, nq, q_chunk, H, D)  # nq stays a shardable batch dim
    kc = jnp.moveaxis(k.reshape(B, nk, kv_chunk, H, D), 1, 0)  # (nk,B,kc,H,D)
    vc = jnp.moveaxis(v.reshape(B, nk, kv_chunk, H, D), 1, 0)

    sdt = jnp.dtype(score_dtype)

    def kv_step(carry, kv_args):
        m, l, o = carry  # (B,nq,H,qc) f32, ..., (B,nq,H,qc,D) f32
        ki, vi, kp, kval = kv_args  # (B,kc,H,D), ..., (kc,), (kc,)
        # the big (qc x kc) score tensor lives in score_dtype (bf16 halves
        # its HBM traffic — the dominant memory term at long seq); the
        # running max/denominator stay f32 for stability.
        s = jnp.einsum("bnqhd,bkhd->bnhqk", qc, ki,
                       preferred_element_type=sdt) * jnp.asarray(scale, sdt)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        mask = kval[None, None, None, None, :]
        qp = q_pos[None, :, None, :, None]  # (1,nq,1,qc,1)
        kpb = kp[None, None, None, None, :]
        if causal:
            mask = mask & (kpb <= qp)
        if window > 0:
            mask = mask & (kpb > qp - window)
        s = jnp.where(mask, s, jnp.asarray(NEG_INF, sdt))
        m_new = jnp.maximum(m, jnp.max(s, axis=-1).astype(jnp.float32))
        p = jnp.exp(s - m_new[..., None].astype(sdt))
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, dtype=jnp.float32)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bnhqk,bkhd->bnhqd", p.astype(vi.dtype), vi,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, o_new), ()

    m0 = jnp.full((B, nq, H, q_chunk), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, nq, H, q_chunk), jnp.float32)
    o0 = jnp.zeros((B, nq, H, q_chunk, D), jnp.float32)
    (m, l, o), _ = jax.lax.scan(kv_step, (m0, l0, o0), (kc, vc, k_pos, k_valid))
    out = o / jnp.maximum(l[..., None], 1e-30)  # (B,nq,H,qc,D)
    out = jnp.moveaxis(out, 2, 3).reshape(B, nq * q_chunk, H, D)
    return out[:, :Sq].astype(q.dtype)


def decode_attention(
    q: jax.Array,  # (B, 1, H, D)
    k_cache: jax.Array,  # (B, S, KV, D)
    v_cache: jax.Array,
    cache_len: jax.Array | int,  # valid prefix length(s)
    *,
    softcap: float = 0.0,
) -> jax.Array:
    """One-token attention against a long cache.

    Written as a stable softmax over the (possibly seq-sharded) cache axis:
    under GSPMD with the cache sharded over `model`, the max/sum/contract
    reductions lower to the flash-decode partial-softmax + combine pattern.
    """
    B, _, H, D = q.shape
    S, KV = k_cache.shape[1], k_cache.shape[2]
    n_rep = H // KV
    scale = D ** -0.5
    qh = q[:, 0].reshape(B, KV, n_rep, D)
    s = jnp.einsum("bknd,bskd->bkns", qh, k_cache, preferred_element_type=jnp.float32) * scale
    if softcap > 0.0:
        s = jnp.tanh(s / softcap) * softcap
    valid = jnp.arange(S)[None, None, None, :] < jnp.asarray(cache_len).reshape(-1, 1, 1, 1)
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bkns,bskd->bknd", (p / l).astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, D).astype(q.dtype)


_PATHS: contextvars.ContextVar = contextvars.ContextVar("attention_paths",
                                                        default=None)


@contextlib.contextmanager
def attention_paths():
    """A tally, by path (``flash``, ``chunked``, ``decode``), of the
    attention calls traced while it is open: how often the flash kernel
    engages in a configuration. Each flash call also counts the backward
    its shapes take, ``flash_bwd_fused`` or ``flash_bwd_split``
    (``flash_attention.fused_backward``). A layer ``scan`` traces its body
    once, and a jit cache hit traces nothing."""
    tally = collections.Counter()
    token = _PATHS.set(tally)
    try:
        yield tally
    finally:
        _PATHS.reset(token)


def _count(path: str) -> None:
    tally = _PATHS.get()
    if tally is not None:
        tally[path] += 1


def flash_path(q_shape, k_shape, rules: pt.AxisRules, *, causal: bool,
               window: int, softcap: float = 0.0):
    """The flash kernel as an attention function of heads-first
    (B, H, S, D) arrays, for q and k of the (B, S, H, D) shapes given, where
    it computes exactly what ``chunked_attention`` would; else None.

    It takes causal self-attention with no window and no softcap (that of
    ``chunked_attention``; ``attention_block`` applies none), over a
    sequence of whole blocks, in a program compiled for a TPU, with the
    sequence unsharded. On a mesh of several devices it runs under
    ``shard_map`` over the batch (and head) axes, so that GSPMD never
    partitions the kernel's custom call."""
    S = q_shape[1]
    if not (causal and window == 0 and softcap == 0.0 and k_shape[1] == S
            and S % fa.LANES == 0 and ops.on_tpu()):
        return None
    axes = ("batch", "seq", "act_heads", None)
    q_spec = tuple(rules.spec(axes, q_shape)) + (None,) * 4
    kv_spec = tuple(rules.spec(axes, k_shape)) + (None,) * 4
    if q_spec[1] is not None or q_spec[2] != kv_spec[2]:
        return None  # context-parallel, or kv heads split unlike q heads

    def run(q, k, v):
        return ops.flash_attention(q, k, v, causal=True)

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return run if jax.device_count() == 1 else None
    if mesh.size == 1 or mesh.are_all_axes_manual:
        return run  # one device, or already inside a shard_map
    if mesh.manual_axes:
        return None
    q_p = jax.P(q_spec[0], q_spec[2], None, None)
    kv_p = jax.P(kv_spec[0], kv_spec[2], None, None)
    return jax.shard_map(run, mesh=mesh, in_specs=(q_p, kv_p, kv_p),
                         out_specs=q_p, check_vma=False)


def attention_block(
    p: dict,
    x: jax.Array,  # (B, S, d_model)
    positions: jax.Array,
    cfg: ModelConfig,
    rules: pt.AxisRules,
    *,
    causal: bool = True,
    window: int = 0,
    cache: Optional[dict] = None,  # decode: {"k","v","len"}
    kv_source: Optional[jax.Array] = None,  # cross-attention memory
    collect_kv: bool = False,  # prefill: also return this block's (k, v)
) -> tuple[jax.Array, Optional[dict]]:
    """Full attention sub-block: qkv proj -> rope -> attention -> out proj.

    Returns (output, updated_cache_or_collected_kv). For decode, x has S=1
    and ``cache`` holds (B, S_cache, KV, D) rings.
    """
    B, S, _ = x.shape
    xs = kv_source if kv_source is not None else x
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    flash = None
    if cache is None and kv_source is None:
        flash = flash_path((B, S, H, D), (B, S, KV, D), rules,
                           causal=causal, window=window)
    # the kernel takes heads-first operands: project straight into them
    heads_first = flash is not None
    lay = "bhsk" if heads_first else "bshk"
    with jax.named_scope("attn/qkv"):
        q = jnp.einsum(f"bsd,dhk->{lay}", x, p["wq"].astype(x.dtype))
        kx = jnp.einsum(f"bsd,dhk->{lay}", xs, p["wk"].astype(x.dtype))
        vx = jnp.einsum(f"bsd,dhk->{lay}", xs, p["wv"].astype(x.dtype))
        if kv_source is None:  # self-attention: rope at absolute positions
            q = rope(q, positions, cfg.rope_theta, heads_first=heads_first)
            kx = rope(kx, positions, cfg.rope_theta, heads_first=heads_first)

    new_cache = None
    with jax.named_scope("attn/core"):
        if cache is not None:
            # decode: write the new K/V at the filled-prefix offset (or an
            # explicit ring position for window-bounded caches)
            k_cache, v_cache, clen = cache["k"], cache["v"], cache["len"]
            write_pos = cache.get("write_pos", clen)
            valid_len = cache.get("valid_len", clen + S)
            k_cache = _scatter_cache(k_cache, kx, write_pos)
            v_cache = _scatter_cache(v_cache, vx, write_pos)
            new_cache = {"k": k_cache, "v": v_cache, "len": clen + S}
            q = pt.constrain(q, rules, ("batch", None, "act_heads", None))
            _count("decode")
            out = decode_attention(q, k_cache, v_cache, valid_len)
        elif heads_first:
            _count("flash")
            _count("flash_bwd_fused" if fa.fused_backward(q.shape, kx.shape)
                   else "flash_bwd_split")
            out = flash(q, kx, vx)
            if collect_kv:  # the cache is (B, S, KV, D)
                kx, vx = jnp.swapaxes(kx, 1, 2), jnp.swapaxes(vx, 1, 2)
        else:
            q = pt.constrain(q, rules, ("batch", "seq", "act_heads", None))
            kx = pt.constrain(kx, rules, ("batch", "kv_seq", None, None))
            vx = pt.constrain(vx, rules, ("batch", "kv_seq", None, None))
            _count("chunked")
            out = chunked_attention(q, kx, vx,
                                    causal=causal and kv_source is None,
                                    window=window, score_dtype=cfg.score_dtype,
                                    q_chunk=cfg.attn_chunk,
                                    kv_chunk=cfg.attn_chunk)
        if collect_kv and cache is None:
            new_cache = {"k": kx.astype(jnp.bfloat16),
                         "v": vx.astype(jnp.bfloat16)}
    with jax.named_scope("attn/out"):
        out = jnp.einsum(f"{lay},hkd->bsd", out.astype(x.dtype),
                         p["wo"].astype(x.dtype))
        return pt.constrain(out, rules, ("batch", "seq", None)), new_cache


def _scatter_cache(cache: jax.Array, new: jax.Array, pos) -> jax.Array:
    """Write ``new`` (B, S_new, KV, D) at offset ``pos`` along the seq dim.

    Uses one-hot matmul form instead of dynamic_update_slice so that the
    update stays efficient when the cache's seq dim is sharded over `model`
    (dynamic-slice on a sharded dim forces a full re-gather in SPMD).
    """
    S = cache.shape[1]
    pos = jnp.asarray(pos)
    idx = pos.reshape(-1, 1) + jnp.arange(new.shape[1])[None, :]  # (B|1, S_new)
    onehot = jax.nn.one_hot(idx, S, dtype=cache.dtype)  # (B|1, S_new, S)
    add = jnp.einsum("bns,bnkd->bskd", onehot, new.astype(cache.dtype))
    keep = 1.0 - jnp.max(onehot, axis=1)  # (B|1, S)
    return cache * keep[..., None, None].astype(cache.dtype) + add


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_defs(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    gated = cfg.mlp_kind in ("swiglu", "geglu")
    defs = {
        "w_in": pt.ParamDef((d, f), ("embed", "mlp")),
        "w_out": pt.ParamDef((f, d), ("mlp", "embed")),
    }
    if gated:
        defs["w_gate"] = pt.ParamDef((d, f), ("embed", "mlp"))
    return defs


@jax.named_scope("mlp")
def mlp_block(p: dict, x: jax.Array, cfg: ModelConfig, rules: pt.AxisRules,
              tiling_factor: int = 1) -> jax.Array:
    from repro.core.tiling import tiled_matmul_xla  # local import to avoid cycle

    kind = cfg.mlp_kind

    def up(w):
        return tiled_matmul_xla(x, w.astype(x.dtype), tiling_factor)

    h = up(p["w_in"])
    if kind == "swiglu":
        h = jax.nn.silu(up(p["w_gate"])) * h
    elif kind == "geglu":
        h = jax.nn.gelu(up(p["w_gate"])) * h
    elif kind == "relu2":
        h = jnp.square(jax.nn.relu(h))
    elif kind == "gelu":
        h = jax.nn.gelu(h)
    h = pt.constrain(h, rules, ("batch", "seq", "act_mlp"))
    out = tiled_matmul_xla(h, p["w_out"].astype(x.dtype), tiling_factor)
    return pt.constrain(out, rules, ("batch", "seq", None))


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------


def embed_defs(cfg: ModelConfig) -> dict:
    v = cfg.padded_vocab()
    defs = {"tok": pt.ParamDef((v, cfg.d_model), ("vocab", "embed"), init="normal")}
    if not cfg.tie_embeddings:
        defs["unembed"] = pt.ParamDef((cfg.d_model, v), ("embed", "vocab"))
    return defs


@jax.named_scope("embed")
def embed(p: dict, tokens: jax.Array, cfg: ModelConfig, rules: pt.AxisRules) -> jax.Array:
    x = p["tok"].astype(jnp.bfloat16)[tokens]
    if cfg.arch.startswith("gemma") or cfg.arch.startswith("recurrentgemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return pt.constrain(x, rules, ("batch", "seq", None))


@jax.named_scope("head")
def logits(p: dict, x: jax.Array, cfg: ModelConfig, rules: pt.AxisRules) -> jax.Array:
    if cfg.tie_embeddings:
        out = jnp.einsum("bsd,vd->bsv", x, p["tok"].astype(x.dtype))
    else:
        out = jnp.einsum("bsd,dv->bsv", x, p["unembed"].astype(x.dtype))
    if cfg.logit_softcap > 0.0:
        out = jnp.tanh(out / cfg.logit_softcap) * cfg.logit_softcap
    return out


@jax.named_scope("head")
def lm_loss(lg: jax.Array, labels: jax.Array, vocab_size: int) -> jax.Array:
    """Cross-entropy over (possibly padded) vocab; labels (B, S) int32."""
    lg = lg.astype(jnp.float32)
    pad = lg.shape[-1] - vocab_size
    if pad > 0:
        mask = jnp.arange(lg.shape[-1]) < vocab_size
        lg = jnp.where(mask, lg, NEG_INF)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
