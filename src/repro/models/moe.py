"""Mixture-of-Experts transformer (llama4-scout 16e top-1, granite 32e top-8).

Dispatch is *sort-based* (MaxText-style), not GShard one-hot-einsum based:
tokens are argsorted by expert id and gathered into (E, capacity, d) buffers,
so dispatch/combine cost ~0 FLOPs (gathers + one scatter-add) and the HLO
FLOPs stay ~= useful expert FLOPs — this keeps the roofline's
MODEL_FLOPS/HLO_FLOPs ratio honest. Experts shard over the `model` mesh axis
(EP); activations are model-replicated between blocks, so expert gathers are
rank-local and the combine is a single psum (comparable traffic to a TP MLP).
Capacity overflow drops tokens; the dropped fraction and per-expert load are
counted by ``routing_stats`` and surfaced as ``moe_dropped_token_fraction`` /
``moe_expert_load`` step metrics (capacity_factor config).

The routing math is factored into ``route_tokens`` (sorted-dispatch plan) and
``expert_mix`` (the per-expert MLP) so the layered zero3 engine can run the
same computation over a *selected subset* of expert rows
(``moe_ffn_selected``): an expert that receives no tokens contributes exactly
zero output and zero gradient (its capacity slots are all masked), so paging
in only the router-selected experts is numerics-preserving.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ParallelConfig, ShapeConfig
from repro.core import partition as pt
from repro.models import common as cm
from repro.models import transformer as tf


def moe_defs(cfg: ModelConfig) -> dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    gated = cfg.mlp_kind in ("swiglu", "geglu")
    defs = {
        "router": pt.ParamDef((d, E), ("embed", None), "float32"),
        "w_in": pt.ParamDef((E, d, f), ("experts", "embed_e", "mlp")),
        "w_out": pt.ParamDef((E, f, d), ("experts", "mlp", "embed_e")),
    }
    if gated:
        defs["w_gate"] = pt.ParamDef((E, d, f), ("experts", "embed_e", "mlp"))
    return defs


def expert_leaf_names(cfg: ModelConfig) -> tuple:
    """Canonical order of the per-expert weight leaves in a paged expert row."""
    gated = cfg.mlp_kind in ("swiglu", "geglu")
    return ("w_in", "w_gate", "w_out") if gated else ("w_in", "w_out")


def expert_row_defs(cfg: ModelConfig) -> dict:
    """ParamDefs of ONE expert's weights (the (E, ...) leading axis stripped):
    the schedule unit the layered engine pages independently."""
    defs = moe_defs(cfg)
    return {
        name: pt.ParamDef(defs[name].shape[1:], defs[name].axes[1:],
                          defs[name].dtype, defs[name].init, defs[name].init_scale)
        for name in expert_leaf_names(cfg)
    }


def block_defs(cfg: ModelConfig) -> dict:
    L = cfg.n_layers

    def stack(defs):
        return jax.tree.map(
            lambda d: pt.ParamDef((L,) + d.shape, ("layers",) + d.axes, d.dtype, d.init, d.init_scale),
            defs,
            is_leaf=lambda x: isinstance(x, pt.ParamDef),
        )

    return stack(
        {
            "ln1": cm.norm_defs(cfg.d_model, cfg.norm_kind),
            "attn": cm.attn_defs(cfg),
            "ln2": cm.norm_defs(cfg.d_model, cfg.norm_kind),
            "moe": moe_defs(cfg),
        }
    )


def param_defs(cfg: ModelConfig) -> dict:
    return {"embed": cm.embed_defs(cfg), "blocks": block_defs(cfg),
            "ln_f": cm.norm_defs(cfg.d_model, cfg.norm_kind)}


def _capacity(cfg: ModelConfig, T: int) -> int:
    cap = max(int(T * cfg.top_k * cfg.capacity_factor / cfg.n_experts), 1)
    return min(cap, T * cfg.top_k)


@jax.named_scope("moe/router")
def route_tokens(router: jax.Array, xg: jax.Array, cfg: ModelConfig) -> dict:
    """Sorted-dispatch routing plan. xg: (G, T, d) grouped tokens.

    Returns the (G, E, C) slot plan shared by the all-resident and the
    selected-expert paths: ``tok_ec`` (token index per slot), ``valid_ec``
    (slot occupied), ``w_ec`` (renormalized gate weight, zero on invalid
    slots), and ``counts`` (G, E) routed-token counts per expert — the
    popularity / load / drop-accounting signal.
    """
    G, T, d = xg.shape
    E, k = cfg.n_experts, cfg.top_k
    cap = _capacity(cfg, T)

    # router in f32-accumulate but with bf16 primal inputs: casting xg to f32
    # here would promote xg's COTANGENT to f32, which forces the dominant
    # cross-expert combine psum (dxg) to run in f32 — 2x collective bytes
    # (found via roofline/breakdown; see EXPERIMENTS.md §Perf llama4 it-2).
    logits = jnp.einsum("gtd,de->gte", xg, router.astype(xg.dtype),
                        preferred_element_type=jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    topg, topi = jax.lax.top_k(gates, k)  # (G,T,k)
    topg = topg / jnp.sum(topg, axis=-1, keepdims=True)

    flat_e = topi.reshape(G, T * k)
    flat_w = topg.reshape(G, T * k)
    order = jnp.argsort(flat_e, axis=1)  # stable
    tok_of_slot = order // k  # token idx for each sorted slot

    counts = jnp.sum(jax.nn.one_hot(flat_e, E, dtype=jnp.int32), axis=1)  # (G,E)
    starts = jnp.cumsum(counts, axis=1) - counts  # exclusive prefix
    # (g, e, c) -> sorted-slot index; invalid slots masked
    slot_ec = starts[:, :, None] + jnp.arange(cap)[None, None, :]  # (G,E,C)
    valid_ec = jnp.arange(cap)[None, None, :] < counts[:, :, None]
    slot_ec = jnp.clip(slot_ec, 0, T * k - 1)

    tok_ec = jnp.take_along_axis(tok_of_slot, slot_ec.reshape(G, -1), axis=1).reshape(G, E, cap)
    w_sorted = jnp.take_along_axis(flat_w, order, axis=1)
    w_ec = jnp.take_along_axis(w_sorted, slot_ec.reshape(G, -1), axis=1).reshape(G, E, cap)
    w_ec = jnp.where(valid_ec, w_ec, 0.0)
    return {"tok_ec": tok_ec, "valid_ec": valid_ec, "w_ec": w_ec,
            "counts": counts, "cap": cap}


@jax.named_scope("moe/router")
def routing_stats(counts: jax.Array, cap: int, k: int) -> dict:
    """counts (G, E) -> the S1 drop/load accounting.

    ``moe_dropped_token_fraction``: fraction of routed (token, expert)
    assignments lost to capacity overflow this layer. ``moe_expert_load``:
    (E,) fraction of routed assignments landing on each expert — the
    popularity signal the hot-expert cache and the predicted prefetch use.
    """
    routed = jnp.maximum(jnp.sum(counts), 1)
    dropped = jnp.sum(jnp.maximum(counts - cap, 0))
    load = jnp.sum(counts, axis=0) / routed
    return {"moe_dropped_token_fraction": dropped / routed,
            "moe_expert_load": load}


@jax.named_scope("moe/experts")
def expert_mix(xin: jax.Array, w_in: jax.Array, w_out: jax.Array,
               w_gate, mlp_kind: str) -> jax.Array:
    """(G, E', C, d) x per-expert weights (E', d, f)/(E', f, d) -> (G, E', C, d).

    E' is either the full expert axis or a selected subset — the einsums are
    identical, which is what makes selected-expert paging exact.
    """
    h = jnp.einsum("gecd,edf->gecf", xin, w_in.astype(xin.dtype))
    if mlp_kind == "swiglu":
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xin, w_gate.astype(xin.dtype))) * h
    elif mlp_kind == "geglu":
        h = jax.nn.gelu(jnp.einsum("gecd,edf->gecf", xin, w_gate.astype(xin.dtype))) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("gecf,efd->gecd", h, w_out.astype(h.dtype))


def moe_ffn(p: dict, x: jax.Array, cfg: ModelConfig, rules: pt.AxisRules,
            group: int = 1024, with_stats: bool = False):
    """x: (B, S, d) -> (B, S, d). Sorted-dispatch MoE over all E experts.

    ``with_stats=True`` additionally returns the ``routing_stats`` dict
    (dropped-token fraction + per-expert load).
    """
    B, S, d = x.shape
    T = min(group, S)
    G = B * (S // T)
    xg = x.reshape(G, T, d)

    r = route_tokens(p["router"], xg, cfg)
    tok_ec, valid_ec, w_ec = r["tok_ec"], r["valid_ec"], r["w_ec"]

    gidx = jnp.arange(G)[:, None, None]
    with jax.named_scope("moe/dispatch"):
        xin = xg[gidx, tok_ec]  # (G,E,C,d) gather; rank-local w/ model-replicated xg
        xin = jnp.where(valid_ec[..., None], xin, 0)
        xin = pt.constrain(xin, rules, ("batch", "experts", None, None))

    out = expert_mix(xin, p["w_in"], p["w_out"], p.get("w_gate"), cfg.mlp_kind)

    # token-major combine: scatter-add back to token order; the cross-expert
    # reduction lowers to the model-axis psum. A gather-based inverse combine
    # was tried and MEASURED (EXPERIMENTS.md §Perf llama4 it-3): neutral for
    # top-1 (llama4) but 4x worse collectives for top-8 (granite) — its
    # backward re-scatters per k. Scatter-add kept as the default.
    with jax.named_scope("moe/combine"):
        out = out * w_ec[..., None].astype(out.dtype)
        cdt = jnp.dtype(cfg.moe_combine_dtype)
        y = jnp.zeros(xg.shape, cdt).at[gidx, tok_ec].add(out.astype(cdt))
        y = pt.constrain(y, rules, ("batch", None, None))
        y = y.astype(x.dtype).reshape(B, S, d)
    if with_stats:
        return y, routing_stats(r["counts"], r["cap"], cfg.top_k)
    return y


def moe_counts(router: jax.Array, x: jax.Array, cfg: ModelConfig,
               group: int = 1024) -> jax.Array:
    """Routing counts only: (B, S, d) -> (G, E) int32. The layered engine
    runs this ahead of the expert waves to pick which rows to page in."""
    B, S, d = x.shape
    T = min(group, S)
    xg = x.reshape(B * (S // T), T, d)
    return route_tokens(router, xg, cfg)["counts"]


def moe_ffn_selected(router: jax.Array, rows: dict, x: jax.Array,
                     sel_ids: jax.Array, sel_mask: jax.Array,
                     cfg: ModelConfig, rules: pt.AxisRules,
                     group: int = 1024) -> jax.Array:
    """Partial MoE output from a *selected* set of expert rows.

    rows: per-expert weights stacked over the selection axis — w_in (W, d, f),
    w_out (W, f, d), optionally w_gate (W, d, f). sel_ids (W,) int32 expert
    ids; sel_mask (W,) zeroes padding slots (padded ids may repeat a real id).

    Summing this over a partition of the experts-with-tokens reproduces
    ``moe_ffn`` exactly: unselected experts have all-invalid slots, hence
    zero w_ec weight, zero output and zero gradient.
    """
    B, S, d = x.shape
    T = min(group, S)
    G = B * (S // T)
    xg = x.reshape(G, T, d)

    r = route_tokens(router, xg, cfg)
    tok_sel = jnp.take(r["tok_ec"], sel_ids, axis=1)  # (G,W,C)
    valid_sel = jnp.take(r["valid_ec"], sel_ids, axis=1)
    w_sel = jnp.take(r["w_ec"], sel_ids, axis=1) * sel_mask[None, :, None]

    gidx = jnp.arange(G)[:, None, None]
    with jax.named_scope("moe/dispatch"):
        xin = xg[gidx, tok_sel]
        xin = jnp.where(valid_sel[..., None], xin, 0)
        xin = pt.constrain(xin, rules, ("batch", "experts", None, None))

    out = expert_mix(xin, rows["w_in"], rows["w_out"], rows.get("w_gate"),
                     cfg.mlp_kind)

    with jax.named_scope("moe/combine"):
        out = out * w_sel[..., None].astype(out.dtype)
        cdt = jnp.dtype(cfg.moe_combine_dtype)
        y = jnp.zeros(xg.shape, cdt).at[gidx, tok_sel].add(out.astype(cdt))
        y = pt.constrain(y, rules, ("batch", None, None))
        return y.astype(x.dtype).reshape(B, S, d)


def make_fns(cfg: ModelConfig, rules: pt.AxisRules, parallel: ParallelConfig):
    policy = tf._remat_policy(parallel)

    def block(x, blk, positions, cache=None, collect_kv=False, with_stats=False):
        a, new_cache = cm.attention_block(
            blk["attn"], cm.norm(x, blk["ln1"], cfg.norm_kind), positions, cfg, rules,
            causal=True, cache=cache, collect_kv=collect_kv,
        )
        x = x + a
        m = moe_ffn(blk["moe"], cm.norm(x, blk["ln2"], cfg.norm_kind), cfg, rules,
                    with_stats=with_stats)
        if with_stats:
            m, stats = m
            return x + m, new_cache, stats
        return x + m, new_cache

    dense = tf.make_fns(cfg, rules, parallel)  # reuse embed/loss/cache scaffolding

    def run_blocks(params, x, positions):
        def body(h, blk):
            out, _, stats = block(h, blk, positions, with_stats=True)
            return out, stats

        if parallel.remat != "none":
            body = jax.checkpoint(body, policy=policy, prevent_cse=False)
        with jax.named_scope("layers"):
            x, stats = jax.lax.scan(body, x, params["blocks"])
        return x, stats  # stats leaves carry a leading (L,) layer axis

    def loss_stats_fn(params, batch):
        tokens = batch["tokens"]
        x = cm.embed(params["embed"], tokens, cfg, rules)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        x, stats = run_blocks(params, x, positions)
        x = cm.norm(x, params["ln_f"], cfg.norm_kind)
        lg = cm.logits(params["embed"], x, cfg, rules)
        with jax.named_scope("head"):  # the shift, and its pad backward
            loss = cm.lm_loss(lg[:, :-1], batch["labels"][:, 1:],
                              cfg.vocab_size)
        # reduce over layers: scalar drop fraction + (E,) mean load
        with jax.named_scope("moe/router"):
            aux = {"moe_dropped_token_fraction":
                       jnp.mean(stats["moe_dropped_token_fraction"]),
                   "moe_expert_load": jnp.mean(stats["moe_expert_load"],
                                               axis=0)}
        return loss, aux

    def loss_fn(params, batch):
        return loss_stats_fn(params, batch)[0]

    def prefill(params, batch):
        tokens = batch["tokens"]
        x = cm.embed(params["embed"], tokens, cfg, rules)
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

        def body(h, blk):
            out, kv = block(h, blk, positions, collect_kv=True)
            return out, (kv["k"], kv["v"])

        if parallel.remat != "none":
            body = jax.checkpoint(body, policy=policy, prevent_cse=False)
        with jax.named_scope("layers"):
            x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
        x = cm.norm(x, params["ln_f"], cfg.norm_kind)
        lg = cm.logits(params["embed"], x[:, -1:], cfg, rules)
        return lg, {"k": ks, "v": vs, "len": jnp.asarray(S, jnp.int32)}

    def decode_step(params, cache, batch):
        tokens = batch["tokens"]
        x = cm.embed(params["embed"], tokens, cfg, rules)
        B = x.shape[0]
        clen = cache["len"]
        # scalar (lockstep) or (B,) per-slot lengths (continuous batching)
        positions = jnp.broadcast_to(jnp.reshape(clen, (-1, 1)), (B, 1))

        def body(h, layer):
            blk, kc, vc = layer
            out, nc = block(h, blk, positions, cache={"k": kc, "v": vc, "len": clen})
            return out, (nc["k"], nc["v"])

        with jax.named_scope("layers"):
            x, (ks, vs) = jax.lax.scan(
                body, x, (params["blocks"], cache["k"], cache["v"]))
        x = cm.norm(x, params["ln_f"], cfg.norm_kind)
        lg = cm.logits(params["embed"], x, cfg, rules)
        return lg, {"k": ks, "v": vs, "len": clen + 1}

    return {
        "loss": loss_fn,
        "loss_stats": loss_stats_fn,
        "prefill": prefill,
        "decode_step": decode_step,
        "cache_defs": dense["cache_defs"],
        "input_specs": dense["input_specs"],
    }
