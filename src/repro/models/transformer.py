"""Dense decoder-only LM (+ VLM backbone variant).

Covers: smollm-135m, llama3.2-3b, gemma-7b, nemotron-4-340b, llava-next-34b.
Blocks are stacked over a leading `layers` dim and executed with
``lax.scan`` so compile time is O(1) in depth (essential for the 96-layer
340B dry-run) and ZeRO-3 gathers happen once per scanned step.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, ParallelConfig, ShapeConfig
from repro.core import partition as pt
from repro.models import common as cm


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
            "mlp": cm.mlp_defs(cfg),
        }
    )


def param_defs(cfg: ModelConfig) -> dict:
    defs = {"embed": cm.embed_defs(cfg), "blocks": block_defs(cfg),
            "ln_f": cm.norm_defs(cfg.d_model, cfg.norm_kind)}
    return defs


def _remat_policy(parallel: ParallelConfig):
    if parallel.remat == "none":
        return None
    if parallel.remat == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def _merge_vision(x_tok: jax.Array, vision: jax.Array) -> jax.Array:
    """VLM stub frontend: precomputed patch embeddings occupy the sequence head."""
    return jnp.concatenate([vision.astype(x_tok.dtype), x_tok], axis=1)


def make_block_fn(cfg: ModelConfig, rules: pt.AxisRules, parallel: ParallelConfig):
    """Standalone (x, blk_params, positions) -> x block fn (train mode).

    Used by the explicit ZeRO-3 engine (core/zero.py), which manages the
    per-layer parameter gather itself and calls the block on gathered params.
    """
    tiles = parallel.tiling_factor

    def block(x, blk, positions):
        a, _ = cm.attention_block(
            blk["attn"], cm.norm(x, blk["ln1"], cfg.norm_kind), positions, cfg, rules,
            causal=True, window=cfg.window,
        )
        x = x + a
        m = cm.mlp_block(blk["mlp"], cm.norm(x, blk["ln2"], cfg.norm_kind), cfg, rules, tiles)
        return x + m

    return block


def make_fns(cfg: ModelConfig, rules: pt.AxisRules, parallel: ParallelConfig):
    tiles = parallel.tiling_factor
    policy = _remat_policy(parallel)

    def block(x, blk, positions, cache=None, collect_kv=False):
        a, new_cache = cm.attention_block(
            blk["attn"], cm.norm(x, blk["ln1"], cfg.norm_kind), positions, cfg, rules,
            causal=True, window=cfg.window, cache=cache, collect_kv=collect_kv,
        )
        x = x + a
        m = cm.mlp_block(blk["mlp"], cm.norm(x, blk["ln2"], cfg.norm_kind), cfg, rules, tiles)
        return x + m, new_cache

    def run_blocks(params, x, positions):
        def body(h, blk):
            out, _ = block(h, blk, positions)
            return out, ()

        if parallel.remat != "none":
            body = jax.checkpoint(body, policy=policy, prevent_cse=False)
        with jax.named_scope("layers"):
            x, _ = jax.lax.scan(body, x, params["blocks"])
        return x

    def backbone_inputs(params, batch):
        tokens = batch["tokens"]
        x = cm.embed(params["embed"], tokens, cfg, rules)
        if cfg.family == "vlm":
            x = _merge_vision(x, batch["vision_embeds"])
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        return x, positions

    # ------------------------------ train ---------------------------------

    def loss_fn(params, batch):
        x, positions = backbone_inputs(params, batch)
        x = run_blocks(params, x, positions)
        x = cm.norm(x, params["ln_f"], cfg.norm_kind)
        lg = cm.logits(params["embed"], x, cfg, rules)
        labels = batch["labels"]
        with jax.named_scope("head"):  # the shift, and its pad backward
            if cfg.family == "vlm":  # loss only on text positions
                lg = lg[:, cfg.vision_len :]
            return cm.lm_loss(lg[:, :-1], labels[:, 1:], cfg.vocab_size)

    # ----------------------------- serving --------------------------------

    def cache_defs(batch: int, cache_len: int) -> dict:
        L, KV, D = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
        return {
            "k": pt.ParamDef((L, batch, cache_len, KV, D),
                             ("layers", "batch", "cache_seq", "kv_heads", "head_dim")),
            "v": pt.ParamDef((L, batch, cache_len, KV, D),
                             ("layers", "batch", "cache_seq", "kv_heads", "head_dim")),
            "len": pt.ParamDef((), (), "int32", "zeros"),
        }

    def prefill(params, batch):
        """Forward over the prompt, building the KV cache; returns last logits."""
        x, positions = backbone_inputs(params, batch)
        B, S, _ = x.shape

        def body(h, blk):
            out, kv = block(h, blk, positions, collect_kv=True)
            return out, (kv["k"], kv["v"])

        if parallel.remat != "none":
            body = jax.checkpoint(body, policy=policy, prevent_cse=False)
        with jax.named_scope("layers"):
            x, (ks, vs) = jax.lax.scan(body, x, params["blocks"])
        x = cm.norm(x, params["ln_f"], cfg.norm_kind)
        lg = cm.logits(params["embed"], x[:, -1:], cfg, rules)
        cache = {"k": ks, "v": vs, "len": jnp.asarray(S, jnp.int32)}
        return lg, cache

    def decode_step(params, cache, batch):
        """One new token against the cache. tokens: (B, 1)."""
        tokens = batch["tokens"]
        x = cm.embed(params["embed"], tokens, cfg, rules)
        B = x.shape[0]
        clen = cache["len"]
        # clen may be a scalar (lockstep batch) or a (B,) vector of per-slot
        # lengths (continuous batching) — reshape covers both
        positions = jnp.broadcast_to(jnp.reshape(clen, (-1, 1)), (B, 1))

        def body(h, layer):
            blk, kc, vc = layer
            out, new_cache = block(h, blk, positions, cache={"k": kc, "v": vc, "len": clen})
            return out, (new_cache["k"], new_cache["v"])

        with jax.named_scope("layers"):
            x, (ks, vs) = jax.lax.scan(
                body, x, (params["blocks"], cache["k"], cache["v"]))
        x = cm.norm(x, params["ln_f"], cfg.norm_kind)
        lg = cm.logits(params["embed"], x, cfg, rules)
        return lg, {"k": ks, "v": vs, "len": clen + 1}

    # --------------------------- input specs -------------------------------

    def input_specs(shape: ShapeConfig) -> dict:
        B, S = shape.global_batch, shape.seq_len
        if shape.kind == "decode":
            specs = {"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
            return specs
        text = S - cfg.vision_len if cfg.family == "vlm" else S
        specs = {"tokens": jax.ShapeDtypeStruct((B, text), jnp.int32)}
        if cfg.family == "vlm":
            specs["vision_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.vision_len, cfg.d_model), jnp.bfloat16
            )
        if shape.kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct((B, text), jnp.int32)
        return specs

    return {
        "loss": loss_fn,
        "prefill": prefill,
        "decode_step": decode_step,
        "cache_defs": cache_defs,
        "input_specs": input_specs,
    }
