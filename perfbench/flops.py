"""Model FLOPs of a decoder-only LM step, counted from the published shapes.

Forward and backward (3x the forward's multiply-adds, 2 FLOPs each), causal
attention included, recomputation excluded, top-k experts only for MoE, the
unpadded vocabulary for the tied head. Every term is a matrix multiplication,
so the same count is the step's matmul FLOPs: whatever the program computes
beyond it (remat, capacity slack, masked attention blocks, vocab padding)
only lowers a share measured against it.
"""
from __future__ import annotations


def matmul_params_per_token(cfg: dict) -> int:
    """Weights each token multiplies with in one forward pass."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, f = cfg["head_dim"], cfg["intermediate_size"]
    attn = d * hd * (2 * H + 2 * KV)  # wq, wk, wv, wo
    E, k = cfg.get("num_local_experts", 0), cfg.get("num_experts_per_tok", 0)
    if E:
        mlp = d * E + k * 3 * d * f  # router + the top-k SwiGLU experts
    else:
        mlp = 3 * d * f
    return L * (attn + mlp) + d * cfg["vocab_size"]  # + head


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """QK^T and PV, forward and backward, averaged over causal positions."""
    L, H, hd = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                cfg["head_dim"])
    mean_keys = (seq_len + 1) / 2
    return 3 * L * 2 * 2 * H * hd * mean_keys


def model_flops_per_token(cfg: dict, seq_len: int) -> float:
    return 6 * matmul_params_per_token(cfg) + attention_flops_per_token(
        cfg, seq_len)


def param_count(cfg: dict) -> dict:
    """Total and per-token-active parameters (tied embedding counted once)."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, f, V = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    E, k = cfg.get("num_local_experts", 0), cfg.get("num_experts_per_tok", 0)
    attn = d * hd * (2 * H + 2 * KV)
    norms = 2 * d
    if E:
        total_mlp, active_mlp = d * E + E * 3 * d * f, d * E + k * 3 * d * f
    else:
        total_mlp = active_mlp = 3 * d * f
    emb = V * d + d  # embedding/head + final norm
    return {"total": L * (attn + norms + total_mlp) + emb,
            "active": L * (attn + norms + active_mlp) + emb}
