"""A reference model in a layout of its own, for the test that an
architecture comes to the benchmark as new files only.

The model: ``decoder.py``'s blocks (attention, a top-k mixture of SwiGLU
experts) with an untied head. The configuration names its sizes with keys
of its own (``d_model``, ``n_layers``, ...), and the canonical weights have
names and shapes of their own: the heads of each projection in one axis, the
head as (d_model, vocab). It trains the whole model at once on one device,
which holds only at test sizes.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp

_spec = importlib.util.spec_from_file_location(
    "untied_reference_blocks", Path(__file__).with_name("decoder.py"))
D = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(D)

# per-layer leaf -> decoder.py's name for it
LAYER = {"attn_norm": "ln1", "q_proj": "wq", "k_proj": "wk", "v_proj": "wv",
         "o_proj": "wo", "ffn_norm": "ln2", "gate": "router",
         "experts_gate": "w_gate", "experts_up": "w_up",
         "experts_down": "w_down"}


def decoder_config(cfg: dict) -> dict:
    """The configuration in decoder.py's keys, for its blocks."""
    return {"hidden_size": cfg["d_model"], "head_dim": cfg["d_head"],
            "num_attention_heads": cfg["n_heads"],
            "num_key_value_heads": cfg["n_kv_heads"],
            "intermediate_size": cfg["d_expert"],
            "num_local_experts": cfg["n_experts"],
            "num_experts_per_tok": cfg["top_k"],
            "router_group": cfg["router_group"],
            "capacity_factor": cfg["capacity_factor"],
            "rope_theta": cfg["rope_theta"], "rms_norm_eps": cfg["norm_eps"]}


def leaf_shapes(cfg: dict) -> dict:
    d, L, V = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    H, KV, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_head"]
    E, f = cfg["n_experts"], cfg["d_expert"]
    return {"tok_embeddings": (V, d), "lm_head": (d, V), "final_norm": (d,),
            "attn_norm": (L, d), "ffn_norm": (L, d),
            "q_proj": (L, d, H * hd), "k_proj": (L, d, KV * hd),
            "v_proj": (L, d, KV * hd), "o_proj": (L, H * hd, d),
            "gate": (L, d, E), "experts_gate": (L, E, d, f),
            "experts_up": (L, E, d, f), "experts_down": (L, E, f, d)}


def init_weights(cfg: dict, key) -> dict:
    """normal(0, 0.02) matrices in bfloat16, the router in float32, gains 0
    (weight 1); leaf ``i`` by sorted name draws from ``fold_in(key, i)``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(leaf_shapes(cfg).items())):
        if name.endswith("norm"):
            out[name] = jnp.zeros(shape, jnp.float32)
            continue
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = (x * 0.02).astype(
            jnp.float32 if name == "gate" else jnp.bfloat16)
    return out


def loss(p: dict, tokens, cfg: dict, mode: str):
    """Mean next-token cross-entropy over the batch."""
    dcfg = decoder_config(cfg)
    d, H, KV, hd = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"], \
        cfg["d_head"]
    x = p["tok_embeddings"][tokens]
    for l in range(cfg["n_layers"]):
        w = {name: p[own][l] for own, name in LAYER.items()}
        w["wq"] = w["wq"].reshape(d, H, hd)
        w["wk"] = w["wk"].reshape(d, KV, hd)
        w["wv"] = w["wv"].reshape(d, KV, hd)
        w["wo"] = w["wo"].reshape(H, hd, d)
        x = D.layer(w, x, dcfg, mode)
    h = D.rms_norm(x, p["final_norm"], cfg["norm_eps"])
    logits = D.matmul_for(mode)("bsd,dv->bsv", h, p["lm_head"])[:, :-1]
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


class Reference:
    """Float32 AdamW training of the whole model on ``devices[0]``."""

    def __init__(self, cfg: dict, devices, *, mode: str = "f32"):
        self.cfg, self.device = cfg, devices[0]
        self._grad = jax.jit(jax.value_and_grad(
            lambda p, t: loss(p, t, cfg, mode)))

    def train(self, weights: dict, batches, hp: dict) -> dict:
        with jax.default_matmul_precision("highest"):
            p = {k: jax.device_put(w, self.device).astype(jnp.float32)
                 for k, w in weights.items()}
            p0, m, v, losses = dict(p), None, None, []
            for step, tokens in enumerate(batches, 1):
                value, g = self._grad(p, jnp.asarray(tokens))
                if step == 1:
                    g1 = leaf_norms(g)
                    m = {k: jnp.zeros_like(x) for k, x in p.items()}
                    v = dict(m)
                lr = hp["lr"] * min(step / max(hp["warmup_steps"], 1), 1.0)
                b1, b2 = hp["beta1"], hp["beta2"]
                for k in p:
                    m[k] = b1 * m[k] + (1 - b1) * g[k]
                    v[k] = b2 * v[k] + (1 - b2) * g[k] * g[k]
                    p[k] = p[k] - lr * (
                        (m[k] / (1 - b1 ** step))
                        / (jnp.sqrt(v[k] / (1 - b2 ** step)) + hp["eps"])
                        + hp["weight_decay"] * p[k])
                losses.append(float(value))
            return {"losses": losses, "grad_norms": g1,
                    "update_norms": leaf_norms(
                        {k: p[k] - p0[k] for k in p})}


def leaf_norms(tree: dict) -> dict:
    """One norm per layer for a per-layer leaf, one for the others."""
    out = {}
    for k, x in tree.items():
        if k in LAYER:
            out[k] = [float(n) for n in jnp.sqrt(jnp.sum(
                jnp.square(x), axis=tuple(range(1, x.ndim))))]
        else:
            out[k] = float(jnp.sqrt(jnp.sum(jnp.square(x))))
    return out


def matmul_params_per_token(cfg: dict) -> int:
    """Weights each token multiplies with in one forward pass: the
    projections, the router, the top-k experts and the untied head."""
    d, hd = cfg["d_model"], cfg["d_head"]
    attn = d * hd * (2 * cfg["n_heads"] + 2 * cfg["n_kv_heads"])
    experts = d * cfg["n_experts"] + cfg["top_k"] * 3 * d * cfg["d_expert"]
    return cfg["n_layers"] * (attn + experts) + d * cfg["vocab_size"]


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """QK^T and PV, forward and backward, averaged over causal positions."""
    return (3 * cfg["n_layers"] * 2 * 2 * cfg["n_heads"] * cfg["d_head"]
            * (seq_len + 1) / 2)
