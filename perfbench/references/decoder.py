"""Plain float32 reference of a decoder-only LM and its AdamW steps.

Written from the published description, for the benchmark's correctness
check; it imports nothing of the system under test. The block is the
Llama/Granite one: RMSNorm with a zero-centred gain, RoPE on the first and
second halves of each head, causal grouped-query attention, then a SwiGLU MLP
or a top-k mixture of SwiGLU experts that routes each group of
``router_group`` consecutive tokens with a capacity of
``capacity_factor * group * k / experts`` slots per expert (assignments past
it, in token order, are dropped). The embedding is tied to the head; the loss
is the mean next-token cross-entropy.

Every matmul runs at HIGHEST precision on float32 operands. With
``mode="fp8"`` the same model runs each matmul on float8 operands instead
(e4m3 forward, e5m2 gradients, one scale per tensor), which is the control
the comparison has to reject.

AdamW keeps a float32 master of every weight, and the forward of each step
reads that master. With ``round_weights=True`` it reads the master rounded
to the type the weight is stored in (bfloat16 matrices, float32 gains), as
mixed-precision training stores its params.

It runs layer by layer so that it fits beside nothing else on the chips:
layer ``l`` and its AdamW state live on ``devices[l * n // L]``, rows go
through in blocks, and each layer's inputs are kept for its backward.

The end of the file counts the model's FLOPs for the benchmark's metrics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_LEAVES_DENSE = ("ln1", "wq", "wk", "wv", "wo", "ln2",
                      "w_gate", "w_up", "w_down")
LAYER_LEAVES_MOE = LAYER_LEAVES_DENSE + ("router",)
F8_MAX = {jnp.float8_e4m3fn: 448.0, jnp.float8_e5m2: 57344.0}


def layer_leaves(cfg: dict) -> tuple:
    return LAYER_LEAVES_MOE if cfg.get("num_local_experts") else \
        LAYER_LEAVES_DENSE


def leaf_shapes(cfg: dict) -> dict:
    """Canonical layout: per-layer leaves stacked on a leading layer axis."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    f, V, E = cfg["intermediate_size"], cfg["vocab_size"], \
        cfg.get("num_local_experts", 0)
    shapes = {"embed": (V, d), "ln_f": (d,),
              "ln1": (L, d), "ln2": (L, d),
              "wq": (L, d, H, hd), "wk": (L, d, KV, hd), "wv": (L, d, KV, hd),
              "wo": (L, H, hd, d)}
    if E:
        shapes.update(router=(L, d, E), w_gate=(L, E, d, f),
                      w_up=(L, E, d, f), w_down=(L, E, f, d))
    else:
        shapes.update(w_gate=(L, d, f), w_up=(L, d, f), w_down=(L, f, d))
    return shapes


def init_weights(cfg: dict, key) -> dict:
    """Weights from ``key``: normal(0, 0.02) matrices in bfloat16, the type
    they are trained in; gains 0 (weight 1) and the router in float32.
    Leaf ``i`` (sorted by name) draws from ``fold_in(key, i)``."""
    out = {}
    for i, (name, shape) in enumerate(sorted(leaf_shapes(cfg).items())):
        if name.startswith("ln"):
            out[name] = jnp.zeros(shape, jnp.float32)
            continue
        x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        dtype = jnp.float32 if name == "router" else jnp.bfloat16
        out[name] = (x * 0.02).astype(dtype)
    return out


# ---------------------------------------------------------------------------
# matmuls
# ---------------------------------------------------------------------------


def _f32_mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _quant(x, dtype):
    """float8 round trip with one scale for the tensor."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX[dtype]
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@functools.lru_cache(maxsize=None)
def _fp8_mm_for(spec):
    @jax.custom_vjp
    def mm(a, b):
        return _f32_mm(spec, _quant(a, jnp.float8_e4m3fn),
                       _quant(b, jnp.float8_e4m3fn))

    def fwd(a, b):
        qa, qb = _quant(a, jnp.float8_e4m3fn), _quant(b, jnp.float8_e4m3fn)
        return _f32_mm(spec, qa, qb), (qa, qb)

    def bwd(res, ct):
        _, vjp = jax.vjp(functools.partial(_f32_mm, spec), *res)
        return vjp(_quant(ct, jnp.float8_e5m2))

    mm.defvjp(fwd, bwd)
    return mm


def matmul_for(mode: str):
    if mode == "f32":
        return _f32_mm
    if mode == "fp8":
        return lambda spec, a, b: _fp8_mm_for(spec)(a, b)
    raise ValueError(f"unknown reference mode {mode!r}")


# ---------------------------------------------------------------------------
# the model, one layer at a time
# ---------------------------------------------------------------------------


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        (1.0 + gain)


def rope(x, theta):
    """x (b, S, heads, hd): rotate (first half, second half) pairs."""
    S, hd = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(S)[:, None] * inv_freq[None, :]  # (S, hd/2)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(w, h, cfg, mm):
    b, S, _ = h.shape
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    q = rope(mm("bsd,dhk->bshk", h, w["wq"]), cfg["rope_theta"])
    k = rope(mm("bsd,dhk->bshk", h, w["wk"]), cfg["rope_theta"])
    v = mm("bsd,dhk->bshk", h, w["wv"])
    q = q.reshape(b, S, KV, H // KV, hd)  # query head j*G+i reads kv head j
    scale = cfg.get("attention_multiplier", hd ** -0.5)
    s = mm("bqkgd,bskd->bkgqs", q, k) * scale
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("bkgqs,bskd->bqkgd", p, v).reshape(b, S, H, hd)
    return mm("bshk,hkd->bsd", o, w["wo"])


def swiglu(h, gate, up, down, mm, spec_in, spec_out):
    return mm(spec_out, jax.nn.silu(mm(spec_in, h, gate)) * mm(spec_in, h, up),
              down)


def moe(w, h, cfg, mm):
    b, S, d = h.shape
    E, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    T = min(cfg["router_group"], S)
    G = b * S // T
    cap = min(max(int(T * k * cfg["capacity_factor"] / E), 1), T * k)
    hg = h.reshape(G, T, d)
    probs = jax.nn.softmax(mm("gtd,de->gte", hg, w["router"]), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)  # (G, T, k)
    top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    # slot of each (token, choice) in its expert, counting in token order
    onehot = jax.nn.one_hot(top_e, E, dtype=jnp.int32).reshape(G, T * k, E)
    before = jnp.cumsum(onehot, axis=1) - onehot
    pos = jnp.sum(before * onehot, -1).reshape(G, T, k)
    kept = pos < cap
    dest = jnp.where(kept, top_e * cap + pos, E * cap)  # E*cap: dropped
    g = jnp.arange(G)[:, None, None]
    tok = jnp.broadcast_to(jnp.arange(T)[None, :, None], (G, T, k))
    slot_tok = jnp.zeros((G, E * cap + 1), jnp.int32).at[g, dest].set(tok)
    xin = hg[jnp.arange(G)[:, None], slot_tok[:, : E * cap]]
    xin = xin.reshape(G, E, cap, d)
    out = swiglu(xin, w["w_gate"], w["w_up"], w["w_down"], mm,
                 "gecd,edf->gecf", "gecf,efd->gecd")
    out = jnp.concatenate(
        [out.reshape(G, E * cap, d), jnp.zeros((G, 1, d), out.dtype)], 1)
    picked = out[jnp.arange(G)[:, None, None], dest]  # (G, T, k, d)
    y = jnp.sum(picked * (top_p * kept)[..., None], axis=2)
    return y.reshape(b, S, d)


def layer(w, x, cfg, mode):
    mm = matmul_for(mode)
    eps, r = cfg["rms_norm_eps"], cfg.get("residual_multiplier", 1.0)
    x = x + r * attention(w, rms_norm(x, w["ln1"], eps), cfg, mm)
    h = rms_norm(x, w["ln2"], eps)
    if cfg.get("num_local_experts"):
        return x + r * moe(w, h, cfg, mm)
    return x + r * swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mm,
                          "bsd,df->bsf", "bsf,fd->bsd")


def head_loss(embed, ln_f, x, tokens, inv_n, cfg, mode):
    """Sum over the block's next-token cross-entropies, times ``inv_n``."""
    mm = matmul_for(mode)
    h = rms_norm(x, ln_f, cfg["rms_norm_eps"])
    logits = mm("bsd,vd->bsv", h, embed) / cfg.get("logits_scaling", 1.0)
    logz = jax.nn.logsumexp(logits[:, :-1], axis=-1)
    gold = jnp.take_along_axis(logits[:, :-1], tokens[:, 1:, None], -1)[..., 0]
    return jnp.sum(logz - gold) * inv_n


class Reference:
    """Float32 training of the model in ``cfg`` from given weights."""

    def __init__(self, cfg: dict, devices, *, mode: str = "f32",
                 round_weights: bool = False, rows_per_block: int = 2):
        self.cfg, self.mode, self.round_weights = cfg, mode, round_weights
        self.devices = list(devices)
        self.rows = rows_per_block
        L, n = cfg["num_hidden_layers"], len(self.devices)
        self.dev_of = [self.devices[l * n // L] for l in range(L)]
        c = dict(cfg)
        self._fwd = jax.jit(lambda w, x: layer(w, x, c, mode))
        self._bwd = jax.jit(
            lambda w, x, ct, acc: _add_grads(
                acc, jax.vjp(lambda w_, x_: layer(w_, x_, c, mode), w, x)[1](ct)),
            donate_argnums=3)
        self._head = jax.jit(jax.value_and_grad(
            lambda e, g, x, t, s: head_loss(e, g, x, t, s, c, mode),
            argnums=(0, 1, 2)))
        mult = cfg.get("embedding_multiplier", 1.0)
        self._embed = jax.jit(lambda e, t: e[t] * mult)
        self._embed_bwd = jax.jit(
            lambda acc, t, dx: acc.at[t].add(dx * mult), donate_argnums=0)

    # ---- state ------------------------------------------------------------

    def place(self, weights: dict) -> dict:
        """Canonical weights -> float32 params, layer ``l`` on its device."""
        d0 = self.devices[0]
        params = {"embed": jax.device_put(weights["embed"], d0).astype(
                      jnp.float32),
                  "ln_f": jax.device_put(weights["ln_f"], d0).astype(
                      jnp.float32),
                  "layers": []}
        for l, dev in enumerate(self.dev_of):
            params["layers"].append({
                name: jax.device_put(weights[name][l], dev).astype(
                    jnp.float32)
                for name in layer_leaves(self.cfg)})
        return params

    def working(self, params: dict, dtypes: dict) -> dict:
        """The params a step's forward reads: the master, or the master
        rounded to each weight's stored type."""
        if not self.round_weights:
            return params
        return {"embed": _round(params["embed"], dtypes["embed"]),
                "ln_f": _round(params["ln_f"], dtypes["ln_f"]),
                "layers": [{n: _round(p, dtypes[n]) for n, p in lp.items()}
                           for lp in params["layers"]]}

    # ---- one forward and backward over the whole batch ----------------------

    def loss_and_grads(self, params, tokens: np.ndarray):
        B, S = tokens.shape
        d0 = self.devices[0]
        inv_n = jnp.float32(1.0 / (B * (S - 1)))
        blocks = [jax.device_put(tokens[i:i + self.rows], d0)
                  for i in range(0, B, self.rows)]
        acts = []
        for t in blocks:  # every block's forward first: the chips pipeline
            x, saved = self._embed(params["embed"], t), []
            for l, dev in enumerate(self.dev_of):
                x = jax.device_put(x, dev)
                saved.append(x)
                x = self._fwd(params["layers"][l], x)
            acts.append((saved, jax.device_put(x, d0)))
        zeros = lambda tree: jax.tree.map(jnp.zeros_like, tree)
        g_embed, g_lnf = zeros(params["embed"]), zeros(params["ln_f"])
        g_layers = [zeros(p) for p in params["layers"]]
        loss = jnp.float32(0.0)
        cts = []
        for t, (_, x) in zip(blocks, acts):
            lb, (ge, gl, dx) = self._head(params["embed"], params["ln_f"], x,
                                          t, inv_n)
            loss, g_embed, g_lnf = loss + lb, g_embed + ge, g_lnf + gl
            cts.append(dx)
        for t, (saved, _), dx in zip(blocks, acts, cts):
            for l in reversed(range(len(self.dev_of))):
                dx = jax.device_put(dx, self.dev_of[l])
                g_layers[l], dx = self._bwd(params["layers"][l], saved[l], dx,
                                            g_layers[l])
            g_embed = self._embed_bwd(g_embed, t, jax.device_put(dx, d0))
        return loss, {"embed": g_embed, "ln_f": g_lnf, "layers": g_layers}

    # ---- AdamW -------------------------------------------------------------

    def adam(self, params, opt, grads, step: int, hp: dict):
        """One AdamW step (decoupled decay, linear warm-up of the rate)."""
        lr = hp["lr"] * min(step / max(hp["warmup_steps"], 1), 1.0)
        b1, b2 = hp["beta1"], hp["beta2"]
        scalars = [jnp.float32(x) for x in (lr, 1.0 - b1 ** step,
                                             1.0 - b2 ** step)]
        flat, tree = jax.tree.flatten(params)
        if opt is None:
            opt = {"m": [jnp.zeros_like(p) for p in flat],
                   "v": [jnp.zeros_like(p) for p in flat]}
        out = [_adamw(p, g, m, v, *scalars, b1=b1, b2=b2, eps=hp["eps"],
                      wd=hp["weight_decay"])
               for p, g, m, v in zip(flat, tree.flatten_up_to(grads),
                                     opt["m"], opt["v"])]
        return (tree.unflatten([o[0] for o in out]),
                {"m": [o[1] for o in out], "v": [o[2] for o in out]})

    def train(self, weights: dict, batches, hp: dict) -> dict:
        """Steps 1..len(batches) from ``weights``: each step's loss, the
        per-leaf norms of the first gradient and of the params' change."""
        dtypes = {name: w.dtype for name, w in weights.items()}
        with jax.default_matmul_precision("highest"):
            params = self.place(weights)
            p0, opt, losses, g1 = params, None, [], None
            for step, tokens in enumerate(batches, 1):
                loss, grads = self.loss_and_grads(
                    self.working(params, dtypes), tokens)
                if step == 1:
                    g1 = leaf_norms(self.cfg, grads)
                params, opt = self.adam(params, opt, grads, step, hp)
                losses.append(loss)
            change = jax.tree.map(jnp.subtract, params, p0)
            return {"losses": [float(x) for x in losses],
                    "grad_norms": g1,
                    "update_norms": leaf_norms(self.cfg, change)}


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd"))
def _adamw(p, g, m, v, lr, c1, c2, *, b1, b2, eps, wd):
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    p = p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p)
    return p, m, v


@functools.partial(jax.jit, static_argnums=1)
def _round(x, dtype):
    return x.astype(dtype).astype(jnp.float32)


def _add_grads(acc, grads):
    dw, dx = grads
    return jax.tree.map(jnp.add, acc, dw), dx


@jax.jit
def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


def leaf_norms(cfg: dict, tree: dict) -> dict:
    """{"embed": norm, "ln_f": norm, "<leaf>": [norm of layer l, ...]}."""
    out = {"embed": float(_norm(tree["embed"])),
           "ln_f": float(_norm(tree["ln_f"]))}
    for name in layer_leaves(cfg):
        out[name] = [float(_norm(p[name])) for p in tree["layers"]]
    return out



# ---------------------------------------------------------------------------
# counts for the benchmark's FLOP metrics
# ---------------------------------------------------------------------------
# From the published shapes: causal attention included, recomputation
# excluded, top-k experts only for MoE, the unpadded vocabulary for the tied
# head. Every term is a matrix multiplication, so the same count is the
# step's matmul FLOPs: whatever the program computes beyond it (remat,
# capacity slack, masked attention blocks, vocab padding) only lowers a share
# measured against it.


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
