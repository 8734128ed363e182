"""Per-kernel allclose sweeps against the ref.py oracles (Pallas interpret
mode, which kernels/ops.py selects on the CPU), including hypothesis
property tests over shapes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro.testing import optional_hypothesis

given, settings, st, HAVE_HYPOTHESIS = optional_hypothesis()

from repro.kernels import ops, ref

ADAM_KW = dict(lr=jnp.float32(1e-3), beta1=0.9, beta2=0.95, eps=1e-8,
               weight_decay=0.1, bc1=jnp.float32(0.1), bc2=jnp.float32(0.05))


@pytest.mark.parametrize("n", [64, 128, 129, 4096, 100_001])
def test_fused_adam_sizes(n):
    ks = jax.random.split(jax.random.PRNGKey(n), 4)
    p = jax.random.normal(ks[0], (n,), jnp.float32)
    g = jax.random.normal(ks[1], (n,), jnp.float32)
    m = jax.random.normal(ks[2], (n,), jnp.float32) * 0.1
    v = jnp.abs(jax.random.normal(ks[3], (n,), jnp.float32)) * 0.01
    p1, m1, v1 = ops.fused_adam(p, g, m, v, **ADAM_KW)
    p2, m2, v2 = ref.adam_ref(p, g, m, v, **ADAM_KW)
    np.testing.assert_allclose(p1, p2, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(m1, m2, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(v1, v2, rtol=1e-4, atol=1e-6)


def test_fused_adam_nd_shape():
    p = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 7), jnp.float32)
    g = jnp.ones_like(p)
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)
    p1, m1, v1 = ops.fused_adam(p, g, m, v, **ADAM_KW)
    p2, m2, v2 = ref.adam_ref(p, g, m, v, **ADAM_KW)
    assert p1.shape == p.shape
    np.testing.assert_allclose(p1, p2, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("shape,blocks", [
    ((128, 256, 128), (64, 64, 128)),
    ((64, 512, 384), (64, 128, 256)),
    ((300, 200, 100), (64, 64, 64)),   # non-divisible
    ((8, 128, 128), (8, 128, 128)),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tiled_matmul(shape, blocks, dtype):
    M, K, N = shape
    bm, bn, bk = blocks
    x = (jax.random.normal(jax.random.PRNGKey(1), (M, K)) * 0.1).astype(dtype)
    w = (jax.random.normal(jax.random.PRNGKey(2), (K, N)) * 0.1).astype(dtype)
    y1 = ops.tiled_matmul(x, w, bm=bm, bn=bn, bk=bk)
    y2 = ref.matmul_ref(x, w)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(y1, np.float32), np.asarray(y2, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,H,KV,Sq,Sk,D,causal", [
    (2, 4, 2, 128, 128, 32, True),
    (1, 8, 8, 64, 64, 64, True),
    (2, 4, 1, 128, 128, 32, False),   # MQA
    (1, 2, 2, 100, 132, 32, True),    # ragged seq lens
    (1, 6, 2, 64, 256, 64, True),     # long KV (decode-ish)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, H, KV, Sq, Sk, D, causal, dtype):
    q = (jax.random.normal(jax.random.PRNGKey(3), (B, H, Sq, D)) * 0.3).astype(dtype)
    k = (jax.random.normal(jax.random.PRNGKey(4), (B, KV, Sk, D)) * 0.3).astype(dtype)
    v = (jax.random.normal(jax.random.PRNGKey(5), (B, KV, Sk, D)) * 0.3).astype(dtype)
    o1 = ops.flash_attention(q, k, v, causal=causal, bq=64, bk=64)
    o2 = ref.attention_ref(q, k, v, causal=causal)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32), np.asarray(o2, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("M,K,N,blocks", [
    (64, 128, 256, (64, 128, 64)),
    (100, 96, 64, (64, 64, 64)),     # non-divisible M, K
    (8, 32, 32, (8, 32, 32)),        # single quant block per row
])
def test_quantized_matmul_matches_dequant_reference(M, K, N, blocks):
    """The fused dequant-matmul on q8 wire operands equals matmul against
    the unfused dequantized weight — the kernel's VMEM dequant is exact."""
    from repro.core import qformat

    bm, bn, bk = blocks
    x = (jax.random.normal(jax.random.PRNGKey(11), (M, K)) * 0.3
         ).astype(jnp.bfloat16)
    w = (jax.random.normal(jax.random.PRNGKey(12), (K, N)) * 0.3
         ).astype(jnp.bfloat16)
    q, s = qformat.quantize_q8_jnp(w)
    y1 = ops.quantized_matmul(x, q, s, bm=bm, bn=bn, bk=bk)
    ref_w = qformat.dequantize_q8_jnp(q, s)
    y2 = x.astype(jnp.float32) @ ref_w
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_quantized_matmul_wire_operands_end_to_end():
    """A wire payload from the numpy encoder feeds the kernel directly —
    the decode-to-full-precision step never happens."""
    import ml_dtypes

    from repro.core import qformat

    rng = np.random.default_rng(13)
    w = (rng.standard_normal((64, 128)) * 0.5).astype(ml_dtypes.bfloat16)
    q, s, out_dtype = qformat.wire_matmul_operands(
        qformat.encode_array(w, "q8"))
    x = (rng.standard_normal((16, 64)) * 0.5).astype(np.float32)
    y = ops.quantized_matmul(jnp.asarray(x), jnp.asarray(q), jnp.asarray(s),
                             bm=16, bn=64, bk=64)
    ref = x @ qformat.decode_array(
        qformat.encode_array(w, "q8")).astype(np.float32)
    assert out_dtype == w.dtype
    np.testing.assert_allclose(np.asarray(y, np.float32), ref,
                               rtol=2e-2, atol=2e-2)


@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 65), k=st.integers(1, 65), n=st.integers(1, 65))
def test_tiled_matmul_property(m, k, n):
    x = jnp.arange(m * k, dtype=jnp.float32).reshape(m, k) % 7 / 7.0
    w = jnp.arange(k * n, dtype=jnp.float32).reshape(k, n) % 5 / 5.0
    y1 = ops.tiled_matmul(x, w, bm=32, bn=32, bk=32)
    np.testing.assert_allclose(y1, x @ w, rtol=1e-5, atol=1e-5)


@settings(max_examples=8, deadline=None)
@given(sq=st.integers(8, 70), sk=st.integers(8, 70),
       h=st.sampled_from([1, 2, 4]), rep=st.sampled_from([1, 2]))
def test_flash_attention_property(sq, sk, h, rep):
    # causal alignment is only well-defined for sq <= sk (no fully-masked rows)
    sq = min(sq, sk)
    D = 16
    q = jax.random.normal(jax.random.PRNGKey(sq), (1, h * rep, sq, D)) * 0.5
    k = jax.random.normal(jax.random.PRNGKey(sk), (1, h, sk, D)) * 0.5
    v = jax.random.normal(jax.random.PRNGKey(sk + 1), (1, h, sk, D)) * 0.5
    o1 = ops.flash_attention(q, k, v, causal=True, bq=32, bk=32)
    o2 = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(o1, o2, rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("H,KV", [(9, 3), (4, 1)])
@pytest.mark.parametrize("S", [256, 512])
def test_flash_attention_grads_match_reference(H, KV, S):
    """Forward and dq/dk/dv of the kernel's own backward against
    ``jax.grad`` of the oracle in f32. Blocks of 128 over S 256 and 512
    hold blocks skipped above the diagonal and masked ones on it."""
    D = 64
    ks = jax.random.split(jax.random.PRNGKey(S + H), 4)
    q = jax.random.normal(ks[0], (1, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (1, KV, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (1, KV, S, D), jnp.float32)
    do = jax.random.normal(ks[3], (1, H, S, D), jnp.float32)

    def flash(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, bq=128, bk=128)

    def oracle(q, k, v):
        return ref.attention_ref(q, k, v, causal=True)

    np.testing.assert_allclose(flash(q, k, v), oracle(q, k, v),
                               rtol=1e-4, atol=1e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * do), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(oracle(*a) * do), (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)


def test_flash_attention_grads_over_several_head_blocks_per_kv_head(
        monkeypatch):
    """Where VMEM holds fewer than all of a kv head's query heads, each
    grid step takes a block of them and dK/dV accumulate over the blocks:
    the gradients still match the oracle."""
    from repro.kernels import flash_attention as fa

    H, KV, S, D = 8, 2, 256, 64
    monkeypatch.setattr(fa, "VMEM_HEAD_ROWS", 2 * 128 * D)
    fwd, bwd = fa.plans((1, H, S, D), (1, KV, S, D), causal=True,
                        interpret=True, bq=128, bk=128)
    assert (fwd.group, fwd.n_sub, bwd.group, bwd.n_sub) == (2, 2, 2, 2)
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (1, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (1, KV, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (1, KV, S, D), jnp.float32)
    do = jax.random.normal(ks[3], (1, H, S, D), jnp.float32)

    def flash(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, bq=128, bk=128)

    got = jax.grad(lambda *a: jnp.sum(flash(*a) * do), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(ref.attention_ref(*a) * do),
                    (0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.fixture
def fa_traced_anew():
    """The kernel module, with ``flash_attention`` traced anew before and
    after the test: its backward is chosen at trace time from module
    constants that the test patches."""
    from repro.kernels import flash_attention as fa

    fa.flash_attention.clear_cache()
    yield fa
    fa.flash_attention.clear_cache()


@pytest.mark.parametrize("H,KV,Sq,Sk,causal,head_rows", [
    (9, 3, 256, 256, True, None),        # GQA 9/3, smollm's grouping
    (4, 1, 256, 256, True, None),        # MQA
    (8, 2, 256, 256, True, 2 * 128 * 32),  # two head blocks per kv head
    (4, 2, 200, 200, True, None),        # keys padded to whole blocks
    (4, 2, 256, 256, False, None),
    (4, 2, 128, 256, False, None),       # more keys than queries
])
def test_fused_backward_matches_split_backward_and_reference(
        monkeypatch, fa_traced_anew, H, KV, Sq, Sk, causal, head_rows):
    """dq/dk/dv of the fused backward kernel, and of the split pair forced
    by a VMEM budget of nothing, against ``jax.grad`` of the oracle in f32;
    each runs the kernels its plan names."""
    import re

    fa = fa_traced_anew
    D = 32
    if head_rows:
        monkeypatch.setattr(fa, "VMEM_HEAD_ROWS", head_rows)
    ks = jax.random.split(jax.random.PRNGKey(H * Sq + Sk), 4)
    q = jax.random.normal(ks[0], (1, H, Sq, D), jnp.float32)
    k = jax.random.normal(ks[1], (1, KV, Sk, D), jnp.float32)
    v = jax.random.normal(ks[2], (1, KV, Sk, D), jnp.float32)
    do = jax.random.normal(ks[3], (1, H, Sq, D), jnp.float32)

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(f(*a) * do), (0, 1, 2))(q, k, v)

    def flash(q, k, v):
        return ops.flash_attention(q, k, v, causal=causal, bq=128, bk=128)

    def kernels():
        fa.flash_attention.clear_cache()
        text = str(jax.make_jaxpr(lambda: grads(flash))())
        return sorted(set(re.findall(r"flash_attention_\w+", text)))

    def backward_plan():
        return fa.plans(q.shape, k.shape, causal=causal, interpret=True,
                        bq=128, bk=128)[1]

    assert backward_plan().fused
    assert (backward_plan().n_sub > 1) == bool(head_rows)
    assert kernels() == ["flash_attention_bwd", "flash_attention_fwd"]
    fused = grads(flash)
    monkeypatch.setattr(fa, "VMEM_DQ_BYTES", 0)
    assert not backward_plan().fused
    assert kernels() == ["flash_attention_dkv", "flash_attention_dq",
                         "flash_attention_fwd"]
    split = grads(flash)
    want = grads(lambda *a: ref.attention_ref(*a, causal=causal))
    for name, a, b, c in zip(("dq", "dk", "dv"), fused, split, want):
        np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-4, err_msg=name)
        np.testing.assert_allclose(b, c, rtol=1e-4, atol=1e-4, err_msg=name)


def test_backward_is_fused_where_its_dq_fits_vmem():
    """The shape predicate: smollm's attention (GQA 9/3, S 2048, head dim
    64: 9 MiB of resident dQ) takes the fused backward; 8 query heads a kv
    head over 32768 positions of head dim 128 (384 MiB) the split pair."""
    from repro.kernels import flash_attention as fa

    assert fa.dq_resident_bytes(3, 2048, 64) == 9 << 20
    assert fa.fused_backward((8, 9, 2048, 64), (8, 3, 2048, 64))
    assert not fa.fused_backward((1, 8, 32768, 128), (1, 1, 32768, 128))


# ---------------------------------------------------------------------------
# which attention path the model takes
# ---------------------------------------------------------------------------


def _routing_case(name):
    """(function, args) of one attention call of the smoke smollm: S 128,
    3 query heads over 1 kv head of 16."""
    from repro import configs
    from repro.core import partition as pt
    from repro.models import common as cm

    cfg = configs.smoke("smollm-135m")
    p = pt.init_tree(jax.random.PRNGKey(0), cm.attn_defs(cfg))
    rules = pt.AxisRules(table=())
    S = 96 if name == "ragged" else 128
    x = jax.random.normal(jax.random.PRNGKey(1), (2, S, cfg.d_model),
                          jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (2, S))
    kw = {}
    if name == "window":
        kw["window"] = 32
    elif name == "cross":
        kw["kv_source"] = x[:, ::-1]
    elif name == "decode":
        D, KV = cfg.resolved_head_dim, cfg.n_kv_heads
        kw["cache"] = {"k": jnp.zeros((2, S, KV, D), jnp.bfloat16),
                       "v": jnp.zeros((2, S, KV, D), jnp.bfloat16),
                       "len": jnp.int32(5)}
        x, pos = x[:, :1], pos[:, :1]
    elif name == "seq_sharded":
        rules = pt.AxisRules(table=(("seq", ("model",)),))
    return (lambda x: cm.attention_block(p, x, pos, cfg, rules, causal=True,
                                         **kw)[0]), (x,)


@pytest.mark.parametrize("name,path", [
    ("causal_self", "flash"), ("window", "chunked"), ("cross", "chunked"),
    ("decode", "decode"), ("seq_sharded", "chunked"), ("ragged", "chunked"),
])
def test_attention_takes_the_flash_kernel_only_where_it_is_exact(
        monkeypatch, name, path):
    """With the program traced for a TPU, only causal self-attention with
    no window or softcap, over whole blocks of an unsharded sequence, takes
    the kernel; the per-trace tally counts the path each call took, and
    for a flash call the backward its shapes take."""
    from repro.models import common as cm

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    fn, args = _routing_case(name)
    with cm.attention_paths() as tally:
        jax.eval_shape(fn, *args)
    want = {path: 1}
    if path == "flash":
        want["flash_bwd_fused"] = 1
    assert dict(tally) == want


def test_softcapped_attention_is_not_the_flash_kernels(monkeypatch):
    """``chunked_attention``'s softcap is maths the kernel does not do."""
    from repro.core import partition as pt
    from repro.models import common as cm

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    rules = pt.AxisRules(table=())
    shapes = (2, 128, 3, 16), (2, 128, 1, 16)
    assert cm.flash_path(*shapes, rules, causal=True, window=0) is not None
    assert cm.flash_path(*shapes, rules, causal=True, window=0,
                         softcap=30.0) is None


def test_attention_stays_on_chunked_attention_off_the_tpu():
    from repro.models import common as cm

    fn, args = _routing_case("causal_self")
    with cm.attention_paths() as tally:
        jax.eval_shape(fn, *args)
    assert dict(tally) == {"chunked": 1}


def test_train_step_through_the_flash_kernel_matches_chunked_attention(
        monkeypatch):
    """The smoke smollm's loss and gradients through the kernel (interpret
    mode) equal those through ``chunked_attention``; the traced step's
    tally holds only flash calls, with the fused backward."""
    from repro import configs
    from repro.config import ShapeConfig
    from repro.models import common as cm
    from repro.models import registry

    cfg = configs.smoke("smollm-135m")
    b = registry.build(cfg)
    params = b.init(jax.random.PRNGKey(0))
    specs = b.input_specs(ShapeConfig("t", 128, 2, "train"))
    toks = jax.random.randint(jax.random.PRNGKey(1), specs["tokens"].shape,
                              0, cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    loss0, g0 = jax.jit(jax.value_and_grad(b.loss))(params, batch)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with cm.attention_paths() as tally:  # a new function: traced anew
        loss1, g1 = jax.jit(jax.value_and_grad(b.loss))(params, batch)
    assert set(tally) == {"flash", "flash_bwd_fused"}
    np.testing.assert_allclose(float(loss1), float(loss0), rtol=1e-3)
    for a, c in zip(jax.tree.leaves(g1), jax.tree.leaves(g0)):
        a, c = np.asarray(a, np.float32), np.asarray(c, np.float32)
        np.testing.assert_allclose(a, c, rtol=0.02,
                                   atol=0.02 * float(np.max(np.abs(c))))


def test_prefill_through_the_flash_kernel_matches_chunked_attention(
        monkeypatch):
    """Prefill through the kernel returns the last logits and a (B, S, KV,
    D) cache equal to those through ``chunked_attention``."""
    from repro import configs
    from repro.config import ShapeConfig
    from repro.models import registry

    cfg = configs.smoke("smollm-135m")
    b = registry.build(cfg)
    params = b.init(jax.random.PRNGKey(0))
    specs = b.input_specs(ShapeConfig("p", 128, 2, "prefill"))
    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(2), specs["tokens"].shape, 0, cfg.vocab_size)}
    want = jax.jit(lambda p, x: b.prefill(p, x))(params, batch)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    got = jax.jit(lambda p, x: b.prefill(p, x))(params, batch)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, c in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.shape == c.shape
        a, c = np.asarray(a, np.float32), np.asarray(c, np.float32)
        np.testing.assert_allclose(a, c, rtol=0.02,
                                   atol=0.02 * float(np.max(np.abs(c)) or 1))
