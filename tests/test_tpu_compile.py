"""Compile-only checks for one TPU v5e chip, made without the chip: the
installed TPU compiler compiles for a described v5e topology and nothing
runs. They catch what CPU interpret mode cannot: block shapes the Mosaic
lowering refuses, more VMEM than a kernel may use, and a train step whose
host-tier layouts the compiler rejects.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.config import ParallelConfig, RunConfig, ShapeConfig, make_offload
from repro.core import partition as pt
from repro.core.engine import ZeroInfinityEngine
from repro.kernels import fused_adam, flash_attention, tiled_matmul
from repro.models import common as cm

CFG = configs.get("smollm-135m")  # d_model 576, d_ff 1536, GQA 9/3, hd 64
SEQ, BATCH = 2048, 8
SPLIT_STEP_TEMP = 5_969_461_248  # the all-HBM step's scratch with the flash
# kernel's split backward: the fused one holds dQ in VMEM, not HBM


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to a persistent cache
        # but cannot be read back without the chip: keep the cache out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_cases(chip):
    d, ff, hd = CFG.d_model, CFG.d_ff, CFG.resolved_head_dim
    rows = d * ff // fused_adam.LANE  # one (d_model, d_ff) leaf, flattened
    f32 = [_spec((rows, fused_adam.LANE), jnp.float32, chip)] * 4
    tokens = BATCH * SEQ // 4
    H, KV = CFG.n_heads, CFG.n_kv_heads
    q = _spec((1, H, SEQ, hd), jnp.bfloat16, chip)
    kv = _spec((1, KV, SEQ, hd), jnp.bfloat16, chip)
    rows = _spec((1, H, 1, SEQ), jnp.float32, chip)  # log-sum-exp, D_i
    _, bwd = flash_attention.plans(q.shape, kv.shape, causal=True,
                                   interpret=False)
    return {
        "fused_adam": (
            lambda *a: fused_adam.fused_adam_flat(*a, interpret=False),
            f32 + [_spec((7,), jnp.float32, chip)]),
        "tiled_matmul": (
            lambda x, w: tiled_matmul.tiled_matmul(x, w, interpret=False),
            [_spec((tokens, d), jnp.bfloat16, chip),
             _spec((d, ff), jnp.bfloat16, chip)]),
        "quantized_matmul": (
            lambda x, q, s: tiled_matmul.quantized_matmul(x, q, s,
                                                          interpret=False),
            [_spec((tokens, d), jnp.bfloat16, chip),
             _spec((d, ff), jnp.int8, chip),
             _spec((d, ff // 32), jnp.float16, chip)]),  # q8 blocks of 32
        "flash_attention": (
            lambda q, k, v: flash_attention.flash_attention(
                q, k, v, causal=True, interpret=False),
            [q, kv, kv]),
        "flash_attention_dq": (
            lambda *a: flash_attention.backward_dq(*a, bwd),
            [q, kv, kv, q, rows, rows]),
        "flash_attention_dkv": (
            lambda *a: flash_attention.backward_dkv(*a, bwd),
            [q, kv, kv, q, rows, rows]),
        "flash_attention_bwd": (
            lambda *a: flash_attention.backward_fused(*a, bwd),
            [q, kv, kv, q, rows, rows]),
    }


@pytest.mark.parametrize("name", ["fused_adam", "tiled_matmul",
                                  "quantized_matmul", "flash_attention",
                                  "flash_attention_dq", "flash_attention_dkv",
                                  "flash_attention_bwd"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_cases(one_chip)[name]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def host_step(topo):
    """The full-width pjit step with the optimizer state in pinned host
    memory, compiled for one described v5e chip: (engine, compiled)."""
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         devices=topo.devices[:1],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    run = RunConfig(model=CFG, offload=make_offload(opt_tier="host"))
    eng = ZeroInfinityEngine(run, mesh)
    return eng, eng.lower_train(ShapeConfig("t", SEQ, BATCH, "train")).compile()


def test_host_tier_train_step_compiles_for_v5e(host_step):
    """The engine reads the host memory kind from its mesh, so a mesh of
    described v5e devices selects ``pinned_host``."""
    eng, compiled = host_step
    opt_kinds = {s.memory_kind for s in jax.tree.leaves(eng.opt_shardings())}
    assert opt_kinds == {"pinned_host"}
    ma = compiled.memory_analysis()
    # the fp32 master/m/v (12 B/param) are host arguments, not HBM ones
    assert ma.host_argument_size_in_bytes > 12 * 100e6
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16e9


def test_every_op_of_the_v5e_step_is_in_a_model_region(host_step):
    """On the TPU every fusion, dot and convolution of the step carries the
    name of a model region (``jax.named_scope``), every region has ops of
    its own (``attn/core``'s are the flash kernels' calls), and the copies
    between pinned host memory and HBM, which carry no name, are the
    ``offload`` region by their memory space."""
    from perfbench import regions

    text = host_step[1].as_text()
    rmap = regions.region_map(text)
    tops = regions.top_level_ops(text)
    assert len(tops) > 100
    assert [t for t in tops if rmap[t][0] == regions.OTHER] == []
    assert {r for r, _ in map(rmap.get, tops + _kernels(text))} >= set(
        regions.REGIONS) - {
        "moe/router", "moe/dispatch", "moe/experts", "moe/combine",
        "offload"}
    host_copies = [t for t in regions.top_level_ops(text, ("copy-start",))
                   if "S(5)" in text.split(f"%{t} = ", 1)[1].split(" ", 1)[0]]
    assert host_copies
    assert {rmap[t][0] for t in host_copies} == {"offload"}


def _mesh(devices, shape):
    return jax.make_mesh(shape, ("data", "model"), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


@pytest.fixture(scope="module")
def hbm_step(topo):
    """The benchmark's all-HBM step (every state in HBM, batch 8 x 2048,
    undonated), compiled for one described v5e chip, with the tally of the
    attention paths its trace took: (compiled, tally)."""
    run = RunConfig(model=CFG, offload=make_offload(opt_tier="device"))
    eng = ZeroInfinityEngine(run, _mesh(topo.devices[:1], (1, 1)))
    with cm.attention_paths() as tally:
        lowered = eng.lower_train(ShapeConfig("t", SEQ, BATCH, "train"),
                                  donate=False)
    return lowered.compile(), dict(tally)


def _kernels(text):
    """The step's top-level Pallas kernel calls."""
    from perfbench import regions

    return [t for t in regions.top_level_ops(text, ("custom-call",))
            if 'custom_call_target="tpu_custom_call"'
            in text.split(f"%{t} = ", 1)[1].split("\n", 1)[0]]


def test_all_hbm_step_runs_attention_through_the_flash_kernel_on_v5e(
        hbm_step):
    """Compiled for the chip, the all-HBM step takes the flash kernel: its
    forward (once more in the rematerialised forward) and its fused
    backward are the step's only Pallas calls, all in the ``attn/core``
    region; every fusion, dot and convolution keeps a model region; and the
    step's scratch is no more than it was with the split backward."""
    from perfbench import regions

    compiled, tally = hbm_step
    # the layer scan traces its body once
    assert tally == {"flash": 1, "flash_bwd_fused": 1}
    text = compiled.as_text()
    rmap = regions.region_map(text)
    tops = regions.top_level_ops(text)
    assert [t for t in tops if rmap[t][0] == regions.OTHER] == []
    kernels = _kernels(text)
    assert {rmap[t] for t in kernels} == {
        ("attn/core", "fwd"), ("attn/core", "remat"), ("attn/core", "bwd")}
    assert sorted(t.rsplit(".", 1)[0] for t in kernels) == [
        "flash_attention_bwd", "flash_attention_fwd", "flash_attention_fwd"]
    assert compiled.memory_analysis().temp_size_in_bytes <= SPLIT_STEP_TEMP


def test_split_backward_compiles_where_dq_outgrows_vmem(one_chip):
    """8 query heads a kv head over 32768 positions of head dim 128 hold
    more dQ than VMEM: the gradient compiles with the split pair."""
    q = _spec((1, 8, 32768, 128), jnp.bfloat16, one_chip)
    kv = _spec((1, 1, 32768, 128), jnp.bfloat16, one_chip)
    assert not flash_attention.fused_backward(q.shape, kv.shape)

    def loss(q, k, v):
        o = flash_attention.flash_attention(q, k, v, causal=True,
                                            interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile(
    ).as_text()
    assert sorted(t.rsplit(".", 1)[0] for t in _kernels(text)) == [
        "flash_attention_dkv", "flash_attention_dq", "flash_attention_fwd"]


def test_flash_attention_runs_per_device_over_four_chips(topo):
    """On a mesh of four described chips with the batch over ``data``, the
    attention block's kernel runs under ``shard_map``: each chip computes
    its own rows, and no collective gathers the kernel's operands."""
    mesh = _mesh(topo.devices, (4, 1))
    rules = pt.make_rules(CFG, mesh, ParallelConfig(), for_state="act")
    rows = jax.sharding.NamedSharding(mesh, jax.P("data"))
    every = jax.sharding.NamedSharding(mesh, jax.P())
    p = {name: _spec(d.shape, jnp.bfloat16, every)
         for name, d in cm.attn_defs(CFG).items()}
    x = _spec((BATCH, SEQ, CFG.d_model), jnp.bfloat16, rows)
    pos = _spec((BATCH, SEQ), jnp.int32, rows)

    def loss(p, x, pos):
        out, _ = cm.attention_block(p, x, pos, CFG, rules)
        return jnp.sum(out.astype(jnp.float32))

    with jax.set_mesh(mesh), cm.attention_paths() as tally:
        lowered = jax.jit(jax.grad(loss, (0, 1))).lower(p, x, pos)
    text = lowered.compile().as_text()
    assert tally == {"flash": 1, "flash_bwd_fused": 1}
    assert len(_kernels(text)) == 2  # forward, fused backward
    assert "all-gather" not in text and "all-to-all" not in text
