"""Subprocess: distributed numerical equivalence on 8 host devices.

1-device == 8-device ZeRO-3 == 8-device ZeRO-0 for one arch per sharding
regime (TP-heads / context-parallel / MoE-EP), plus explicit-zero3 ==
pjit-zero3 for the dense family, plus host-offload streaming variant.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.config import RunConfig, ParallelConfig, ShapeConfig, TrainConfig
from repro.core.engine import ZeroInfinityEngine
from repro.core.zero import ExplicitZero3Engine
from repro.models import registry

auto = (jax.sharding.AxisType.Auto,)
MESH8 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"), axis_types=auto * 3)
MESH1 = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1], axis_types=auto)


def batch_for(cfg, shape, seed=0):
    b0 = registry.build(cfg)
    out = {}
    for i, (k, v) in enumerate(sorted(b0.input_specs(shape).items())):
        key = jax.random.PRNGKey(seed + i)
        if np.issubdtype(np.dtype(v.dtype), np.integer):
            out[k] = jax.random.randint(key, v.shape, 0, min(cfg.vocab_size, 100))
        else:
            out[k] = (jax.random.normal(key, v.shape) * 0.1).astype(v.dtype)
    return out


def loss_after_steps(cfg, mesh, pc, batch, n=2):
    run = RunConfig(model=cfg, parallel=pc, train=TrainConfig(lr=1e-3))
    eng = ZeroInfinityEngine(run, mesh)
    state = eng.init_state(jax.random.PRNGKey(42))
    with jax.set_mesh(mesh):
        step = jax.jit(eng.make_train_step())
        for _ in range(n):
            state, m = step(state, batch)
    # check stage-3 actually shards a big opt leaf
    if pc.zero_stage == 3 and len(mesh.devices.flat) > 1:
        big = max(jax.tree.leaves(state["opt"].m), key=lambda l: l.size)
        assert len(big.sharding.device_set) >= 4, "opt state not dp-sharded"
    return float(m["loss"])


def main():
    shape = ShapeConfig("t", 32, 4, "train")
    for arch in ("gemma-7b", "llava-next-34b", "granite-moe-1b-a400m"):
        cfg = configs.smoke(arch)
        batch = batch_for(cfg, shape)
        l1 = loss_after_steps(cfg, MESH1, ParallelConfig(zero_stage=3), batch)
        l3 = loss_after_steps(cfg, MESH8, ParallelConfig(zero_stage=3), batch)
        l0 = loss_after_steps(cfg, MESH8, ParallelConfig(zero_stage=0), batch)
        print(arch, l1, l3, l0)
        assert abs(l1 - l3) < 0.05 and abs(l3 - l0) < 0.05, (arch, l1, l3, l0)

    # explicit-collective engine == pjit engine (dense family)
    cfg = configs.smoke("llama3.2-3b")
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(0), (8, 32), 0, cfg.vocab_size),
             "labels": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)}
    mesh8 = jax.make_mesh((8,), ("data",), axis_types=auto)
    losses = []
    for prefetch in (1, 0):
        run = RunConfig(model=cfg, parallel=ParallelConfig(
            partition_mode="allgather", prefetch=prefetch, engine="zero3"),
            train=TrainConfig(lr=1e-3))
        eng = ExplicitZero3Engine(run, mesh8)
        st = eng.init_state(jax.random.PRNGKey(42))
        with jax.set_mesh(mesh8):
            step = jax.jit(eng.make_train_step())
            for _ in range(2):
                st, m = step(st, batch)
        losses.append(float(m["loss"]))
    l_pjit = loss_after_steps(cfg, mesh8, ParallelConfig(zero_stage=3), batch)
    print("explicit:", losses, "pjit:", l_pjit)
    assert abs(losses[0] - losses[1]) < 1e-5
    assert abs(losses[0] - l_pjit) < 0.02

    # broadcast (owner) baseline matches, where L % dp == 0  (L=2, dp=2)
    mesh2 = jax.make_mesh((2,), ("data",), devices=jax.devices()[:2], axis_types=auto)
    run_b = RunConfig(model=cfg, parallel=ParallelConfig(
        partition_mode="broadcast", prefetch=0, engine="zero3"), train=TrainConfig(lr=1e-3))
    eng_b = ExplicitZero3Engine(run_b, mesh2)
    st = eng_b.init_state(jax.random.PRNGKey(42))
    with jax.set_mesh(mesh2):
        step = jax.jit(eng_b.make_train_step())
        for _ in range(2):
            st, mb = step(st, batch)
    print("broadcast:", float(mb["loss"]))
    assert abs(float(mb["loss"]) - losses[0]) < 0.02

    print("EQUIVALENCE OK")


if __name__ == "__main__":
    main()
