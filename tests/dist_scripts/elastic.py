"""Subprocess: elastic checkpoint restore across dp degrees (8 host devices)."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.checkpoint.manager import CheckpointManager
from repro.config import RunConfig, ParallelConfig, TrainConfig
from repro.core.engine import ZeroInfinityEngine

auto = (jax.sharding.AxisType.Auto,)


def make_engine(dp):
    mesh = jax.make_mesh((dp,), ("data",), devices=jax.devices()[:dp], axis_types=auto)
    run = RunConfig(model=configs.smoke("smollm-135m"),
                    parallel=ParallelConfig(zero_stage=3), train=TrainConfig())
    return ZeroInfinityEngine(run, mesh), mesh


def main():
    d = os.environ["ELASTIC_DIR"]
    eng4, _ = make_engine(4)
    state = eng4.init_state(jax.random.PRNGKey(0))
    mgr = CheckpointManager(d, async_save=False)
    mgr.save(3, state, {"next_step": 3}).result()

    ref = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), state)
    for dp in (2, 8):
        eng, mesh = make_engine(dp)
        specs = eng.state_specs()
        shardings = jax.tree.map(lambda s: s.sharding, specs)
        like = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), specs)
        restored, extra = mgr.restore(like, shardings=shardings)
        assert extra["next_step"] == 3
        got = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), restored)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)), ref, got)
        # verify the big leaves actually landed sharded over the new dp
        leaves = [l for l in jax.tree.leaves(restored) if l.size > 1000]
        assert any(len(l.sharding.device_set) == dp for l in leaves), dp
    print("ELASTIC OK")


if __name__ == "__main__":
    main()
