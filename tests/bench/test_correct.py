"""The check that decides ``correct``, driven through a whole run on the CPU
at the program's smoke sizes, under the cells' own limits: sound runs pass,
the control and each fault a training cell can have fail.

The harness's look for a chip is skipped by handing it the CPU devices.
"""
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import pytest
import tiny

from perfbench import compare, harness

SEED = 2**31 + 99


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """Runs in this process leave JAX's persistent cache as they found it."""
    monkeypatch.setattr(harness, "enable_cache", lambda root: None)


def run(root, name="tiny-dense-hbm"):
    return harness.run(root, name, SEED, 0.5, False,
                       t_start=time.perf_counter(), devices=jax.devices()[:1])


@pytest.mark.parametrize("name", ["tiny-dense-hbm", "tiny-moe-dp"])
def test_sound_run_is_correct(root, name):
    result = run(root, name)
    assert result["correct"], result["compared"]
    assert list(result)[-1] == "compared"


def broken(monkeypatch, wrap):
    """Plant a fault in the timed path: every call of the program's step
    goes through ``wrap(step, state, batch)``."""
    init = harness.Program.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        step = self.step
        self.step = lambda state, batch: wrap(step, state, batch)

    monkeypatch.setattr(harness.Program, "__init__", patched)


def unchanged(step, state, batch):
    _, metrics = step(state, batch)
    return state, metrics


def half_batch(step, state, batch):
    rows = batch["tokens"].shape[0] // 2
    return step(state, {k: v[:rows] for k, v in batch.items()})


@pytest.mark.parametrize("fault", [unchanged, half_batch])
def test_fault_is_caught(root, monkeypatch, fault):
    broken(monkeypatch, fault)
    assert not run(root)["correct"]


def test_control_is_caught(root, monkeypatch):
    """The reference at float8 in the program's place."""
    checked = harness.checked_steps

    def control(prog, key):
        state, _, extra = checked(prog, key)
        import numpy as np
        batches = [np.asarray(prog.feed(key, i)["tokens"])
                   for i in range(1, harness.CHECKED_STEPS + 1)]
        cell = harness.load_cell(root, "tiny-dense-hbm")
        out = harness.reference_run(cell, harness.reference_module(cell),
                                    jax.devices()[:1], key, batches,
                                    mode="fp8")
        return state, out, extra

    monkeypatch.setattr(harness, "checked_steps", control)
    assert not run(root)["correct"]


def test_no_exchange_is_caught(tmp_path):
    """Four virtual CPU devices: the sound ZeRO-3 run passes, and the step
    that sees one chip's share of the rows (no exchange) fails."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, str(Path(__file__).parent /
                                              "dp4_run.py")],
                         cwd=tiny.REPO, env=env, capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.split()[-2:] == ["sound=True", "no_exchange=False"]


def test_worst_leaf_gap_by_hand():
    ref = {"embed": 2.0, "a": [1.0, 4.0, 0.001]}
    prog = {"embed": 2.2, "a": [1.0, 4.0, 0.5]}
    # median of (2, 1, 4, 0.001) is 1.5; the small leaf's gap counts against
    # the median: 0.499 / 1.5; the embedding's 0.2 / 2
    gap, where = compare.worst_leaf_gap(prog, ref)
    assert where == ("a", 2) and gap == pytest.approx(0.499 / 1.5)
    assert compare.moved_leaves({"a": [1.0, 1e-5], "b": 2.0}) == [("a", 0),
                                                                  ("b", None)]
