"""End-to-end behaviour tests: the training driver (device + NVMe-offload
optimizer tiers) and the serving driver, run via their CLIs exactly as a
user would."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_cli(args, timeout=900, **env_extra):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"), **env_extra)
    r = subprocess.run([sys.executable, "-m"] + args, env=env,
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout[-3000:]}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
def test_train_cli_device_tier(tmp_path):
    out = run_cli(["repro.launch.train", "--arch", "smollm-135m", "--smoke",
                   "--steps", "12", "--batch", "4", "--seq", "64", "--lr", "3e-3",
                   "--ckpt-dir", str(tmp_path), "--ckpt-every", "6"])
    first = float(out.split("first loss")[1].split("|")[0])
    last = float(out.split("last loss")[1].split("|")[0])
    assert last < first - 0.2, out.splitlines()[-1]
    assert os.path.exists(os.path.join(str(tmp_path), "step-00000012"))


@pytest.mark.slow
def test_train_cli_nvme_tier(tmp_path):
    """The paper's NVMe-resident optimizer: states stream through the store,
    training still converges, bandwidth counters report."""
    out = run_cli(["repro.launch.train", "--arch", "smollm-135m", "--smoke",
                   "--steps", "10", "--batch", "4", "--seq", "64", "--lr", "3e-3",
                   "--offload-opt", "nvme", "--nvme-dir", str(tmp_path / "nvme"),
                   "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "0"])
    first = float(out.split("first loss")[1].split("|")[0])
    last = float(out.split("last loss")[1].split("|")[0])
    assert last < first - 0.1
    assert "nvme: read" in out


@pytest.mark.slow
def test_serve_cli(tmp_path):
    out = run_cli(["repro.launch.serve", "--arch", "smollm-135m", "--smoke",
                   "--batch", "2", "--prompt-len", "16", "--new-tokens", "8"])
    assert "prefill:" in out and "decode:" in out and "slot 0:" in out
    assert "compile:" in out  # warm-up reported separately from throughput
    assert "SERVE SMOKE OK" in out


@pytest.mark.slow
def test_serve_cli_paged_nvme(tmp_path):
    out = run_cli(["repro.launch.serve", "--arch", "smollm-135m", "--smoke",
                   "--batch", "5", "--kv-slots", "2", "--kv-tier", "nvme",
                   "--kv-dir", str(tmp_path), "--prompt-len", "16",
                   "--new-tokens", "8"])
    assert "kv[nvme]:" in out and "SERVE SMOKE OK" in out


def test_chip_smoke_refuses_without_a_tpu():
    """No CPU fallback: without a TPU the chip smoke exits non-zero at once,
    names the device it found, and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr and "cpu device" in r.stderr
    assert '"ok"' not in r.stdout


def test_compile_cache_dir(monkeypatch, tmp_path):
    """Without JAX_COMPILATION_CACHE_DIR the cache is the checkout's fixed
    .jax_cache; with it, JAX's own setting stands and compiles land there."""
    import jax

    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = compile_cache.enable_compile_cache()
        assert got == jax.config.jax_compilation_cache_dir
        assert got == os.path.join(os.path.abspath(ROOT), ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

    code = ("from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "import jax, jax.numpy as jnp\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip() == str(tmp_path)
    assert any(tmp_path.iterdir())
