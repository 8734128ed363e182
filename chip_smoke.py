"""Bring-up smoke run of the trainer and the server on TPU, at the full width
of smollm-135m (30 layers, d_model 576, GQA 9/3, vocab 49152).

    python chip_smoke.py             # one chip: the five phases below
    python chip_smoke.py --chips 4   # four chips: zero3 dp=4 and its reference

One chip:
  train-device  pjit engine, every state in HBM
  train-host    the same, optimizer state in pinned host memory
  train-nvme    zero3 layered epoch, params/grads/optimizer state on NVMe
  serve         4 requests through 2 decode slots, waiting KV on the host
  kernels       fused_adam and flash_attention compiled, against kernels/ref

Four chips: the zero3 engine at dp=4 against the same global batch on one
device, in this process.

Every phase goes through the entry points a user calls
(``repro.launch.train.train`` and ``repro.launch.serve.run_serve`` with
their own argument parsers) and prints one line of readings. The readings
are bring-up numbers, not benchmark results. The last line of standard
output is a JSON object naming the device; it is printed only when every
phase passed. Without a TPU the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import statistics
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SCRATCH = ROOT / ".chip_smoke"  # NVMe tier and checkpoint dirs (gitignored)

ARCH = ["--arch", "smollm-135m"]
SEQ = 2048
TRAIN_BATCH = 8
TRAIN_STEPS = 6
DP4_BATCH = 16  # global batch of the four-chip comparison
# bf16 has 8 significant bits: engines that reduce in different orders agree
# on a loss to about one part in 2**7
LOSS_RTOL = 2.0 ** -7


def require_tpu(n_chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind}). There is "
            "no CPU fallback.")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: --chips {n_chips} needs {n_chips} "
                         f"TPU devices, found {len(devs)}")
    return devs


def peak_bytes() -> int:
    """Process-wide device peak so far: the backend cannot reset it."""
    import jax

    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def train_phase(name: str, flags: list, *, batch: int, seq: int,
                steps: int, arch=ARCH) -> tuple:
    """One ``train(args)`` run; returns its readings and its history."""
    from repro.launch.train import build_argparser, train

    t0 = time.perf_counter()
    tmp = SCRATCH / name
    argv = arch + ["--batch", str(batch), "--seq", str(seq),
                   "--steps", str(steps), "--seed", "0", "--log-every", "1",
                   "--ckpt-every", "0", "--ckpt-dir", str(tmp / "ckpt"),
                   "--nvme-dir", str(tmp / "nvme"), "--max-restarts", "0"]
    hist = train(build_argparser().parse_args(argv + flags))
    losses, step_s = hist["losses"], hist["step_s"]
    steady = statistics.median(step_s[1:])
    out = {"phase": name,
           "compile_s": step_s[0] - steady,  # first step less a steady one
           "step_s_median": steady,
           "tokens_per_s": batch * seq / steady,
           "loss_first": losses[0], "loss_last": losses[-1],
           "peak_bytes_in_use": peak_bytes(),
           "wall_s": time.perf_counter() - t0}
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{name}: non-finite loss {losses}")
    return out, hist


def phase_train_device(size) -> dict:
    out, _ = train_phase("train-device", [], **size)
    return out


def phase_train_host(size) -> dict:
    import jax

    out, hist = train_phase("train-host", ["--offload-opt", "host"], **size)
    kinds = {leaf.sharding.memory_kind
             for leaf in jax.tree.leaves(hist["final_state"]["opt"])}
    if kinds != {"pinned_host"}:
        raise AssertionError(f"train-host: optimizer state in {kinds}, "
                             "not pinned_host")
    out["opt_memory_kind"] = "pinned_host"
    return out


def phase_train_nvme(size) -> dict:
    flags = ["--engine", "zero3", "--offload-param", "nvme",
             "--offload-grad", "nvme", "--offload-opt", "nvme",
             "--prefetch-layers", "2"]
    out, hist = train_phase("train-nvme", flags, **size)
    m = hist["last_metrics"]
    peak, total = m["peak_resident_param_bytes"], m["param_total_bytes"]
    if not 0 < peak < total:
        raise AssertionError(f"train-nvme: peak resident params {peak} B "
                             f"not below the {total} B total")
    out.update(peak_resident_param_bytes=int(peak),
               param_total_bytes=int(total))
    return out


def phase_serve(size) -> dict:
    from repro.launch.serve import _parse, run_serve

    new = size["new_tokens"]
    argv = size["arch"] + [
        "--batch", "4", "--kv-slots", "2", "--kv-tier", "host",
        "--prompt-len", str(size["prompt_len"]), "--new-tokens", str(new),
        "--seed", "0"]
    res = run_serve(_parse(argv), argv)
    gen, t = res["generated"], res["timings"]
    if not all(res["done"]) or any(len(g) != new for g in gen):
        raise AssertionError(f"serve: incomplete generations "
                             f"{[len(g) for g in gen]} (budget {new})")
    vocab = size["vocab"]
    if any(not 0 <= tok < vocab for g in gen for tok in g):
        raise AssertionError("serve: token id outside the vocabulary")
    if res["admissions"] != 2 or res["kv"]["in_bytes"] <= 0:
        raise AssertionError(f"serve: expected 2 admissions through the "
                             f"host KV tier, got {res['admissions']} "
                             f"({res['kv']['in_bytes']} B read back)")
    decoded = sum(len(g) for g in gen) - len(gen)  # prefill emits token 1
    return {"phase": "serve",
            "compile_s": t["compile_prefill_s"] + t["compile_decode_s"],
            "prefill_tokens_per_s": 4 * size["prompt_len"] / t["prefill_s"],
            "decode_tokens_per_s": decoded / t["decode_s"],
            "decode_token_s_p50": res["latency"]["decode_token"]["p50"],
            "admissions": res["admissions"],
            "kv_in_bytes": res["kv"]["in_bytes"],
            "peak_bytes_in_use": peak_bytes()}


def phase_kernels(size) -> dict:
    """The Pallas kernels at smollm widths, compiled, against kernels/ref."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    out = {"phase": "kernels"}
    d, ff, H, KV, D = size["d_model"], size["d_ff"], size["heads"], \
        size["kv_heads"], size["head_dim"]
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    # one (d_model, d_ff) weight leaf's optimizer update
    p, g = (jax.random.normal(k, (d, ff), jnp.float32) for k in ks[:2])
    m = jax.random.normal(ks[2], (d, ff), jnp.float32) * 0.1
    v = jnp.abs(jax.random.normal(ks[3], (d, ff), jnp.float32)) * 0.01
    kw = dict(lr=jnp.float32(3e-4), beta1=0.9, beta2=0.95, eps=1e-8,
              weight_decay=0.1, bc1=jnp.float32(0.1), bc2=jnp.float32(0.05))
    adam = jax.jit(lambda *a: ops.fused_adam(*a, **kw))
    # causal GQA attention over one full training sequence
    S = size["seq"]
    q = (jax.random.normal(ks[4], (1, H, S, D)) * 0.3).astype(jnp.bfloat16)
    k = (jax.random.normal(ks[5], (1, KV, S, D)) * 0.3).astype(jnp.bfloat16)
    vv = (jax.random.normal(ks[6], (1, KV, S, D)) * 0.3).astype(jnp.bfloat16)
    attn = jax.jit(lambda *a: ops.flash_attention(*a, causal=True))
    for name, fn, args in (("fused_adam", adam, (p, g, m, v)),
                           ("flash_attention", attn, (q, k, vv))):
        if "tpu_custom_call" not in fn.lower(*args).as_text():
            raise AssertionError(f"kernels: {name} did not lower to a "
                                 "compiled TPU kernel")
    got = adam(p, g, m, v)
    want = ref.adam_ref(p, g, m, v, **kw)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)
    o = attn(q, k, vv)
    o_ref = ref.attention_ref(q, k, vv, causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=2e-2, atol=2e-2)
    out["fused_adam_max_abs_err"] = float(max(
        jnp.max(jnp.abs(a - b)) for a, b in zip(got, want)))
    out["flash_attention_max_abs_err"] = float(jnp.max(jnp.abs(
        o.astype(jnp.float32) - o_ref.astype(jnp.float32))))
    out["peak_bytes_in_use"] = peak_bytes()
    return out


def check_first_losses(readings: list) -> None:
    """Same seed, same data, same init: the first step's loss is one number
    whatever the engine and the tier."""
    firsts = {r["phase"]: r["loss_first"] for r in readings
              if "loss_first" in r}
    base = next(iter(firsts.values()))
    for name, x in firsts.items():
        if not math.isclose(x, base, rel_tol=LOSS_RTOL):
            raise AssertionError(f"first-step losses disagree: {firsts}")


def opt_bytes_by_device(state) -> dict:
    """Optimizer-state bytes each device holds, from the arrays' own
    addressable shards: ZeRO-partitioned master/m/v and the replicated
    optimizer state of the small 'other' leaves, apart."""
    import jax

    by_dev = {}
    for key in ("master", "m", "v", "other_opt"):
        for leaf in jax.tree.leaves(state[key]):
            for shard in leaf.addressable_shards:
                part = by_dev.setdefault(shard.device.id,
                                         {"partitioned": 0, "replicated": 0})
                kind = "replicated" if key == "other_opt" else "partitioned"
                part[kind] += shard.data.nbytes
    return by_dev


def run_dp4(size) -> list:
    """zero3 at dp=4 against the same global batch on one device."""
    import jax

    flags = ["--engine", "zero3"]
    out4, hist4 = train_phase("train-zero3-dp4", flags + ["--data-mesh", "4"],
                              **size)
    state = hist4["final_state"]
    total = sum(leaf.nbytes for k in ("master", "m", "v")
                for leaf in jax.tree.leaves(state[k]))
    by_dev = opt_bytes_by_device(state)
    if len(by_dev) != 4:
        raise AssertionError(f"dp4: optimizer state on {sorted(by_dev)}")
    for dev, b in by_dev.items():
        if b["partitioned"] != total // 4:
            raise AssertionError(
                f"dp4: device {dev} holds {b['partitioned']} B of the "
                f"{total} B partitioned optimizer state, not a quarter")
    out4["opt_partitioned_bytes_per_device"] = {
        d: b["partitioned"] for d, b in sorted(by_dev.items())}
    out4["opt_replicated_bytes_per_device"] = {
        d: b["replicated"] for d, b in sorted(by_dev.items())}
    out4["opt_partitioned_bytes_total"] = total
    losses4 = hist4["losses"]
    del state, hist4
    gc.collect()
    out1, hist1 = train_phase("train-zero3-dp1", flags, **size)
    losses1 = hist1["losses"]
    del hist1
    for a, b in zip(losses4, losses1):
        if not math.isclose(a, b, rel_tol=LOSS_RTOL):
            raise AssertionError(f"dp4 losses {losses4} disagree with the "
                                 f"one-device reference {losses1}")
    return [out4, out1]


def full_size() -> dict:
    """The widths every phase runs at, read from the model's own config."""
    from repro import configs

    cfg = configs.get(ARCH[1])
    return {"arch": ARCH, "seq": SEQ, "vocab": cfg.vocab_size,
            "d_model": cfg.d_model, "d_ff": cfg.d_ff, "heads": cfg.n_heads,
            "kv_heads": cfg.n_kv_heads, "head_dim": cfg.resolved_head_dim,
            "prompt_len": 128, "new_tokens": 32}


def one_chip_phases(full: dict) -> list:
    train_size = {"batch": TRAIN_BATCH, "seq": full["seq"],
                  "steps": TRAIN_STEPS, "arch": full["arch"]}
    return [("train-device", phase_train_device, train_size),
            ("train-host", phase_train_host, train_size),
            ("train-nvme", phase_train_nvme, train_size),
            ("serve", phase_serve, full),
            ("kernels", phase_kernels, full)]


def run_phases(phases) -> tuple:
    """Run every phase, even after one fails; returns (readings, failed)."""
    readings, failed = [], []
    for name, fn, size in phases:
        t0 = time.perf_counter()
        try:
            res = fn(size)
        except Exception:  # report the phase and go on to the next
            traceback.print_exc()
            print(f"phase {name}: FAILED after "
                  f"{time.perf_counter() - t0:.1f} s", flush=True)
            failed.append(name)
            continue
        for r in res if isinstance(res, list) else [res]:
            r.setdefault("wall_s", time.perf_counter() - t0)
            print(f"phase {r['phase']}: {json.dumps(r)}", flush=True)
            readings.append(r)
        gc.collect()
    return readings, failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        phases = [("zero3-dp4", run_dp4,
                   {"batch": DP4_BATCH, "seq": SEQ, "steps": TRAIN_STEPS})]
    else:
        phases = one_chip_phases(full_size())
    try:
        readings, failed = run_phases(phases)
        if not failed and args.chips == 1:
            check_first_losses(readings)
    finally:
        shutil.rmtree(SCRATCH, ignore_errors=True)
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
