"""Continuous-batching serving driver with tier-paged KV blocks.

A fixed batch of device decode slots advances in lockstep (the
static-shape-friendly form of continuous batching): per-slot lengths and
EOS are tracked, a slot whose sequence finishes (EOS or token budget) is
refilled from the waiting queue, and idle slots keep decoding into padding
that is masked out of the returned text. Sequences beyond the device KV
budget wait in the pinned-host (or NVMe) tier as fixed-size per-sequence
KV blocks (``core/kvcache.py``) and stream back through the shared pinned
pool when admitted — concurrent-sequence count is bounded by the slow
tier, not HBM (paper Secs. 3-4 applied to serving state).

With ``--plan auto`` the KV tier, slot count, block size, and prefetch
depth come from ``repro.plan`` (the same Sec. 3 byte arithmetic that
places parameters); ``--kv-*`` flags override per field. Jitted prefill /
decode compile untimed (ahead-of-time) and compile time is reported
separately from throughput.

Example (CPU, reduced config; 8 sequences through 2 device slots):
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --batch 8 --kv-slots 2 --kv-tier host --prompt-len 32 --new-tokens 16
"""
from __future__ import annotations

import argparse
import collections
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro import plan as plan_mod
from repro.config import ParallelConfig, RunConfig, ShapeConfig
from repro.core import kvcache, qformat
from repro.core.engine import ZeroInfinityEngine
from repro.core.offload import HostArrayStore, NvmeStore, PinnedBufferPool
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.runtime import metrics as metrics_mod
from repro.runtime import trace


def _parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="total sequences to serve; those beyond --kv-slots "
                         "wait on the KV tier as paged blocks")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16,
                    help="per-sequence token budget (includes the EOS token)")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="EOS token id; a slot emitting it finishes early "
                         "(-1: budget-only)")
    ap.add_argument("--kv-slots", type=int, default=0,
                    help="device decode slots (0 = all sequences resident, "
                         "or the plan's derivation with --plan auto)")
    ap.add_argument("--kv-tier", default="device",
                    choices=["device", "host", "nvme"],
                    help="tier for waiting sequences' KV blocks ('device' "
                         "stages any overflow through host DRAM)")
    ap.add_argument("--kv-block-tokens", type=int, default=0,
                    help="tokens per paged KV block (0 = auto)")
    ap.add_argument("--kv-dir", default="/tmp/repro_kv",
                    help="directory backing the NVMe KV tier")
    ap.add_argument("--kv-quant", default="none",
                    choices=["none", "q8", "q4"],
                    help="block-quantized wire format for parked sequences' "
                         "KV blocks (core/qformat.py): waiting KV costs "
                         "~1/2 (q8) or ~1/3 (q4) of the slow tier")
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", nargs="?", const="trace.json", default=None,
                    metavar="OUT.json",
                    help="record spans and write a Chrome/Perfetto trace "
                         "(runtime/trace.py) for the serve run")
    plan_mod.add_plan_args(ap)
    return ap.parse_args(argv)


def _percentiles(xs) -> dict:
    """p50/p95/p99 of a latency sample, in seconds (zeros when empty)."""
    if not xs:
        return {"p50": 0.0, "p95": 0.0, "p99": 0.0}
    a = np.asarray(xs, np.float64)
    return {f"p{q}": float(np.percentile(a, q)) for q in (50, 95, 99)}


def run_serve(args, argv=None) -> dict:
    """The serving run; returns per-sequence tokens + timings + KV metrics
    (the test surface — ``main`` just prints)."""
    cfg = configs.smoke(args.arch) if args.smoke else configs.get(args.arch)
    n_seqs, P, N = args.batch, args.prompt_len, args.new_tokens
    eos = args.eos_id
    plan = plan_mod.resolve_plan(
        args, cfg, ShapeConfig("serve-plan", P + N, n_seqs, "decode"),
        argv=argv)
    if plan is not None:
        run = plan.to_run_config()
        kv_tier = plan.kv_tier
        slots = plan.kv_slots or n_seqs
        block_tokens = plan.kv_block_tokens
        kv_prefetch = plan.kv_prefetch_blocks
    else:
        run = RunConfig(model=cfg, parallel=ParallelConfig(remat="none"))
        kv_tier = args.kv_tier
        slots = args.kv_slots or n_seqs
        block_tokens = args.kv_block_tokens
        kv_prefetch = 2
    slots = max(1, min(int(slots), n_seqs))
    block_tokens = int(block_tokens) or kvcache.default_block_tokens(P + N)

    mesh = make_local_mesh(args.data_mesh, args.model_mesh)
    eng = ZeroInfinityEngine(run, mesh)
    state = eng.init_state(jax.random.PRNGKey(args.seed))
    params = state["params"]

    # the slow tier for waiting sequences (unused when every slot fits)
    pool = PinnedBufferPool(run.offload.pinned_buffer_mb << 20)
    if kv_tier == "nvme":
        store = NvmeStore(os.path.join(args.kv_dir, "kv"), pool=pool,
                          workers=run.offload.nvme_workers)
    else:
        store = HostArrayStore(pool=pool, workers=2)
    store.trace_cls = "kv"
    # parked KV rides the same wire format as slow-tier params: blocks are
    # encoded on park and decoded on admission, so the waiting-sequence
    # footprint (and flush/fetch traffic) shrinks by the compression ratio
    store = qformat.maybe_wrap_store(store, args.kv_quant)
    seq_names = (("k", "v") if cfg.family in kvcache.SEQ_CACHE_FAMILIES
                 else ())
    kv = kvcache.PagedKVCache(store, block_tokens=block_tokens,
                              seq_axis_names=seq_names,
                              prefetch_blocks=kv_prefetch)

    # ---- prompts for every sequence (waves of `slots` share one jit) ----
    rng = np.random.default_rng(args.seed)
    specs = eng.bundle.input_specs(ShapeConfig("serve", P, slots, "prefill"))
    full = {}
    for k, v in specs.items():
        shp = (n_seqs,) + tuple(v.shape[1:])
        if np.issubdtype(np.dtype(v.dtype), np.integer):
            full[k] = rng.integers(0, cfg.vocab_size, shp, dtype=np.int32)
        else:
            full[k] = (rng.standard_normal(shp) * 0.1).astype(v.dtype)

    def wave_rows(w):
        lo = w * slots
        idx = list(range(lo, min(lo + slots, n_seqs)))
        valid = len(idx)
        while len(idx) < slots:
            idx.append(0)  # padding rows; results discarded
        return idx, valid

    def wave_batch(idx):
        return {k: jnp.asarray(a[idx]) for k, a in full.items()}

    n_waves = -(-n_seqs // slots)
    gen = [[] for _ in range(n_seqs)]
    done = [False] * n_seqs
    waiting: collections.deque = collections.deque()

    pc = time.perf_counter
    with jax.set_mesh(mesh):
        # untimed ahead-of-time compile: throughput below is compute-only
        t0 = pc()
        prefill_c = jax.jit(eng.bundle.prefill).lower(
            params, wave_batch(wave_rows(0)[0])).compile()
        t_compile_prefill = pc() - t0

        t_prefill = 0.0
        wave0 = None
        ttft = [0.0] * n_seqs  # time to first token, from serve start
        t_serve = pc()
        for w in range(n_waves):
            idx, valid = wave_rows(w)
            t0 = pc()
            with trace.span("prefill", sys="serve", attr="compute", unit=w):
                logits, cache = prefill_c(params, wave_batch(idx))
                jax.block_until_ready(logits)
            t_prefill += pc() - t0
            first = np.asarray(
                jnp.argmax(logits[:, -1], axis=-1)).astype(np.int32)
            prefill_len = int(np.asarray(cache["len"]))
            t_first = pc() - t_serve
            for j in range(valid):
                s = idx[j]
                ttft[s] = t_first
                gen[s].append(int(first[j]))
                if int(first[j]) == eos or N <= 1:
                    done[s] = True  # finished at birth: EOS-masked already
            if w == 0:
                wave0 = (cache, idx, valid)
            else:
                for j in range(valid):
                    s = idx[j]
                    if not done[s]:
                        kv.park(f"seq{s}",
                                kvcache.slice_sequence(cache, j), prefill_len)
                        waiting.append(s)
        kv.flush()

        # ---- device slot cache: wave 0 grown to decode capacity, with a
        # per-slot length vector in place of the scalar prefill length ----
        cache0, idx0, valid0 = wave0
        slot_cache = kvcache.grow_cache(cache0, N, cfg.family)
        slot_cache = {**slot_cache,
                      "len": jnp.full((slots,), prefill_len, jnp.int32)}
        cap = prefill_len + N
        resident = kvcache.device_kv_bytes(slot_cache)

        slot_seq = [idx0[j] if j < valid0 else None for j in range(slots)]
        active = [j < valid0 and not done[idx0[j]] for j in range(slots)]
        cur = np.zeros((slots,), np.int32)
        for j in range(valid0):
            cur[j] = gen[idx0[j]][-1]

        def _insert(cache_t, single, b, length):
            def upd(path, leaf, s):
                key = path[-1].key if hasattr(path[-1], "key") else None
                if key == "len":
                    return leaf.at[b].set(length)
                return jax.lax.dynamic_update_index_in_dim(
                    leaf, s.astype(leaf.dtype), b, 1)
            return jax.tree_util.tree_map_with_path(upd, cache_t, single)

        insert_c = jax.jit(_insert, donate_argnums=(0,))

        t0 = pc()
        decode_c = jax.jit(eng.bundle.decode_step, donate_argnums=(1,)).lower(
            params, slot_cache, {"tokens": jnp.zeros((slots, 1), jnp.int32)}
        ).compile()
        t_compile_decode = pc() - t0

        # ---- continuous-batching decode loop ----
        # Admission fetches are issued AHEAD of need (kv.start_fetch): the
        # block reads run on the store's workers while decode steps execute,
        # so a freed slot pays only the uncovered remainder — reported as
        # admit_stall_s, separately from the total admission time.
        history = []
        tok_lat = []  # per-token decode latency (one entry per token)
        t_decode = t_admit = t_admit_stall = 0.0
        steps = admissions = 0
        prefetched: collections.deque = collections.deque()

        def top_up_admissions():
            while waiting and len(prefetched) < slots:
                s = waiting.popleft()
                prefetched.append((s, kv.start_fetch(f"seq{s}", cap)))

        top_up_admissions()  # first admissions overlap the first decodes
        while True:
            m = kv.mark()
            for b in range(slots):
                if active[b] or not prefetched:
                    continue
                s, handle = prefetched.popleft()
                ta = pc()
                with trace.span("admit_wait", sys="serve", attr="io_wait",
                                cls="kv", unit=s):
                    single, length = handle.result()
                t_admit_stall += pc() - ta
                with trace.span("admit_insert", sys="serve", attr="compute",
                                cls="kv", unit=s):
                    slot_cache = insert_c(
                        slot_cache, jax.tree.map(jnp.asarray, single),
                        jnp.int32(b), jnp.int32(length))
                t_admit += pc() - ta
                kv.drop(f"seq{s}")
                slot_seq[b], active[b] = s, True
                cur[b] = gen[s][-1]
                admissions += 1
            top_up_admissions()
            for _, handle in prefetched:
                handle.poll()  # keep windows full without blocking
            if not any(active):
                break
            t0 = pc()
            with trace.span("decode_step", sys="serve", attr="compute",
                            unit=steps):
                logits, slot_cache = decode_c(
                    params, slot_cache, {"tokens": jnp.asarray(cur[:, None])})
                toks = np.asarray(
                    jnp.argmax(logits[:, -1], axis=-1)).astype(np.int32)
            step_dt = pc() - t0
            t_decode += step_dt
            steps += 1
            history.append(
                metrics_mod.kv_step_metrics(kv.delta_since(m), resident))
            for b in range(slots):
                if not active[b]:
                    continue  # idle slot: padding decode, masked out
                s = slot_seq[b]
                tok_lat.append(step_dt)
                gen[s].append(int(toks[b]))
                cur[b] = toks[b]
                if int(toks[b]) == eos or len(gen[s]) >= N:
                    done[s], active[b], slot_seq[b] = True, False, None
                    cur[b] = 0

    stats = store.bandwidth_stats()
    return {
        "generated": gen,
        "done": done,
        "slots": slots,
        "kv_tier": kv_tier,
        "block_tokens": block_tokens,
        "steps": steps,
        "admissions": admissions,
        "plan": plan,
        "history": history,
        "latency": {
            "ttft_s": list(ttft),
            "decode_token_s": list(tok_lat),
            "ttft": _percentiles(ttft),
            "decode_token": _percentiles(tok_lat),
        },
        "kv": {
            "resident_bytes": resident,
            "in_bytes": int(stats.get("logical_bytes_read",
                                      stats["bytes_read"])),
            "out_bytes": int(stats.get("logical_bytes_written",
                                       stats["bytes_written"])),
            "in_wire_bytes": int(stats["bytes_read"]),
            "out_wire_bytes": int(stats["bytes_written"]),
            "parked_peak_bytes": kv.parked_bytes(),
            "pinned_peak_bytes": int(pool.peak_resident),
            "pinned_budget_bytes": int(run.offload.pinned_buffer_mb) << 20,
        },
        "timings": {
            "compile_prefill_s": t_compile_prefill,
            "compile_decode_s": t_compile_decode,
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "admit_s": t_admit,
            "admit_stall_s": t_admit_stall,
        },
    }


def main(argv=None) -> None:
    args = _parse(argv)
    enable_compile_cache()
    if args.trace:
        trace.enable()
    out = run_serve(args, argv)
    t = out["timings"]
    gen, slots = out["generated"], out["slots"]
    n_seqs, P = args.batch, args.prompt_len
    dec_toks = sum(len(g) for g in gen) - n_seqs  # prefill emits token 1
    print(f"compile: prefill {t['compile_prefill_s']*1e3:.1f} ms | "
          f"decode {t['compile_decode_s']*1e3:.1f} ms (untimed warm-up; "
          f"excluded from throughput)")
    print(f"prefill: {n_seqs}x{P} tokens in {t['prefill_s']*1e3:.1f} ms "
          f"({n_seqs * P / max(t['prefill_s'], 1e-9):.0f} tok/s, "
          f"{slots} slots/wave)")
    print(f"decode: {dec_toks} tokens over {out['steps']} steps in "
          f"{t['decode_s']*1e3:.1f} ms "
          f"({dec_toks / max(t['decode_s'], 1e-9):.0f} tok/s) | "
          f"{out['admissions']} admissions (+{t['admit_s']*1e3:.1f} ms "
          f"KV streaming, of which {t['admit_stall_s']*1e3:.1f} ms stalled "
          f"waiting on reads the decode overlap did not cover)")
    kvm = out["kv"]
    wire = ""
    if kvm["in_wire_bytes"] != kvm["in_bytes"] or \
            kvm["out_wire_bytes"] != kvm["out_bytes"]:
        wire = (f"wire in {kvm['in_wire_bytes']} B / "
                f"out {kvm['out_wire_bytes']} B | ")
    print(f"kv[{out['kv_tier']}]: resident {kvm['resident_bytes']} B | "
          f"in {kvm['in_bytes']} B | out {kvm['out_bytes']} B | {wire}"
          f"pinned peak {kvm['pinned_peak_bytes']} B "
          f"(budget {kvm['pinned_budget_bytes']} B)")
    lat = out["latency"]
    ttft_p, tok_p = lat["ttft"], lat["decode_token"]
    print(f"latency: TTFT p50/p95/p99 = {ttft_p['p50']*1e3:.1f}/"
          f"{ttft_p['p95']*1e3:.1f}/{ttft_p['p99']*1e3:.1f} ms | "
          f"decode tok p50/p95/p99 = {tok_p['p50']*1e3:.2f}/"
          f"{tok_p['p95']*1e3:.2f}/{tok_p['p99']*1e3:.2f} ms "
          f"({len(lat['decode_token_s'])} tokens)")
    if args.trace:
        trace.export_chrome(args.trace)
        print(f"trace: wrote {args.trace} "
              f"({len(trace.TRACER.events())} spans)")
    for s in range(min(n_seqs, 4)):
        print(f"slot {s}: {gen[s][:16]}")

    if args.smoke:
        if not all(out["done"]):
            raise SystemExit("SERVE SMOKE FAIL: decode did not complete "
                             f"(done={out['done']})")
        for s, g in enumerate(gen):
            if args.eos_id in g and g.index(args.eos_id) != len(g) - 1:
                raise SystemExit(
                    f"SERVE SMOKE FAIL: seq {s} has tokens after EOS: {g}")
            if len(g) > args.new_tokens:
                raise SystemExit(
                    f"SERVE SMOKE FAIL: seq {s} exceeded the "
                    f"{args.new_tokens}-token budget: {len(g)}")
        plan = out["plan"]
        if plan is not None and "kv_resident_bytes" in plan.predictions:
            pred = plan.predictions["kv_resident_bytes"]
            if kvm["resident_bytes"] > pred:
                raise SystemExit(
                    f"SERVE SMOKE FAIL: measured device KV "
                    f"{kvm['resident_bytes']} B > planned {pred:.0f} B")
        if kvm["pinned_peak_bytes"] > kvm["pinned_budget_bytes"]:
            raise SystemExit(
                f"SERVE SMOKE FAIL: pinned staging "
                f"{kvm['pinned_peak_bytes']} B exceeded the "
                f"{kvm['pinned_budget_bytes']} B budget")
        for which in ("ttft", "decode_token"):
            p = lat.get(which)
            if p is None or any(k not in p for k in ("p50", "p95", "p99")):
                raise SystemExit(
                    f"SERVE SMOKE FAIL: latency percentiles missing for "
                    f"{which}: {p}")
            if p["p50"] > p["p99"]:
                raise SystemExit(
                    f"SERVE SMOKE FAIL: {which} latency percentiles "
                    f"inverted: p50 {p['p50']*1e3:.2f} ms > "
                    f"p99 {p['p99']*1e3:.2f} ms")
        print(f"SERVE SMOKE OK: {n_seqs} seqs through {slots} "
              f"{out['kv_tier']}-tier slots, {out['steps']} steps, "
              f"{out['admissions']} admissions, EOS-masked, "
              f"KV residency within plan, latency percentiles sane "
              f"(decode tok p50 {tok_p['p50']*1e3:.2f} ms)")


if __name__ == "__main__":
    main()
