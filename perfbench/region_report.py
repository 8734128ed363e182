"""Where a cell's train step spends its device time, by model region.

    python3 perfbench/region_report.py --workload <name> --seed <n> \
        --seconds <s> --out <dir>

From the root of a checkout, on a machine that holds the cell's chips. It
builds the cell and runs its checked steps as ``perfbench/run.py`` does, then
three profiled windows of the same closed loop: ``--seconds`` with the
program's Tracer off, the same with it on (its spans on the profiler's
clock), and two steps with it on, kept for the reduction's tests. Each
window prints one JSON line: window tokens/s, device idle share, the step
module's busy time and the share of it that named regions cover, the
regions split by direction, the idle gaps labelled by program span, the
device ``Steps`` line's length, the ``attn/core`` roofline share, and the
top ops with their region. ``<dir>`` receives the compiled step's HLO text,
its region map and the two-step trace.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def report(cell, ref_mod, rmap: dict, trace_dir: Path, steps: int,
           tokens: int, peak_flops: float, hlo_lines: dict) -> dict:
    from perfbench import regions, trace_reduce

    summary = trace_reduce.reduce_dir(trace_dir, cell.workload["chips"])
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    st = regions.reduce_file(path, rmap, cell.workload["chips"])
    busy = st.step_busy_s()
    attn = ref_mod.attention_flops_per_token(
        cell.config, cell.traffic["seq_len"]) * tokens
    top = sorted(((e - s, name, r, d) for s, e, name, r, d in st.ops[0]),
                 reverse=True)
    per_op = {}
    for dt, name, r, d in top:
        per_op.setdefault(name, [0.0, r, d])[0] += dt / 1e9
    top_ops = sorted(per_op.items(), key=lambda kv: -kv[1][0])[:15]
    return {
        "steps": steps, "window_s": summary.window_s,
        "tokens_per_s_trace_window": tokens / summary.window_s,
        "device_idle_pct": 100 * (1 - summary.busy_s / summary.window_s),
        "step_busy_s": busy,
        "named_share_pct": 100 * (1 - st.region_s(regions.OTHER) / busy),
        "steps_line_events": len(st.steps),
        "steps_line": [[n, (e - s) / 1e9] for s, e, n in st.steps],
        "attention_roofline_pct": 100 * attn / (
            st.region_s("attn/core") * peak_flops),
        "attention_roofline_unrestricted_pct": 100 * attn / (
            regions.summary_region_s(summary, rmap, "attn/core")
            * peak_flops),
        "regions": st.regions(20),
        "idle_gaps_program": st.idle_gaps_program(),
        "idle_gaps": summary.breakdown()["idle_gaps"],
        "top_ops": [[name, s, r, d, hlo_lines.get(name, "")[:400]]
                    for name, (s, r, d) in top_ops],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from perfbench import harness, peaks, regions

    cell = harness.load_cell(ROOT, args.workload)
    devices = harness.require_chips(cell.workload["chips"])
    harness.enable_cache(ROOT)
    import jax

    from repro.runtime import trace

    counter = harness.CompileCounter()
    ref_mod = harness.reference_module(cell)
    prog = harness.Program(cell, devices, ref_mod)
    key = harness.seed_key(args.seed)
    state, _, _ = harness.checked_steps(prog, key)
    text = prog.jitted.lower(
        state, prog.feed(key, harness.CHECKED_STEPS + 1)).compile().as_text()
    rmap = regions.region_map(text)
    hlo_lines = {}
    for line in text.splitlines():
        name = line.strip().removeprefix("ROOT ").split(" = ", 1)[0]
        hlo_lines.setdefault(name.lstrip("%"), line.strip())
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{cell.name}.hlo.txt").write_text(text)
    peak = peaks.peaks_for(devices[0].device_kind).flops
    print(json.dumps({"setup_s": time.perf_counter() - T_START,
                      "instructions": len(rmap)}), flush=True)

    for label, seconds, tracer in (("tracer_off", args.seconds, False),
                                   ("tracer_on", args.seconds, True),
                                   ("two_steps", 1.5, True)):
        trace_dir = ROOT / ".perfbench" / "regions" / label
        shutil.rmtree(trace_dir, ignore_errors=True)
        if tracer:
            trace.enable()
        jax.profiler.start_trace(str(trace_dir))
        state, bad, window_s, ends = harness.window(prog, key, state,
                                                    seconds, counter)
        jax.profiler.stop_trace()
        trace.disable()
        trace.clear()
        tokens = len(ends) * prog.tokens_per_step
        line = {"window": label, "tracer": tracer, "bad": bad,
                "compiles_in_window": counter.n,
                "tokens_per_s_host": tokens / window_s}
        line.update(report(cell, ref_mod, rmap, trace_dir, len(ends), tokens,
                           peak, hlo_lines))
        print(json.dumps(line), flush=True)
        if label == "two_steps":
            path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
            with open(path, "rb") as f, gzip.open(
                    out / f"{cell.name}.v5e.xplane.pb.gz", "wb") as g:
                shutil.copyfileobj(f, g)
            with gzip.open(out / f"{cell.name}.v5e.regions.json.gz",
                           "wt") as g:
                json.dump(rmap, g)
        shutil.rmtree(trace_dir, ignore_errors=True)
    prog.executor.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
