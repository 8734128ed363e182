"""Causal attention FLOPs of the steps in the traced window (QK^T and PV,
forward and backward; perfbench/flops.py) over the device time of the ops in
the program's ``attn/core`` region, summed over the chips, times the peak
FLOP/s: the attention kernel's share of its compute roofline.

The region of an op comes from the compiled step's named scopes
(``perfbench/regions.py``). The harness hands a reader no compiled program,
so this one rebuilds the step of the run's cell (``perfbench/run.py
--workload``) and compiles it again (``regions.compiled_step_text``); a
trace without that command line raises, so the metric cannot vanish
unseen. A program without the ``attn/core`` scope has no time there: no
value."""
import sys
from pathlib import Path

from perfbench import flops, harness, regions

ROOT = Path(__file__).resolve().parents[2]


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    workload = regions.run_workload(sys.argv)
    if workload is None:
        raise RuntimeError("attention_roofline.train: no --workload on the "
                           f"command line {sys.argv!r}")
    rmap = regions.region_map(regions.compiled_step_text(ROOT, workload))
    core_s = regions.summary_region_s(t, rmap, "attn/core")
    if core_s <= 0:
        return None
    cfg = harness.load_cell(ROOT, workload).config
    done = flops.attention_flops_per_token(cfg, ctx["seq_len"]) * ctx["tokens"]
    return 100.0 * done / (core_s * ctx["peak_flops"])
