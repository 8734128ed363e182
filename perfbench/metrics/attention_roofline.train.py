"""Causal attention FLOPs of the steps in the traced window (QK^T and PV,
forward and backward, as the cell's reference counts them) over the device
time of the ops in the program's ``attn/core`` region, summed over the
chips, times the peak FLOP/s: the attention kernel's share of its compute
roofline.

The region of an op comes from the named scopes in the compiled step's text
that the harness hands over (``perfbench/regions.py``); a trace without that
text raises, so the metric cannot vanish unseen. A program without the
``attn/core`` scope has no time there: no value."""
from perfbench import regions


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    if ctx["step_text"] is None:
        raise RuntimeError("attention_roofline.train: a trace without the "
                           "compiled step's text")
    rmap = regions.region_map(ctx["step_text"])
    core_s = regions.summary_region_s(t, rmap, "attn/core")
    if core_s <= 0:
        return None
    done = ctx["reference"].attention_flops_per_token(
        ctx["config"], ctx["seq_len"]) * ctx["tokens"]
    return 100.0 * done / (core_s * ctx["peak_flops"])
