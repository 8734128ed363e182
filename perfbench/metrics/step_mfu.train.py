"""Model FLOPs of the steps in the traced window (forward and backward,
causal attention, no recompute; as the cell's reference counts them) over
the window times the chips times the peak FLOP/s."""


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    done = ctx["model_flops_per_token"] * ctx["tokens"]
    return 100.0 * done / (t.window_s * ctx["chips"] * ctx["peak_flops"])
