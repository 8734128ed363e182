"""Model FLOPs of the steps in the traced window over the device time of
the matmul (convolution) operations, summed over the chips, times the peak
FLOP/s: the kernel layer's share of its compute roofline."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t.class_s("matmul") <= 0:
        return None
    done = ctx["model_flops_per_token"] * ctx["tokens"]
    return 100.0 * done / (t.class_s("matmul") * ctx["peak_flops"])
