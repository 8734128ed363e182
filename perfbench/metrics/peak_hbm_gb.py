"""HBM on the fullest chip, in GB: the allocator's peak of live arrays after
the window, plus the scratch of the compiled step the window drives."""


def read(ctx):
    return ctx["peak_bytes"] / 1e9
