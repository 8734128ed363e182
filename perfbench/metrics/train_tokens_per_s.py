"""Tokens of all steps completed in the window, on all chips, over the
window's seconds (host clock; the window closes when the last loss is read)."""


def read(ctx):
    return ctx["tokens"] / ctx["window_s"]
