"""Process start to window start: imports, device init, weights from the
seed, compile or cache load, the checked steps (the first warms up)."""


def read(ctx):
    return ctx["setup_s"]
