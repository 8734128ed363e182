"""A sound run and a run without the exchange between chips, on four CPU
devices (XLA_FLAGS=--xla_force_host_platform_device_count=4); prints
``sound=<correct> no_exchange=<correct>``."""
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]

import jax  # noqa: E402

import tiny  # noqa: E402
from perfbench import harness  # noqa: E402

CELL = "tiny-moe-dp4"


def main():
    with tempfile.TemporaryDirectory() as tmp:
        root = tiny.make_root(Path(tmp) / "checkout", cells={
            CELL: (tiny.MOE, tiny.traffic(data_mesh=4, batch=16), 4)})
        check(root, jax.devices()[:4])


def check(root, devices):
    """A sound run, then the same cell with each step fed one chip's rows."""
    def once():
        return harness.run(root, CELL, 2**31 + 5, 0.5, False,
                           t_start=time.perf_counter(),
                           devices=devices)["correct"]

    sound = once()
    init = harness.Program.__init__

    def one_chip_share(self, *a, **kw):
        init(self, *a, **kw)
        step = self.step

        def no_exchange(state, batch):
            rows = batch["tokens"].shape[0] // len(devices)
            return step(state, {k: v[:rows] for k, v in batch.items()})

        self.step = no_exchange

    harness.Program.__init__ = one_chip_share
    print(f"sound={sound} no_exchange={once()}")


if __name__ == "__main__":
    main()
