"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 perfbench/calibrate.py --workload <name> --seeds 1000 1001 ... \
        [--control 3] [--faults 3] [--look]

In one process, at the cell's own size: the program's checked steps on each
seed, compared with the float32 reference (the lower readings); the control,
the reference computed with float8 matmuls in the program's place (the upper
readings); and the faults a training cell can have, planted in the reference
put in the program's place: half of each batch's rows left out, and, on a
cell of several chips, the exchange between them left out (every shard
updated from one chip's share of the rows). A state left unchanged reads 1
by construction and needs no run. With ``--look`` each seed is also read
against the reference that feeds its forward the master rounded to the
params' stored type, as the program stores them, rather than the float32
master itself (the control and the half batch on the first seeds too), to
show what that rounding accounts for. Each reading is one JSON line on
stdout.
The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3,
                    help="seeds (the first ones) on which to read the control")
    ap.add_argument("--faults", type=int, default=3,
                    help="seeds (the first ones) on which to read the faults")
    ap.add_argument("--look", action="store_true",
                    help="also read against a reference on bf16-rounded "
                         "weights")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    import numpy as np

    from perfbench import compare, harness

    cell = harness.load_cell(ROOT, args.workload)
    devices = harness.require_chips(cell.workload["chips"])
    harness.enable_cache(ROOT)
    ref_mod = harness.reference_module(cell)
    prog = harness.Program(cell, devices, ref_mod)
    runs = []
    for seed in args.seeds:
        key = harness.seed_key(seed)
        t0 = time.perf_counter()
        state, readings, extra = harness.checked_steps(prog, key)
        del state
        batches = [np.asarray(prog.feed(key, i)["tokens"])
                   for i in range(1, harness.CHECKED_STEPS + 1)]
        runs.append((seed, key, readings, batches))
        emit(kind="program", seed=seed, s=time.perf_counter() - t0,
             losses=readings["losses"], **extra)
    prog.executor.close()
    del prog
    gc.collect()

    rows = cell.traffic["global_batch"]
    for n, (seed, key, readings, batches) in enumerate(runs):
        for tag, rounded in [("", False)] + [("_bf16w", True)] * args.look:
            t0 = time.perf_counter()
            ref = harness.reference_run(cell, ref_mod, devices, key, batches,
                                        round_weights=rounded)
            emit(kind="lower" + tag, seed=seed, s=time.perf_counter() - t0,
                 ref_losses=ref["losses"], **compare.gaps(readings, ref))
            variants = []
            if n < args.control:
                variants.append(("control", "fp8", batches))
            if n < args.faults:
                variants.append(("half_batch", "f32",
                                 [b[: rows // 2] for b in batches]))
                chips = cell.workload["chips"]
                if chips > 1:
                    variants.append(("no_exchange", "f32",
                                     [b[: rows // chips] for b in batches]))
            for kind, mode, bs in variants:
                t0 = time.perf_counter()
                other = harness.reference_run(cell, ref_mod, devices, key, bs,
                                              mode=mode, round_weights=rounded)
                emit(kind=kind + tag, seed=seed, s=time.perf_counter() - t0,
                     **compare.gaps(other, ref))
    emit(kind="done", s=time.perf_counter() - T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
