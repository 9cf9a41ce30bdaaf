#!/usr/bin/env python3
"""Readings that set a cell's limits: run on the chip, not by the
benchmark's own runs.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,...  [--control-seeds 1,2,3] [--faults half,answer]

For every seed it does a cell's set-up (the program's first call at the
cell's own size) and reads the numbers ``correct`` compares between the
program and the plain reference: the lower readings.  On the control
seeds it also reads them between the reference computed at
``Precision.HIGH`` (three bf16 passes, the step below the configuration's
float32 at HIGHEST) put in the program's place and the reference, and
between the reference with each planted fault and the reference: the
upper readings.  Each reading is also judged against the cell's limits
(``correct``: the control and the faults have to come out false).  One
JSON line per reading goes to standard output and
is appended to ``<out>/<cell>.jsonl`` (``--out``, by default
``.bench_calibrate`` in the checkout).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import harness  # noqa: E402


def _ints(text: str):
    return [int(x) for x in text.split(",") if x]


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default=".bench_calibrate")
    args = ap.parse_args(argv)

    import jax
    cell = harness.load_cell(root, args.workload)
    harness.require_program(root)
    harness.use_compile_cache(root)
    devices = harness.require_chips(cell.chips)
    out_dir = root / args.out
    out_dir.mkdir(parents=True, exist_ok=True)
    faults = [f for f in args.faults.split(",") if f]

    with open(out_dir / f"{cell.name}.jsonl", "a") as log:
        def emit(seed, kind, numbers, **extra):
            _, correct = harness.judge(numbers, cell.limits)
            line = json.dumps({"cell": cell.name, "seed": seed, "kind": kind,
                               "correct": correct, "numbers": numbers,
                               **extra})
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()

        for seed in args.seeds:
            t0 = time.perf_counter()
            driver = harness.load_driver(cell, seed, devices)
            setup = time.perf_counter() - t0
            driver.release()
            gc.collect()
            ref = driver.reference()
            emit(seed, "program", driver.numbers(driver.first, ref),
                 setup_s=setup, device=devices[0].device_kind)
            if seed in args.control_seeds:
                low = driver.reference(precision=jax.lax.Precision.HIGH)
                emit(seed, "control_high", driver.numbers(low, ref))
                for fault in faults:
                    bad = driver.reference(fault=fault)
                    emit(seed, f"fault_{fault}", driver.numbers(bad, ref))
            del driver, ref
            gc.collect()
    print(f"calibrate: {time.perf_counter() - T_START:.1f} s",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
