#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its result.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Everything a cell is sits in data found by name: its entry in
``BENCHMARK.json`` names a configuration file and a traffic mix;
``traffic/<mix>.json`` names a driver (``drivers/<driver>.py``) and its
parameters; each per-layer metric is read by ``metrics/<metric>.py``;
the limits of the correctness check are ``limits/<cell>.json``.

A run sets up (builds the inputs and weights from ``--seed``, builds the
program, drives its first call, which compiles), measures calls for
``--seconds``, reads the devices' peak memory, frees the program's state,
then checks the first call against the plain reference.  With
``--trace 1`` the window is recorded by the profiler and the per-layer
metrics are printed instead of the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its
limit); the numbers compared are also the last lines of standard error.
The run fails, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for, and where the program is not in the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import harness  # noqa: E402


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(root, args.workload)
    harness.require_program(root)
    harness.use_compile_cache(root)
    devices = harness.require_chips(cell.chips)
    peaks = harness.peaks_for(devices[0].device_kind)

    driver = harness.load_driver(cell, args.seed, devices)
    setup_s = time.perf_counter() - T_START

    trace_dir = root / ".bench_trace" / cell.name if args.trace else None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
    window = harness.run_window(driver, args.seconds, trace_dir,
                                cell.params.get("trace_seconds"))
    print(f"window: {window.calls} calls in {window.seconds:.3f} s, "
          f"{window.traced_calls} traced; compile events in the window: "
          f"{len(window.compiles)} {sorted(set(window.compiles))}",
          file=sys.stderr, flush=True)
    peak_bytes = harness.peak_bytes(devices)
    counts = driver.counts()
    driver.release()
    gc.collect()

    numbers = driver.verify()
    checks, correct = harness.judge(numbers, cell.limits)

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": window.calls,
              "failed": 0 if correct else 1}
    ctx = harness.Context(cell=cell, window=window, counts=counts,
                          n_chips=len(devices), peaks=peaks,
                          setup_s=setup_s, peak_bytes=peak_bytes)
    if args.trace:
        ctx.summary = summary = harness.summarize_trace(trace_dir)
        result["metrics"] = harness.per_layer(cell, ctx)
        device["busy_s"] = summary.mean_busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_ops],
            "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
    else:
        result["metrics"] = harness.end_to_end(cell, ctx)
    result["device"] = device
    result["checks"] = checks

    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
