"""Benchmark aggregator — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run              # all benches
  PYTHONPATH=src python -m benchmarks.run regulation   # one bench

Prints ``bench/name,value,derived`` CSV rows and writes JSON to
experiments/bench/.
"""
from __future__ import annotations

import sys
import time
import traceback

BENCHES = ("federated_round", "llm_round", "population", "regulation",
           "convergence", "selection", "reg_variants", "backends",
           "comm_cost", "llm_models", "theory")


def run_one(name: str) -> bool:
    mod_name = f"benchmarks.bench_{name}"
    print(f"## bench:{name}", flush=True)
    try:
        mod = __import__(mod_name, fromlist=["main"])
        mod.main()
        return True
    except Exception:
        traceback.print_exc()
        print(f"{name}/_status,FAIL,")
        return False


def main() -> None:
    todo = sys.argv[1:] or BENCHES
    t0 = time.time()
    failed = [n for n in todo if not run_one(n)]
    print(f"## total_wall_s={time.time()-t0:.0f} "
          f"ok={len(todo)-len(failed)}/{len(todo)}"
          + (f" FAILED={failed}" if failed else ""))
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
