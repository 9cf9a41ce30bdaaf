"""Shared benchmark harness: tasks, timing, CSV/JSON emission, and the
persistent compilation cache every bench process shares."""
from __future__ import annotations

import json
import os
import time
from pathlib import Path
from typing import Dict, List

import jax

from repro.data.tasks import build_task

ROOT = Path(__file__).resolve().parent.parent
OUT_DIR = ROOT / "experiments" / "bench"


def use_compile_cache() -> str:
    """Keep compiled programs across processes.  ``JAX_COMPILATION_CACHE_DIR``
    wins when set (jax reads it itself); otherwise the cache is the fixed
    ``.jax_cache/`` of this checkout — a fixed path, because the path is
    part of the cache key."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


use_compile_cache()

_TASK_CACHE: Dict = {}


def get_task(name: str = "genomic", *, n_clients: int = 5,
             train_size: int = 250, seed: int = 0, **kw):
    key = (name, n_clients, train_size, seed, tuple(sorted(kw.items())))
    if key not in _TASK_CACHE:
        _TASK_CACHE[key] = build_task(
            name, n_clients=n_clients, train_size=train_size,
            test_size=100, val_size=60, seed=seed, **kw)
    return _TASK_CACHE[key]


def emit(bench: str, rows: List[dict], *, t0: float = None):
    """Print CSV rows and persist JSON."""
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    (OUT_DIR / f"{bench}.json").write_text(json.dumps(rows, indent=1))
    for r in rows:
        derived = r.get("derived", "")
        val = r.get("value", "")
        print(f"{bench}/{r['name']},{val},{derived}")
    if t0 is not None:
        print(f"{bench}/_wall_s,{time.time()-t0:.1f},")


def round_summary(res) -> dict:
    return {
        "rounds": len(res.rounds),
        "final_server_loss": res.rounds[-1].server_loss,
        "final_test_acc": res.rounds[-1].server_test_acc,
        "server_loss_series": [r.server_loss for r in res.rounds],
        "test_acc_series": [r.server_test_acc for r in res.rounds],
        "maxiter_series": [r.maxiters for r in res.rounds],
        "cum_evals_final": res.rounds[-1].cum_evals,
        "terminated_early": res.terminated_early,
    }
