"""What every cell's run shares: finding a cell's files by name, the
device and cache set-up, the measured window, the check against the
limits, and the metric readers.

A driver (``drivers/<name>.py``) defines ``Driver(config, params, seed,
devices)``, whose construction is the cell's set-up and ends after the
program's first call, and the methods ``call() -> work`` (one timed call,
ending in the device-to-host transfer of its results), ``counts()``,
``release()`` (frees the program's state) and ``verify() -> {name:
number}`` (the first call against the plain reference).

A metric reader (``metrics/<name>.py``) defines ``read(ctx)``, which
returns the metric's value or None where the run has nothing to read.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent


@dataclass
class Cell:
    name: str
    dir: Path                       # the benchmark's directory
    chips: int
    config: dict
    driver: str
    params: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


@dataclass
class Window:
    """The measured calls: their host-clock durations and work, the time
    from the window's start to the end of the last call, and the part of
    the window the profiler recorded (``traced_*``: its calls, and the
    time from the window's start to the end of its last call)."""
    durations: List[float] = field(default_factory=list)
    work: List[float] = field(default_factory=list)
    seconds: float = 0.0
    traced_calls: int = 0
    traced_seconds: float = 0.0
    compiles: List[str] = field(default_factory=list)

    @property
    def calls(self) -> int:
        return len(self.durations)


@dataclass
class Context:
    """What a metric reader may read."""
    cell: Cell
    window: Window
    counts: Dict[str, Any]
    n_chips: int
    peaks: Optional[dict] = None
    summary: Any = None             # trace.Summary in a traced run
    setup_s: float = 0.0
    peak_bytes: int = 0


def _load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    """A metric applies to the cells it lists, or to every cell."""
    return cell in metric.get("workloads", (cell,))


def load_cell(root: Path, name: str) -> Cell:
    """A cell of ``root/BENCHMARK.json`` with its files, which lie in
    ``root/benchmarks/chip``."""
    here = root / "benchmarks" / "chip"
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    traffic = _load_json(here / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name=name, dir=here, chips=int(w["chips"]),
                config=_load_json(root / entry["file"]),
                driver=traffic["driver"], params=traffic["params"],
                limits=_load_json(here / "limits" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def require_program(root: Path) -> None:
    if not (root / "src" / "repro").is_dir():
        raise SystemExit(f"the program under test is not in {root}/src")


def use_compile_cache(root: Path) -> Path:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, so that only a cell's first run there compiles."""
    import jax
    path = root / ".jax_cache" / "bench"
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(n: int):
    """The first ``n`` TPU devices; exits where JAX found no TPU or too
    few.  Never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise SystemExit(f"the cell needs {n} chips; JAX sees {len(devs)}")
    return devs[:n]


def peaks_for(kind: str) -> dict:
    table = _load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def load_driver(cell: Cell, seed: int, devices):
    mod = _load_module(cell.dir / "drivers" / f"{cell.driver}.py",
                       f"bench_driver_{cell.driver}")
    return mod.Driver(cell.config, cell.params, seed, devices)


def run_window(driver, seconds: float, trace_dir: Optional[Path] = None,
               trace_seconds: Optional[float] = None) -> Window:
    """Calls until the next would not end within ``seconds``, judged by
    the shortest call so far; the first call always runs.  With
    ``trace_dir`` the profiler records, after one call of its own, the
    calls of the first ``trace_seconds`` (all of the window by default)
    inside a ``bench.window`` span.  Compilations and compile-cache reads during
    the window are counted: there should be none."""
    import jax
    import jax.numpy as jnp
    w = Window()

    def on_event(event, *args, **kwargs):
        if event.startswith("/jax/core/compile") or "cache_hits" in event:
            w.compiles.append(f"{event} {kwargs.get('fun_name', '')}".strip())

    def one(t0) -> bool:
        ts = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.call"):
            work = driver.call()
        te = time.perf_counter()
        w.durations.append(te - ts)
        w.work.append(float(work))
        w.seconds = te - t0
        return w.seconds + min(w.durations) > seconds

    done = False
    if trace_dir is not None:
        # the reduction reads the benchmark's own annotations and each
        # op's instruction text, so Python calls and HLO protos are not
        # recorded
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            # the profiler's own start-up and a first call under it, both
            # outside the traced window (a program's first run under the
            # profiler can stall the host)
            jax.block_until_ready(jnp.zeros(()) + 1)
            tw = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.profiler_warmup"):
                driver.call()
            print(f"window: call under the profiler before the window "
                  f"{time.perf_counter() - tw:.3f} s", file=sys.stderr,
                  flush=True)
            limit = seconds if trace_seconds is None else trace_seconds
            with _counting(on_event), \
                    jax.profiler.TraceAnnotation("bench.window"):
                t0 = time.perf_counter()
                while not done and (w.calls == 0 or w.seconds < limit):
                    done = one(t0)
        finally:
            jax.profiler.stop_trace()
        w.traced_calls, w.traced_seconds = w.calls, w.seconds
    else:
        t0 = time.perf_counter()
    with _counting(on_event):
        while not done:
            done = one(t0)
    return w


@contextlib.contextmanager
def _counting(on_event):
    import jax
    jax.monitoring.register_event_duration_secs_listener(on_event)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
        jax.monitoring.unregister_event_listener(on_event)


def peak_bytes(devices) -> int:
    """The largest ``peak_bytes_in_use`` over the cell's devices."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def judge(numbers: Dict[str, float], limits: Dict[str, float]):
    """Each compared number beside its limit (``limits`` names the numbers
    compared); correct when every one is there, finite and at most its
    limit."""
    checks = {name: {"value": numbers.get(name), "limit": lim}
              for name, lim in sorted(limits.items())}
    ok = bool(checks) and all(
        c["value"] is not None and math.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in checks.values())
    return checks, ok


def summarize_trace(trace_dir: Path):
    from benchmarks.chip import trace
    return trace.summarize(*trace.read(trace.find(str(trace_dir))))


def _read_metrics(metrics: List[dict], ctx: Context) -> dict:
    out = {}
    for m in metrics:
        mod = _load_module(ctx.cell.dir / "metrics" / f"{m['name']}.py",
                           f"bench_metric_{m['name']}")
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def end_to_end(cell: Cell, ctx: Context) -> dict:
    return _read_metrics(cell.end_to_end, ctx)


def per_layer(cell: Cell, ctx: Context) -> dict:
    return _read_metrics(cell.per_layer, ctx)


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    closest ranks, over all values."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)
