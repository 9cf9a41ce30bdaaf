#!/usr/bin/env python3
"""Records the small TPU trace that ``test_trace.py`` reads.

    python3 benchmarks/chip/tests/record_trace.py <out_dir>

On one TPU chip it traces three calls of a jitted step (a matmul, an
elementwise fusion and a ``while`` loop) inside the spans a benchmark
run writes (``bench.window`` around ``bench.call``), with a host pause
of 50 ms in each call after the step has ended, so the trace holds idle
gaps of a known length inside the calls.  It copies the ``.xplane.pb``
to ``<out_dir>/tpu_trace.xplane.pb`` and prints what the reduction reads
from it.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT)]

PAUSE_S = 0.05
N_CALLS = 3


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import trace

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU")

    @jax.jit
    def step(a, b):
        y = jnp.tanh(a @ b)

        def body(i, x):
            return x * 0.5 + jnp.sin(x)
        return jax.lax.fori_loop(0, 8, body, y).sum()

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (2048, 2048), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1), (2048, 2048),
                          jnp.float32)
    step(a, b).block_until_ready()
    tmp = tempfile.mkdtemp(dir=out_dir)
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(N_CALLS):
            with jax.profiler.TraceAnnotation("bench.call"):
                step(a, b).block_until_ready()
                time.sleep(PAUSE_S)
    jax.profiler.stop_trace()
    src = trace.find(tmp)
    dst = Path(out_dir) / "tpu_trace.xplane.pb"
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    s = trace.summarize(*trace.read(str(dst)))
    print(f"{dst}: {dst.stat().st_size} bytes; window {s.window_s:.6f} s, "
          f"busy {s.busy_s}, classes {s.class_s}, top {s.top_ops[:4]}, "
          f"gaps {s.idle_gaps[:4]}, spans {s.spans}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
