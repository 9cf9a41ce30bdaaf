#!/usr/bin/env python3
"""Records the small TPU trace that ``test_layers.py`` reads.

    python3 benchmarks/chip/tests/record_scoped_trace.py <out_dir>

On one TPU chip it traces three calls of a jitted step whose layers are
named as the program names its own (``jax.named_scope``): a matmul under
``model.head``, a host callback that sleeps ``CALLBACK_S`` under
``qfl.local`` (the device idles inside the execution), then a ``while``
loop under ``tape.replay``.  Each call runs inside the spans a benchmark
run and the program write (``bench.window`` around ``bench.call``
around a ``qfl.rounds`` step with ``qfl.rounds.dispatch`` and
``qfl.rounds.fetch``), and sleeps ``PAUSE_S`` in a ``qfl.rounds.unpack``
span after the step has ended, so the trace holds idle gaps of known
causes.  It writes ``<out_dir>/tpu_scoped_trace.xplane.pb`` and the
step's compiled text, ``<out_dir>/tpu_scoped_trace.hlo.txt``, and prints
what ``layers.py`` reads from them.
"""
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT)]

PAUSE_S = 0.05
CALLBACK_S = 0.02
N_CALLS = 3
NAME = "tpu_scoped_trace"


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import io_callback

    from benchmarks.chip import layers, trace

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU")

    def pause(x):
        time.sleep(CALLBACK_S)
        return x

    @jax.jit
    def step(a, b):
        with jax.named_scope("model.head"):
            y = jnp.tanh(a @ b)
        with jax.named_scope("qfl.local"):
            y = io_callback(pause, jax.ShapeDtypeStruct((), y.dtype),
                            y[0, 0], ordered=True) + y

        def body(i, x):
            return x * 0.5 + jnp.sin(x)
        with jax.named_scope("tape.replay"):
            return jax.lax.fori_loop(0, 8, body, y).sum()

    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (2048, 2048), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(key, 1), (2048, 2048),
                          jnp.float32)
    step(a, b).block_until_ready()
    text = step.lower(a, b).compile().as_text()
    tmp = tempfile.mkdtemp(dir=out_dir)
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(N_CALLS):
            with jax.profiler.TraceAnnotation("bench.call"), \
                    jax.profiler.StepTraceAnnotation("qfl.rounds",
                                                     step_num=i + 1):
                with jax.profiler.TraceAnnotation("qfl.rounds.dispatch"):
                    out = step(a, b)
                with jax.profiler.TraceAnnotation("qfl.rounds.fetch",
                                                  bytes=4):
                    out.block_until_ready()
                with jax.profiler.TraceAnnotation("qfl.rounds.unpack"):
                    time.sleep(PAUSE_S)
    jax.profiler.stop_trace()
    src = trace.find(tmp)
    dst = Path(out_dir) / f"{NAME}.xplane.pb"
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    (Path(out_dir) / f"{NAME}.hlo.txt").write_text(text)
    lay = layers.reduce_trace(str(dst), lambda mods, n, want: {
        m: text for m in mods if m == "jit_step"})
    print(f"{dst}: {dst.stat().st_size} bytes; {len(text)} bytes of text; "
          f"{json.dumps(lay.summary() if lay else None)}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
