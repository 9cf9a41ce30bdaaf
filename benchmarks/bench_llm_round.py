"""Sequential vs batched LLM fine-tuning stage (Alg. 1 Step 1).

Times the whole stage — per-client LoRA fine-tuning, the FedAvg
distillation blend, and the eval_loss/f1/teacher-probs label-head evals —
for the sequential host loop (``llm_client.run_sequential_stage``, C
clients × llm_steps host dispatches) and the fused device program
(``batched_llm.BatchedLLMEngine``, one jitted scan over vmapped train
steps).  Both draw under the ``llm_key(seed, client, step)`` contract,
so the parity row (max |Δ eval loss| / |Δ teacher|) is a correctness
gate, not just a smell test.

``--sweep-clients 8,16,32`` scales the client count (batched cold+warm
per point, 1 device vs the mesh when ``--n-devices`` > 1); ``--n-devices
N`` forces N host devices before jax initializes and shards the client
axis of the engine across the 'clients' mesh.  ``--smoke`` shrinks the
workload for CI.

Heavy imports live inside ``main`` so the device-count flag can be set
after argparse but before the first jax touch.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.hostdev import force_host_devices, require_visible


def main(argv=()):
    # default () — not None — so the run.py aggregator's ``main()`` call
    # never re-parses the aggregator's own sys.argv
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small CI workload (fewer steps/examples)")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--steps", type=int, default=None,
                    help="fine-tune steps per client (llm_steps)")
    ap.add_argument("--train-size", type=int, default=0,
                    help="TOTAL training examples across clients "
                         "(0 = 25/client smoke, 40/client full)")
    ap.add_argument("--n-devices", type=int, default=0,
                    help="force N host devices and shard the batched "
                         "stage over an N-wide 'clients' mesh (0 = off)")
    ap.add_argument("--sweep-clients", default="",
                    help="comma list of client counts (e.g. 8,16,32): "
                         "batched stage wall-time, 1 device vs the mesh")
    args = ap.parse_args(list(argv))

    if args.n_devices > 1 and "jax" not in sys.modules:
        force_host_devices(args.n_devices)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import emit, get_task
    from repro.core.batched_llm import BatchedLLMEngine
    from repro.core.llm_client import run_sequential_stage, task_llm_config
    from repro.models import model as M

    n_dev = require_visible(args.n_devices, "llm_round")

    steps = args.steps or (8 if args.smoke else 30)
    per_client = args.train_size // args.clients if args.train_size \
        else (25 if args.smoke else 40)
    seed = 0

    def make(clients):
        task = get_task("genomic", n_clients=clients,
                        train_size=per_client * clients, seed=seed)
        cfg = task_llm_config("tiny-llm", task.vocab_size,
                              task.llm_seq_len)
        base = M.init_params(cfg, jax.random.PRNGKey(seed),
                             dtype=jnp.float32)
        return task, cfg, base

    def run_batched(task, cfg, base, devices=None):
        t0 = time.perf_counter()
        eng = BatchedLLMEngine(task, cfg, base, seed=seed, steps=steps,
                               n_devices=devices)
        out = eng.run()
        return time.perf_counter() - t0, out

    t0 = time.time()
    rows = []
    task, cfg, base = make(args.clients)

    t_seq0 = time.perf_counter()
    _, seq_losses, seq_f1, seq_teachers = run_sequential_stage(
        task, cfg, base, seed=seed, steps=steps)
    t_seq = time.perf_counter() - t_seq0
    rows.append({"name": "sequential_stage_s", "value": f"{t_seq:.3f}",
                 "derived": (f"clients={args.clients} steps={steps} "
                             f"per_client={per_client}")})

    devices = n_dev if n_dev > 1 else None
    t_cold, out = run_batched(task, cfg, base, devices=devices)
    t_warm, out = run_batched(task, cfg, base, devices=devices)
    dloss = max(abs(a - b) for a, b in zip(seq_losses, out.losses))
    df1 = max(abs(a - b) for a, b in zip(seq_f1, out.f1))
    dteach = max(float(np.abs(np.asarray(ts, np.float32)
                              - out.teacher[i, :len(ts)]).max())
                 for i, ts in enumerate(seq_teachers))
    rows.append({"name": "batched_stage_cold_s", "value": f"{t_cold:.3f}",
                 "derived": (f"n_devices={devices or 1} "
                             f"speedup_vs_seq={t_seq / t_cold:.2f}x")})
    rows.append({"name": "batched_stage_warm_s", "value": f"{t_warm:.3f}",
                 "derived": (f"n_devices={devices or 1} "
                             f"speedup_vs_seq={t_seq / t_warm:.2f}x")})
    rows.append({"name": "parity_gap", "value": f"{dloss:.2e}",
                 "derived": (f"max|dL_LLM|={dloss:.2e} max|df1|={df1:.2e} "
                             f"max|dteacher|={dteach:.2e} "
                             f"gate:|dL|<=5e-3,|df1|<=0.1 "
                             f"(identical draws; fp32 arithmetic-order "
                             f"drift compounds over steps)")})
    if dloss > 5e-3 or df1 > 0.1:
        # the correctness gate: broken draw parity shows up as O(0.1)
        # gaps, far above fp32 drift — fail the CI step, don't just log
        emit("llm_round", rows, t0=t0)
        raise SystemExit(
            f"llm_round parity gate failed: dloss={dloss:.2e} "
            f"df1={df1:.2e}")

    if args.sweep_clients:
        sweep = [int(c) for c in args.sweep_clients.split(",") if c]
        for C in sweep:
            task, cfg, base = make(C)
            for devs in (None, n_dev) if n_dev > 1 else (None,):
                run_batched(task, cfg, base, devices=devs)     # compile
                wall, _ = run_batched(task, cfg, base, devices=devs)
                d = devs or 1
                rows.append({
                    "name": f"sweep_c{C}_d{d}_stage_s",
                    "value": f"{wall:.3f}",
                    "derived": (f"clients={C} n_devices={d} warm "
                                f"steps={steps} "
                                f"per_client={per_client}")})
    emit("llm_round", rows, t0=t0)


if __name__ == "__main__":
    main(sys.argv[1:])
