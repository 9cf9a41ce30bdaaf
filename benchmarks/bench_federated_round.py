"""Sequential vs batched federated round engine on the 5-client VQC task.

Times ``run_experiment`` end-to-end for both engines on the same task and
config (method="qfl" so the one-time LLM fine-tune does not dilute the
round timing) and emits per-round wall-times, the speedup, and the
convergence gap — the acceptance gate is batched ≥5× sequential at
matched convergence.

``--optimizer`` selects the update law both paths run: "spsa" or
"nelder-mead" (the paper's default, batched via speculative simplex
candidate evaluation).  ``--backend`` picks the quantum backend — the
noisy ones run keyed finite-shot sampling on the fast path, so the
speedup/parity gate covers Table I's shot-noise setting too.  ``--smoke``
shrinks the workload for CI; ``--engine X`` runs one engine only (for
profiling).

``--n-devices N`` forces N host devices (setting
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before jax
initializes; the bench fails if jax is already live with fewer devices,
e.g. under the run.py aggregator) and runs the batched engine on an
N-wide 'clients' mesh.  ``--sweep-clients
8,16,32,64`` adds the ROADMAP scaling sweep: for each client count C the
batched engine runs once on a single device and once on the mesh,
reporting round wall-time vs device count.

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
                    help="small CI workload (fewer rounds/iters/examples)")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--maxiter", type=int, default=None)
    ap.add_argument("--clients", type=int, default=5)
    ap.add_argument("--engine", choices=["sequential", "batched", "both"],
                    default="both")
    ap.add_argument("--optimizer", choices=["spsa", "nelder-mead"],
                    default="spsa")
    ap.add_argument("--backend", default="exact",
                    help="quantum backend; noisy ones (fake/aersim/real) "
                         "run keyed finite-shot sampling in both engines")
    ap.add_argument("--n-devices", type=int, default=0,
                    help="force N host devices and run the batched "
                         "engine on an N-wide 'clients' mesh (0 = off)")
    ap.add_argument("--sweep-clients", default="",
                    help="comma list of client counts (e.g. 8,16,32,64): "
                         "bench batched round time 1 device vs the mesh")
    ap.add_argument("--sweep-qubits", default="",
                    help="comma list of qubit counts (e.g. 4,6,8,10): "
                         "qubit-scaling sweep through the batched engine "
                         "(statevector cost doubles per qubit)")
    ap.add_argument("--train-size", type=int, default=0,
                    help="TOTAL training examples, split across clients "
                         "(0 = 120 smoke / 250 full); raise it with "
                         "--sweep-clients so per-client work doesn't "
                         "shrink as C grows")
    args = ap.parse_args(list(argv))

    if args.n_devices > 1 and "jax" not in sys.modules:
        force_host_devices(args.n_devices)

    import numpy as np

    from benchmarks.common import emit, get_task
    from repro.core.orchestrator import run_experiment
    from repro.quantum.backends import BACKENDS

    if args.backend not in BACKENDS:
        ap.error(f"--backend must be one of {sorted(BACKENDS)}")
    n_dev = require_visible(args.n_devices, "federated_round")

    def _run(engine, *, rounds, maxiter, clients=args.clients,
             devices=None, n_qubits=4):
        task = get_task("genomic", n_clients=clients,
                        train_size=args.train_size
                        or (120 if args.smoke else 250),
                        **({"n_features": n_qubits} if n_qubits != 4
                           else {}))
        t0 = time.perf_counter()
        res = run_experiment(
            task, method="qfl", optimizer=args.optimizer, engine=engine,
            n_rounds=rounds, maxiter0=maxiter, early_stop=False,
            backend=args.backend, n_qubits=n_qubits,
            n_devices=devices if engine == "batched" else None)
        return time.perf_counter() - t0, res

    rounds = args.rounds or (2 if args.smoke else 3)
    maxiter = args.maxiter or (5 if args.smoke else 25)

    t0 = time.time()
    rows = []
    results = {}
    for engine in (("sequential", "batched") if args.engine == "both"
                   else (args.engine,)):
        devices = n_dev if n_dev > 1 else None
        wall, res = _run(engine, rounds=rounds, maxiter=maxiter,
                         devices=devices)
        results[engine] = (wall, res)
        rows.append({
            "name": f"{engine}_round_s",
            "value": f"{wall / rounds:.3f}",
            "derived": (f"optimizer={args.optimizer} "
                        f"backend={args.backend} total={wall:.2f}s "
                        f"rounds={rounds} maxiter={maxiter} "
                        f"clients={args.clients} "
                        + (f"n_devices={devices} "
                           if engine == "batched" and devices else "")
                        + f"final_loss={res.rounds[-1].server_loss:.6f}")})

    if len(results) == 2:
        w_seq, r_seq = results["sequential"]
        w_bat, r_bat = results["batched"]
        gap = max(abs(a.server_loss - b.server_loss)
                  for a, b in zip(r_seq.rounds, r_bat.rounds))
        dtheta = float(np.abs(r_seq.theta_g - r_bat.theta_g).max())
        rows.append({
            "name": "speedup",
            "value": f"{w_seq / w_bat:.2f}",
            "derived": (f"loss_gap={gap:.2e} dtheta={dtheta:.2e} "
                        f"target>=5x")})
        # warm engine: the compiled round program is cached module-wide,
        # so a second run isolates steady-state round wall-time (the
        # sequential path has no warm state — it re-traces every round
        # by construction, which is precisely its bottleneck)
        w_warm, _ = _run("batched", rounds=rounds, maxiter=maxiter,
                         devices=n_dev if n_dev > 1 else None)
        rows.append({
            "name": "batched_warm_round_s",
            "value": f"{w_warm / rounds:.3f}",
            "derived": (f"speedup_vs_seq_round="
                        f"{w_seq / w_warm:.1f}x total={w_warm:.2f}s")})

    if args.sweep_clients:
        # ROADMAP scaling sweep: batched round wall-time vs device count
        # at growing client counts.  Cold+warm per point; the warm number
        # is the steady-state round time the mesh is judged on.
        sweep = [int(c) for c in args.sweep_clients.split(",") if c]
        for C in sweep:
            for devices in (None, n_dev) if n_dev > 1 else (None,):
                _run("batched", rounds=1, maxiter=maxiter, clients=C,
                     devices=devices)                        # compile
                wall, res = _run("batched", rounds=rounds,
                                 maxiter=maxiter, clients=C,
                                 devices=devices)            # warm
                d = devices or 1
                rows.append({
                    "name": f"sweep_c{C}_d{d}_round_s",
                    "value": f"{wall / rounds:.3f}",
                    "derived": (f"clients={C} n_devices={d} warm "
                                f"optimizer={args.optimizer} "
                                f"final_loss="
                                f"{res.rounds[-1].server_loss:.6f}")})

    if args.sweep_qubits:
        # ROADMAP scale-knobs sweep: statevector cost doubles per qubit,
        # so this is where the tape executor's kernel choices show up.
        # Batched engine only (the scaling target); cold+warm per point.
        qsweep = [int(q) for q in args.sweep_qubits.split(",") if q]
        devices = n_dev if n_dev > 1 else None
        for q in qsweep:
            _run("batched", rounds=1, maxiter=maxiter,
                 devices=devices, n_qubits=q)                 # compile
            wall, res = _run("batched", rounds=rounds, maxiter=maxiter,
                             devices=devices, n_qubits=q)     # warm
            rows.append({
                "name": f"sweep_q{q}_round_s",
                "value": f"{wall / rounds:.3f}",
                "derived": (f"n_qubits={q} warm "
                            f"n_devices={devices or 1} "
                            f"optimizer={args.optimizer} "
                            f"final_loss="
                            f"{res.rounds[-1].server_loss:.6f}")})
    emit("federated_round", rows, t0=t0)


if __name__ == "__main__":
    main(sys.argv[1:])
