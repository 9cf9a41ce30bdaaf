"""Population-scale fused round loop vs the per-round host loop.

The fused driver (``core/fused_rounds.py``) runs R federated rounds —
local phase, FedAvg, regulation, selection, termination, loss reporting
— as ONE jitted ``lax.scan``, over a client population ``--c-pop`` with
per-round keyed cohorts of ``--c-round`` clients.  This bench times the
warm fused program against ``run_host_reference`` — the status-quo
per-round host loop (jitted local phase, host aggregation/selection,
per-client report transfers) on identical population semantics — and
reports rounds/sec for both plus the speedup (the ISSUE/ROADMAP gate:
warm fused beats the host loop at C_pop ≥ 1024, C_round = 32 on the
8-way mesh).

``--sweep-participation 0.25,0.5,1.0`` adds the convergence-vs-
participation sweep: cohort sizes ``round(frac · c_round)`` at one seed
(comparable by the driver's subsampling-inertness guarantee — a client's
draws never depend on cohort composition), reporting the final server
loss and warm rounds/sec per fraction.  ``--smoke`` shrinks everything
for CI; ``--n-devices N`` forces N host devices and shards the
population over the 'clients' mesh.

Heavy imports live inside ``main`` so the device-count flag can be set
after argparse but before the first jax touch.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.hostdev import force_host_devices, require_visible


def main(argv=()):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small CI workload (tiny population, 3 rounds)")
    ap.add_argument("--c-pop", type=int, default=0,
                    help="client population size (0 = 48 smoke / 1024)")
    ap.add_argument("--c-round", type=int, default=0,
                    help="per-round cohort size (0 = 8 smoke / 32)")
    ap.add_argument("--rounds", type=int, default=0,
                    help="scheduled rounds R (0 = 3 smoke / 6)")
    ap.add_argument("--maxiter", type=int, default=0,
                    help="per-client iteration budget (0 = 3 smoke / 4)")
    ap.add_argument("--optimizer", choices=["spsa", "nelder-mead"],
                    default="spsa")
    ap.add_argument("--backend", default="exact")
    ap.add_argument("--dropout", type=float, default=0.0)
    ap.add_argument("--n-devices", type=int, default=0,
                    help="force N host devices; shard the population "
                         "over the 'clients' mesh (0 = off)")
    ap.add_argument("--sweep-participation", default="",
                    help="comma list of cohort fractions of c_round "
                         "(e.g. 0.25,0.5,1.0): final-loss-vs-"
                         "participation sweep at one seed")
    ap.add_argument("--train-size", type=int, default=0,
                    help="TOTAL training examples across the population "
                         "(0 = 4 per client)")
    args = ap.parse_args(list(argv))

    if args.n_devices > 1 and "jax" not in sys.modules:
        force_host_devices(args.n_devices)

    import jax
    import numpy as np

    from benchmarks.common import emit, get_task
    from repro.core.fused_rounds import FusedRoundDriver
    from repro.quantum import backends as backend_mod
    from repro.quantum import qnn

    if args.backend not in backend_mod.BACKENDS:
        ap.error(f"--backend must be one of "
                 f"{sorted(backend_mod.BACKENDS)}")
    n_dev = require_visible(args.n_devices, "population")

    c_pop = args.c_pop or (48 if args.smoke else 1024)
    c_round = args.c_round or (8 if args.smoke else 32)
    rounds = args.rounds or (3 if args.smoke else 6)
    maxiter = args.maxiter or (3 if args.smoke else 4)
    c_round = min(c_round, c_pop)
    if n_dev > 1:
        c_round = max(n_dev, (c_round // n_dev) * n_dev)
    train = args.train_size or 4 * c_pop

    task = get_task("genomic", n_clients=c_pop, train_size=train)
    spec = qnn.QNNSpec("vqc", n_qubits=4, n_classes=task.n_classes)
    backend = backend_mod.get(args.backend)
    theta0 = np.asarray(spec.init_params(jax.random.PRNGKey(0)),
                        np.float64)

    def make_driver(cr):
        return FusedRoundDriver(
            task, spec, backend, optimizer=args.optimizer, seed=0,
            use_llm=False, maxiter0=maxiter, n_rounds=rounds,
            early_stop=False, c_round=cr, dropout=args.dropout,
            n_devices=n_dev if n_dev > 1 else None)

    t0 = time.time()
    rows = []
    driver = make_driver(c_round)

    tc = time.perf_counter()
    out = driver.run(theta0)                       # compile + run
    cold = time.perf_counter() - tc
    tw = time.perf_counter()
    out = driver.run(theta0)                       # warm
    warm = time.perf_counter() - tw
    tag = (f"c_pop={c_pop} c_round={c_round} rounds={rounds} "
           f"maxiter={maxiter} optimizer={args.optimizer} "
           f"backend={args.backend} n_devices={n_dev or 1} "
           f"dropout={args.dropout}")
    rows.append({"name": "fused_rounds_per_s",
                 "value": f"{rounds / warm:.2f}",
                 "derived": (f"{tag} warm={warm:.3f}s cold={cold:.2f}s "
                             f"final_loss={out.server_loss[-1]:.6f}")})

    th = time.perf_counter()
    href = driver.run_host_reference(theta0)       # warms its round jit
    th = time.perf_counter()
    href = driver.run_host_reference(theta0)       # warm
    host = time.perf_counter() - th
    gap = float(np.abs(out.theta_g
                       - href.theta_g.astype(np.float32)).max())
    rows.append({"name": "host_rounds_per_s",
                 "value": f"{rounds / host:.2f}",
                 "derived": (f"per-round host loop warm={host:.3f}s "
                             f"final_loss={href.server_loss[-1]:.6f}")})
    rows.append({"name": "fused_speedup",
                 "value": f"{host / warm:.2f}",
                 "derived": (f"warm fused vs per-round host loop "
                             f"dtheta={gap:.2e} target>1x")})

    if args.sweep_participation:
        fracs = [float(f) for f in args.sweep_participation.split(",")
                 if f]
        for frac in fracs:
            cr = max(1, int(round(frac * c_round)))
            if n_dev > 1:
                cr = max(n_dev, (cr // n_dev) * n_dev)
            d = make_driver(cr)
            d.run(theta0)                          # compile
            ts = time.perf_counter()
            o = d.run(theta0)                      # warm
            w = time.perf_counter() - ts
            rows.append({
                "name": f"participation_{frac:g}",
                "value": f"{o.server_loss[-1]:.6f}",
                "derived": (f"c_round={cr}/{c_pop} final_server_loss "
                            f"rounds_per_s={rounds / w:.2f} "
                            f"test_acc={o.test_acc[-1]:.4f}")})

    emit("population", rows, t0=t0)


if __name__ == "__main__":
    main(sys.argv[1:])
