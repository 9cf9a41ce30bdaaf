"""Driver of the fused federated rounds: ``FusedRoundDriver.run``.

Set-up runs the LLM stage once through the LLM-stage driver
(``drivers/llm_stage.py``: the federation, the frozen base and the
initial adapters made from the seed, one ``BatchedLLMEngine`` call of
``llm_steps`` steps): its soft labels and losses are what the rounds
distil from and regulate by.  It then builds
one ``FusedRoundDriver`` (VQC clients, Nelder-Mead, exact backend,
adaptive regulation, every client selected, no early stop, so every
round works) and drives its first call, which compiles.  Each timed
call runs ``rounds_per_call`` rounds from the same initial parameters,
made from the seed, and transfers their outputs to the host.  The work
of a call is its rounds.

The check replays set-up's LLM stage with its plain reference
(``ref_llm.stage``) and then the first call with ``ref_rounds.rounds``,
on the reference's own soft labels and LLM losses: nothing the program
made goes into the reference.
"""
from __future__ import annotations

import sys
import time
from types import SimpleNamespace

import jax
import numpy as np

from benchmarks.chip import compare, gen, ref_rounds
from benchmarks.chip.drivers import llm_stage


class Driver:
    def __init__(self, config: dict, params: dict, seed: int, devices):
        from repro.core.fused_rounds import FusedRoundDriver
        from repro.quantum import backends, qnn

        t0 = time.perf_counter()
        p = self.p = dict(params)
        self.stage = llm_stage.Driver(
            config, dict(p, steps_per_call=p["llm_steps"]), seed, devices)
        self.stage.release()          # keeps the base for the reference
        self.fed = self.stage.fed
        self.teacher = _per_client(self.fed, self.stage.first["teacher"])
        self.llm_losses = self.stage.first["eval_loss"]
        self._stage_refs = {}
        _log(f"LLM stage {time.perf_counter() - t0:.3f} s")

        self.spec = qnn.QNNSpec("vqc", n_qubits=int(p["n_qubits"]),
                                n_classes=self.fed.n_classes,
                                fm_reps=int(p["fm_reps"]),
                                ansatz_reps=int(p["ansatz_reps"]))
        rng = np.random.default_rng(gen.sub_seed(seed, 5))
        self.theta0 = rng.uniform(-np.pi, np.pi,
                                  self.spec.n_params).astype(np.float32)
        self.rounds = FusedRoundDriver(
            self.fed, self.spec, backends.get("exact"),
            optimizer="nelder-mead", seed=gen.sub_seed(seed, 6),
            lam=float(p["lam"]), mu=float(p["mu"]), use_llm=True,
            teacher_probs=self.teacher, llm_losses=self.llm_losses,
            maxiter0=int(p["maxiter0"]), maxiter_cap=int(p["maxiter_cap"]),
            regulation="adaptive", select_frac=1.0,
            epsilon=float(p["epsilon"]), n_rounds=int(p["rounds_per_call"]),
            early_stop=False)
        t1 = time.perf_counter()
        self.first = self.rounds.run(self.theta0)
        _log(f"first call (compiles or loads) {time.perf_counter() - t1:.3f}"
             f" s; budgets {self.first.budgets.tolist()} server loss "
             f"{self.first.server_loss.tolist()}")
        self.calls = 0

    def call(self) -> float:
        with jax.profiler.TraceAnnotation("bench.fused_rounds.run"):
            self.rounds.run(self.theta0)
        self.calls += 1
        return int(self.p["rounds_per_call"])

    def counts(self) -> dict:
        C = self.fed.n_clients
        f = self.first
        return {"clients": C, "examples": [cl.n for cl in self.fed.clients],
                "n_qubits": self.spec.n_qubits, "n_params": self.spec.n_params,
                "gates": ref_rounds.gate_counts(self.spec.n_qubits,
                                                self.spec.fm_reps,
                                                self.spec.ansatz_reps),
                "n_evals": f.n_evals[:, :C].tolist(),
                "budgets": f.budgets[:, :C].tolist(),
                "max_iter": int(self.rounds.max_iter), "calls": self.calls}

    def release(self) -> None:
        self.rounds = None

    def reference(self, precision=jax.lax.Precision.HIGHEST,
                  fault: str = "") -> dict:
        """The plain reference's LLM stage, then its first call
        (per-round arrays); ``fault`` is planted in the rounds."""
        if precision not in self._stage_refs:
            self._stage_refs[precision] = self.stage.reference(
                precision=precision)
        stage = self._stage_refs[precision]
        t0 = time.perf_counter()
        p = self.p
        ref = ref_rounds.rounds(
            self.fed, _per_client(self.fed, stage["teacher"]),
            stage["eval_loss"], self.theta0,
            n_rounds=int(p["rounds_per_call"]), maxiter0=int(p["maxiter0"]),
            maxiter_cap=int(p["maxiter_cap"]), lam=float(p["lam"]),
            mu=float(p["mu"]), fm_reps=int(p["fm_reps"]),
            ansatz_reps=int(p["ansatz_reps"]), precision=precision,
            fault=fault)
        _log(f"reference rounds ({precision}, fault {fault or 'none'}) "
             f"{time.perf_counter() - t0:.3f} s")
        return ref

    def numbers(self, prog, ref: dict) -> dict:
        """``prog``: the program's output, or a reference's dict."""
        if isinstance(prog, dict):
            prog = SimpleNamespace(**prog)
        return compare.rounds(prog, ref, self.theta0, self.fed.n_clients)

    def verify(self) -> dict:
        return self.numbers(self.first, self.reference())


def _log(msg: str) -> None:
    print(f"fused_rounds: {msg}", file=sys.stderr, flush=True)


def _per_client(fed, teacher) -> list:
    """A (C, Nmax, classes) stack of soft labels as one array a client."""
    return [np.asarray(teacher[c, :cl.n]) for c, cl in enumerate(fed.clients)]
