#!/usr/bin/env python3
"""Bring-up smoke of the federated LLM-QFL path on a TPU.

    python chip_smoke.py             # one chip: phases A and B
    python chip_smoke.py --chips 4   # four chips: the mesh phase only

The path is the README quickstart's: ``Orchestrator`` over
``build_task("genomic", n_clients=5, ...)`` with ``RunConfig(method=
"llm-qfl", engine="batched", rounds="fused", optimizer="nelder-mead",
llm_name="llama3.2-1b", n_rounds=3, llm_lr=3e-4)``.  That is the LoRA
stage at Llama-3.2-1B's layer count and widths (a random frozen base
made from the run seed; the one cut is the vocabulary, the task
tokenizer's), then three fused quantum rounds.  The default
``llm_lr=3e-3`` suits ``tiny-llm``; at this width it does not learn
(eval loss stays near ln 2) and the two engines' last-bit differences
grow over the 30 steps past any tolerance, where 3e-4 learns the task.

Phase A   the main run, cold and then warm.  Prints the config, the
          wall time of each stage (each ends in a device→host transfer
          of its results, so it includes the device work), the device's
          ``peak_bytes_in_use`` and the losses per round.
Phase B   the same run on the sequential reference engine, on the
          chip, held to the tolerances of the repo's parity tests.
--chips 4 phase A with ``n_devices=4`` against the same run on one
          device, held to the sharded-parity tolerances; prints which
          devices hold the client stacks and the replicated base.  No
          other phase runs.

Everything runs in this one process.  A platform other than ``tpu``, a
non-finite loss, a failed check or a phase that raises exits non-zero
without the last line.  On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
The persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when
set, else ``.jax_cache/`` in this checkout.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import use_compile_cache  # noqa: E402
from repro.core.llm_client import task_llm_config  # noqa: E402
from repro.core.orchestrator import Orchestrator, RunConfig  # noqa: E402
from repro.data.tasks import build_task  # noqa: E402

LLM = "llama3.2-1b"
LLM_LR = 3e-4
PUBLISHED_VOCAB = 128256
TASK = dict(n_clients=5, train_size=250, test_size=100, val_size=60,
            seed=0)


def say(msg: str) -> None:
    print(msg, flush=True)


def accelerator(n_chips: int):
    """The visible TPU devices; exits when JAX found none — this smoke
    never falls back to the CPU."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); refusing to run on it")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: --chips {n_chips} but JAX sees "
                 f"{len(devs)} device(s)")
    return devs


class Checks:
    """Named pass/fail checks; every one is printed with its gap."""

    def __init__(self):
        self.failed = []

    def _record(self, name, ok, detail):
        say(f"  check {name}: {detail} -> {'ok' if ok else 'FAIL'}")
        if not ok:
            self.failed.append(name)

    def close(self, name, got, want, atol):
        got = np.asarray(got, np.float64)
        want = np.asarray(want, np.float64)
        same_shape = got.shape == want.shape
        gap = float(np.max(np.abs(got - want))) if same_shape and got.size \
            else float("inf")
        self._record(name, same_shape and gap <= atol,
                     f"max|gap| {gap:.3e} (tol {atol:g})")

    def equal(self, name, got, want):
        self._record(name, got == want, "equal" if got == want
                     else f"{got} != {want}")

    def true(self, name, ok, detail):
        self._record(name, bool(ok), detail)


def finite_losses(res) -> bool:
    vals = list(res.llm_losses) + list(res.llm_f1)
    for r in res.rounds:
        vals += [r.server_loss] + list(r.client_losses)
    return bool(vals) and bool(np.all(np.isfinite(vals)))


def timed_run(task, rc: RunConfig, tag: str):
    """One orchestrator run; prints each stage's wall time."""
    orch = Orchestrator(task, rc)
    t0 = time.perf_counter()
    res = orch.run()
    wall = time.perf_counter() - t0
    t_llm = res.llm_finetune_time_s
    say(f"{tag}: LLM stage {t_llm:.3f} s, quantum rounds "
        f"{wall - t_llm:.3f} s, total {wall:.3f} s (chip wall time)")
    return orch, res


def run_cold_warm(task, rc: RunConfig, tag: str):
    """Cold then warm run of one config (the warm one reuses every
    compiled program).  Returns the warm orchestrator and both results;
    the cold run's device state is freed before the warm run builds
    its own base."""
    orch, cold = timed_run(task, rc, f"[{tag}] cold")
    del orch
    gc.collect()
    orch, warm = timed_run(task, rc, f"[{tag}] warm")
    return orch, cold, warm


def report(res, tag: str) -> None:
    say(f"[{tag}] LLM eval loss per client "
        f"{np.round(res.llm_losses, 6).tolist()}, macro-F1 "
        f"{np.round(res.llm_f1, 4).tolist()}")
    for r in res.rounds:
        say(f"[{tag}] round {r.t}: server loss {r.server_loss:.6f} "
            f"val acc {r.server_val_acc:.4f} test acc "
            f"{r.server_test_acc:.4f} client losses "
            f"{np.round(r.client_losses, 6).tolist()} budgets "
            f"{r.maxiters} selected {r.selected} cum evals {r.cum_evals}")


def peak_memory(devs, tag: str) -> None:
    for d in devs:
        stats = d.memory_stats() or {}
        say(f"[{tag}] device {d.id} peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use', 'not reported')} of "
            f"bytes_limit {stats.get('bytes_limit', 'not reported')}")


def compare_runs(checks: Checks, got, want, *, llm_atol, f1_atol,
                 loss_atol, final_atol=None):
    """The parity tests' comparison of two runs of one config."""
    checks.close("llm_losses", got.llm_losses, want.llm_losses, llm_atol)
    checks.close("llm_f1", got.llm_f1, want.llm_f1, f1_atol)
    checks.equal("rounds run", len(got.rounds), len(want.rounds))
    checks.equal("terminated_early", got.terminated_early,
                 want.terminated_early)
    for field in ("maxiters", "cum_evals", "selected"):
        checks.equal(field, got.series(field), want.series(field))
    if len(got.rounds) == len(want.rounds):
        checks.close("server_loss per round", got.series("server_loss"),
                     want.series("server_loss"), loss_atol)
        if final_atol is not None:
            checks.close("final server_loss", got.rounds[-1].server_loss,
                         want.rounds[-1].server_loss, final_atol)


def describe_config(task, rc: RunConfig) -> None:
    cfg = task_llm_config(rc.llm_name, task.vocab_size, task.llm_seq_len)
    say(f"config: {cfg.name} layers {cfg.n_layers} d_model {cfg.d_model} "
        f"d_ff {cfg.d_ff} heads {cfg.n_heads} (kv {cfg.n_kv_heads}, "
        f"head_dim {cfg.head_dim}) LoRA rank {cfg.lora.rank} alpha "
        f"{cfg.lora.alpha}; f32 base, matmuls at HIGHEST precision")
    say(f"cut from the published config: vocab_size {cfg.vocab_size} "
        f"(the task tokenizer's), not {PUBLISHED_VOCAB}")
    say(f"run: {task.n_clients} clients x "
        f"{[cl.n for cl in task.clients]} examples, seq len "
        f"{task.llm_seq_len}, llm_steps {rc.llm_steps}, llm_lr "
        f"{rc.llm_lr:g}, batch 16, "
        f"{rc.n_rounds} fused rounds, {rc.optimizer}, {rc.backend} "
        f"backend")


def main_config() -> RunConfig:
    return RunConfig(method="llm-qfl", engine="batched", rounds="fused",
                     optimizer="nelder-mead", llm_name=LLM, n_rounds=3,
                     llm_lr=LLM_LR)


def phase_a_b(task, devs, checks: Checks) -> None:
    rc = main_config()
    describe_config(task, rc)
    say("== phase A: main run ==")
    orch, cold, warm = run_cold_warm(task, rc, "A")
    del orch
    gc.collect()
    report(cold, "A")
    peak_memory(devs[:1], "A")
    checks.true("phase A losses finite", finite_losses(cold)
                and finite_losses(warm), "llm, server and client losses")

    say("== phase B: parity against the sequential engine ==")
    seq_rc = dataclasses.replace(rc, engine="sequential", rounds="host")
    orch, seq = timed_run(task, seq_rc, "[B] sequential")
    del orch
    gc.collect()
    report(seq, "B")
    peak_memory(devs[:1], "B")
    checks.true("phase B losses finite", finite_losses(seq),
                "llm, server and client losses")
    # tolerances of tests/test_batched_llm.py (LLM stage) and
    # tests/test_batched_engine.py (llm-qfl Nelder-Mead rounds)
    compare_runs(checks, cold, seq, llm_atol=5e-4, f1_atol=0.05,
                 loss_atol=1e-4, final_atol=1e-5)


def placement(orch, n: int, checks: Checks) -> None:
    """Where the LLM stage's client stacks and frozen base live."""
    eng = orch._llm_engine
    stack = jax.tree.leaves(eng.adapters)[0]
    rows = [(s.device.id, s.index[0].start or 0,
             s.index[0].stop or stack.shape[0])
            for s in stack.addressable_shards]
    say(f"client stacks (LoRA adapter leaf {stack.shape}): "
        + ", ".join(f"rows {a}:{b} on device {d}" for d, a, b in rows))
    base = jax.tree.leaves(eng._base)[0]
    base_devs = sorted(s.device.id for s in base.addressable_shards)
    say(f"frozen base (leaf {base.shape}): full copies on devices "
        f"{base_devs}")
    checks.true("client stacks spread", len({d for d, _, _ in rows}) == n
                and all(b - a == stack.shape[0] // n for _, a, b in rows),
                f"{len({d for d, _, _ in rows})} devices hold rows")
    checks.true("base replicated", len(base_devs) == n and all(
        s.data.shape == base.shape for s in base.addressable_shards),
        f"{len(base_devs)} full copies")


def phase_mesh(task, devs, n: int, checks: Checks) -> None:
    rc = main_config()
    describe_config(task, rc)
    say("== one device (the reference) ==")
    orch, one = timed_run(task, rc, "[1 device]")
    del orch
    gc.collect()
    report(one, "1 device")
    say(f"== 'clients' mesh over {n} devices ==")
    orch, cold, warm = run_cold_warm(
        task, dataclasses.replace(rc, n_devices=n), f"{n} devices")
    placement(orch, n, checks)
    del orch
    gc.collect()
    report(cold, f"{n} devices")
    peak_memory(devs[:n], f"{n} devices")
    checks.true("losses finite", finite_losses(one)
                and finite_losses(cold) and finite_losses(warm),
                "llm, server and client losses")
    # tolerances of tests/test_batched_llm.py::test_sharded_llm_qfl_run_parity
    compare_runs(checks, cold, one, llm_atol=1e-4, f1_atol=0.05,
                 loss_atol=1e-4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 'clients'-mesh phase on four "
                         "chips against one device")
    args = ap.parse_args(argv)

    devs = accelerator(args.chips)
    say(f"device: {devs[0].platform} {devs[0].device_kind} x {len(devs)}; "
        f"compile cache {use_compile_cache()}")
    task = build_task("genomic", **TASK)
    checks = Checks()
    if args.chips == 1:
        phase_a_b(task, devs, checks)
    else:
        phase_mesh(task, devs, args.chips, checks)
    if checks.failed:
        say(f"FAILED: {checks.failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
