"""Whole runs on the CPU at test size: a cell added as files alone runs;
a run off the TPU fails; a run whose timed path is broken underneath
comes out not correct.  The look for a chip is replaced by the CPU
devices (``on_cpu``); every other part of a run is the benchmark's."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from conftest import REPO, add_cell, run_cell

BENCH = REPO / "benchmarks" / "chip"


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_cell_added_as_files_runs(tmp_path, on_cpu):
    (tmp_path / "src").symlink_to(REPO / "src")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digest(tmp_path / "benchmarks" / "chip")
    add_cell(tmp_path, "tiny.finetune", "tiny", "tiny-finetune", 1,
             json.loads((BENCH / "limits" / "dsk7b.finetune.json")
                        .read_text()))
    after = _digest(tmp_path / "benchmarks" / "chip")
    assert all(after[p] == h for p, h in before.items())   # nothing edited
    out = run_cell(tmp_path, "tiny.finetune", seconds=0.5)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"llm_tokens_per_s", "peak_hbm_gb",
                                   "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["device"]["count"] == 1


def test_traced_run_reports_per_layer_metrics(checkout, on_cpu):
    out = run_cell(checkout, "tiny.rounds", seconds=0.5, trace=1)
    assert out["correct"] is True
    # the CPU trace has no TPU planes: device readers find nothing
    assert set(out["metrics"]) == {"mfu.rounds",
                                   "nm_useful_eval_share.rounds"}
    assert 0 < out["metrics"]["nm_useful_eval_share.rounds"]["value"] < 100
    assert out["device"]["busy_s"] == 0.0 and out["device"]["window_s"] > 0


def _run_script(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, str(cwd / "benchmarks" / "chip"
                                               / "run.py"), *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ("--workload", "dsk7b.finetune", "--seed", "3", "--seconds", "1",
        "--trace", "0")


def test_off_the_tpu_the_run_fails_without_a_result():
    res = _run_script(REPO, *ARGS)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert res.stdout.strip() == ""


def test_without_the_program_the_run_fails(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    res = _run_script(tmp_path, *ARGS)
    assert res.returncode != 0
    assert res.stdout.strip() == ""


def test_an_unknown_device_kind_is_an_error():
    from benchmarks.chip import harness
    with pytest.raises(SystemExit, match="not in peaks.json"):
        harness.peaks_for("TPU v99")


# -- faults planted in the program under the timed path ----------------------

def _real_limits(checkout, cell, real):
    src = BENCH / "limits" / f"{real}.json"
    shutil.copy(src, checkout / "benchmarks" / "chip" / "limits"
                / f"{cell}.json")


def _fresh_programs(monkeypatch):
    from repro.core import batched_llm, fused_rounds
    from repro.models import model as M
    monkeypatch.setattr(batched_llm, "_LLM_ROUND_CACHE", {})
    monkeypatch.setattr(fused_rounds, "_FUSED_CACHE", {})
    monkeypatch.setattr(M, "_TRAIN_STEP_CACHE", {})


def _frozen_llm(monkeypatch):
    from repro.optim import adamw
    monkeypatch.setattr(adamw, "update",
                        lambda g, state, params, **kw: (params, state))


def _half_batch_llm(monkeypatch):
    from repro.models import model as M
    make = M.make_train_step

    def half(cfg, **kw):
        step = make(cfg, **kw)

        def run(params, adapters, opt, batch):
            b = batch["tokens"].shape[0] // 2
            return step(params, adapters, opt,
                        {k: v[:b] for k, v in batch.items()})
        return run
    monkeypatch.setattr(M, "make_train_step", half)


def _no_exchange_llm(monkeypatch):
    """Each chip blends toward the mean of its own clients only."""
    from repro.peft import lora
    n_chips = 4

    def local_blend(adapters, a_g, rho):
        def leaf(a):
            g = a.reshape((n_chips, -1) + a.shape[1:])
            m = jnp.broadcast_to(g.mean(1, keepdims=True), g.shape)
            return (1 - rho) * a + rho * m.reshape(a.shape)
        return jax.tree.map(leaf, adapters)
    monkeypatch.setattr(lora, "blend_adapters", local_blend)


def _answer_llm(monkeypatch):
    from repro.core import llm_client
    label_logits = llm_client.label_logits

    def altered(*a, **kw):
        logits, gold = label_logits(*a, **kw)
        return logits.at[:, 0].add(1.0), gold
    monkeypatch.setattr(llm_client, "label_logits", altered)


def _frozen_rounds(monkeypatch):
    from repro.core import fused_rounds
    build = fused_rounds.build_local_phase

    def frozen(*a, **kw):
        lp = build(*a, **kw)

        def run(qX, qy, mask, teacher, theta_g, iters, ckeys, **k):
            x, n = lp(qX, qy, mask, teacher, theta_g, iters, ckeys, **k)
            return jnp.broadcast_to(theta_g, x.shape), n
        return run
    monkeypatch.setattr(fused_rounds, "build_local_phase", frozen)


def _half_batch_rounds(monkeypatch):
    from repro.core import fused_rounds
    build = fused_rounds.build_local_phase

    def half(*a, **kw):
        lp = build(*a, **kw)

        def run(qX, qy, mask, teacher, theta_g, iters, ckeys, **k):
            keep = jnp.arange(mask.shape[1]) < mask.shape[1] // 2
            return lp(qX, qy, mask * keep, teacher, theta_g, iters, ckeys,
                      **k)
        return run
    monkeypatch.setattr(fused_rounds, "build_local_phase", half)


def _answer_rounds(monkeypatch):
    from repro.core import fused_rounds
    run = fused_rounds.FusedRoundDriver.run

    def altered(self, theta_g):
        out = run(self, theta_g)
        out.losses = out.losses.copy()
        out.losses[0, 0] *= 2.0
        return out
    monkeypatch.setattr(fused_rounds.FusedRoundDriver, "run", altered)


FAULTS = [
    ("tiny.finetune", "dsk7b.finetune", _frozen_llm),
    ("tiny.finetune", "dsk7b.finetune", _half_batch_llm),
    ("tiny.finetune", "dsk7b.finetune", _answer_llm),
    ("tiny.finetune.mesh4", "dsk7b.finetune", _no_exchange_llm),
    ("tiny.rounds", "gpt2.rounds", _frozen_rounds),
    ("tiny.rounds", "gpt2.rounds", _half_batch_rounds),
    ("tiny.rounds", "gpt2.rounds", _answer_rounds),
]


@pytest.mark.parametrize("cell,real", sorted({(c, r) for c, r, _ in FAULTS}))
def test_sound_runs_pass_the_limits(checkout, on_cpu, monkeypatch, cell,
                                    real):
    _real_limits(checkout, cell, real)
    _fresh_programs(monkeypatch)
    assert run_cell(checkout, cell, seconds=0.3)["correct"] is True


@pytest.mark.parametrize("cell,real,fault", FAULTS,
                         ids=[f"{c}-{f.__name__[1:]}" for c, _, f in FAULTS])
def test_a_broken_timed_path_is_not_correct(checkout, on_cpu, monkeypatch,
                                            cell, real, fault):
    _real_limits(checkout, cell, real)
    _fresh_programs(monkeypatch)
    fault(monkeypatch)
    out = run_cell(checkout, cell, seconds=0.3)
    assert out["correct"] is False, out["checks"]
