"""The programs' own measurement (``repro.telemetry``): the device scopes
in the compiled text of the LLM stage and of the fused rounds, the host
spans each ``run()`` writes under its step span, and the Nelder–Mead
loop's trip count the fused program reports."""
import glob
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hlo_scopes import op_names, scopes, unscoped_share
from repro import telemetry as tel
from repro.core.batched_llm import BatchedLLMEngine
from repro.core.fused_rounds import FusedRoundDriver
from repro.core.llm_client import task_llm_config
from repro.data.tasks import build_task
from repro.models import model as M
from repro.optim.batched_nm import batched_nm, lockstep_iters
from repro.quantum import backends as backend_mod
from repro.quantum import qnn

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def task():
    return build_task("genomic", n_clients=3, train_size=49, test_size=16,
                      val_size=16, seed=3)


@pytest.fixture(scope="module")
def engine(task):
    cfg = task_llm_config("tiny-llm", task.vocab_size, task.llm_seq_len)
    base = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return BatchedLLMEngine(task, cfg, base, seed=11, steps=2)


def _driver(task, **kw):
    spec = qnn.QNNSpec("vqc", n_qubits=4, n_classes=task.n_classes)
    teacher = [np.full((cl.n, task.n_classes), 1.0 / task.n_classes)
               for cl in task.clients]
    args = dict(optimizer="nelder-mead", use_llm=True, teacher_probs=teacher,
                llm_losses=[0.5, 0.2, 0.05], maxiter0=3, maxiter_cap=8,
                n_rounds=3, early_stop=False, seed=2)
    args.update(kw)
    return FusedRoundDriver(task, spec, backend_mod.get("exact"), **args)


@pytest.fixture(scope="module")
def rounds(task):
    """Full participation with alignment selection on."""
    return _driver(task, select_frac=0.5)


@pytest.fixture(scope="module")
def llm_text(engine):
    return engine.compiled_text()


@pytest.fixture(scope="module")
def rounds_text(rounds):
    return rounds.compiled_text()


def test_llm_program_carries_every_llm_scope(llm_text):
    assert set(tel.LLM_SCOPES) <= scopes(llm_text)


def test_backward_and_recompute_are_named(llm_text):
    names = op_names(llm_text)
    assert any("transpose(" in n and tel.LLM_STEP in n for n in names)
    assert any("rematted_computation" in n for n in names)
    assert any(f"transpose(jvp({tel.MODEL_HEAD}))" in n for n in names)


@pytest.mark.parametrize("population", [False, True])
def test_fused_program_carries_every_round_scope(task, rounds_text,
                                                 population):
    if population:
        # cohorts of 2 with dropout: the gather and the scatter work
        text = _driver(task, optimizer="spsa", c_round=2,
                       dropout=0.25).compiled_text()
        want = {tel.QFL_GATHER, tel.QFL_SCATTER, tel.QFL_LOCAL,
                tel.TAPE_REPLAY}
    else:
        text = rounds_text
        want = set(tel.ROUND_SCOPES) - {tel.QFL_GATHER}
    assert want <= scopes(text)


@pytest.mark.parametrize("program", ["llm", "rounds"])
def test_few_operations_lie_outside_every_scope(request, program):
    text = request.getfixturevalue(f"{program}_text")
    assert unscoped_share(text) < 0.1


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("llm.", "qfl.")):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_each_run_writes_its_spans_inside_its_step(tmp_path, engine,
                                                   rounds):
    theta0 = np.zeros(rounds.spec.n_params)
    engine.run()
    rounds.run(theta0)                       # compiled before the trace
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(2):
            engine.run()
        rounds.run(theta0)
    events = _host_events(tmp_path)
    calls = {tel.LLM_STAGE: (tel.LLM_STAGE_DISPATCH, tel.LLM_STAGE_FETCH),
             tel.QFL_ROUNDS: (tel.QFL_ROUNDS_ARGS, tel.QFL_ROUNDS_DISPATCH,
                              tel.QFL_ROUNDS_FETCH, tel.QFL_ROUNDS_UNPACK)}
    for step, phases in calls.items():
        steps = [e for e in events if e[0] == step]
        assert len(steps) == (2 if step == tel.LLM_STAGE else 1)
        assert len({e[3]["step_num"] for e in steps}) == len(steps)
        for _, s0, e0, _ in steps:
            inside = [e for e in events if e[0] in phases
                      and s0 <= e[1] and e[2] <= e0]
            assert [e[0] for e in sorted(inside, key=lambda e: e[1])] \
                == list(phases)
            fetch = next(e for e in inside if e[0].endswith(".fetch"))
            assert fetch[3]["bytes"] > 0


def test_batched_nm_returns_its_trip_count():
    x0 = np.zeros((3, 2), np.float32)
    iters = np.array([4, 9, 6])
    active = np.array([True, False, True])
    *_, n_steps = batched_nm(lambda xs: jnp.sum(xs ** 2, axis=1), x0,
                             iters, 8, active=active)
    assert int(n_steps) == 6 == int(lockstep_iters(iters, 8, active))
    assert int(lockstep_iters(iters, 8)) == 8


def _lockstep_share_metric():
    path = (ROOT / "benchmarks" / "chip" / "metrics"
            / "nm_useful_eval_share.rounds.py")
    spec = importlib.util.spec_from_file_location("nm_share", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_nm_iters_is_the_lockstep_trip_count(task, rounds):
    out = rounds.run(np.zeros(rounds.spec.n_params))
    C, P = task.n_clients, rounds.spec.n_params
    budgets = out.budgets[:, :C]
    assert len(set(budgets.max(axis=1).tolist())) > 1   # regulation moved
    np.testing.assert_array_equal(
        out.nm_iters, np.minimum(budgets.max(axis=1), rounds.max_iter))
    np.testing.assert_array_equal(
        out.nm_iters, rounds.run_host_reference(
            np.zeros(P)).nm_iters)
    # the benchmark's share rebuilds the lockstep count from the budgets;
    # the program's own count gives the same share
    useful = out.n_evals[:, :C].sum()
    counts = {"clients": C, "n_params": P,
              "n_evals": out.n_evals[:, :C].tolist(),
              "budgets": budgets.tolist(), "max_iter": rounds.max_iter}
    share = _lockstep_share_metric()(SimpleNamespace(counts=counts))
    lockstep = sum(C * (P + 1) + int(k) * C * (P + 3) for k in out.nm_iters)
    assert share == pytest.approx(100.0 * useful / lockstep, rel=1e-12)
