"""Batched Nelder–Mead vs the sequential simplex reference.

The contract is *decision parity*: on the same objective, the batched
engine must take the same reflect/expand/contract/shrink branch as
``gradfree.nm_run`` at every iteration, spend the same sequential-
equivalent eval counts, and land on the same simplex (f32 noise aside).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import gradfree
from repro.optim.batched_nm import (BRANCH_CONTRACT, BRANCH_EXPAND_XE,
                                    BRANCH_EXPAND_XR, BRANCH_INACTIVE,
                                    BRANCH_REFLECT, BRANCH_SHRINK,
                                    batched_nm, best_point, init_simplexes)


def _quad_batch(centers):
    c = jnp.asarray(np.stack(centers), jnp.float32)
    return lambda xs: jnp.sum((xs - c) ** 2, axis=-1)


def _quad_host(center):
    c32 = np.asarray(center, np.float32)
    return lambda x: float(np.sum((np.asarray(x, np.float32) - c32) ** 2))


def test_batched_nm_matches_sequential_per_client():
    dim, iters = 6, np.array([12, 5, 0])
    centers = [np.linspace(-1, 1, dim) * (c + 1) for c in range(3)]
    x0 = np.full((3, dim), 0.5, np.float32)

    simplex, fvals, n_evals, branches, _ = batched_nm(
        _quad_batch(centers), x0, iters, 12)
    xb, fb = best_point(simplex, fvals)

    for c in range(3):
        trace = []
        st = gradfree.nm_init(_quad_host(centers[c]), x0[c])
        st = gradfree.nm_run(_quad_host(centers[c]), st, int(iters[c]),
                             trace=trace)
        taken = [int(b) for b in branches[c] if b != BRANCH_INACTIVE]
        assert taken == trace                      # decision-for-decision
        assert int(n_evals[c]) == st.n_evals       # eval-for-eval
        np.testing.assert_allclose(np.asarray(xb[c]), st.best_x, atol=1e-5)
        np.testing.assert_allclose(float(fb[c]), st.best_f, atol=1e-5)

    # zero-budget client: simplex bitwise-frozen at init
    np.testing.assert_array_equal(
        np.asarray(simplex[2]),
        np.asarray(init_simplexes(jnp.asarray(x0))[2]))
    assert all(int(b) == BRANCH_INACTIVE for b in branches[2])


def test_batched_nm_exercises_all_branches():
    """Rosenbrock's bent valley forces every simplex transformation."""
    rosen_h = lambda x: float(
        (1 - x[0]) ** 2 + 100.0 * (x[1] - x[0] ** 2) ** 2)
    f = lambda xs: ((1 - xs[:, 0]) ** 2
                    + 100.0 * (xs[:, 1] - xs[:, 0] ** 2) ** 2)
    x0 = np.array([[-1.2, 1.0]], np.float32)
    m = 60
    _, _, n_evals, branches, _ = batched_nm(f, x0, np.array([m]), m)

    trace = []
    st = gradfree.nm_init(rosen_h, x0[0])
    st = gradfree.nm_run(rosen_h, st, m, trace=trace)
    assert [int(b) for b in branches[0]] == trace
    assert int(n_evals[0]) == st.n_evals
    seen = set(trace)
    assert {BRANCH_REFLECT, BRANCH_CONTRACT} <= seen
    assert seen & {BRANCH_EXPAND_XE, BRANCH_EXPAND_XR, BRANCH_SHRINK}


def test_batched_nm_eval_accounting_per_branch():
    """n_evals = (n+1) init + Σ taken-branch cost (2 / 2 / 1 / 2 / 2+n)."""
    dim = 3
    centers = [np.ones(dim) * 2.0]
    x0 = np.zeros((1, dim), np.float32)
    m = 15
    _, _, n_evals, branches, _ = batched_nm(_quad_batch(centers), x0,
                                         np.array([m]), m)
    cost = {BRANCH_EXPAND_XE: 2, BRANCH_EXPAND_XR: 2, BRANCH_REFLECT: 1,
            BRANCH_CONTRACT: 2, BRANCH_SHRINK: 2 + dim}
    want = dim + 1 + sum(cost[int(b)] for b in branches[0])
    assert int(n_evals[0]) == want


def test_batched_nm_converges_quadratic():
    # mirrors test_gradfree.test_nm_converges_quadratic (dim 4, 150 iters)
    centers = [np.ones(4)]
    x0 = np.zeros((1, 4), np.float32)
    simplex, fvals, _, _, _ = batched_nm(_quad_batch(centers), x0,
                                      np.array([150]), 150)
    _, fb = best_point(simplex, fvals)
    assert float(fb[0]) < 1e-6


def test_batched_nm_budget_masks_are_prefixes():
    """A client with budget k replays the first k decisions of a client
    with a larger budget (same start, same objective)."""
    dim = 4
    centers = [np.linspace(0.5, 2.0, dim)] * 2
    x0 = np.full((2, dim), 0.25, np.float32)
    _, _, _, branches, _ = batched_nm(_quad_batch(centers), x0,
                                   np.array([4, 10]), 10)
    short = [int(b) for b in branches[0] if b != BRANCH_INACTIVE]
    long = [int(b) for b in branches[1]]
    assert len(short) == 4 and short == long[:4]
