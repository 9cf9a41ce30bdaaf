"""Device-resident masked batched Nelder–Mead: C simplexes, one program.

``gradfree.nm_run`` — the paper's default regulated optimizer — advances
one simplex with 1–4 lazy host evaluations per iteration, which makes it
the slowest possible citizen of the batched round engine: every eval is a
host↔device sync and the branch structure defeats batching.  The key
observation (ROADMAP "Batched Nelder–Mead") is that *every candidate
point of one simplex iteration depends only on the current simplex*:
reflect, expand, contract, and the ``n`` shrink points can all be
evaluated **speculatively** as one dense ``(C, n+3, P)`` batch through the
vmapped tape objective, and the branch the sequential method would have
taken is then selected per client with masked ``jnp.where`` logic.  The
loop body is branch-free, so ``lax.fori_loop`` compiles once and the
regulated per-client ``maxiter`` budgets arrive as a traced ``(C,)``
iteration mask exactly as in ``batched_spsa``.

Speculative evaluation spends ``n+3`` objective calls per iteration where
the sequential path spends 1–4 — wasted FLOPs, but they run as one fused
device batch, so wall-time per iteration is that of a *single* eval.
Communication-time accounting must not see the speculation: per-iteration
eval counts are accumulated on device from the branch actually taken
(expand 2, reflect 1, contract 2, shrink 2+n) so ``n_evals`` matches the
sequential ``nm_run`` eval-for-eval.

Branch decisions per iteration are recorded in a ``(C, max_iter)`` code
array (``BRANCH_*`` below; ``BRANCH_INACTIVE`` past a client's budget) —
the parity contract with ``gradfree.nm_run(..., trace=...)`` is decision-
for-decision equality, which ``tests/test_batched_nm.py`` enforces.

Finite-shot objectives (``keyed=True``) are called as ``f(xs, slot)``
with the slot schedule of the ``backends.py`` key-derivation contract:
init row ``r`` → slot ``r``; iteration ``i``'s speculative candidates
``[xr, xe, xc, shrink 1..n]`` → ``base..base+n+2`` with
``base = (n+1) + i·(n+3)``.  A candidate owns its slot whether it is
evaluated speculatively (here) or lazily (``gradfree.nm_run``), so the
draws of every candidate the sequential path *does* evaluate match
bitwise and the branch ladder decides identically.

Sharding safety: this optimizer is what runs under the engine's
``'clients'`` mesh axis, so two invariants are load-bearing (see
``core/batched_engine.py``):  every op in ``body`` must stay
**per-client independent** — elementwise or batched along ``C``, no
reduction/gather/permute across the client axis (``argsort`` and
``take_along_axis`` act on axis 1, within one client's simplex; the
scalar ``max(iters)`` loop bound is the single pre-loop exception) —
and the keyed slot schedule must stay a pure function of the
evaluation's **structural position**, never of client order or shard
placement.  Break either and the sharded round stops being bitwise
the single-device round.
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.telemetry import NM_INIT, NM_ITERATE

# branch codes, aligned with gradfree.nm_run(trace=...)
BRANCH_EXPAND_XE = 0      # fr < f_best, fe < fr  → worst ← xe   (2 evals)
BRANCH_EXPAND_XR = 1      # fr < f_best, fe ≥ fr  → worst ← xr   (2 evals)
BRANCH_REFLECT = 2        # f_best ≤ fr < f_2nd   → worst ← xr   (1 eval)
BRANCH_CONTRACT = 3       # fc < f_worst          → worst ← xc   (2 evals)
BRANCH_SHRINK = 4         # rows 1..n shrink toward best      (2+n evals)
BRANCH_INACTIVE = -1      # iteration ≥ the client's regulated budget


def init_simplexes(x0: jnp.ndarray, *, step: float = 0.25) -> jnp.ndarray:
    """(C, P) starts → (C, P+1, P) simplex stacks, the ``nm_init`` rule:
    row i+1 offsets coordinate i by ``step`` (or ``step·|x|+step``)."""
    x0 = jnp.asarray(x0, jnp.float32)
    n = x0.shape[-1]
    offset = jnp.where(x0 == 0, step, step * jnp.abs(x0) + step)  # (C, P)
    basis = jnp.eye(n + 1, n, k=-1, dtype=x0.dtype)               # (n+1, n)
    return x0[:, None, :] + basis[None] * offset[:, None, :]


def batched_nm(f: Callable, x0: jnp.ndarray, iters: jnp.ndarray,
               max_iter: int, *,
               alpha=1.0, gamma=2.0, rho=0.5, sigma=0.5, step: float = 0.25,
               keyed: bool = False, active: jnp.ndarray = None
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Masked batched Nelder–Mead.  Traceable (use under ``jax.jit``).

    f        : (C, P) → (C,)  vmapped objective; with ``keyed=True`` it
               is called as ``f(xs, slot)`` where ``slot`` is the
               (traced) contract slot (see module docstring)
    x0       : (C, P) start (typically θ_g broadcast to all clients)
    iters    : (C,)   per-client iteration budgets (mask, not trip count)
    max_iter : static upper bound on any budget (branch-record width)
    active   : optional (C,) bool participation mask (see
               ``batched_spsa``): an inactive client's budget is forced
               to 0 — its simplex stays the untouched init simplex, its
               branch row stays ``BRANCH_INACTIVE`` — and both its init
               and per-iteration eval counts are 0.  ``None`` is bitwise
               the all-active behavior.

    Returns ``(simplex (C, n+1, P), fvals (C, n+1), n_evals (C,),
    branches (C, max_iter) int32, n_steps () int32)``.  ``n_evals``
    counts what the sequential path spends: ``n+1`` init plus the taken
    branch's evals per iteration.  ``n_steps`` is the loop's trip count,
    the iterations every client ran in lockstep (``lockstep_iters``).
    The best point is ``simplex[c, argmin(fvals[c])]``.
    """
    x0 = jnp.asarray(x0, jnp.float32)
    iters = jnp.asarray(iters, jnp.int32)
    C, n = x0.shape
    if active is not None:
        active = jnp.asarray(active, bool)
        iters = jnp.where(active, iters, 0)

    # f over a (C, K, P) candidate stack (+ (K,) slots) → (C, K)
    if keyed:
        fstack = jax.vmap(f, in_axes=(1, 0), out_axes=1)
    else:
        fstack = lambda cand, slots: jax.vmap(
            lambda xs: f(xs), in_axes=1, out_axes=1)(cand)

    with jax.named_scope(NM_INIT):
        simplex0 = init_simplexes(x0, step=step)
        fvals0 = fstack(simplex0, jnp.arange(n + 1))         # (C, n+1)
        evals0 = jnp.full((C,), n + 1, jnp.int32)
        if active is not None:
            evals0 = jnp.where(active, evals0, 0)
    branches0 = jnp.full((C, int(max_iter)), BRANCH_INACTIVE, jnp.int32)

    def body(i, carry):
        simplex, fvals, evals, branches = carry
        order = jnp.argsort(fvals, axis=1)                   # stable
        sx = jnp.take_along_axis(simplex, order[:, :, None], axis=1)
        sf = jnp.take_along_axis(fvals, order, axis=1)
        best, worst = sx[:, 0, :], sx[:, -1, :]
        f_best, f_2nd, f_worst = sf[:, 0], sf[:, -2], sf[:, -1]
        centroid = jnp.mean(sx[:, :-1, :], axis=1)           # (C, P)

        xr = centroid + alpha * (centroid - worst)
        xe = centroid + gamma * (xr - centroid)
        xc = centroid + rho * (worst - centroid)
        shrink_x = best[:, None, :] + sigma * (sx[:, 1:, :] - best[:, None, :])
        cand = jnp.concatenate(
            [jnp.stack([xr, xe, xc], axis=1), shrink_x], axis=1)
        slots = (n + 1) + i * (n + 3) + jnp.arange(n + 3)
        fcand = fstack(cand, slots)                          # (C, n+3)
        fr, fe, fc = fcand[:, 0], fcand[:, 1], fcand[:, 2]
        f_shrink = fcand[:, 3:]

        # the sequential branch ladder, as per-client masks
        expand = fr < f_best
        take_xe = expand & (fe < fr)
        reflect = ~expand & (fr < f_2nd)
        contract = ~expand & ~reflect & (fc < f_worst)
        shrink = ~expand & ~reflect & ~contract

        use_xr = (expand & ~take_xe) | reflect
        new_worst_x = jnp.where(take_xe[:, None], xe,
                                jnp.where(use_xr[:, None], xr, xc))
        new_worst_f = jnp.where(take_xe, fe, jnp.where(use_xr, fr, fc))
        repl_x = sx.at[:, -1, :].set(new_worst_x)
        repl_f = sf.at[:, -1].set(new_worst_f)
        shr_x = jnp.concatenate([sx[:, :1, :], shrink_x], axis=1)
        shr_f = jnp.concatenate([sf[:, :1], f_shrink], axis=1)
        upd_x = jnp.where(shrink[:, None, None], shr_x, repl_x)
        upd_f = jnp.where(shrink[:, None], shr_f, repl_f)

        active = i < iters
        simplex = jnp.where(active[:, None, None], upd_x, simplex)
        fvals = jnp.where(active[:, None], upd_f, fvals)
        spent = jnp.where(reflect, 1,
                          jnp.where(shrink, 2 + n, 2)).astype(jnp.int32)
        evals = evals + jnp.where(active, spent, 0)
        code = jnp.where(
            take_xe, BRANCH_EXPAND_XE,
            jnp.where(expand, BRANCH_EXPAND_XR,
                      jnp.where(reflect, BRANCH_REFLECT,
                                jnp.where(contract, BRANCH_CONTRACT,
                                          BRANCH_SHRINK)))).astype(jnp.int32)
        branches = jax.lax.dynamic_update_slice(
            branches, jnp.where(active, code, BRANCH_INACTIVE)[:, None],
            (0, i))
        return simplex, fvals, evals, branches

    with jax.named_scope(NM_ITERATE):
        n_steps = lockstep_iters(iters, max_iter)
        out = jax.lax.fori_loop(0, n_steps, body,
                                (simplex0, fvals0, evals0, branches0))
    return (*out, n_steps)


def lockstep_iters(iters: jnp.ndarray, max_iter: int,
                   active: jnp.ndarray = None) -> jnp.ndarray:
    """The trip count of ``batched_nm``'s loop: the largest budget of an
    active client, capped at ``max_iter``.  Every client runs (and pays
    the ``n+3`` candidates of) each of these iterations."""
    iters = jnp.asarray(iters, jnp.int32)
    if active is not None:
        iters = jnp.where(jnp.asarray(active, bool), iters, 0)
    return jnp.minimum(jnp.max(iters), max_iter)


def best_point(simplex: jnp.ndarray, fvals: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-client incumbent: (x (C, P), f (C,)) at ``argmin(fvals)``."""
    idx = jnp.argmin(fvals, axis=1)
    x = jnp.take_along_axis(simplex, idx[:, None, None], axis=1)[:, 0, :]
    return x, jnp.take_along_axis(fvals, idx[:, None], axis=1)[:, 0]
