"""Fused multi-round federation driver — R rounds as ONE device program.

The host orchestrator (``core/orchestrator.py``) exits the device every
round for FedAvg, regulation, selection, termination, and the per-client
loss report — at small client counts the host round-trip, not the
quantum circuit, is the wall-time ceiling (ROADMAP).  This module runs
the **entire round loop** as a single jitted ``lax.scan`` over rounds:

    carry = (θ_g, budgets, last_losses, cum_evals,
             prev_server_loss, small_count, done_flag)

with every host-side step replaced by a traceable twin of the reference
module it mirrors:

  - **FedAvg** — masked weighted mean of the trained ``(C, P)`` stack on
    device (the host aggregates in float64; the fused program is float32,
    so θ_g trajectories agree to f32 tolerance while every quantized
    quantity below is exact).
  - **Regulation** — ``regulate_batched``, a vectorized twin of
    ``regulation.regulate`` (same guard ladder, same round-half-to-even,
    same ``[min_iter, cap]`` clamp), applied as a masked integer budget
    update: only eligible cohort members after round 1.
  - **Selection** — ``select_topk_mask``, the mask form of
    ``selection.select_aligned``: top-k over ``|L_i − L_s|`` with
    NaN/inf hardened to +inf (sorts last) and stable ties (lower index
    wins), intersected with the round's eligibility mask.
  - **Termination** — ``termination_step``, the per-round transition of
    ``TerminationCriterion`` (relative-improvement + patience, t_max
    short-circuit *before* the patience update, exactly like the host
    class).  The resulting ``done`` flag masks every carry update of
    post-convergence rounds, so an early-terminated fused run is
    bit-identical in state to one that stopped the scan.
  - **Reporting** — per-client losses are computed inside the scan body
    (masked NLL at ``REPORT_EVAL_SLOT`` on the client's key stream) and
    returned in the scanned outputs: one device→host transfer per run,
    not C per round as in the orchestrator's ``_nll`` loop.

Population semantics
--------------------
On top of the fused loop, the driver supports a client *population*
C_pop ≫ C_round.  Per round ``t`` it draws a cohort of ``c_round``
distinct population ids from the reserved ``POP_CLIENT`` stream
(``eval_key(base, t, POP_CLIENT, POP_SLOT_COHORT)``), gathers the
cohort's rows out of the ``(C_pop, …)`` data/budget/loss/delta stacks,
runs the round on the ``(c_round, …)`` slices, and scatters budgets /
last losses / cumulative evals back.  A ``dropout`` probability
additionally drops each cohort member by a coin on the **client's own**
stream (``DROPOUT_EVAL_SLOT``) — dropped or outside-cohort clients are
bitwise untouched: their carry rows keep their prior values, their key
streams are pure functions of ``(seed, round, client_id)`` and never
shift with cohort composition, and their eval spend is 0 (the batched
optimizers' ``active`` mask).  That inertness is what makes
participation sweeps at one seed comparable (``tests/test_fused_rounds``
pins it).

Sharding: under full participation the client stacks shard over the
existing ``'clients'`` mesh (``put_client_stacks``; the population axis
IS the client axis).  In population mode the layout flips: the
``(C_pop, …)`` population state is **replicated** and only the gathered
``(c_round, …)`` cohort — the round's compute — is pinned to the mesh
(``constrain_client_axis``; the carries stay replicated via
``constrain_replicated``).  Sharding the population stacks instead
turns every round's dynamic gather/scatter into a cross-device
collective chain inside the scan that costs more than the round itself.
``c_round`` must divide the mesh width.

Parity contract (``tests/test_fused_rounds.py``): a fused run with full
participation matches the host orchestrator round-for-round at pinned
seeds — selected sets, regulated budgets, eval counts, and the
termination round **exactly**; θ_g, client losses, and server metrics to
f32 tolerance (the host aggregates and divides in float64).  Finite-shot
draws are identical by the ``eval_key`` contract; note the report-eval
draw shape is the padded ``(Bmax, n_classes)``, so loss parity with the
host's unpadded ``_nll`` is bitwise only for equal client shards.
``run_host_reference`` extends the same oracle to population mode
(cohorts, dropout) for the semantics the orchestrator cannot express.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry as tel
from repro.core import regulation as regulation_mod
from repro.core.batched_engine import build_local_phase
from repro.core.termination import TerminationCriterion
from repro.distributed import sharding as shd
from repro.optim.batched_nm import lockstep_iters
from repro.optim.batched_spsa import make_deltas
from repro.quantum import backends as backend_mod
from repro.quantum import qnn, tape as tape_mod

_FUSED_CACHE: Dict[tuple, object] = {}


# ---------------------------------------------------------------------------
# traceable twins of the host-side round steps
# ---------------------------------------------------------------------------
def regulate_batched(maxiter, qnn_loss, llm_loss, *, variant: str = "adaptive",
                     cap: int = 100, min_iter: int = 1, weight: float = 0.5,
                     increment: int = 2):
    """Vectorized twin of ``regulation.regulate`` — same guard ladder,
    same formulas, same clamp, elementwise over ``(C,)`` stacks.

    Guard order (must mirror the host function exactly):
      1. llm_loss <= 0 or non-finite  → maxiter unchanged (no clamp!),
      2. qnn_loss non-finite          → clamp(maxiter) (hold the budget),
      3. qnn_loss <= llm_loss         → clamp(maxiter) (only boost when
                                        behind — Alg. 1 line 12),
      4. else                         → clamp(round(variant formula)).

    ``jnp.round`` rounds half-to-even exactly like Python's ``round``,
    so the integer budgets agree with the host bitwise except on f32/f64
    knife edges of the ratio itself.
    """
    if variant not in regulation_mod.VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; one of "
                         f"{regulation_mod.VARIANTS}")
    maxiter = jnp.asarray(maxiter, jnp.int32)
    q = jnp.asarray(qnn_loss, jnp.float32)
    llm = jnp.asarray(llm_loss, jnp.float32)
    m = maxiter.astype(jnp.float32)
    ratio = q / llm
    if variant == "adaptive":
        new = m * ratio
    elif variant == "incremental":
        new = m + increment * jnp.minimum(jnp.ceil(ratio), 5.0)
    elif variant == "logarithmic":
        new = m * (1.0 + jnp.log(ratio))
    else:  # dynamic
        new = (1 - weight) * m + weight * m * ratio
    boosted = jnp.clip(jnp.round(new), min_iter, cap).astype(jnp.int32)
    held = jnp.clip(maxiter, min_iter, cap)
    bad_llm = (llm <= 0) | ~jnp.isfinite(llm)
    bad_qnn = ~jnp.isfinite(q)
    behind = q > llm
    return jnp.where(bad_llm, maxiter,
                     jnp.where(bad_qnn | ~behind, held, boosted))


def select_topk_mask(dists, k):
    """Boolean mask form of ``selection.select_aligned``'s index list:
    True on the ``k`` smallest distances.  Non-finite distances harden
    to +inf (diverged clients sort last, never poison the sort), and
    ``jnp.argsort`` is stable, so ties resolve to the lower index —
    both exactly as in the host module.  ``k`` may be traced."""
    d = jnp.asarray(dists)
    d = jnp.where(jnp.isfinite(d), d, jnp.inf)
    order = jnp.argsort(d)                      # stable (jnp default)
    ranks = jnp.zeros_like(order).at[order].set(jnp.arange(d.shape[0]))
    return ranks < k


def termination_step(prev_loss, small, loss, t, *, epsilon: float,
                     t_max: int, patience: int = 1):
    """One round's transition of ``TerminationCriterion.update`` as a
    pure function: ``(prev_loss, small) × (loss, t) → (stop, small')``.

    Mirrors the host class exactly: ``t >= t_max`` stops *before* the
    patience counter updates (the host returns early, leaving ``_small``
    stale); with fewer than two recorded losses (``t < 2``) nothing is
    checked; a zero-loss plateau counts as converged while a fresh drop
    to exactly 0 counts as progress."""
    loss = jnp.asarray(loss, jnp.float32)
    prev_loss = jnp.asarray(prev_loss, jnp.float32)
    have_two = t >= 2
    nonzero = jnp.abs(loss) > 0
    rel = jnp.where(
        nonzero,
        jnp.abs(loss - prev_loss) / jnp.where(nonzero, jnp.abs(loss), 1.0),
        jnp.where(prev_loss == loss, jnp.float32(0.0), jnp.float32(jnp.inf)))
    small_new = jnp.where(have_two,
                          jnp.where(rel < epsilon, small + 1,
                                    jnp.zeros_like(small)),
                          small)
    at_cap = t >= t_max
    stop = at_cap | (have_two & (small_new >= patience))
    return stop, jnp.where(at_cap, small, small_new)


# ---------------------------------------------------------------------------
# the fused program
# ---------------------------------------------------------------------------
def _build_fused_program(spec, backend, *, lam, mu, use_llm, optimizer,
                         max_iter, regulation, maxiter_cap, select_frac,
                         epsilon, patience, n_rounds, early_stop, c_pop,
                         c_pad, c_round, dropout, mesh):
    cq = tape_mod.compile_qnn(spec)
    sampling = backend.shots > 0
    local_phase = build_local_phase(spec, backend, lam=lam, mu=mu,
                                    use_llm=use_llm, optimizer=optimizer,
                                    max_iter=max_iter)
    init_evals = 1 if optimizer == "spsa" else spec.n_params + 1
    subsample = c_round is not None
    c_width = int(c_round) if subsample else c_pad
    select_on = use_llm and select_frac < 1.0
    # top-k size: static whenever the per-round eligibility count is
    # static (no dropout) — then it is the host formula verbatim, in
    # float64.  With dropout the count is traced and k is computed in
    # f32 (knife-edge rounding of frac·n may differ from f64 — the
    # host reference mirrors the f32 form in that mode).
    k_static = None
    if select_on and dropout == 0.0:
        k_static = max(1, int(round(select_frac * (c_width if subsample
                                                   else c_pop))))

    def measure(theta, X, key):
        probs = tape_mod.tape_probs(cq, theta, X)
        if sampling:
            return backend.transform_probs(probs, key)
        return backend.apply_channel(probs)

    def report_one(theta, Xc, yc, mc, ckey):
        # on-device twin of orchestrator._nll at REPORT_EVAL_SLOT; the
        # masked mean equals nll_loss bitwise on a full (unpadded) shard
        noisy = measure(theta, Xc,
                        jax.random.fold_in(ckey,
                                           backend_mod.REPORT_EVAL_SLOT)
                        if sampling else None)
        p = jnp.take_along_axis(noisy, yc[:, None], axis=1)[:, 0]
        m_sum = jnp.maximum(jnp.sum(mc), 1.0)
        return -jnp.sum(jnp.log(p + 1e-9) * mc) / m_sum

    def program(theta0, budgets0, last0, cum0, qX, qy, mask, teacher,
                deltas, weights, evaltime, llm, val_qX, val_qy, test_qX,
                test_qy, base_key):

        is_real_pad = jnp.arange(c_pad) < c_pop

        def server_nll(theta, X, y, t, slot):
            key = (backend_mod.eval_key(base_key, t,
                                        backend_mod.SERVER_CLIENT, slot)
                   if sampling else None)
            return qnn.nll_loss(measure(theta, X, key), y)

        def server_acc(theta, X, y, t, slot):
            key = (backend_mod.eval_key(base_key, t,
                                        backend_mod.SERVER_CLIENT, slot)
                   if sampling else None)
            return qnn.accuracy(measure(theta, X, key), y)

        def body(carry, t):
            (theta_g, budgets, last_losses, cum_evals,
             prev_loss, small, done) = carry
            run = ~done

            # -- cohort, and the gather of its rows -----------------------
            with jax.named_scope(tel.QFL_GATHER):
                if subsample:
                    ck = backend_mod.eval_key(base_key, t,
                                              backend_mod.POP_CLIENT,
                                              backend_mod.POP_SLOT_COHORT)
                    cohort = jnp.sort(jax.random.choice(
                        ck, c_pop, (c_width,),
                        replace=False)).astype(jnp.int32)
                    real = jnp.ones((c_width,), bool)
                else:
                    cohort = jnp.arange(c_pad, dtype=jnp.int32)
                    real = is_real_pad
                if dropout > 0.0:
                    u = jax.vmap(lambda cid: jax.random.uniform(
                        backend_mod.eval_key(
                            base_key, t, cid,
                            backend_mod.DROPOUT_EVAL_SLOT)))(cohort)
                    dropped = (u < dropout) & real
                else:
                    dropped = jnp.zeros((c_width,), bool)
                eligible = real & ~dropped

                if subsample:
                    def g(a):
                        return shd.constrain_client_axis(
                            jnp.take(a, cohort, axis=0), mesh)
                    gqX, gqy, gmask = g(qX), g(qy), g(mask)
                    gteacher = g(teacher)
                    gdeltas, gweights = g(deltas), g(weights)
                    gevaltime, gllm = g(evaltime), g(llm)
                    gbud0, glast = g(budgets), g(last_losses)
                else:
                    gqX, gqy, gmask, gteacher = qX, qy, mask, teacher
                    gdeltas, gweights, gevaltime, gllm = (deltas, weights,
                                                          evaltime, llm)
                    gbud0, glast = budgets, last_losses

            # -- regulation (Alg. 1 lines 11-17; after round 1 only) ------
            with jax.named_scope(tel.QFL_REGULATE):
                if use_llm:
                    boosted = regulate_batched(gbud0, glast, gllm,
                                               variant=regulation,
                                               cap=maxiter_cap)
                    gbud = jnp.where((t > 1) & eligible, boosted, gbud0)
                    gratios = jnp.where(
                        (t > 1) & jnp.isfinite(glast) & (gllm > 0.0),
                        glast / gllm, jnp.float32(1.0))
                else:
                    gbud = gbud0
                    gratios = jnp.ones((c_width,), jnp.float32)

            # -- local phase: the engine's traceable body -----------------
            with jax.named_scope(tel.QFL_LOCAL):
                rk = jax.random.fold_in(base_key, t)
                ckeys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
                    rk, cohort)
                th, n_evals = local_phase(gqX, gqy, gmask, gteacher,
                                          theta_g, gbud, ckeys,
                                          deltas=gdeltas, active=eligible)
                # the lockstep loop's trip count, by the rule its bound
                # follows (optim/batched_nm.lockstep_iters)
                nm_iters = (lockstep_iters(gbud, max_iter, eligible)
                            if optimizer == "nelder-mead" else jnp.int32(0))

            # -- report F_i from the carry (no host loop) -----------------
            with jax.named_scope(tel.QFL_REPORT):
                glosses = jax.vmap(report_one)(th, gqX, gqy, gmask, ckeys)
                glosses = jnp.where(eligible, glosses, jnp.nan)

            with jax.named_scope(tel.QFL_SERVER):   # before FedAvg
                s_pre = server_nll(theta_g, val_qX, val_qy, t,
                                   backend_mod.SERVER_SLOT_LOSS_PRE)

            # -- alignment selection (Sec. III-B) -------------------------
            with jax.named_scope(tel.QFL_SELECT):
                if select_on:
                    d = jnp.abs(glosses - s_pre)
                    d = jnp.where(jnp.isfinite(d) & eligible, d, jnp.inf)
                    if k_static is not None:
                        k = k_static
                    else:
                        n_el = jnp.sum(eligible).astype(jnp.float32)
                        k = jnp.maximum(1, jnp.round(
                            select_frac * n_el)).astype(jnp.int32)
                    sel = select_topk_mask(d, k) & eligible
                else:
                    sel = eligible

            # -- FedAvg (Eq. 3) over the selected set ---------------------
            with jax.named_scope(tel.QFL_FEDAVG):
                w = jnp.where(sel, gweights, 0.0)
                wsum = jnp.sum(w)
                theta_new = jnp.sum(
                    (w / jnp.maximum(wsum, 1e-30))[:, None] * th, axis=0)
                theta_new = jnp.where(wsum > 0, theta_new, theta_g)
                theta_g = jnp.where(run, theta_new, theta_g)

            with jax.named_scope(tel.QFL_SERVER):   # after FedAvg
                s_post = server_nll(theta_g, val_qX, val_qy, t,
                                    backend_mod.SERVER_SLOT_LOSS_POST)
                v_acc = server_acc(theta_g, val_qX, val_qy, t,
                                   backend_mod.SERVER_SLOT_VAL_ACC)
                t_acc = server_acc(theta_g, test_qX, test_qy, t,
                                   backend_mod.SERVER_SLOT_TEST_ACC)

            # -- termination ---------------------------------------------
            with jax.named_scope(tel.QFL_TERMINATE):
                stop, small_new = termination_step(
                    prev_loss, small, s_post, t, epsilon=epsilon,
                    t_max=n_rounds, patience=patience)
                prev_loss = jnp.where(run, s_post, prev_loss)
                small = jnp.where(run, small_new, small)
                if early_stop:
                    done_next = done | (run & stop)
                else:
                    done_next = done

            # -- scatter cohort state back to the population carries ------
            with jax.named_scope(tel.QFL_SCATTER):
                upd = run & eligible
                evals_add = jnp.where(upd, n_evals, 0)
                if subsample:
                    budgets = budgets.at[cohort].set(
                        jnp.where(upd, gbud, gbud0))
                    last_losses = last_losses.at[cohort].set(
                        jnp.where(upd, glosses, glast))
                    cum_evals = cum_evals.at[cohort].add(evals_add)
                else:
                    budgets = jnp.where(upd, gbud, budgets)
                    last_losses = jnp.where(upd, glosses, last_losses)
                    cum_evals = cum_evals + evals_add
                if mesh is not None:
                    # full participation: the carries ARE the sharded
                    # client stacks.  Population mode: carries stay
                    # replicated (the scatter of sharded cohort values must
                    # not let GSPMD drift the carry sharding between scan
                    # iterations).
                    pin = (shd.constrain_replicated if subsample
                           else shd.constrain_client_axis)
                    budgets = pin(budgets, mesh)
                    last_losses = pin(last_losses, mesh)
                    cum_evals = pin(cum_evals, mesh)

                comm = jnp.max(jnp.where(
                    eligible,
                    gevaltime * (n_evals - init_evals).astype(jnp.float32),
                    0.0))
                comm = jnp.where(run, comm, 0.0)

            ys = dict(active=run, stop=run & stop, cohort=cohort,
                      dropped=dropped, selected=sel, losses=glosses,
                      ratios=gratios, n_evals=evals_add,
                      budgets=budgets, cum_evals=cum_evals,
                      server_loss_pre=s_pre, server_loss=s_post,
                      val_acc=v_acc, test_acc=t_acc, comm_time_s=comm,
                      theta=theta_g, nm_iters=nm_iters)
            carry = (theta_g, budgets, last_losses, cum_evals,
                     prev_loss, small, done_next)
            return carry, ys

        carry0 = (jnp.asarray(theta0, jnp.float32), budgets0, last0, cum0,
                  jnp.float32(jnp.nan), jnp.int32(0),
                  jnp.asarray(False))
        ts = jnp.arange(1, n_rounds + 1, dtype=jnp.int32)
        carry, ys = jax.lax.scan(body, carry0, ts)
        ys["theta_g"] = carry[0]
        ys["budgets_final"] = carry[1]
        ys["last_losses_final"] = carry[2]
        ys["cum_evals_final"] = carry[3]
        return ys

    return jax.jit(program)


def get_fused_program(spec, backend, *, lam, mu, use_llm, optimizer,
                      max_iter, regulation, maxiter_cap, select_frac,
                      epsilon, patience, n_rounds, early_stop, c_pop,
                      c_pad, c_round, dropout, mesh):
    """Module-wide cache, like ``batched_engine.get_round_fn``: fresh
    driver instances with the same static config reuse the compiled
    scan (population stacks and θ_g are traced arguments)."""
    key = (spec, backend, int(backend.shots), float(lam), float(mu),
           bool(use_llm), optimizer, int(max_iter), regulation,
           int(maxiter_cap), float(select_frac), float(epsilon),
           int(patience), int(n_rounds), bool(early_stop), int(c_pop),
           int(c_pad), None if c_round is None else int(c_round),
           float(dropout), mesh)
    if key not in _FUSED_CACHE:
        _FUSED_CACHE[key] = _build_fused_program(
            spec, backend, lam=lam, mu=mu, use_llm=use_llm,
            optimizer=optimizer, max_iter=max_iter, regulation=regulation,
            maxiter_cap=maxiter_cap, select_frac=select_frac,
            epsilon=epsilon, patience=patience, n_rounds=n_rounds,
            early_stop=early_stop, c_pop=c_pop, c_pad=c_pad,
            c_round=c_round, dropout=dropout, mesh=mesh)
    return _FUSED_CACHE[key]


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
@dataclass
class FusedRunOutput:
    """Per-round arrays over the full R scheduled rounds (rows past the
    termination round have ``active=False`` and frozen/zero payloads)
    plus the final population carries.  ``c_width`` is the cohort array
    length — ``c_round`` in population mode, the padded client count
    under full participation."""
    active: np.ndarray            # (R,)  bool — round executed
    stop: np.ndarray              # (R,)  bool — termination fired here
    cohort: np.ndarray            # (R, c_width) int32 population ids
    dropped: np.ndarray           # (R, c_width) bool
    selected: np.ndarray          # (R, c_width) bool (cohort positions)
    losses: np.ndarray            # (R, c_width) reported F_i (NaN if out)
    ratios: np.ndarray            # (R, c_width) regulation ratios
    n_evals: np.ndarray           # (R, c_width) this round's eval spend
    budgets: np.ndarray           # (R, c_pad) post-regulation budgets
    cum_evals: np.ndarray         # (R, c_pad)
    server_loss_pre: np.ndarray   # (R,)
    server_loss: np.ndarray       # (R,)
    val_acc: np.ndarray           # (R,)
    test_acc: np.ndarray          # (R,)
    comm_time_s: np.ndarray       # (R,)
    theta: np.ndarray             # (R, P) θ_g after each round
    nm_iters: np.ndarray          # (R,)  Nelder–Mead iterations the
    #                               round's lockstep loop ran (0: SPSA)
    theta_g: np.ndarray           # (P,)  final global parameters
    budgets_final: np.ndarray     # (c_pad,)
    last_losses_final: np.ndarray  # (c_pad,)
    cum_evals_final: np.ndarray   # (c_pad,)

    @property
    def stop_round(self) -> Optional[int]:
        """1-based round where termination fired, or None."""
        hit = np.nonzero(self.stop & self.active)[0]
        return int(hit[0]) + 1 if hit.size else None

    @property
    def n_active(self) -> int:
        return int(np.sum(self.active))


class FusedRoundDriver:
    """Stacks the population once; runs R federated rounds per call."""

    def __init__(self, task, spec, backend, *, optimizer: str = "nelder-mead",
                 seed: int = 0, lam: float = 0.1, mu: float = 0.01,
                 use_llm: bool = False, teacher_probs: Optional[List] = None,
                 llm_losses: Optional[Sequence[float]] = None,
                 maxiter0: int = 10, maxiter_cap: int = 100,
                 regulation: str = "adaptive", select_frac: float = 1.0,
                 epsilon: float = 1e-3, n_rounds: int = 10,
                 early_stop: bool = True, patience: int = 1,
                 c_round: Optional[int] = None, dropout: float = 0.0,
                 n_devices: Optional[int] = None):
        C = task.n_clients
        if c_round is not None:
            c_round = int(c_round)
            if not 1 <= c_round <= C:
                raise ValueError(
                    f"c_round={c_round} must be in [1, C_pop={C}]")
            if c_round == C:
                c_round = None            # full participation
        if not 0.0 <= dropout < 1.0:
            raise ValueError(f"dropout={dropout} must be in [0, 1)")
        if use_llm and (teacher_probs is None or llm_losses is None):
            raise ValueError("use_llm=True needs teacher_probs and "
                             "llm_losses from the LLM fine-tuning stage")

        self._mesh = None
        c_pad = C
        if n_devices is not None and int(n_devices) > 1:
            self._mesh = shd.client_mesh(int(n_devices))
            c_pad = shd.pad_client_count(C, int(n_devices))
            if c_round is not None:
                # the gathered cohort is what shards per round — it must
                # divide the mesh (no padding inside the scan body)
                shd.check_client_divisibility(c_round, int(n_devices))

        n_cls = task.n_classes
        b_max = max(cl.n for cl in task.clients)
        qX = np.zeros((c_pad, b_max, spec.n_qubits), np.float32)
        qy = np.zeros((c_pad, b_max), np.int32)
        mask = np.zeros((c_pad, b_max), np.float32)
        teacher = np.full((c_pad, b_max, n_cls), 1.0 / n_cls, np.float32)
        for i, cl in enumerate(task.clients):
            qX[i, :cl.n] = cl.qX
            qy[i, :cl.n] = cl.qy
            mask[i, :cl.n] = 1.0
            if teacher_probs is not None and teacher_probs[i] is not None:
                teacher[i, :cl.n] = np.asarray(teacher_probs[i], np.float32)

        # same budget-record width rule as the orchestrator's engine:
        # regulation can boost budgets up to the cap; without the LLM
        # they stay at maxiter0 (SPSA ignores unused delta rows and NM's
        # loop bound is min(max(iters), max_iter), so a wider record is
        # behavior-identical — just wasted delta memory)
        max_iter = max(maxiter_cap, maxiter0) if use_llm else maxiter0
        if optimizer == "spsa":
            deltas = np.ones((c_pad, max_iter, spec.n_params), np.float64)
            deltas[:C] = make_deltas([seed * 997 + i for i in range(C)],
                                     max_iter, spec.n_params)
            self._deltas = jnp.asarray(deltas, jnp.float32)
        else:
            self._deltas = jnp.zeros((c_pad, 1, 1), jnp.float32)

        weights = np.zeros((c_pad,), np.float32)
        weights[:C] = np.asarray(task.weights, np.float32)
        evaltime = np.zeros((c_pad,), np.float32)
        evaltime[:C] = [backend.eval_time(cl.n) for cl in task.clients]
        llm = np.zeros((c_pad,), np.float32)
        if llm_losses is not None:
            llm[:C] = np.asarray(llm_losses, np.float32)
        budgets0 = np.zeros((c_pad,), np.int32)
        budgets0[:C] = int(maxiter0)
        last0 = np.full((c_pad,), np.inf, np.float32)
        cum0 = np.zeros((c_pad,), np.int32)

        self._qX, self._qy = jnp.asarray(qX), jnp.asarray(qy)
        self._mask = jnp.asarray(mask)
        self._teacher = jnp.asarray(teacher)
        self._weights = jnp.asarray(weights)
        self._evaltime = jnp.asarray(evaltime)
        self._llm = jnp.asarray(llm)
        self._budgets0 = jnp.asarray(budgets0)
        self._last0 = jnp.asarray(last0)
        self._cum0 = jnp.asarray(cum0)
        self._val_qX = jnp.asarray(task.val_qX, jnp.float32)
        self._val_qy = jnp.asarray(task.val_qy, jnp.int32)
        self._test_qX = jnp.asarray(task.test_qX, jnp.float32)
        self._test_qy = jnp.asarray(task.test_qy, jnp.int32)
        self._base_key = jax.random.PRNGKey(seed)

        if self._mesh is not None:
            stacks = (self._qX, self._qy, self._mask, self._teacher,
                      self._deltas, self._weights, self._evaltime,
                      self._llm, self._budgets0, self._last0, self._cum0)
            if c_round is not None:
                # population mode: REPLICATE the population state and
                # shard only the gathered per-round cohort (the compute).
                # Sharding the (C_pop, …) stacks makes every round's
                # dynamic cohort gather and carry scatter a cross-device
                # collective chain inside the scan, which costs more
                # than the round itself (bench_population measured the
                # sharded-stack layout at 0.84× the host loop; the
                # replicated layout beats it).  Full participation keeps
                # the sharded stacks — there the stacks ARE the round.
                placed = tuple(shd.put_replicated(self._mesh, a)
                               for a in stacks)
            else:
                placed = shd.put_client_stacks(self._mesh, stacks, c_pad)
            (self._qX, self._qy, self._mask, self._teacher, self._deltas,
             self._weights, self._evaltime, self._llm, self._budgets0,
             self._last0, self._cum0) = placed
            (self._val_qX, self._val_qy, self._test_qX,
             self._test_qy) = (shd.put_replicated(self._mesh, a)
                               for a in (self._val_qX, self._val_qy,
                                         self._test_qX, self._test_qy))

        self.task, self.spec, self.backend = task, spec, backend
        self.c_pop, self.c_pad, self.c_round = C, c_pad, c_round
        self.c_width = c_round if c_round is not None else c_pad
        self.dropout, self.seed = float(dropout), int(seed)
        self.optimizer, self.max_iter = optimizer, max_iter
        self.use_llm, self.n_rounds = use_llm, int(n_rounds)
        self.init_evals = 1 if optimizer == "spsa" else spec.n_params + 1
        self._cfg = dict(
            lam=lam, mu=mu, use_llm=use_llm, optimizer=optimizer,
            max_iter=max_iter, regulation=regulation,
            maxiter_cap=maxiter_cap, select_frac=select_frac,
            epsilon=epsilon, patience=patience, n_rounds=int(n_rounds),
            early_stop=early_stop, c_pop=C, c_pad=c_pad, c_round=c_round,
            dropout=float(dropout))
        self.program = get_fused_program(spec, backend, mesh=self._mesh,
                                         **self._cfg)
        self._fwd = None          # host-reference lazies
        self._local_jit = None
        self._calls = 0           # run() calls: the host step number

    # -- fused path ---------------------------------------------------------
    def program_args(self, theta_g) -> tuple:
        """The fused program's arguments for a run from ``theta_g``."""
        th = jnp.asarray(theta_g, jnp.float32)
        if self._mesh is not None:
            th = shd.put_replicated(self._mesh, th)
        return (th, self._budgets0, self._last0, self._cum0, self._qX,
                self._qy, self._mask, self._teacher, self._deltas,
                self._weights, self._evaltime, self._llm, self._val_qX,
                self._val_qy, self._test_qX, self._test_qy, self._base_key)

    def run(self, theta_g) -> FusedRunOutput:
        """All R rounds as one program execution; one device→host
        transfer for the whole run's outputs."""
        self._calls += 1
        with jax.profiler.StepTraceAnnotation(tel.QFL_ROUNDS,
                                              step_num=self._calls):
            with jax.profiler.TraceAnnotation(tel.QFL_ROUNDS_ARGS):
                args = self.program_args(theta_g)
            with jax.profiler.TraceAnnotation(tel.QFL_ROUNDS_DISPATCH):
                out = self.program(*args)
            with jax.profiler.TraceAnnotation(tel.QFL_ROUNDS_FETCH,
                                              bytes=tel.nbytes(out)):
                host = jax.device_get(out)
            with jax.profiler.TraceAnnotation(tel.QFL_ROUNDS_UNPACK):
                return FusedRunOutput(**{k: np.asarray(v)
                                         for k, v in host.items()})

    def compiled_text(self, theta_g=None) -> str:
        """The optimized HLO text of the fused program for a run from
        ``theta_g`` (zeros by default; the compilation cache serves it)."""
        if theta_g is None:
            theta_g = np.zeros(self.spec.n_params, np.float32)
        return self.program.lower(
            *self.program_args(theta_g)).compile().as_text()

    # -- host-reference path (the per-round loop baseline / oracle) ---------
    def _host_round_pieces(self):
        if self._local_jit is None:
            lp = build_local_phase(
                self.spec, self.backend, lam=self._cfg["lam"],
                mu=self._cfg["mu"], use_llm=self.use_llm,
                optimizer=self.optimizer, max_iter=self.max_iter)
            self._local_jit = jax.jit(
                lambda qX, qy, mask, teacher, thg, iters, ckeys, deltas,
                active: lp(qX, qy, mask, teacher, thg, iters, ckeys,
                           deltas=deltas, active=active))
            self._fwd = tape_mod.make_tape_forward(self.spec)
        return self._local_jit, self._fwd

    def run_host_reference(self, theta_g) -> FusedRunOutput:
        """The status-quo per-round host loop over the same population
        semantics: one jitted program per round for the local phase, but
        regulation / selection / aggregation / termination on host via
        the reference modules (``regulation.regulate``, the stable-sort
        selection rule, ``TerminationCriterion``, float64 FedAvg) and
        the orchestrator-style per-client report evals (one device→host
        transfer per client per round).  The fused program must match
        it round-for-round; ``bench_population`` times it as the
        baseline."""
        cfg = self._cfg
        local, fwd = self._host_round_pieces()
        sampling = self.backend.shots > 0
        base = self._base_key
        C, c_pad, c_width = self.c_pop, self.c_pad, self.c_width
        R = self.n_rounds
        subsample = self.c_round is not None
        select_on = self.use_llm and cfg["select_frac"] < 1.0

        qX = np.asarray(self._qX)
        qy = np.asarray(self._qy)
        mask = np.asarray(self._mask)
        teacher = np.asarray(self._teacher)
        deltas = np.asarray(self._deltas)
        weights = np.asarray(self._weights, np.float64)
        evaltime = np.asarray(self._evaltime, np.float64)
        llm = np.asarray(self._llm)

        theta = np.asarray(theta_g, np.float64)
        budgets = np.asarray(self._budgets0).copy()
        last = np.asarray(self._last0).copy()
        cum = np.asarray(self._cum0).copy()
        term = TerminationCriterion(epsilon=cfg["epsilon"], t_max=R,
                                    patience=cfg["patience"])

        def znan(shape):
            return np.full(shape, np.nan, np.float32)

        out = dict(
            active=np.zeros(R, bool), stop=np.zeros(R, bool),
            cohort=np.zeros((R, c_width), np.int32),
            dropped=np.zeros((R, c_width), bool),
            selected=np.zeros((R, c_width), bool),
            losses=znan((R, c_width)), ratios=np.ones((R, c_width),
                                                      np.float32),
            n_evals=np.zeros((R, c_width), np.int32),
            budgets=np.zeros((R, c_pad), np.int32),
            cum_evals=np.zeros((R, c_pad), np.int32),
            server_loss_pre=znan(R), server_loss=znan(R), val_acc=znan(R),
            test_acc=znan(R), comm_time_s=np.zeros(R, np.float32),
            theta=np.zeros((R, theta.size), np.float64),
            nm_iters=np.zeros(R, np.int32))

        def nll_host(th, X, y, t, client, slot):
            probs = fwd(jnp.asarray(th, jnp.float32), jnp.asarray(X))
            key = (backend_mod.eval_key(base, t, client, slot)
                   if sampling else None)
            probs = self.backend.transform_probs(probs, key) \
                if sampling else self.backend.apply_channel(probs)
            return float(qnn.nll_loss(probs, jnp.asarray(y)))

        def acc_host(th, X, y, t, slot):
            probs = fwd(jnp.asarray(th, jnp.float32), jnp.asarray(X))
            key = (backend_mod.eval_key(base, t,
                                        backend_mod.SERVER_CLIENT, slot)
                   if sampling else None)
            probs = self.backend.transform_probs(probs, key) \
                if sampling else self.backend.apply_channel(probs)
            return float(qnn.accuracy(probs, jnp.asarray(y)))

        for r in range(R):
            t = r + 1
            if subsample:
                ck = backend_mod.eval_key(base, t, backend_mod.POP_CLIENT,
                                          backend_mod.POP_SLOT_COHORT)
                cohort = np.sort(np.asarray(jax.random.choice(
                    ck, C, (c_width,), replace=False))).astype(np.int32)
                real = np.ones(c_width, bool)
            else:
                cohort = np.arange(c_pad, dtype=np.int32)
                real = cohort < C
            if self.dropout > 0.0:
                u = np.asarray([float(jax.random.uniform(
                    backend_mod.eval_key(base, t, int(cid),
                                         backend_mod.DROPOUT_EVAL_SLOT)))
                    for cid in cohort])
                dropped = (u < self.dropout) & real
            else:
                dropped = np.zeros(c_width, bool)
            eligible = real & ~dropped

            gbud = budgets[cohort].copy()
            if self.use_llm and t > 1:
                for p in np.nonzero(eligible)[0]:
                    cid = int(cohort[p])
                    gbud[p] = regulation_mod.regulate(
                        int(gbud[p]), float(last[cid]), float(llm[cid]),
                        variant=cfg["regulation"], cap=cfg["maxiter_cap"])
            ratios = np.ones(c_width, np.float32)
            if self.use_llm and t > 1:
                fin = np.isfinite(last[cohort]) & (llm[cohort] > 0)
                with np.errstate(invalid="ignore"):
                    ratios = np.where(fin, last[cohort] / llm[cohort],
                                      1.0).astype(np.float32)

            rk = jax.random.fold_in(base, t)
            ckeys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
                rk, jnp.asarray(cohort))
            th_stack, n_evals = local(
                jnp.asarray(qX[cohort]), jnp.asarray(qy[cohort]),
                jnp.asarray(mask[cohort]), jnp.asarray(teacher[cohort]),
                jnp.asarray(theta, jnp.float32), jnp.asarray(gbud),
                ckeys, jnp.asarray(deltas[cohort]), jnp.asarray(eligible))
            th_stack = np.asarray(th_stack, np.float64)
            n_evals = np.asarray(n_evals, np.int32)

            # orchestrator-style reporting: one transfer per client
            losses = np.full(c_width, np.nan, np.float32)
            for p in np.nonzero(eligible)[0]:
                cid = int(cohort[p])
                cl = self.task.clients[cid]
                losses[p] = nll_host(th_stack[p], cl.qX, cl.qy, t, cid,
                                     backend_mod.REPORT_EVAL_SLOT)

            s_pre = nll_host(theta, self.task.val_qX, self.task.val_qy, t,
                             backend_mod.SERVER_CLIENT,
                             backend_mod.SERVER_SLOT_LOSS_PRE)

            if select_on:
                with np.errstate(invalid="ignore"):
                    d = np.abs(losses.astype(np.float64) - s_pre)
                d = np.where(np.isfinite(d) & eligible, d, np.inf)
                n_el = int(np.sum(eligible))
                if self.dropout > 0.0:
                    # mirror the fused program's traced-k f32 form
                    k = int(max(1, np.round(np.float32(cfg["select_frac"])
                                            * np.float32(n_el))))
                else:
                    k = max(1, int(round(cfg["select_frac"]
                                         * (c_width if subsample else C))))
                order = np.argsort(d, kind="stable")[:k]
                sel = np.zeros(c_width, bool)
                sel[order] = True
                sel &= eligible
            else:
                sel = eligible.copy()

            w = np.where(sel, weights[cohort], 0.0)
            if w.sum() > 0:
                wn = w / w.sum()
                theta = sum(wn[p] * th_stack[p]
                            for p in np.nonzero(sel)[0])

            s_post = nll_host(theta, self.task.val_qX, self.task.val_qy,
                              t, backend_mod.SERVER_CLIENT,
                              backend_mod.SERVER_SLOT_LOSS_POST)
            v_acc = acc_host(theta, self.task.val_qX, self.task.val_qy, t,
                             backend_mod.SERVER_SLOT_VAL_ACC)
            t_acc = acc_host(theta, self.task.test_qX, self.task.test_qy,
                             t, backend_mod.SERVER_SLOT_TEST_ACC)

            upd = eligible
            budgets[cohort[upd]] = gbud[upd]
            last[cohort[upd]] = losses[upd]
            cum[cohort[upd]] += n_evals[upd]
            comm = float(np.max(np.where(
                eligible, evaltime[cohort] * (n_evals - self.init_evals),
                0.0), initial=0.0))

            out["active"][r] = True
            out["cohort"][r] = cohort
            out["dropped"][r] = dropped
            out["selected"][r] = sel
            out["losses"][r] = losses
            out["ratios"][r] = ratios
            out["n_evals"][r] = np.where(upd, n_evals, 0)
            out["budgets"][r] = budgets
            out["cum_evals"][r] = cum
            out["server_loss_pre"][r] = s_pre
            out["server_loss"][r] = s_post
            out["val_acc"][r] = v_acc
            out["test_acc"][r] = t_acc
            out["comm_time_s"][r] = comm
            out["theta"][r] = theta
            if self.optimizer == "nelder-mead":
                out["nm_iters"][r] = min(
                    int(np.max(np.where(eligible, gbud, 0))), self.max_iter)

            if term.update(s_post, t):
                out["stop"][r] = True
                if cfg["early_stop"]:
                    break

        return FusedRunOutput(theta_g=np.asarray(theta, np.float32),
                              budgets_final=budgets.copy(),
                              last_losses_final=last.copy(),
                              cum_evals_final=cum.copy(), **out)
