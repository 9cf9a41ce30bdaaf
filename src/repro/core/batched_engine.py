"""Batched federated round engine — one jitted program per local phase.

The sequential orchestrator trains clients one at a time, and every
optimizer evaluation is a host↔device roundtrip (``float(fn(x))``).  This
engine executes the **entire local-training phase of a round** — all
clients, every regulated SPSA iteration, the distillation objective — as
a single compiled device program built from:

  - the circuit tape compiler (``repro.quantum.tape``): the client QNN as
    a tape replayed gate by gate as straight-line code on batched
    statevectors,
  - a device-resident masked optimizer — batched SPSA
    (``repro.optim.batched_spsa``) or batched Nelder–Mead
    (``repro.optim.batched_nm``, the paper's default method run natively:
    speculative (C, n+3, P) candidate batches + masked branch selection),
  - a vmapped per-client objective  F_i + λ·KL(teacher‖student) + µ·prox
    mirroring ``distill.make_client_objective`` term for term.

Padding/mask contract
---------------------
Client shards have ragged sizes, so the engine stacks them once at
construction into dense ``(C, Bmax, …)`` arrays, ``Bmax = max_i n_i``:

  - ``qX``      (C, Bmax, n_qubits)  zero-padded features,
  - ``qy``      (C, Bmax)            zero-padded labels,
  - ``mask``    (C, Bmax)            1.0 on real rows, 0.0 on padding,
  - ``teacher`` (C, Bmax, n_classes) LLM soft labels, uniform on padding.

Every batch reduction is mask-weighted: NLL and KL average as
``Σ mask·term / Σ mask``, so padded rows are evaluated (dense shapes keep
XLA happy) but contribute exactly nothing — a padded client objective
equals its unpadded value.  Padded feature rows are all-zero, a valid
circuit input, so no NaNs leak through ``log``.

Per-client ``maxiter`` budgets become **iteration masks** (see
``batched_spsa`` / ``batched_nm``): the round always compiles to the same
shapes, budgets arrive as a traced ``(C,)`` array, and regulation never
recompiles.  The compiled round program is cached module-wide keyed by
the static config (which includes ``backend.shots`` — keyed sampling
changes the traced program), so fresh engine instances (new runs, tests,
benches) with the same task shape reuse it.

Shot-noise key contract
-----------------------
Finite-shot backends (``backend.shots > 0``) sample **inside** the fused
round program, per evaluation, under the ``backends.py`` derivation

    ``eval_key(PRNGKey(seed), round, client, slot)``

``run_round`` takes the orchestrator's 1-based round index and folds it
with each client id into a ``(C,)`` stack of per-client round keys
(traced inputs — no recompilation across rounds); the batched optimizers
fold in the structural evaluation ``slot``.  The sequential path derives
from the same chain (``orchestrator`` hands ``gradfree`` a per-client
``key_stream``), so on ``fake``/``aersim``/``real`` both engines use the
same key for the same evaluation — noisy parity is draw-for-draw, not
just in distribution.  (Identical keys make identical draws whenever the
two forwards agree on the sampled CDF; the tape and eager forwards
differ by ~2e-7 ulp noise, so a uniform draw landing inside that sliver
of a class boundary could in principle flip one shot — the parity tests
pin seeds where no draw does.)  With ``shots == 0`` the keys are inert
and the objective is the deterministic channel.

The sequential path remains the parity reference for both optimizers:
branch decisions, trajectories, and eval counts of the batched
Nelder–Mead match ``gradfree.nm_run`` decision-for-decision
(``tests/test_batched_nm.py`` / ``tests/test_batched_engine.py``).

Sharding-safety invariants (the 'clients' mesh axis)
----------------------------------------------------
With ``n_devices > 1`` the engine lays its ``(C, …)`` stacks across a
1-D ``'clients'`` device mesh (``distributed/sharding.py``) and lets the
jitted round program partition by computation-follows-data.  This is
safe because the round program preserves two invariants that sharding
relies on — keep them when editing this module or the batched
optimizers:

  1. **Per-client independence until aggregation.**  Nothing inside
     ``round_fn`` reduces, gathers, or permutes across the client axis;
     every op is elementwise or batched along ``C`` (the one exception,
     ``max(iters)`` for the shared loop bound, is a scalar all-reduce
     before the loop starts).  Each device therefore advances its slice
     of clients through the full NM/SPSA inner loop with zero
     cross-device collectives; the only cross-client mixing is the
     orchestrator's host-side weighted aggregation after ``run_round``
     returns.
  2. **Key folding is position-, not order-, dependent.**  Client
     ``c``'s round key is ``fold_in(fold_in(base, round), c)`` — a pure
     function of the client *id*, never of evaluation order or of which
     device holds the shard.  Sharding (or padding) the client axis
     must not renumber clients: real clients keep ids ``0..C-1`` and
     padding rows are appended after them, so every real client draws
     the same shots wherever it lands.

Ragged client counts are padded (``sharding.pad_client_count``) with
**inert** clients — all-zero masks, zero iteration budgets, uniform
teacher rows — and sliced off the outputs; the masked-mean denominator
is clamped to 1 so an all-padding client stays finite (bitwise inert
for real clients, whose mask sum is always >= 1).  With one device (or
``n_devices=None``) nothing is padded or placed and behavior is
identical to PR 1–3.

What "parity" means for the sharded round: the key draws are identical
by construction (invariant 2), and every client's program is the same
math — but XLA re-vectorizes within-client reductions for the
per-shard leading dim, which can shift noiseless f32 sums by
arithmetic-order noise (~2e-7, the same class as the documented
tape-vs-eager gap).  Paths that quantize — the NM branch ladder,
finite-shot sampling — absorb it, so sharded == single-device
**bitwise** at pinned seeds for Nelder–Mead and for ``shots > 0``
runs; noiseless SPSA (whose update consumes raw f differences) agrees
to ~1e-6 with identical draw/eval/branch accounting
(``tests/test_client_sharding.py`` pins each cell of that matrix).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import sharding as shd
from repro.optim.batched_nm import batched_nm, best_point
from repro.optim.batched_spsa import batched_spsa, make_deltas
from repro.quantum import tape as tape_mod

_ROUND_CACHE: Dict[tuple, object] = {}


def build_local_phase(spec, backend, *, lam: float, mu: float,
                      use_llm: bool, optimizer: str = "spsa",
                      max_iter: int = 100):
    """Traceable local-training phase — the round program's body.

    Returns ``local_phase(qX, qy, mask, teacher, theta_g, iters, ckeys,
    deltas=None, active=None) → (x (C, P) f32, n_evals (C,) int32)``,
    pure and jit-free: ``_build_round_fn`` wraps it in ``jax.jit`` for
    the per-round engine, and ``core/fused_rounds.py`` calls it inside
    its ``lax.scan`` body so the fused multi-round driver runs exactly
    the same math as the per-round program.

    ``deltas`` is required for SPSA (ignored by NM); ``active`` is the
    optional (C,) participation mask threaded to the batched optimizer
    (inactive clients keep ``theta_g`` and spend 0 evals; ``None`` is
    bitwise the all-active path).  ``ckeys`` is the (C,) per-client
    round-key stack (see the module's shot-noise key contract); inert
    when ``backend.shots == 0``.
    """
    cq = tape_mod.compile_qnn(spec)
    eps = 1e-9
    sampling = backend.shots > 0

    def client_objective(theta, Xc, yc, mc, tc, theta_g, ckey, slot):
        """F_i + λ·KL + µ·prox for ONE client on its padded shard."""
        probs = tape_mod.tape_probs(cq, theta, Xc)      # raw (B, cls)
        if sampling:
            noisy = backend.transform_probs(
                probs, jax.random.fold_in(ckey, slot))
        else:
            noisy = backend.apply_channel(probs)
        # clamp: all-padding clients (ragged C on a mesh) have Σmask = 0
        # and must stay finite; real clients have Σmask >= 1, for which
        # the maximum is bitwise inert
        m_sum = jnp.maximum(jnp.sum(mc), 1.0)
        p = jnp.take_along_axis(noisy, yc[:, None], axis=1)[:, 0]
        loss = -jnp.sum(jnp.log(p + eps) * mc) / m_sum  # masked NLL
        if use_llm and lam > 0:
            pt = jnp.clip(tc, eps, 1.0)                 # KL on raw probs
            ps = jnp.clip(probs, eps, 1.0)
            rows = jnp.sum(pt * (jnp.log(pt) - jnp.log(ps)), axis=-1)
            loss = loss + lam * jnp.sum(rows * mc) / m_sum
        if use_llm and mu > 0:
            loss = loss + mu * jnp.mean((theta - theta_g) ** 2)
        return loss

    vobj = jax.vmap(client_objective,
                    in_axes=(0, 0, 0, 0, 0, None, 0, None))

    def prep(qX, qy, mask, teacher, theta_g, ckeys):
        """Shared per-round start stack + closed-over objective.

        The objective is keyed (``f(xs, slot)``) iff the backend
        samples; the batched optimizers drive the slot schedule.
        """
        x0 = jnp.tile(theta_g[None, :], (qX.shape[0], 1))

        if sampling:
            def f(xs, slot):
                return vobj(xs, qX, qy, mask, teacher, theta_g,
                            ckeys, slot)
        else:
            def f(xs):
                return vobj(xs, qX, qy, mask, teacher, theta_g,
                            ckeys, jnp.int32(0))

        return x0, f

    if optimizer == "nelder-mead":
        def local_phase(qX, qy, mask, teacher, theta_g, iters, ckeys,
                        deltas=None, active=None):
            x0, f = prep(qX, qy, mask, teacher, theta_g, ckeys)
            simplex, fvals, n_evals, _, _ = batched_nm(
                f, x0, iters, int(max_iter), keyed=sampling, active=active)
            x, _ = best_point(simplex, fvals)
            if active is not None:
                # an untouched init simplex's best vertex is an offset
                # row, not x0 — inactive clients must return their start
                x = jnp.where(active[:, None], x, x0)
            return x, n_evals
    elif optimizer == "spsa":
        def local_phase(qX, qy, mask, teacher, theta_g, iters, ckeys,
                        deltas=None, active=None):
            x0, f = prep(qX, qy, mask, teacher, theta_g, ckeys)
            x, _, n_evals = batched_spsa(f, x0, iters, deltas,
                                         keyed=sampling, active=active)
            if active is not None:
                x = jnp.where(active[:, None], x, x0)
            return x, n_evals
    else:
        raise ValueError(f"unknown batched optimizer {optimizer!r}")

    return local_phase


def _build_round_fn(spec, backend, lam: float, mu: float, use_llm: bool,
                    optimizer: str = "spsa", max_iter: int = 100):
    """Jitted per-round wrapper over ``build_local_phase`` →
    (x (C,P), n_evals (C,)).

    spsa        : (qX, qy, mask, teacher, θ_g, iters, deltas, ckeys)
    nelder-mead : (qX, qy, mask, teacher, θ_g, iters, ckeys) —
                  ``max_iter`` is a static bound (branch-record width),
                  budgets stay traced.
    """
    lp = build_local_phase(spec, backend, lam=lam, mu=mu, use_llm=use_llm,
                           optimizer=optimizer, max_iter=max_iter)
    if optimizer == "nelder-mead":
        @jax.jit
        def round_fn(qX, qy, mask, teacher, theta_g, iters, ckeys):
            return lp(qX, qy, mask, teacher, theta_g, iters, ckeys)
    else:
        @jax.jit
        def round_fn(qX, qy, mask, teacher, theta_g, iters, deltas, ckeys):
            return lp(qX, qy, mask, teacher, theta_g, iters, ckeys,
                      deltas=deltas)
    return round_fn


def get_round_fn(spec, backend, *, lam: float, mu: float, use_llm: bool,
                 optimizer: str = "spsa", max_iter: int = 100):
    # max_iter only shapes the NM branch record — keep SPSA keys stable.
    # backend (frozen dataclass) already hashes shots; the explicit
    # element documents that sampling is part of the program's identity.
    key = (spec, backend, int(backend.shots), float(lam), float(mu),
           bool(use_llm), optimizer,
           int(max_iter) if optimizer == "nelder-mead" else None)
    if key not in _ROUND_CACHE:
        _ROUND_CACHE[key] = _build_round_fn(spec, backend, lam, mu,
                                            use_llm, optimizer, max_iter)
    return _ROUND_CACHE[key]


class BatchedRoundEngine:
    """Stacks client data once; runs each round's local phase on device."""

    def __init__(self, task, spec, backend, *, lam: float, mu: float,
                 use_llm: bool, teacher_probs: Optional[List] = None,
                 seeds: Sequence[int] = (), max_iter: int = 100,
                 optimizer: str = "spsa", seed: int = 0,
                 n_devices: Optional[int] = None):
        C = task.n_clients
        n_cls = task.n_classes
        b_max = max(cl.n for cl in task.clients)

        # 'clients' mesh: shard the stacks' leading axis across devices
        # (see the module docstring's sharding-safety invariants); one
        # device (the default) skips padding and placement entirely.
        self._mesh = None
        c_pad = C
        if n_devices is not None and int(n_devices) > 1:
            self._mesh = shd.client_mesh(int(n_devices))
            c_pad = shd.pad_client_count(C, int(n_devices))

        qX = np.zeros((c_pad, b_max, spec.n_qubits), np.float32)
        qy = np.zeros((c_pad, b_max), np.int32)
        mask = np.zeros((c_pad, b_max), np.float32)
        teacher = np.full((c_pad, b_max, n_cls), 1.0 / n_cls, np.float32)
        for i, cl in enumerate(task.clients):
            qX[i, :cl.n] = cl.qX
            qy[i, :cl.n] = cl.qy
            mask[i, :cl.n] = 1.0
            if teacher_probs is not None and teacher_probs[i] is not None:
                teacher[i, :cl.n] = np.asarray(teacher_probs[i],
                                               np.float32)
        self._qX, self._qy = jnp.asarray(qX), jnp.asarray(qy)
        self._mask, self._teacher = jnp.asarray(mask), jnp.asarray(teacher)
        self._optimizer = optimizer
        if optimizer == "spsa":
            # padding clients never update (zero budgets) but their delta
            # rows are still indexed every masked iteration — keep them
            # valid Rademacher signs, not zeros (0 ⇒ 1/δ = inf)
            deltas = np.ones((c_pad, max_iter, spec.n_params), np.float64)
            deltas[:C] = make_deltas(seeds, max_iter, spec.n_params)
            self._deltas = jnp.asarray(deltas, jnp.float32)
        else:
            self._deltas = None        # NM is deterministic — no draws
        # sequential-path evals spent before the metered run: spsa_init
        # does 1, nm_init does n+1 (the initial simplex)
        self.init_evals = 1 if optimizer == "spsa" else spec.n_params + 1
        # shot-noise key chain root: fold_in(round)/fold_in(client) happen
        # per run_round, fold_in(slot) inside the optimizers
        self._base_key = jax.random.PRNGKey(seed)
        self._n_clients = C
        self._c_pad = c_pad
        if self._mesh is not None:
            stacks = (self._qX, self._qy, self._mask, self._teacher)
            if self._deltas is not None:
                stacks = stacks + (self._deltas,)
            placed = shd.put_client_stacks(self._mesh, stacks, c_pad)
            (self._qX, self._qy, self._mask, self._teacher,
             *rest) = placed
            if rest:
                self._deltas = rest[0]
        self._round = get_round_fn(spec, backend, lam=lam, mu=mu,
                                   use_llm=use_llm, optimizer=optimizer,
                                   max_iter=max_iter)

    def run_round(self, theta_g: np.ndarray, maxiters: Sequence[int],
                  round_idx: int = 0) -> Tuple[np.ndarray, np.ndarray]:
        """One local-training phase for all clients.

        ``round_idx`` is the orchestrator's 1-based round counter — the
        ``round`` stage of the key-derivation contract.  Returns
        (thetas (C, P) float64, n_evals (C,) int) — the trained
        per-client parameters and the sequential-equivalent evaluation
        counts (``init_evals`` + the metered run's branch-dependent spend)
        for comm accounting.

        On a client mesh the per-round inputs are placed like the
        stacks (budgets/keys along 'clients', θ_g replicated) and the
        padding rows — zero budgets, key ids ``C..c_pad-1`` that fold
        *after* every real client's id — are sliced off the outputs.
        """
        rk = jax.random.fold_in(self._base_key, round_idx)
        ckeys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            rk, jnp.arange(self._c_pad))
        iters = np.zeros((self._c_pad,), np.int32)
        iters[:self._n_clients] = np.asarray(maxiters, np.int32)
        theta_g = jnp.asarray(theta_g, jnp.float32)
        iters = jnp.asarray(iters)
        if self._mesh is not None:
            # θ_g is replicated explicitly: its leading dim (n_params)
            # must never be mistaken for a client axis by shape inference
            theta_g = shd.put_replicated(self._mesh, theta_g)
            iters, ckeys = shd.put_client_stacks(
                self._mesh, (iters, ckeys), self._c_pad)
        args = [self._qX, self._qy, self._mask, self._teacher,
                theta_g, iters]
        if self._optimizer == "spsa":
            args.append(self._deltas)
        args.append(ckeys)
        x, n_evals = self._round(*args)
        C = self._n_clients
        return (np.asarray(x, np.float64)[:C],
                np.asarray(n_evals, np.int64)[:C])
