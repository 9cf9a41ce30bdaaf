"""Plain reference of R federated LLM-QFL rounds of quantum clients.

Written from the paper's description, with nothing of the program
imported.  A client is a VQC: Qiskit's ZZFeatureMap (``fm_reps``
repetitions of H and P(2 x_i) on every qubit, then for every pair i < j
CX(i, j), P(2 (pi - x_i)(pi - x_j)) on j, CX(i, j)) and RealAmplitudes
(``ansatz_reps`` layers of RY on every qubit and CX on every pair i < j,
then a last RY layer), read out as class probabilities by the parity of
the measured bitstring.  Statevectors are complex64; every contraction
asks for ``precision``.

A round: each client minimises its local objective (NLL of its labels,
plus ``lam`` times the KL divergence from the LLM's soft labels, plus
``mu`` times the mean squared distance from the global parameters) by
Nelder-Mead from the global parameters, one lazy evaluation at a time
(initial simplex: coordinate i offset by 0.25, or 0.25 |x_i| + 0.25;
reflect 1, expand 2, inside contraction 0.5, shrink 0.5 toward the best
vertex), within its budget of iterations; its reported loss is the NLL
of its result.  The server's loss on the validation set is taken before
and after the weighted average of all clients' results.  From round 2
on, a client whose last loss exceeds its LLM's loss scales its budget
by that ratio (rounded half to even, clamped to [1, cap]).

Host-side arithmetic (simplexes, averages, budgets) is float32, as the
program states for its rounds.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-9
_H = np.array([[1, 1], [1, -1]], np.complex64) / np.sqrt(2)
_CX = np.zeros((2, 2, 2, 2), np.complex64)       # [c', t', c, t]
for _c in range(2):
    for _t in range(2):
        _CX[_c, _t ^ _c, _c, _t] = 1


def gate_counts(n: int, fm_reps: int, ansatz_reps: int):
    """(one-qubit, two-qubit) gates of one circuit evaluation."""
    pairs = n * (n - 1) // 2
    one = fm_reps * (2 * n + pairs) + n * (ansatz_reps + 1)
    two = fm_reps * 2 * pairs + ansatz_reps * pairs
    return one, two


def _one(psi, u, q, prec):
    """u (B, 2, 2) on qubit q of psi (B, 2, ..., 2)."""
    psi = jnp.moveaxis(psi, 1 + q, 1)
    out = jnp.einsum("zab,zb...->za...", u, psi, precision=prec)
    return jnp.moveaxis(out, 1, 1 + q)


def _cx(psi, c, t, prec):
    psi = jnp.moveaxis(psi, (1 + c, 1 + t), (1, 2))
    out = jnp.einsum("ABab,zab...->zAB...", jnp.asarray(_CX), psi,
                     precision=prec)
    return jnp.moveaxis(out, (1, 2), (1 + c, 1 + t))


def _phase(phi):
    one = jnp.ones_like(phi, jnp.complex64)
    zero = jnp.zeros_like(phi, jnp.complex64)
    return jnp.stack([jnp.stack([one, zero], -1),
                      jnp.stack([zero, jnp.exp(1j * phi.astype(jnp.complex64))],
                                -1)], -2)


def _ry(theta, B):
    c = jnp.cos(theta / 2).astype(jnp.complex64)
    s = jnp.sin(theta / 2).astype(jnp.complex64)
    return jnp.broadcast_to(jnp.stack([jnp.stack([c, -s]), jnp.stack([s, c])]),
                            (B, 2, 2))


def class_probs(theta, X, *, fm_reps, ansatz_reps, n_classes, prec):
    B, n = X.shape
    psi = jnp.zeros((B,) + (2,) * n, jnp.complex64)
    psi = psi.at[(slice(None),) + (0,) * n].set(1)
    h = jnp.broadcast_to(jnp.asarray(_H), (B, 2, 2))
    pairs = [(i, j) for i in range(n) for j in range(i + 1, n)]
    for _ in range(fm_reps):
        for q in range(n):
            psi = _one(psi, h, q, prec)
            psi = _one(psi, _phase(2.0 * X[:, q]), q, prec)
        for i, j in pairs:
            psi = _cx(psi, i, j, prec)
            psi = _one(psi, _phase(2.0 * (jnp.pi - X[:, i])
                                   * (jnp.pi - X[:, j])), j, prec)
            psi = _cx(psi, i, j, prec)
    th = theta.reshape(ansatz_reps + 1, n)
    for r in range(ansatz_reps):
        for q in range(n):
            psi = _one(psi, _ry(th[r, q], B), q, prec)
        for i, j in pairs:
            psi = _cx(psi, i, j, prec)
    for q in range(n):
        psi = _one(psi, _ry(th[ansatz_reps, q], B), q, prec)
    p = (jnp.abs(psi) ** 2).reshape(B, -1)
    idx = np.arange(2 ** n)
    parity = np.array([bin(k).count("1") for k in idx]) % n_classes
    onehot = jnp.asarray(np.eye(n_classes, dtype=np.float32)[parity])
    return jnp.einsum("zk,kc->zc", p, onehot, precision=prec)


@functools.partial(jax.jit, static_argnums=(5,))
def _objective(theta, X, y, teacher, theta_g, static):
    """(local objective, NLL) of one client at ``theta``."""
    fm, ar, nc, lam, mu, prec = static
    p = class_probs(theta, X, fm_reps=fm, ansatz_reps=ar, n_classes=nc,
                    prec=prec)
    nll = -jnp.mean(jnp.log(jnp.take_along_axis(p, y[:, None], 1)[:, 0]
                            + EPS))
    pt = jnp.clip(teacher, EPS, 1.0)
    kl = jnp.mean(jnp.sum(pt * (jnp.log(pt) - jnp.log(jnp.clip(p, EPS, 1.0))),
                          -1))
    return nll + lam * kl + mu * jnp.mean((theta - theta_g) ** 2), nll


def nelder_mead(f, x0: np.ndarray, iters: int, *, step=0.25, alpha=1.0,
                gamma=2.0, rho=0.5, sigma=0.5):
    """Lazy Nelder-Mead; returns (best x, evaluations)."""
    n = x0.size
    f32 = np.float32
    off = np.where(x0 == 0, f32(step), f32(step) * np.abs(x0) + f32(step))
    sx = np.stack([x0] + [x0 + np.eye(n, dtype=f32)[i] * off[i]
                          for i in range(n)]).astype(f32)
    sf = np.array([f(x) for x in sx], f32)
    evals = n + 1
    for _ in range(iters):
        order = np.argsort(sf, kind="stable")
        sx, sf = sx[order], sf[order]
        best, worst = sx[0], sx[-1]
        c = np.mean(sx[:-1], axis=0, dtype=f32)
        xr = (c + f32(alpha) * (c - worst)).astype(f32)
        fr = f(xr)
        if fr < sf[0]:
            xe = (c + f32(gamma) * (xr - c)).astype(f32)
            fe = f(xe)
            sx[-1], sf[-1] = (xe, fe) if fe < fr else (xr, fr)
            evals += 2
        elif fr < sf[-2]:
            sx[-1], sf[-1] = xr, fr
            evals += 1
        else:
            xc = (c + f32(rho) * (worst - c)).astype(f32)
            fc = f(xc)
            if fc < sf[-1]:
                sx[-1], sf[-1] = xc, fc
                evals += 2
            else:
                sx[1:] = (best + f32(sigma) * (sx[1:] - best)).astype(f32)
                sf[1:] = [f(x) for x in sx[1:]]
                evals += 2 + n
    return sx[int(np.argmin(sf))], evals


def regulate(budget: int, q: float, llm: float, cap: int) -> int:
    f32 = np.float32
    if not (llm > 0 and math.isfinite(llm)):
        return budget
    held = int(min(max(budget, 1), cap))
    if not math.isfinite(q) or q <= llm:
        return held
    new = f32(budget) * (f32(q) / f32(llm))
    return int(min(max(int(np.round(new)), 1), cap))


def rounds(fed, teacher, llm_losses, theta0, *, n_rounds, maxiter0,
           maxiter_cap, lam, mu, fm_reps, ansatz_reps,
           precision=jax.lax.Precision.HIGHEST, fault: str = ""):
    """The R rounds from ``theta0``.  ``fault`` plants, for the
    calibration of the limits, ``frozen`` (clients return the global
    parameters), ``half`` (the local objective sees the first half of
    each client's rows) or ``answer`` (client 0's reported loss is
    doubled)."""
    C = fed.n_clients
    static = (fm_reps, ansatz_reps, fed.n_classes, float(lam), float(mu),
              precision)
    w = np.asarray(fed.weights, np.float32)
    w = w / w.sum()
    val = (jnp.asarray(fed.val_qX), jnp.asarray(fed.val_qy))
    zeros_t = jnp.full((len(fed.val_qy), fed.n_classes), 1.0 / fed.n_classes)

    def server(theta):
        return float(_objective(jnp.asarray(theta), *val, zeros_t,
                                jnp.asarray(theta), static)[1])

    theta_g = np.asarray(theta0, np.float32)
    budgets = [int(maxiter0)] * C
    last = [math.inf] * C
    out = {k: [] for k in ("budgets", "n_evals", "losses", "server_loss_pre",
                           "server_loss", "theta")}
    for t in range(1, n_rounds + 1):
        if t > 1:
            budgets = [regulate(budgets[c], last[c], float(llm_losses[c]),
                                maxiter_cap) for c in range(C)]
        xs, evals, losses = [], [], []
        for c, cl in enumerate(fed.clients):
            args = (jnp.asarray(cl.qX), jnp.asarray(cl.qy),
                    jnp.asarray(teacher[c]), jnp.asarray(theta_g))
            rows = cl.n // 2 if fault == "half" else cl.n
            fargs = tuple(a[:rows] for a in args[:3]) + args[3:]

            def f(th, args=fargs):
                return np.float32(_objective(jnp.asarray(th), *args,
                                             static)[0])

            x, e = nelder_mead(f, theta_g, budgets[c])
            if fault == "frozen":
                x = theta_g.copy()
            xs.append(x)
            evals.append(e)
            nll = float(_objective(jnp.asarray(x), *args, static)[1])
            losses.append(2 * nll if fault == "answer" and c == 0 else nll)
        s_pre = server(theta_g)
        theta_g = np.sum(w[:, None] * np.stack(xs), axis=0, dtype=np.float32)
        out["budgets"].append(list(budgets))
        out["n_evals"].append(evals)
        out["losses"].append(losses)
        out["server_loss_pre"].append(s_pre)
        out["server_loss"].append(server(theta_g))
        out["theta"].append(theta_g.copy())
        last = losses
    return {k: np.asarray(v) for k, v in out.items()}
