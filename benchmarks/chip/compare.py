"""The numbers that decide ``correct``: gaps between what the timed path
produced and what the plain reference computes from the same inputs.

A gap of norms is taken leaf by leaf, per client: the gap between the
program's norm and the reference's, over the reference's norm of that
leaf or of the median leaf, whichever is larger; the number is the worst
leaf's (and, as ``*_median``, the median leaf's).  Leaves whose
reference gradient is nought to rounding move by round-off alone: a leaf
is left out where the reference's AdamW first moment is under a
thousandth of the median leaf's.

Each function returns more numbers than a cell compares: the cell's
``limits/<cell>.json`` names those compared, and the others are read by
``calibrate.py`` alone (PERF.md gives why each cell compares what it
does).
"""
from __future__ import annotations

import numpy as np

NEGLIGIBLE = 1e-3


def _leaves(tree) -> dict:
    """{path: array} of a nested dict/tuple of arrays."""
    out = {}

    def walk(t, path):
        if isinstance(t, dict):
            for k in sorted(t):
                walk(t[k], path + (k,))
        elif isinstance(t, (tuple, list)):
            for i, v in enumerate(t):
                walk(v, path + (i,))
        else:
            out[path] = np.asarray(t, np.float64)
    walk(tree, ())
    return out


def per_client_leaves(stacked) -> list:
    """A client-stacked tree as one {path: array} per client."""
    leaves = _leaves(stacked)
    n = next(iter(leaves.values())).shape[0]
    return [{p: a[c] for p, a in leaves.items()} for c in range(n)]


def norm_gaps(prog: list, ref: list, keep: set) -> np.ndarray:
    """Relative gaps of per-(client, leaf) norms over ``keep``."""
    rn = {k: np.linalg.norm(ref[k[0]][k[1]]) for k in keep}
    pn = {k: np.linalg.norm(prog[k[0]][k[1]]) for k in keep}
    med = float(np.median(list(rn.values())))
    return np.array([abs(pn[k] - rn[k]) / max(rn[k], med) for k in keep])


def moving_leaves(ref_moments: list) -> set:
    """(client, path) of the leaves whose reference gradient is not
    nought to rounding."""
    norms = {(c, p): np.linalg.norm(a)
             for c, leaves in enumerate(ref_moments)
             for p, a in leaves.items()}
    med = float(np.median(list(norms.values())))
    return {k for k, v in norms.items() if v >= NEGLIGIBLE * med}


def llm_stage(prog: dict, ref: dict, a0: list) -> dict:
    """``prog`` and ``ref`` hold per-client leaf dicts (``adapters``,
    ``moments``) and per-client arrays (``train_loss``, ``eval_loss``,
    ``teacher``); ``a0`` the initial adapters, per client."""
    keep = moving_leaves(ref["moments"])
    n = len(ref["moments"])

    def moved(side):
        return [{p: side["adapters"][c][p] - a0[c][p] for p in a0[c]}
                for c in range(n)]

    change = norm_gaps(moved(prog), moved(ref), keep)
    moment = norm_gaps(prog["moments"], ref["moments"], keep)
    gap = {k: np.abs(np.asarray(prog[k], np.float64) - ref[k])
           for k in ("train_loss", "eval_loss", "teacher")}
    return {
        "train_loss": float(gap["train_loss"].max()),
        "eval_loss": float(gap["eval_loss"].max()),
        "teacher": float(gap["teacher"].max()),
        "adapter_change": float(change.max()),
        "first_moment": float(moment.max()),
        "first_moment_median": float(np.median(moment)),
    }


def rounds(prog, ref: dict, theta0, n_clients: int) -> dict:
    """``prog`` is the program's ``FusedRunOutput``, ``ref`` the
    reference's per-round arrays; both from ``theta0``.  Per round ``t``
    (``_r<t>``): the gaps of the server's loss after the average and of
    the clients' reported losses, the gap of the global parameters'
    change from ``theta0`` over the reference's, and how many clients'
    evaluation counts differ; over all rounds, how many budgets differ."""
    C = n_clients
    t0 = np.asarray(theta0, np.float64)
    out = {"budgets_differ": float(np.sum(prog.budgets[:, :C]
                                          != ref["budgets"]))}
    for t in range(len(ref["server_loss"])):
        ch_p = np.linalg.norm(np.asarray(prog.theta[t], np.float64) - t0)
        ch_r = np.linalg.norm(np.asarray(ref["theta"][t], np.float64) - t0)
        r = f"_r{t + 1}"
        out["server_loss" + r] = float(abs(prog.server_loss[t]
                                           - ref["server_loss"][t]))
        out["client_loss" + r] = float(np.max(np.abs(
            prog.losses[t, :C] - ref["losses"][t])))
        out["theta_change" + r] = float(abs(ch_p - ch_r) / ch_r)
        out["evals_differ" + r] = float(np.sum(prog.n_evals[t, :C]
                                               != ref["n_evals"][t]))
    return out
