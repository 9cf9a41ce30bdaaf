"""Evaluations the program reports over the candidates batched
Nelder-Mead evaluates in lockstep: per round, every client's initial
simplex (P+1) and, for as many iterations as the largest budget, P+3
candidates per client.  Counts of the first call; every call repeats it."""


def read(ctx):
    c = ctx.counts
    C, P = c["clients"], c["n_params"]
    useful = sum(sum(row) for row in c["n_evals"])
    lockstep = sum(C * (P + 1) + min(max(b), c["max_iter"]) * C * (P + 3)
                   for b in c["budgets"])
    return 100.0 * useful / lockstep
