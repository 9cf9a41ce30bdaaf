"""Quantum execution backends: ideal / noisy simulators / emulated QPU.

Replaces AerSimulator, FakeManila and IBM_Brisbane per DESIGN.md §2:
 - exact:    statevector probabilities (AerSimulator, noise-free)
 - aersim:   depolarizing-by-depth + readout bit-flip noise calibrated to
             the "AerSimulator with IBM_Brisbane noise model" setting
 - fake:     FakeManila-style snapshot (stronger readout error, 5 qubits)
 - real:     same noise as aersim plus queue/latency emulation so the
             communication-time accounting of Table I is reproducible

Each backend transforms *class probabilities* (post-interpret) in two
stages — a deterministic noise channel and keyed finite-shot sampling —
and reports a wall-time estimate per evaluation batch (used by
bench_backends / bench_comm_cost).

Key-derivation contract
-----------------------
Finite-shot sampling is deterministic-by-seed and identical across the
sequential and batched engines.  Every objective evaluation draws its
shots from

    ``eval_key(PRNGKey(seed), round, client, slot)``
    = ``fold_in(fold_in(fold_in(PRNGKey(seed), round), client), slot)``

where ``slot`` is the evaluation's *structural position* in the round's
schedule — not a running counter.  Structural slots are what make
engine parity possible: the batched Nelder–Mead evaluates every
speculative candidate while the sequential method evaluates lazily, so a
counter would desynchronize, but the reflect point of iteration ``i``
always owns the same slot in both engines.  The schedule (``n`` = number
of parameters):

  Nelder–Mead:  init simplex row ``r``            → slot ``r``  (0..n)
                iteration ``i`` (global, resumes included),
                ``base = (n+1) + i·(n+3)``:
                reflect → ``base``, expand → ``base+1``,
                contract → ``base+2``, shrink row ``j`` → ``base+2+j``
  SPSA:         init → slot 0; iteration ``k`` (global):
                f(x+ckδ) → ``1+3k``, f(x−ckδ) → ``2+3k``,
                candidate → ``3+3k``; final polish → ``FINAL_EVAL_SLOT``
  Reporting:    the orchestrator's per-round client-loss report uses
                ``REPORT_EVAL_SLOT`` on the client's stream; server-side
                evaluations use the reserved client id
                ``SERVER_CLIENT`` with slots ``SERVER_SLOT_*``.
  Population:   the fused multi-round driver's per-round cohort
                subsample draws from the reserved ``POP_CLIENT`` stream
                at ``POP_SLOT_COHORT``; a client's dropout coin draws
                from the client's **own** stream at
                ``DROPOUT_EVAL_SLOT`` — a pure function of
                ``(seed, round, client)``, so whether a client drops is
                independent of cohort size or composition and
                participation sweeps at one seed stay comparable.

``apply_channel`` is traceable with no key; ``transform_probs`` *raises*
when ``shots > 0`` and no key is supplied — a finite-shot backend must
never silently fall back to deterministic channel-only evaluation.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

# Reserved slot / client ids of the key-derivation contract (see module
# docstring).  Slots are int32; optimizer schedules use small non-negative
# slots, so the reserved ids live at the edges of the range.
FINAL_EVAL_SLOT = 0x7FFFFFFF      # SPSA's post-loop polish evaluation
REPORT_EVAL_SLOT = 0x7FFFFFFE     # orchestrator per-client loss report
DROPOUT_EVAL_SLOT = 0x7FFFFFFD    # per-round dropout coin on the
                                  # client's own stream (fused driver)
SERVER_CLIENT = 0x7FFFFFFF        # server-side evals (not a device id;
                                  # fold_in coerces to uint32, so ids
                                  # must be non-negative)
POP_CLIENT = 0x7FFFFFFD           # population-control stream: cohort
                                  # subsampling draws (fused driver)
POP_SLOT_COHORT = 0               # per-round cohort subsample draw
SERVER_SLOT_LOSS_PRE = 0          # server loss of θ_g before aggregation
SERVER_SLOT_LOSS_POST = 1         # server loss after aggregation
SERVER_SLOT_VAL_ACC = 2
SERVER_SLOT_TEST_ACC = 3


def eval_key(base_key: jax.Array, round_idx, client, slot) -> jax.Array:
    """The contract's key chain; every argument past the first may be a
    traced integer (usable under ``jit`` / ``vmap`` / ``fori_loop``)."""
    k = jax.random.fold_in(base_key, round_idx)
    k = jax.random.fold_in(k, client)
    return jax.random.fold_in(k, slot)


@dataclass(frozen=True)
class Backend:
    name: str
    depolarizing: float = 0.0     # prob of replacing output by uniform
    readout_flip: float = 0.0     # per-class confusion strength
    shots: int = 0                # 0 = exact probabilities
    # latency model (seconds) — calibrated to Table I comm-time ratios
    t_per_job: float = 0.0        # fixed overhead per optimizer evaluation
    t_per_shot: float = 0.0
    t_queue: float = 0.0          # QPU queue wait per job

    def apply_channel(self, probs: jnp.ndarray) -> jnp.ndarray:
        """Deterministic noise channel on (B, C) class probabilities.

        Traceable, key-free: safe inside ``vmap``/``fori_loop`` bodies and
        for channel-only evaluation (``shots == 0`` or explicit
        measurement without sampling).
        """
        C = probs.shape[-1]
        if self.depolarizing:
            probs = (1 - self.depolarizing) * probs + self.depolarizing / C
        if self.readout_flip:
            # symmetric confusion: stay w.p. 1-f, uniform flip otherwise
            f = self.readout_flip
            conf = (1 - f) * jnp.eye(C) + f / (C - 1) * (1 - jnp.eye(C))
            probs = jnp.dot(probs, conf.astype(probs.dtype),
                            precision=jax.lax.Precision.HIGHEST)
        return probs

    def sample(self, probs: jnp.ndarray, key: jax.Array) -> jnp.ndarray:
        """Finite-shot readout: empirical frequencies of ``shots`` draws
        per row.  Identity when ``shots == 0``."""
        if not self.shots:
            return probs
        counts = sample_counts(key, probs, self.shots)
        # multiply by the host-rounded reciprocal: XLA strength-reduces
        # a divide-by-constant the same way, so eager and jitted
        # evaluation of the same draws stay bitwise identical
        return counts * (1.0 / self.shots)

    def transform_probs(self, probs: jnp.ndarray,
                        key: Optional[jax.Array] = None) -> jnp.ndarray:
        """Channel + finite-shot sampling on (B, C).

        Raises when ``shots > 0`` and no key is supplied: a finite-shot
        backend evaluated without a key would silently revert to the
        deterministic channel, which is exactly the bug class this
        contract exists to prevent.  Channel-only evaluation is an
        explicit choice — call ``apply_channel``.
        """
        probs = self.apply_channel(probs)
        if self.shots:
            if key is None:
                raise ValueError(
                    f"backend {self.name!r} has shots={self.shots} but "
                    "transform_probs was called without a PRNG key; pass "
                    "an eval_key(...) or use apply_channel() for "
                    "channel-only evaluation")
            probs = self.sample(probs, key)
        return probs

    def eval_time(self, n_circuits: int) -> float:
        """Estimated wall-time for one optimizer evaluation over a batch."""
        return (self.t_queue + self.t_per_job
                + self.t_per_shot * max(self.shots, 1) * n_circuits)


def sample_counts(key, probs: jnp.ndarray, shots: int) -> jnp.ndarray:
    """Multinomial shot sampling per row of (B, C) probabilities.

    O(B·C + B·shots) memory: inverse-CDF sampling — per-row cumulative
    probabilities (B, C), uniform draws (shots, B) located by a batched
    ``searchsorted``, scatter-added straight into the (B, C) count
    matrix.  (``jax.random.categorical`` would materialize a
    (shots, B, C) gumbel tensor internally.)

    Degenerate rows with (numerically) zero mass — all entries clipped
    to 0 — fall back to the uniform distribution instead of dumping
    every shot into class ``C-1`` via the clamped ``searchsorted``.
    **NaN rows are not degenerate — they are diverged**: their counts
    come back all-NaN so the client's loss stays NaN and
    ``selection.py``'s +inf hardening sorts it last, instead of the
    uniform fallback laundering divergence into a plausible finite
    loss.  (The NaN row is sampled internally as uniform so every other
    row consumes exactly the same draws — finite rows are bitwise
    unchanged by the overwrite, preserving the pinned parity seeds.)
    Counts are returned in ``probs.dtype`` but accumulated in float32:
    scatter-adding in a low-precision dtype would saturate (bfloat16
    stops incrementing at 256) and silently lose shots.
    """
    B, C = probs.shape
    nan_row = jnp.any(jnp.isnan(probs), axis=-1, keepdims=True)  # (B, 1)
    p = jnp.clip(probs, 0.0, 1.0)
    p = jnp.where(nan_row, jnp.ones_like(p) / C, p)   # draw-stable stand-in
    mass = jnp.sum(p, axis=-1, keepdims=True)
    p = jnp.where(mass > 1e-12, p, jnp.ones_like(p) / C)
    cdf = jnp.cumsum(p, axis=-1)                               # (B, C)
    # renormalize — the old categorical path did so implicitly via logits
    cdf = cdf / cdf[:, -1:]
    u = jax.random.uniform(key, (shots, B), cdf.dtype)
    draws = jax.vmap(
        lambda row_cdf, row_u: jnp.searchsorted(row_cdf, row_u,
                                                side="right"),
        in_axes=(0, 1), out_axes=1)(cdf, u)                    # (shots, B)
    draws = jnp.minimum(draws, C - 1)      # cumsum rounding below 1.0
    counts = jnp.zeros((B, C), jnp.float32)
    counts = counts.at[jnp.arange(B)[None, :], draws].add(1.0)
    counts = jnp.where(nan_row, jnp.nan, counts)      # divergence surfaces
    return counts.astype(probs.dtype)


# Calibrated instances.  Latencies reproduce Table-I orderings:
# Fake ≈ 162.9s, AerSim ≈ 325.0s, Real ≈ 1395.9s for Exp-1-sized runs.
EXACT = Backend("exact")
FAKE = Backend("fake", depolarizing=0.015, readout_flip=0.03, shots=100,
               t_per_job=0.02, t_per_shot=1.2e-4)
AERSIM = Backend("aersim", depolarizing=0.03, readout_flip=0.015, shots=100,
                 t_per_job=0.04, t_per_shot=2.4e-4)
REAL = Backend("real", depolarizing=0.035, readout_flip=0.02, shots=100,
               t_per_job=0.05, t_per_shot=2.4e-4, t_queue=1.55)

BACKENDS = {b.name: b for b in (EXACT, FAKE, AERSIM, REAL)}


def get(name: str) -> Backend:
    return BACKENDS[name]
