"""SamplerQNN: parameterized-circuit neural networks with parity interpret.

Mirrors the paper's Qiskit ``SamplerQNN`` usage: the circuit's
quasi-probabilities are mapped to discrete classes via a custom interpret
function computing the **parity of the bitstring** (Sec. I-B.2), giving a
binary (or n-class) classifier head on top of a VQC or QCNN.

Two model families (Table II):
  - VQC  : ZZFeatureMap(reps=2) + RealAmplitudes(reps=3)      [Experiment I]
  - QCNN : ZZFeatureMap encoding + conv/pool stages            [Experiment II]
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.quantum import circuits as C
from repro.quantum import statevector as sv


def parity_interpret(probs: jnp.ndarray, n_qubits: int,
                     n_classes: int = 2) -> jnp.ndarray:
    """Map 2**n basis probabilities to class probs by bitstring parity
    (popcount mod n_classes)."""
    idx = jnp.arange(probs.shape[-1])
    pop = jnp.zeros_like(idx)
    for b in range(n_qubits):
        pop = pop + ((idx >> b) & 1)
    cls = pop % n_classes
    onehot = jax.nn.one_hot(cls, n_classes, dtype=probs.dtype)
    # full f32: the TPU's default precision would round probs to bfloat16
    return jnp.dot(probs, onehot, precision=jax.lax.Precision.HIGHEST)


def last_qubit_interpret(psi: jnp.ndarray, q: int) -> jnp.ndarray:
    """P(qubit q = 0/1) — QCNN readout on the surviving qubit."""
    p = jnp.abs(psi) ** 2
    axes = tuple(i for i in range(psi.ndim) if i != q)
    pq = p.sum(axis=axes)
    return jnp.stack([pq[0], pq[1]]).real


@dataclass(frozen=True)
class QNNSpec:
    kind: str                  # "vqc" | "qcnn"
    n_qubits: int = 4
    n_classes: int = 2
    fm_reps: int = 2
    ansatz_reps: int = 3

    @property
    def n_params(self) -> int:
        if self.kind == "vqc":
            return C.real_amplitudes_n_params(self.n_qubits,
                                              self.ansatz_reps)
        if self.kind == "qcnn":
            return C.qcnn_n_params(self.n_qubits)
        raise ValueError(self.kind)

    def init_params(self, key) -> jnp.ndarray:
        return jax.random.uniform(key, (self.n_params,), jnp.float32,
                                  -jnp.pi, jnp.pi)


def _forward_one(spec: QNNSpec, theta: jnp.ndarray,
                 x: jnp.ndarray) -> jnp.ndarray:
    """Class probabilities for a single example x (n_qubits features)."""
    psi = C.zz_feature_map(x, reps=spec.fm_reps)
    if spec.kind == "vqc":
        psi = C.real_amplitudes(psi, theta, reps=spec.ansatz_reps)
        probs = sv.probabilities(psi)
        return parity_interpret(probs, spec.n_qubits, spec.n_classes)
    if spec.kind == "qcnn":
        psi, q = C.qcnn(psi, theta)
        out = last_qubit_interpret(psi, q)
        if spec.n_classes == 2:
            return out
        # >2 classes: fall back to parity on the full register
        return parity_interpret(sv.probabilities(psi), spec.n_qubits,
                                spec.n_classes)
    raise ValueError(spec.kind)


def make_forward(spec: QNNSpec) -> Callable:
    """(theta, X (B,n)) -> class probs (B, n_classes), jit-compiled."""
    f = jax.vmap(functools.partial(_forward_one, spec), in_axes=(None, 0))
    return jax.jit(f)


def nll_loss(probs: jnp.ndarray, labels: jnp.ndarray,
             eps: float = 1e-9) -> jnp.ndarray:
    """Mean negative log-likelihood of class probabilities."""
    p = jnp.take_along_axis(probs, labels[:, None], axis=1)[:, 0]
    return -jnp.mean(jnp.log(p + eps))


def accuracy(probs: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean((jnp.argmax(probs, axis=1) == labels).astype(jnp.float32))


def make_loss_fn(spec: QNNSpec, X: jnp.ndarray, y: jnp.ndarray,
                 backend=None) -> Callable:
    """theta -> scalar NLL on (X, y), optionally through a noisy backend.

    With a finite-shot backend (``backend.shots > 0``) the returned loss
    is **keyed** — called as ``loss(theta, key)`` with a per-evaluation
    ``backends.eval_key`` so shot sampling is live and deterministic-by-
    seed; otherwise the channel-only single-argument form is returned.
    """
    fwd = make_forward(spec)

    if backend is not None and backend.shots:
        def loss_sampled(theta, key):
            probs = backend.transform_probs(fwd(theta, X), key)
            return nll_loss(probs, y)

        return jax.jit(loss_sampled)

    def loss(theta):
        probs = fwd(theta, X)
        if backend is not None:
            probs = backend.apply_channel(probs)
        return nll_loss(probs, y)

    return jax.jit(loss)
