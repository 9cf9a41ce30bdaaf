"""JAX statevector simulator — the quantum substrate the paper runs on.

Dense statevector of n qubits as a (2,)*n tensor (batchable, jit/vmap
friendly).  Qubit 0 is the leftmost tensor axis (big-endian bitstrings,
matching the parity-interpret convention in ``qnn.py``).

This replaces Qiskit AerSimulator/IBM hardware per the repro≤2 simulation
guidance (DESIGN.md §2) — exact amplitudes, with shot sampling and noise
channels layered on in ``backends.py``.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

CDTYPE = jnp.complex64

# Gate contractions run at full f32 precision: the TPU's default precision
# for f32 dots rounds the operands to bfloat16, ~1e-3 relative error per
# gate, where the CPU is exact.
_EXACT = jax.lax.Precision.HIGHEST


def zero_state(n_qubits: int) -> jnp.ndarray:
    psi = jnp.zeros((2,) * n_qubits, CDTYPE)
    return psi.at[(0,) * n_qubits].set(1.0)


def _apply_1q(psi: jnp.ndarray, gate: jnp.ndarray, q: int) -> jnp.ndarray:
    psi = jnp.tensordot(gate, psi, axes=[[1], [q]], precision=_EXACT)
    return jnp.moveaxis(psi, 0, q)


def _apply_2q(psi: jnp.ndarray, gate: jnp.ndarray, q1: int, q2: int
              ) -> jnp.ndarray:
    g = gate.reshape(2, 2, 2, 2)
    psi = jnp.tensordot(g, psi, axes=[[2, 3], [q1, q2]], precision=_EXACT)
    return jnp.moveaxis(psi, (0, 1), (q1, q2))


# --- gate matrices ---------------------------------------------------------
_H = jnp.array([[1, 1], [1, -1]], CDTYPE) / jnp.sqrt(2.0).astype(CDTYPE)
_X = jnp.array([[0, 1], [1, 0]], CDTYPE)
_Z = jnp.array([[1, 0], [0, -1]], CDTYPE)
_I2 = jnp.eye(2, dtype=CDTYPE)


def rx_mat(theta):
    c = jnp.cos(theta / 2).astype(CDTYPE)
    s = (-1j * jnp.sin(theta / 2)).astype(CDTYPE)
    return jnp.stack([jnp.stack([c, s]), jnp.stack([s, c])])


def ry_mat(theta):
    c = jnp.cos(theta / 2).astype(CDTYPE)
    s = jnp.sin(theta / 2).astype(CDTYPE)
    return jnp.stack([jnp.stack([c, -s]), jnp.stack([s, c])])


def rz_mat(theta):
    e = jnp.exp(-0.5j * theta.astype(jnp.complex64))
    z = jnp.zeros((), CDTYPE)
    return jnp.stack([jnp.stack([e, z]), jnp.stack([z, jnp.conj(e)])])


_CX = jnp.array([[1, 0, 0, 0], [0, 1, 0, 0],
                 [0, 0, 0, 1], [0, 0, 1, 0]], CDTYPE)
_CZ = jnp.diag(jnp.array([1, 1, 1, -1], CDTYPE))


# --- public ops ------------------------------------------------------------
def h(psi, q):
    return _apply_1q(psi, _H, q)


def x(psi, q):
    return _apply_1q(psi, _X, q)


def rx(psi, theta, q):
    return _apply_1q(psi, rx_mat(jnp.asarray(theta)), q)


def ry(psi, theta, q):
    return _apply_1q(psi, ry_mat(jnp.asarray(theta)), q)


def rz(psi, theta, q):
    return _apply_1q(psi, rz_mat(jnp.asarray(theta)), q)


def cx(psi, control, target):
    return _apply_2q(psi, _CX, control, target)


def cz(psi, q1, q2):
    return _apply_2q(psi, _CZ, q1, q2)


def crz(psi, theta, control, target):
    th = jnp.asarray(theta).astype(jnp.complex64)
    g = jnp.diag(jnp.concatenate([
        jnp.ones((2,), CDTYPE),
        jnp.stack([jnp.exp(-0.5j * th), jnp.exp(0.5j * th)])]))
    return _apply_2q(psi, g, control, target)


def probabilities(psi: jnp.ndarray) -> jnp.ndarray:
    """|amp|² over the 2**n computational basis (big-endian flatten)."""
    return jnp.abs(psi.reshape(-1)) ** 2


def expect_z(psi: jnp.ndarray, q: int) -> jnp.ndarray:
    p = jnp.abs(psi) ** 2
    axes = tuple(i for i in range(psi.ndim) if i != q)
    pq = p.sum(axis=axes)
    return (pq[0] - pq[1]).real


def norm(psi: jnp.ndarray) -> jnp.ndarray:
    return jnp.sqrt((jnp.abs(psi) ** 2).sum())
