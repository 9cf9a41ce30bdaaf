"""Circuit tape compiler: flat gate tapes executed by one fused program.

The eager circuits in ``circuits.py`` apply one gate at a time through
``tensordot``/``moveaxis`` on a ``(2,)*n`` tensor — correct, but the
federated hot path pays Python dispatch per gate per example.  Here the
same circuits are compiled **once** into a flat tape of

  (gate_id, target, control, angle-source)

rows and replayed by ``run_tape``, unrolled at trace time: the gate
kinds, targets and controls are Python ints, so each replay is one
straight line of gates (no loop, no branch, no gather) and only the
angles are data.  Every gate the paper's three circuits need reduces to
an (optionally controlled) 2×2 unitary:

  H, P(θ), RY(θ), RZ(θ), and CX = controlled-X.

Angle sources cover the three ways an angle is produced:

  - a constant (QCNN's ±π/2 frame rotations),
  - a feature term (``2·x[i]`` or the ZZ phase ``2(π−x_i)(π−x_j)``),
  - a trainable parameter ``theta[k]``.

``angle = const + feature_term + theta_pad[theta_idx]`` with
``theta_pad = [0, *theta]`` so index 0 means "no parameter".

Qubit convention matches ``statevector.py``: qubit 0 is the leftmost
tensor axis, i.e. bit ``n-1-q`` of the flat big-endian index.

A gate acts on the ``(B, 2, …, 2)`` view of the ``(B, 2**n)`` batch of
statevectors: static slices take the two halves of the target's axis
(of the control-1 half of the control's axis, for a controlled gate),
the 2×2 matrix mixes them in complex64, and the halves are joined
again; CX exchanges them.  The gate apply has three interchangeable
implementations: the jnp path below (default), the
``kernels/statevector_gates.py`` Pallas kernel
(``gate_apply=tape.pallas_gate_apply``, which gathers by index pairs and
runs interpreted), and the ``kernels/ref.py`` oracle — all contracted
equal by ``tests/test_tape.py``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.quantum import statevector as sv
from repro.telemetry import TAPE_REPLAY

GATE_H, GATE_P, GATE_RY, GATE_RZ, GATE_X = 0, 1, 2, 3, 4

XMODE_NONE, XMODE_LINEAR, XMODE_ZZ = 0, 1, 2


@dataclass(frozen=True)
class GateTape:
    """Flat compiled circuit: parallel arrays, one row per gate."""
    n_qubits: int
    gate_id: np.ndarray      # (G,) int32 in {H, P, RY, RZ, X}
    target: np.ndarray       # (G,) int32
    control: np.ndarray      # (G,) int32, -1 = uncontrolled
    const: np.ndarray        # (G,) float32 additive constant angle
    xmode: np.ndarray        # (G,) int32 ∈ {NONE, LINEAR, ZZ}
    xi: np.ndarray           # (G,) int32 feature index i
    xj: np.ndarray           # (G,) int32 feature index j (ZZ only)
    theta_idx: np.ndarray    # (G,) int32 into [0, *theta]; 0 = none

    @property
    def n_gates(self) -> int:
        return int(self.gate_id.shape[0])


class TapeBuilder:
    def __init__(self, n_qubits: int):
        self.n_qubits = n_qubits
        self._rows: List[Tuple] = []

    def _add(self, gid, target, control=-1, const=0.0, xmode=XMODE_NONE,
             xi=0, xj=0, theta=-1):
        self._rows.append((gid, target, control, const, xmode, xi, xj,
                           theta + 1))

    def h(self, q):
        self._add(GATE_H, q)

    def p_linear(self, q, feat):
        """P(2·x[feat]) on qubit q (ZZFeatureMap single-qubit phase)."""
        self._add(GATE_P, q, xmode=XMODE_LINEAR, xi=feat)

    def p_zz(self, q, fi, fj):
        """P(2·(π−x[fi])(π−x[fj])) on qubit q (ZZ entangling phase)."""
        self._add(GATE_P, q, xmode=XMODE_ZZ, xi=fi, xj=fj)

    def ry_theta(self, q, k):
        self._add(GATE_RY, q, theta=k)

    def rz_theta(self, q, k):
        self._add(GATE_RZ, q, theta=k)

    def rz_const(self, q, angle):
        self._add(GATE_RZ, q, const=angle)

    def cx(self, control, target):
        self._add(GATE_X, target, control=control)

    def build(self) -> GateTape:
        cols = list(zip(*self._rows))
        i32 = functools.partial(np.asarray, dtype=np.int32)
        return GateTape(
            n_qubits=self.n_qubits,
            gate_id=i32(cols[0]), target=i32(cols[1]), control=i32(cols[2]),
            const=np.asarray(cols[3], np.float32), xmode=i32(cols[4]),
            xi=i32(cols[5]), xj=i32(cols[6]), theta_idx=i32(cols[7]))


# ---------------------------------------------------------------------------
# compilers — mirror circuits.py gate-for-gate (tests/test_tape.py guards
# drift against the eager implementations)
# ---------------------------------------------------------------------------
def compile_zz_feature_map(tb: TapeBuilder, *, reps: int = 2) -> None:
    n = tb.n_qubits
    for _ in range(reps):
        for q in range(n):
            tb.h(q)
            tb.p_linear(q, q)
        for i in range(n):
            for j in range(i + 1, n):
                tb.cx(i, j)
                tb.p_zz(j, i, j)
                tb.cx(i, j)


def compile_real_amplitudes(tb: TapeBuilder, *, reps: int = 3,
                            entangle: str = "full") -> None:
    n = tb.n_qubits
    for r in range(reps):
        for q in range(n):
            tb.ry_theta(q, r * n + q)
        if entangle == "full":
            pairs = [(i, j) for i in range(n) for j in range(i + 1, n)]
        else:
            pairs = [(i, i + 1) for i in range(n - 1)]
        for (i, j) in pairs:
            tb.cx(i, j)
    for q in range(n):
        tb.ry_theta(q, reps * n + q)


def _compile_conv2(tb, k, q1, q2):
    tb.rz_const(q2, -np.pi / 2)
    tb.cx(q2, q1)
    tb.rz_theta(q1, k)
    tb.ry_theta(q2, k + 1)
    tb.cx(q1, q2)
    tb.ry_theta(q2, k + 2)
    tb.cx(q2, q1)
    tb.rz_const(q1, np.pi / 2)


def _compile_pool2(tb, k, src, dst):
    tb.rz_const(dst, -np.pi / 2)
    tb.cx(dst, src)
    tb.rz_theta(src, k)
    tb.ry_theta(dst, k + 1)
    tb.cx(src, dst)
    tb.ry_theta(dst, k + 2)


def compile_qcnn(tb: TapeBuilder) -> int:
    """QCNN conv/pool stages; returns the readout qubit index."""
    active = list(range(tb.n_qubits))
    k = 0
    while len(active) > 1:
        pairs = [(active[2 * i], active[2 * i + 1])
                 for i in range(len(active) // 2)]
        for (a, b) in pairs:
            _compile_conv2(tb, k, a, b)
            k += 3
        survivors = []
        for (a, b) in pairs:
            _compile_pool2(tb, k, a, b)
            k += 3
            survivors.append(b)
        if len(active) % 2:
            survivors.append(active[-1])
        active = survivors
    return active[0]


@dataclass(frozen=True)
class CompiledQNN:
    """A QNNSpec lowered to a tape + readout recipe."""
    kind: str
    n_qubits: int
    n_classes: int
    tape: GateTape
    readout: int = -1        # QCNN surviving qubit; -1 = parity interpret


def compile_qnn(spec) -> CompiledQNN:
    """Lower a ``qnn.QNNSpec`` to a ``CompiledQNN``."""
    tb = TapeBuilder(spec.n_qubits)
    compile_zz_feature_map(tb, reps=spec.fm_reps)
    readout = -1
    if spec.kind == "vqc":
        compile_real_amplitudes(tb, reps=spec.ansatz_reps)
    elif spec.kind == "qcnn":
        readout = compile_qcnn(tb)
    else:
        raise ValueError(spec.kind)
    return CompiledQNN(spec.kind, spec.n_qubits, spec.n_classes,
                       tb.build(), readout)


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------
def _take_cols(a: jnp.ndarray, idx: np.ndarray) -> jnp.ndarray:
    """``a[:, idx]`` for a static index array, as static slices joined
    into one table: no gather."""
    cols = [a[:, k:k + 1] for k in range(a.shape[1])]
    return jnp.concatenate([cols[k] for k in idx.tolist()], axis=1)


def tape_angles(tape: GateTape, X: jnp.ndarray,
                theta: jnp.ndarray) -> jnp.ndarray:
    """Resolve per-gate angles for a batch of examples → (B, G) float32."""
    xi = _take_cols(X, tape.xi)                          # (B, G)
    xj = _take_cols(X, tape.xj)
    xterm = jnp.where(
        tape.xmode == XMODE_LINEAR, 2.0 * xi,
        jnp.where(tape.xmode == XMODE_ZZ,
                  2.0 * (jnp.pi - xi) * (jnp.pi - xj), 0.0))
    theta_pad = jnp.concatenate(
        [jnp.zeros((1,), theta.dtype), theta.astype(jnp.float32)])
    return tape.const[None, :] + xterm + _take_cols(theta_pad[None, :],
                                                    tape.theta_idx)


# Each builder gives a gate's matrix as its entries (g00, g01, g10, g11):
# complex64 arrays of the angles' shape, or scalars where constant.
def _mat_h(ang):
    return tuple(np.asarray(sv._H).ravel())


def _mat_p(ang):
    return 1.0, 0.0, 0.0, jnp.exp(1j * ang.astype(jnp.complex64))


def _mat_ry(ang):
    c = jnp.cos(ang / 2).astype(sv.CDTYPE)
    s = jnp.sin(ang / 2).astype(sv.CDTYPE)
    return c, -s, s, c


def _mat_rz(ang):
    e = jnp.exp(-0.5j * ang.astype(jnp.complex64))
    return e, 0.0, 0.0, jnp.conj(e)


def _mat_x(ang):
    return tuple(np.asarray(sv._X).ravel())


_MAT_FNS = (_mat_h, _mat_p, _mat_ry, _mat_rz, _mat_x)


def gate_matrix(gid: int, ang: jnp.ndarray) -> jnp.ndarray:
    """Gate ``gid``'s matrix per angle of ``ang`` (B,) → (B, 2, 2)."""
    g = [jnp.broadcast_to(x, ang.shape).astype(sv.CDTYPE)
         for x in _MAT_FNS[gid](ang)]
    return jnp.stack([jnp.stack(g[:2], -1), jnp.stack(g[2:], -1)], -2)


def pair_indices(target, control, n_qubits: int):
    """Index pairs (amp with target bit 0, partner) + control mask.

    Returns (idx0, idx1) each (2**n / 2,) int32 and cmask (2**n / 2,) bool —
    True where the gate acts (control bit set, or no control).  The
    Pallas kernel's pairing metadata; the jnp path needs none.
    """
    half = (1 << n_qubits) // 2
    shift = n_qubits - 1 - target
    stride = jnp.left_shift(1, shift)
    k = jnp.arange(half, dtype=jnp.int32)
    idx0 = ((k >> shift) << (shift + 1)) | (k & (stride - 1))
    idx1 = idx0 | stride
    cshift = jnp.where(control < 0, 0, n_qubits - 1 - control)
    cmask = jnp.where(control < 0, True, ((idx0 >> cshift) & 1) == 1)
    return idx0, idx1, cmask


def _apply_2x2(t: jnp.ndarray, axis: int, g) -> jnp.ndarray:
    """The gate with entries ``g`` on axis ``axis`` of ``t`` (B, 2, …, 2):
    static slices of the bit-0 and bit-1 halves, mixed and joined."""
    a0 = jax.lax.slice_in_dim(t, 0, 1, axis=axis)
    a1 = jax.lax.slice_in_dim(t, 1, 2, axis=axis)
    g00, g01, g10, g11 = g
    return jax.lax.concatenate([g00 * a0 + g01 * a1, g10 * a0 + g11 * a1],
                               axis)


def _swap(t: jnp.ndarray, axis: int) -> jnp.ndarray:
    """X on axis ``axis``: the halves exchanged, exactly."""
    return jax.lax.concatenate([jax.lax.slice_in_dim(t, 1, 2, axis=axis),
                                jax.lax.slice_in_dim(t, 0, 1, axis=axis)],
                               axis)


def _on_target(t: jnp.ndarray, target: int, control: int,
               fn: Callable) -> jnp.ndarray:
    """``fn(t, axis)`` on the target's axis of ``t`` (B, 2, …, 2), qubit q
    on axis 1 + q; with a control, on its control-1 half only, the
    control-0 half kept as it is."""
    if control < 0:
        return fn(t, 1 + target)
    off = jax.lax.slice_in_dim(t, 0, 1, axis=1 + control)
    on = jax.lax.slice_in_dim(t, 1, 2, axis=1 + control)
    return jax.lax.concatenate([off, fn(on, 1 + target)], 1 + control)


def jnp_gate_apply(psi, g, target: int, control: int, n_qubits: int):
    """Batched (controlled) 2×2 gate ``g`` (B, 2, 2) on (B, 2**n)
    statevectors, as ``run_tape`` applies each gate.

    ``target`` and ``control`` (−1: none) are static ints.  The state is
    viewed as a (B, 2, …, 2) tensor and the two halves of the target's
    axis are taken by static slices and joined again: no gather, no
    scatter.
    """
    B = psi.shape[0]
    col = (B,) + (1,) * n_qubits
    entries = tuple(g[:, i, j].reshape(col) for i in (0, 1) for j in (0, 1))
    t = psi.reshape((B,) + (2,) * n_qubits)
    t = _on_target(t, int(target), int(control),
                   functools.partial(_apply_2x2, g=entries))
    return t.reshape(psi.shape)


def pallas_gate_apply(psi, g, target, control, n_qubits: int):
    """Same contract as ``jnp_gate_apply`` through the Pallas kernel."""
    from repro.kernels import ops
    idx0, idx1, cmask = pair_indices(target, control, n_qubits)
    re, im = ops.statevector_gate(
        jnp.real(psi), jnp.imag(psi), jnp.real(g), jnp.imag(g),
        idx0, idx1, cmask.astype(jnp.float32))
    return jax.lax.complex(re, im).astype(psi.dtype)


def run_tape(tape: GateTape, angles: jnp.ndarray, *,
             gate_apply: Optional[Callable] = None) -> jnp.ndarray:
    """Replay the tape on |0…0⟩ for a batch → (B, 2**n) complex64.

    The replay is unrolled at trace time: each gate's kind, target and
    control are Python ints read from the tape, so the program is one
    straight line of gates (no loop, no branch) and only the angles,
    column g of ``angles`` for gate g, are data.  It is traced once per
    circuit and batch shape, however often a program replays it.
    ``gate_apply``, where given, gets each gate as a (B, 2, 2) matrix.
    """
    rows = tuple(zip(tape.gate_id.tolist(), tape.target.tolist(),
                     tape.control.tolist()))
    return _replay(tape.n_qubits, rows, gate_apply)(angles)


@functools.lru_cache(maxsize=None)
def _replay(n: int, rows: Tuple[Tuple[int, int, int], ...],
            gate_apply: Optional[Callable]) -> Callable:
    """The jitted replay of one circuit's gate rows."""
    basis0 = np.zeros((1,) + (2,) * n, np.complex64)
    basis0[(0,) * (n + 1)] = 1.0

    @jax.jit
    def replay(angles):
        B = angles.shape[0]
        psi = jnp.broadcast_to(basis0, (B,) + basis0.shape[1:])
        if gate_apply is not None:
            psi = psi.reshape(B, 1 << n)
            for g, (gid, tq, cq) in enumerate(rows):
                psi = gate_apply(psi, gate_matrix(gid, angles[:, g]), tq, cq,
                                 n)
            return psi
        for g, (gid, tq, cq) in enumerate(rows):
            if gid == GATE_X:
                fn = _swap
            else:
                ang = jax.lax.slice_in_dim(angles, g, g + 1, axis=1)
                fn = functools.partial(
                    _apply_2x2,
                    g=_MAT_FNS[gid](ang.reshape((B,) + (1,) * n)))
            psi = _on_target(psi, tq, cq, fn)
        return psi.reshape(B, 1 << n)

    return replay


def tape_probs(cq: CompiledQNN, theta: jnp.ndarray, X: jnp.ndarray, *,
               gate_apply: Optional[Callable] = None) -> jnp.ndarray:
    """Class probabilities (B, n_classes), matching ``qnn._forward_one``."""
    from repro.quantum import qnn
    with jax.named_scope(TAPE_REPLAY):
        angles = tape_angles(cq.tape, X, theta)
        psi = run_tape(cq.tape, angles, gate_apply=gate_apply)
        probs = jnp.abs(psi) ** 2                        # (B, 2**n)
        if cq.kind == "qcnn" and cq.n_classes == 2:
            B = probs.shape[0]
            q = cq.readout
            grouped = probs.reshape(B, 1 << q, 2, -1)
            return grouped.sum(axis=(1, 3))
        return qnn.parity_interpret(probs, cq.n_qubits, cq.n_classes)


def make_tape_forward(spec, *, gate_apply: Optional[Callable] = None
                      ) -> Callable:
    """(theta, X (B,n)) → class probs (B, n_classes); drop-in for
    ``qnn.make_forward`` backed by the compiled tape."""
    cq = compile_qnn(spec)
    return jax.jit(functools.partial(tape_probs, cq, gate_apply=gate_apply))
