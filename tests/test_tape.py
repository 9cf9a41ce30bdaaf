"""Circuit tape compiler: tape-vs-eager statevector equality (VQC + QCNN),
the batched gate-apply kernel contract (jnp path = Pallas = oracle), and
the replay's form: straight-line code with no loop, branch or gather."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry as tel
from repro.kernels import ops, ref
from repro.quantum import circuits as C, qnn, statevector as sv, tape as T

KEY = jax.random.PRNGKey(11)


def _batch(n=6):
    return jax.random.uniform(KEY, (n, 4), jnp.float32, 0, np.pi)


# --- tape vs eager circuits --------------------------------------------------
def test_vqc_tape_statevector_equality():
    spec = qnn.QNNSpec("vqc", n_qubits=4)
    th = spec.init_params(jax.random.PRNGKey(1))
    cq = T.compile_qnn(spec)
    X = _batch()
    psi_tape = T.run_tape(cq.tape, T.tape_angles(cq.tape, X, th))
    psi_eager = jnp.stack([
        C.real_amplitudes(C.zz_feature_map(x, reps=spec.fm_reps), th,
                          reps=spec.ansatz_reps).reshape(-1) for x in X])
    np.testing.assert_allclose(np.asarray(psi_tape), np.asarray(psi_eager),
                               atol=1e-6)


def test_qcnn_tape_statevector_equality_and_readout():
    spec = qnn.QNNSpec("qcnn", n_qubits=4)
    th = spec.init_params(jax.random.PRNGKey(2))
    cq = T.compile_qnn(spec)
    X = _batch()
    psi_tape = T.run_tape(cq.tape, T.tape_angles(cq.tape, X, th))
    eager = [C.qcnn(C.zz_feature_map(x, reps=spec.fm_reps), th) for x in X]
    psi_eager = jnp.stack([p.reshape(-1) for p, _ in eager])
    np.testing.assert_allclose(np.asarray(psi_tape), np.asarray(psi_eager),
                               atol=1e-6)
    assert cq.readout == eager[0][1]


@pytest.mark.parametrize("kind", ["vqc", "qcnn"])
def test_tape_forward_matches_qnn_forward(kind):
    spec = qnn.QNNSpec(kind, n_qubits=4)
    th = spec.init_params(jax.random.PRNGKey(3))
    X = _batch(8)
    p_tape = T.make_tape_forward(spec)(th, X)
    p_eager = qnn.make_forward(spec)(th, X)
    assert p_tape.shape == p_eager.shape == (8, 2)
    np.testing.assert_allclose(np.asarray(p_tape), np.asarray(p_eager),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(p_tape.sum(1)), 1.0, atol=1e-5)


def test_tape_angles_sources():
    """Constant, feature-linear, ZZ, and theta angle sources resolve."""
    tb = T.TapeBuilder(2)
    tb.rz_const(0, 0.5)
    tb.p_linear(0, 1)
    tb.p_zz(1, 0, 1)
    tb.ry_theta(1, 0)
    tape = tb.build()
    X = jnp.array([[1.0, 2.0]], jnp.float32)
    theta = jnp.array([0.25], jnp.float32)
    ang = np.asarray(T.tape_angles(tape, X, theta))[0]
    assert ang[0] == pytest.approx(0.5)
    assert ang[1] == pytest.approx(4.0)          # 2·x[1]
    assert ang[2] == pytest.approx(2 * (np.pi - 1) * (np.pi - 2), rel=1e-6)
    assert ang[3] == pytest.approx(0.25)


@pytest.mark.parametrize("fm_reps", [1, 2])
@pytest.mark.parametrize("n_qubits", [2, 3, 4, 5])
@pytest.mark.parametrize("kind", ["vqc", "qcnn"])
def test_replay_matches_eager_circuits(kind, n_qubits, fm_reps):
    spec = qnn.QNNSpec(kind, n_qubits=n_qubits, fm_reps=fm_reps)
    th = spec.init_params(jax.random.PRNGKey(n_qubits))
    cq = T.compile_qnn(spec)
    X = jax.random.uniform(KEY, (3, n_qubits), jnp.float32, 0, np.pi)
    psi_tape = jax.jit(lambda th, X: T.run_tape(
        cq.tape, T.tape_angles(cq.tape, X, th)))(th, X)
    psi_eager = []
    for x in X:
        psi = C.zz_feature_map(x, reps=fm_reps)
        if kind == "vqc":
            psi = C.real_amplitudes(psi, th, reps=spec.ansatz_reps)
        else:
            psi, readout = C.qcnn(psi, th)
            assert readout == cq.readout
        psi_eager.append(psi.reshape(-1))
    np.testing.assert_allclose(np.asarray(psi_tape),
                               np.asarray(jnp.stack(psi_eager)), atol=1e-6)


@pytest.mark.parametrize("n_qubits", [2, 3, 4])
def test_replay_matches_oracle_gate_by_gate(n_qubits):
    """A linear-entangling ansatz, then one controlled X and one
    controlled RY on every (control, target) pair, against
    ``ref.statevector_gate`` applied gate by gate."""
    n = n_qubits
    tb = T.TapeBuilder(n)
    T.compile_real_amplitudes(tb, reps=2, entangle="linear")
    k = 3 * n
    for c in range(n):
        for t in range(n):
            if c != t:
                tb.cx(c, t)
                tb._add(T.GATE_RY, t, control=c, theta=k)
                k += 1
    tape = tb.build()
    theta = jax.random.uniform(KEY, (k,), jnp.float32, -3, 3)
    X = jnp.zeros((5, n), jnp.float32)
    ang = T.tape_angles(tape, X, theta)
    got = jax.jit(lambda a: T.run_tape(tape, a))(ang)
    re = jnp.zeros((5, 1 << n), jnp.float32).at[:, 0].set(1.0)
    im = jnp.zeros_like(re)
    for g in range(tape.n_gates):
        mat = T.gate_matrix(int(tape.gate_id[g]), ang[:, g])
        idx0, idx1, cmask = T.pair_indices(int(tape.target[g]),
                                           int(tape.control[g]), n)
        re, im = ref.statevector_gate(re, im, jnp.real(mat), jnp.imag(mat),
                                      idx0, idx1, cmask.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(jnp.real(got)), np.asarray(re),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(jnp.imag(got)), np.asarray(im),
                               atol=1e-6)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_replay_is_straight_line_code():
    """``tape_probs`` as the fused round runs it (vmapped over 19 NM
    candidates x 5 clients, 50 examples) traces to no loop or branch and
    no gather or scatter, all under the ``tape.replay`` scope."""
    spec = qnn.QNNSpec("vqc", n_qubits=4)
    cq = T.compile_qnn(spec)
    f = jax.vmap(jax.vmap(lambda th, X: T.tape_probs(cq, th, X),
                          (0, None)), (0, 0))
    jaxpr = jax.make_jaxpr(f)(jnp.zeros((5, 19, spec.n_params)),
                              jnp.zeros((5, 50, 4)))
    eqns = list(_eqns(jaxpr.jaxpr))
    prims = {e.primitive.name for e in eqns}
    assert not prims & {"scan", "while", "cond"}, prims
    assert not {p for p in prims if p.startswith(("gather", "scatter"))}
    assert all(tel.TAPE_REPLAY in str(e.source_info.name_stack)
               for e in jaxpr.jaxpr.eqns)


# --- batched gate apply: jnp path = Pallas kernel = oracle -------------------
def test_gate_apply_pallas_matches_oracle_and_jnp():
    n = 4
    B, N = 8, 1 << n
    k1, k2, k3 = jax.random.split(KEY, 3)
    psi = (jax.random.normal(k1, (B, N)) +
           1j * jax.random.normal(k2, (B, N))).astype(sv.CDTYPE)
    g = T.gate_matrix(T.GATE_RY,
                      jax.random.uniform(k3, (B,), jnp.float32, -3, 3))
    for target, control in [(0, -1), (2, -1), (1, 3), (3, 0)]:
        idx0, idx1, cmask = T.pair_indices(target, control, n)
        want = ref.statevector_gate(
            jnp.real(psi), jnp.imag(psi), jnp.real(g), jnp.imag(g),
            idx0, idx1, cmask.astype(jnp.float32))
        got = ops.statevector_gate(
            jnp.real(psi), jnp.imag(psi), jnp.real(g), jnp.imag(g),
            idx0, idx1, cmask.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                                   atol=1e-6)
        via_jnp = T.jnp_gate_apply(psi, g, jnp.int32(target),
                                   jnp.int32(control), n)
        np.testing.assert_allclose(np.asarray(jnp.real(via_jnp)),
                                   np.asarray(want[0]), atol=1e-6)


def test_run_tape_pallas_path_matches_jnp_path():
    spec = qnn.QNNSpec("vqc", n_qubits=4)
    th = spec.init_params(jax.random.PRNGKey(4))
    cq = T.compile_qnn(spec)
    X = _batch(4)
    ang = T.tape_angles(cq.tape, X, th)
    psi_jnp = T.run_tape(cq.tape, ang)
    psi_pl = T.run_tape(cq.tape, ang, gate_apply=T.pallas_gate_apply)
    np.testing.assert_allclose(np.asarray(psi_pl), np.asarray(psi_jnp),
                               atol=1e-6)


def test_gate_apply_controlled_identity_on_zero_control():
    """CX with control bit 0 must leave amplitudes untouched."""
    n = 2
    psi = sv.zero_state(n).reshape(1, -1)        # |00>: control bit is 0
    g = T.gate_matrix(T.GATE_X, jnp.zeros((1,), jnp.float32))
    out = T.jnp_gate_apply(psi, g, jnp.int32(1), jnp.int32(0), n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(psi), atol=1e-7)


def test_statevector_gate_never_compiles_silently():
    """The kernel has no Mosaic lowering: asking for one raises instead
    of interpreting behind the caller's back."""
    z = jnp.zeros((2, 4), jnp.float32)
    g = jnp.zeros((2, 2, 2), jnp.float32)
    i = jnp.zeros((2,), jnp.int32)
    with pytest.raises(NotImplementedError, match="Mosaic"):
        ops.statevector_gate(z, z, g, g, i, i, i.astype(jnp.float32),
                             interpret=False)
