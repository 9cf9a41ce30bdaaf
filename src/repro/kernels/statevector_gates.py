"""Pallas kernel: batched (controlled) 2×2 gate apply on flat statevectors.

The circuit-tape executor (``repro.quantum.tape``) reduces every gate of
the paper's circuits to one controlled 2×2 unitary acting on index pairs
of a ``(B, 2**n)`` statevector batch.  This kernel fuses the gather of
both amplitude planes, the complex 2×2 mat-vec, the control masking, and
the scatter back — one read and one write of the statevector per gate.

Complex amplitudes travel as separate real/imag float32 planes (TPU
Pallas has no complex dtype); the per-example gate matrices arrive as
``(B, 2, 2)`` re/im planes.  Pairing metadata is precomputed outside
(``tape.pair_indices``): ``idx0``/``idx1`` are the flat indices of the
target-bit-0/1 amplitudes and ``cmask`` is 1.0 where the gate acts
(control bit set, or uncontrolled).

Grid: (B/bb,).  Blocks: planes (bb, N), gates (bb, 2, 2), metadata
(N/2,) broadcast to every program.  Oracle: ``ref.statevector_gate``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(pr_ref, pi_ref, gr_ref, gi_ref, i0_ref, i1_ref, m_ref,
            or_ref, oi_ref):
    pr = pr_ref[...].astype(jnp.float32)
    pi = pi_ref[...].astype(jnp.float32)
    i0 = i0_ref[...]
    i1 = i1_ref[...]
    m = m_ref[...][None, :]

    a0r, a0i = pr[:, i0], pi[:, i0]
    a1r, a1i = pr[:, i1], pi[:, i1]

    gr = gr_ref[...].astype(jnp.float32)
    gi = gi_ref[...].astype(jnp.float32)
    g00r, g01r = gr[:, 0, 0, None], gr[:, 0, 1, None]
    g10r, g11r = gr[:, 1, 0, None], gr[:, 1, 1, None]
    g00i, g01i = gi[:, 0, 0, None], gi[:, 0, 1, None]
    g10i, g11i = gi[:, 1, 0, None], gi[:, 1, 1, None]

    n0r = g00r * a0r - g00i * a0i + g01r * a1r - g01i * a1i
    n0i = g00r * a0i + g00i * a0r + g01r * a1i + g01i * a1r
    n1r = g10r * a0r - g10i * a0i + g11r * a1r - g11i * a1i
    n1i = g10r * a0i + g10i * a0r + g11r * a1i + g11i * a1r

    n0r = m * n0r + (1.0 - m) * a0r
    n0i = m * n0i + (1.0 - m) * a0i
    n1r = m * n1r + (1.0 - m) * a1r
    n1i = m * n1i + (1.0 - m) * a1i

    or_ref[...] = pr.at[:, i0].set(n0r).at[:, i1].set(n1r)
    oi_ref[...] = pi.at[:, i0].set(n0i).at[:, i1].set(n1i)


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def statevector_gate(psi_re, psi_im, g_re, g_im, idx0, idx1, cmask, *,
                     bb: int = 256, interpret: Optional[bool] = None):
    """(B,N)×2 planes, (B,2,2)×2 gate planes, (N/2,) pairing → new planes.

    Interpret-only: the body's dynamic gather/scatter on ``idx0``/``idx1``
    does not lower through Mosaic.  ``interpret=None`` interprets on the
    CPU backend and raises on any other unless ``interpret=True`` is
    asked for."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if not interpret:
        raise NotImplementedError(
            "statevector_gate does not lower through Mosaic (dynamic "
            "gather/scatter); on an accelerator use the tape's jnp gate "
            "apply, or pass interpret=True to run the Pallas interpreter")
    B, N = psi_re.shape
    bb = min(bb, B)
    while B % bb:
        bb //= 2
    assert B % bb == 0
    half = N // 2
    meta_spec = pl.BlockSpec((half,), lambda i: (0,))
    plane_spec = pl.BlockSpec((bb, N), lambda i: (i, 0))
    gate_spec = pl.BlockSpec((bb, 2, 2), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(B // bb,),
        in_specs=[plane_spec, plane_spec, gate_spec, gate_spec,
                  meta_spec, meta_spec, meta_spec],
        out_specs=[plane_spec, plane_spec],
        out_shape=[jax.ShapeDtypeStruct((B, N), jnp.float32),
                   jax.ShapeDtypeStruct((B, N), jnp.float32)],
        interpret=interpret,
    )(psi_re, psi_im, g_re, g_im, idx0, idx1, cmask)
    return out[0], out[1]
