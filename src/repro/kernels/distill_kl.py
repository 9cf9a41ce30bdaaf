"""Fused distillation-KL Pallas kernel: per-row KL(P_t ‖ softmax(z)).

Fuses the student softmax (max-shifted logsumexp) with the KL reduction so
the normalized student distribution never hits HBM — one read of (P_t, z),
one write of (B,) row KLs.

Grid: (B/bb,).  Blocks: teacher (bb, C), logits (bb, C), out (bb, 1) — a
column, since a 1-D (bb,) block does not match XLA's layout of a (B,)
array on the TPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(t_ref, z_ref, o_ref, *, eps: float):
    pt = jnp.clip(t_ref[...].astype(jnp.float32), eps, 1.0)
    z = z_ref[...].astype(jnp.float32)
    m = jnp.max(z, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(z - m), axis=-1, keepdims=True)) + m
    logq = z - lse
    o_ref[...] = jnp.sum(pt * (jnp.log(pt) - logq), axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("eps", "bb", "interpret"))
def distill_kl(teacher_probs, student_logits, *, eps: float = 1e-9,
               bb: int = 256, interpret: Optional[bool] = None):
    """(B, C), (B, C) → per-row KL (B,) float32.

    ``interpret=None`` interprets on the CPU backend only."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, C = teacher_probs.shape
    bb = min(bb, B)
    while B % bb:
        bb //= 2
    assert B % bb == 0
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(B // bb,),
        in_specs=[pl.BlockSpec((bb, C), lambda i: (i, 0)),
                  pl.BlockSpec((bb, C), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.float32),
        interpret=interpret,
    )(teacher_probs, student_logits)
    return out[:, 0]
