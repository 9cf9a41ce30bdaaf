"""Fused LoRA matmul Pallas kernel:  y = x@W + s·(x@A)@B.

TPU adaptation (DESIGN.md §5): the rank-r bottleneck (x@A, (bm, r)) is
computed in VMEM and consumed immediately by the B-projection — the
low-rank intermediate never round-trips HBM, and both matmuls feed the
MXU with 128-aligned tiles.

Grid: (M/bm, N/bn).  Per step the kernel sees
    x     (bm, K)   — full reduction dim in VMEM
    w     (K, bn)
    a     (K, r)    — broadcast over the N grid axis
    b     (r, bn)
VMEM at defaults (bm=bn=128, K≤8192, bf16): ~4.3 MiB — fits v5e's 16 MiB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, a_ref, b_ref, o_ref, *, scale: float):
    x = x_ref[...].astype(jnp.float32)
    acc = jnp.dot(x, w_ref[...].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    xa = jnp.dot(x, a_ref[...].astype(jnp.float32),
                 preferred_element_type=jnp.float32)        # (bm, r)
    acc = acc + scale * jnp.dot(xa, b_ref[...].astype(jnp.float32),
                                preferred_element_type=jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "bm", "bn", "interpret"))
def lora_matmul(x, w, a, b, *, scale: float, bm: int = 128, bn: int = 128,
                interpret: Optional[bool] = None):
    """x (M,K) @ w (K,N) + scale·(x@a (K,r))@b (r,N) → (M,N).

    ``interpret=None`` interprets on the CPU backend only."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    M, K = x.shape
    _, N = w.shape
    r = a.shape[1]
    bm, bn = min(bm, M), min(bn, N)
    while M % bm:
        bm //= 2
    while N % bn:
        bn //= 2
    assert M % bm == 0 and N % bn == 0, (M, N, bm, bn)
    grid = (M // bm, N // bn)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((K, r), lambda i, j: (0, 0)),
            pl.BlockSpec((r, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        interpret=interpret,
    )(x, w, a, b)
