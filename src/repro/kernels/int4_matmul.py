"""QLoRA int4 matmul Pallas kernel:  y = x @ dequant(packed, scales).

The packed base weight stays int4 in HBM (4× smaller than bf16) and is
dequantized **in VMEM** tile-by-tile right before the MXU consumes it —
the full-precision weight never materializes in HBM (the QLoRA memory
story, adapted to the TPU hierarchy).

Grid: (M/bm, N/bn, K/bk), the reduction axis innermost with an f32
accumulator in VMEM scratch.  Blocks:
    x       (bm, bk)
    packed  (bk, bn//2)  uint8  (two nibbles per byte, even|odd columns)
    scales  (bk, bn//qblock) f32, cut from a (N//bn, K, bn//qblock)
            relayout of the (K, N//qblock) scales so that the block's
            minor dim is the whole array dim (Mosaic's (8, 128) rule).

Mosaic cannot interleave lanes cheaply, so each output block is written
as [even columns | odd columns] — one matmul per nibble plane — and the
wrapper restores the column order with one transpose of the output.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, p_ref, s_ref, o_ref, acc_ref, *, qblock: int, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)               # (bm, bk)
    packed = p_ref[...].astype(jnp.int32)            # (bk, bn//2)
    half = packed.shape[1]
    # packed lane c holds columns 2c and 2c+1, both in scale block
    # c // (qblock/2): expand the scales by a lane select per block
    s = s_ref[...]                                   # (bk, bn//qblock)
    blk = jax.lax.broadcasted_iota(jnp.int32, packed.shape, 1) \
        // (qblock // 2)
    scale = jnp.zeros(packed.shape, jnp.float32)
    for b in range(s.shape[1]):
        scale = jnp.where(blk == b, s[:, b:b + 1], scale)
    even = ((packed & 0xF) - 8).astype(jnp.float32) * scale
    odd = ((packed >> 4) - 8).astype(jnp.float32) * scale
    acc_ref[:, :half] += jnp.dot(x, even, preferred_element_type=jnp.float32)
    acc_ref[:, half:] += jnp.dot(x, odd, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _fit(block: int, dim: int, step: int = 1) -> int:
    """Halve ``block`` (not below ``step``) until it divides ``dim``;
    the whole dim when no such block does."""
    block = min(block, dim)
    while dim % block and block // 2 >= step:
        block //= 2
    return block if dim % block == 0 else dim


@functools.partial(jax.jit, static_argnames=("qblock", "bm", "bn", "bk",
                                             "interpret"))
def int4_matmul(x, packed, scales, *, qblock: int = 64, bm: int = 128,
                bn: int = 512, bk: int = 512,
                interpret: Optional[bool] = None):
    """x (M,K) @ dequant(packed (K,N//2), scales (K,N//qblock)) → (M,N).

    ``interpret=None`` interprets on the CPU backend only."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    M, K = x.shape
    N = packed.shape[1] * 2
    bm, bk, bn = _fit(bm, M), _fit(bk, K), _fit(bn, N, qblock)
    assert bn % qblock == 0, (N, bn, qblock)
    nb, n_k = bn // qblock, K // bk
    s3 = scales.reshape(K, N // bn, nb).transpose(1, 0, 2)
    y = pl.pallas_call(
        functools.partial(_kernel, qblock=qblock, n_k=n_k),
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn // 2), lambda i, j, k: (k, j)),
            pl.BlockSpec((None, bk, nb), lambda i, j, k: (j, k, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, packed, s3)
    # per block [evens | odds] → the natural column order
    return y.reshape(M, N // bn, 2, bn // 2).swapaxes(2, 3).reshape(M, N)
