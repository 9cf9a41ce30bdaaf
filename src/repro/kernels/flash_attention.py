"""Flash attention Pallas kernel (online softmax, optional sliding window).

Canonical TPU tiling: grid (B·H, S_q/bq, S_k/bk) with the KV axis
innermost; running max / sum / accumulator live in VMEM scratch and
persist across the KV grid steps (revisiting semantics).  The (bq, bk)
logits tile exists only in VMEM — attention memory is O(S·D), not O(S²).

Sliding window (starcoder2, long-decode variants): blocks entirely outside
[qpos−window+1, qpos] are masked; with block-aligned windows the mask is a
cheap iota comparison (no gather).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: int, bq: int, bk: int,
            n_k: int):
    j = pl.program_id(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # (bq, D)
    k = k_ref[0].astype(jnp.float32)                    # (bk, D)
    v = v_ref[0].astype(jnp.float32)                    # (bk, D)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                 # (bq,)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_cur[:, None])
    # rows with no valid key yet: keep everything zeroed
    alpha = jnp.exp(m_prev - m_cur)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + jnp.dot(p, v, preferred_element_type=jnp.float32))
    m_ref[...] = m_cur

    @pl.when(j == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float = None, bq: int = 128, bk: int = 128,
                    interpret: Optional[bool] = None):
    """q (B,H,S,D), k/v (B,H,S_k,D) already GQA-expanded → (B,H,S,D).

    ``interpret=None`` interprets on the CPU backend only."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, H, S, D = q.shape
    Sk = k.shape[2]
    scale = scale or (D ** -0.5)
    bq, bk = min(bq, S), min(bk, Sk)
    assert S % bq == 0 and Sk % bk == 0
    qr = q.reshape(B * H, S, D)
    kr = k.reshape(B * H, Sk, D)
    vr = v.reshape(B * H, Sk, D)
    n_k = Sk // bk
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, n_k=n_k),
        grid=(B * H, S // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running sum
            pltpu.VMEM((bq, D), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, H, S, D)
