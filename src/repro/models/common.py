"""Shared model utilities: norms, rotary embeddings, init, LoRA dense."""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# Precision of the model's matmuls on the training/prefill path.  The
# TPU's default rounds f32 operands to bfloat16: after a 30-step LoRA
# fine-tune at Llama-3.2-1B width the batched and sequential LLM engines'
# soft labels then differ by ~1e-3, twice the parity tests' tolerance.
# HIGHEST multiplies f32 in f32; bfloat16 operands take one pass either
# way.
MATMUL_PRECISION = jax.lax.Precision.HIGHEST


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(dt)


def init_dense(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def dense(x: jnp.ndarray, w: jnp.ndarray,
          lora: Optional[Tuple[jnp.ndarray, jnp.ndarray, float]] = None
          ) -> jnp.ndarray:
    """y = x @ w  (+ LoRA path  scale * (x @ A) @ B  in f32 adapters).

    ``w`` may be bf16 (frozen base); LoRA adapters are f32 and the adapter
    path is computed in the activation dtype.
    """
    y = jnp.einsum("...d,df->...f", x, w.astype(x.dtype),
                   precision=MATMUL_PRECISION)
    if lora is not None:
        a, b, scale = lora
        ax = jnp.einsum("...d,dr->...r", x, a.astype(x.dtype),
                        precision=MATMUL_PRECISION)
        y = y + scale * jnp.einsum("...r,rf->...f", ax, b.astype(x.dtype),
                                   precision=MATMUL_PRECISION)
    return y


def weight(params: dict, name: str) -> jnp.ndarray:
    """Resolve a (possibly QLoRA int4-quantized) base weight.

    Quantized layers store ``{name}__q`` (packed uint8 nibbles) and
    ``{name}__s`` (blockwise scales) instead of ``name`` — 4× smaller in
    HBM *and on the wire*: the FSDP all-gather moves the packed form and
    dequantization happens after the collective, per use (the QLoRA
    deployment mode of the paper, realized as collective compression).
    On TPU the fused dequant-matmul is ``repro.kernels.int4_matmul``.
    """
    w = params.get(name)
    if w is not None:
        return w
    from repro.distributed.sharding import constrain, packed_gather_spec
    from repro.peft.lora import dequantize
    # force the FSDP gather in the packed domain (uint8 on the wire);
    # the rule name may carry a cross-attention 'x' prefix
    rule = name[1:] if name.startswith("x") else name
    q = constrain(params[f"{name}__q"], packed_gather_spec(rule))
    s = constrain(params[f"{name}__s"], packed_gather_spec(rule))
    return dequantize(q, s)


def lora_pair(params: dict, name: str, lora_cfg) -> Optional[Tuple]:
    """Fetch (A, B, scale) for target ``name`` if adapters exist."""
    a = params.get(f"{name}_lora_a")
    if a is None:
        return None
    b = params[f"{name}_lora_b"]
    return (a, b, lora_cfg.alpha / lora_cfg.rank)


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE / sectioned M-RoPE realization)
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float,
               sections: Tuple[int, ...] = ()) -> jnp.ndarray:
    """Per-pair inverse frequencies, shape (head_dim//2,).

    For M-RoPE (qwen2-vl) the rotary dims are partitioned into
    temporal/height/width sections; with scalar (text) positions all three
    share the position index, so the realization reduces to concatenated
    per-section frequency ladders (documented in DESIGN.md).
    """
    half = head_dim // 2
    if not sections:
        return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / head_dim))
    freqs = []
    for sec in sections:
        freqs.append(1.0 / (theta ** (jnp.arange(sec, dtype=jnp.float32) * 2
                                      / (2 * sec))))
    out = jnp.concatenate(freqs)
    assert out.shape[0] == half, (sections, head_dim)
    return out


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               freqs: jnp.ndarray) -> jnp.ndarray:
    """x: (..., S, H, D) or (..., S, D); positions: (..., S)."""
    angles = positions.astype(jnp.float32)[..., None] * freqs   # (..., S, D/2)
    if x.ndim == angles.ndim + 1:                               # head axis
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jnp.ndarray) -> jnp.ndarray:
    """Input is the fused (gate‖up) projection; returns silu(gate)*up."""
    gate, up = jnp.split(x, 2, axis=-1)
    return jax.nn.silu(gate) * up


def soft_cap(x, cap: float):
    return cap * jnp.tanh(x / cap)
