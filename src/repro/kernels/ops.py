"""Public jit'd wrappers over the Pallas kernels.

Each kernel takes ``interpret=None`` by default and resolves it when it
is traced: the Pallas interpreter on the CPU backend, Mosaic on a TPU.
``statevector_gate`` has no Mosaic lowering and raises off the CPU
unless ``interpret=True`` is passed.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import distill_kl as _kl
from repro.kernels import flash_attention as _fa
from repro.kernels import int4_matmul as _i4
from repro.kernels import lora_matmul as _lm
from repro.kernels import statevector_gates as _svg


def lora_matmul(x, w, a, b, *, scale: float, **kw):
    return _lm.lora_matmul(x, w, a, b, scale=scale, **kw)


def int4_matmul(x, packed, scales, *, qblock: int = 64, **kw):
    return _i4.int4_matmul(x, packed, scales, qblock=qblock, **kw)


def distill_kl(teacher_probs, student_logits, **kw):
    return _kl.distill_kl(teacher_probs, student_logits, **kw)


def distill_kl_mean(teacher_probs, student_logits, **kw):
    return jnp.mean(distill_kl(teacher_probs, student_logits, **kw))


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0, **kw):
    return _fa.flash_attention(q, k, v, causal=causal, window=window, **kw)


def statevector_gate(psi_re, psi_im, g_re, g_im, idx0, idx1, cmask, **kw):
    return _svg.statevector_gate(psi_re, psi_im, g_re, g_im,
                                 idx0, idx1, cmask, **kw)
