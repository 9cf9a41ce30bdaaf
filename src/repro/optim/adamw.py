"""Minimal AdamW for adapter (LoRA) training — pytree-native, jit-safe."""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.telemetry import LLM_ADAMW


class AdamWState(NamedTuple):
    step: jnp.ndarray
    mu: dict
    nu: dict


def init(params) -> AdamWState:
    z = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
    return AdamWState(step=jnp.zeros((), jnp.int32), mu=z,
                      nu=jax.tree.map(jnp.copy, z))


def update(grads, state: AdamWState, params, *, lr=1e-4, b1=0.9, b2=0.999,
           eps=1e-8, weight_decay=0.0):
    with jax.named_scope(LLM_ADAMW):
        return _update(grads, state, params, lr=lr, b1=b1, b2=b2, eps=eps,
                       weight_decay=weight_decay)


def _update(grads, state, params, *, lr, b1, b2, eps, weight_decay):
    step = state.step + 1
    t = step.astype(jnp.float32)
    c1 = 1.0 - b1 ** t
    c2 = 1.0 - b2 ** t

    def upd(g, m, v, p):
        g = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        upd = (m / c1) / (jnp.sqrt(v / c2) + eps)
        if weight_decay:
            upd = upd + weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * upd).astype(p.dtype), m, v

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = tdef.flatten_up_to(grads)
    flat_m = tdef.flatten_up_to(state.mu)
    flat_v = tdef.flatten_up_to(state.nu)
    out = [upd(g, m, v, p) for g, m, v, p in
           zip(flat_g, flat_m, flat_v, flat_p)]
    new_p = tdef.unflatten([o[0] for o in out])
    new_m = tdef.unflatten([o[1] for o in out])
    new_v = tdef.unflatten([o[2] for o in out])
    return new_p, AdamWState(step=step, mu=new_m, nu=new_v)
