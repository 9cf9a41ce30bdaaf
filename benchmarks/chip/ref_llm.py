"""Plain reference of the federated LoRA stage, in float32.

Written from the published equations, with nothing of the program
imported: a pre-norm decoder (RMSNorm, rotary positions on the two
halves of each head, causal softmax attention, SwiGLU feed-forward), a
LoRA pair ``x @ A @ B * alpha / rank`` beside each adapted projection,
cross-entropy over the vocabulary at each label position, AdamW
(b1 0.9, b2 0.999, eps 1e-8, no weight decay, bias-corrected), then the
federated blend ``a_i <- (1 - rho) a_i + rho sum_j w_j a_j`` and the
label-head evaluation of every client on its whole shard.

Minibatches follow the program's documented draw contract:
``key = fold_in(fold_in(fold_in(PRNGKey(seed), 0x4C4C4D), client),
step)``, indices ``min(floor(uniform(key, (batch,)) * n), n - 1)``.

Clients run one at a time and every matmul asks for ``precision``
(``HIGHEST`` for the reference, a lower one for the control).
``faults`` plants, for the calibration of the limits, the faults the
check has to catch: ``half`` (every step sees half its batch),
``frozen`` (no update), ``no_exchange`` (each chip averages only its own
clients), ``answer`` (client 0's first label logit is raised by 1).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LLM_DOMAIN = 0x4C4C4D
B1, B2, EPS = 0.9, 0.999, 1e-8


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, S, H, D): rotate the pairs (x[:D/2], x[D/2:])."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(D // 2, dtype=jnp.float32) * 2 / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv          # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _proj(x, w, a, b, scale, prec):
    y = jnp.einsum("bsd,df->bsf", x, w, precision=prec)
    ax = jnp.einsum("bsd,dr->bsr", x, a, precision=prec)
    return y + scale * jnp.einsum("bsr,rf->bsf", ax, b, precision=prec)


def hidden(d, base, adp, tokens, prec):
    """Post-norm hidden states (B, S, d_model); a scan over the layers."""
    B, S = tokens.shape
    H, KH, D = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    eps, sc = float(d["norm_eps"]), d["alpha"] / d["rank"]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, pl):
        lay, ad = pl

        def p(name):
            return (lay[name], ad[f"{name}_lora_a"], ad[f"{name}_lora_b"],
                    sc, prec)
        xn = _rms(x, lay["ln"], eps)
        q = _proj(xn, *p("wq")).reshape(B, S, H, D)
        kv = _proj(xn, *p("wkv")).reshape(B, S, 2, KH, D)
        q, k, v = (_rope(q, d["rope_theta"]),
                   _rope(kv[:, :, 0], d["rope_theta"]), kv[:, :, 1])
        k, v = (jnp.repeat(t, H // KH, axis=2) for t in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=prec) / math.sqrt(D)
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                       precision=prec).reshape(B, S, H * D)
        x = x + _proj(o, *p("wo"))
        xn = _rms(x, lay["ln2"], eps)
        gate, up = jnp.split(_proj(xn, *p("w_in")), 2, -1)
        return x + _proj(jax.nn.silu(gate) * up, *p("w_out")), None

    x, _ = jax.lax.scan(layer, base["embed"][tokens],
                        (base["groups"][0], adp["groups"][0]))
    return _rms(x, base["final_norm"], eps)


def _head(d, base):
    return base["embed"].T if d["tie_embeddings"] else base["lm_head"]


def lm_loss(adp, d, base, tokens, labels, prec):
    h = hidden(d, base, adp, tokens, prec)
    logits = jnp.einsum("bsd,dv->bsv", h, _head(d, base), precision=prec)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                               -1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def draw(seed: int, client: int, step, n: int, batch: int):
    key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed), LLM_DOMAIN), client), step)
    u = jax.random.uniform(key, (batch,))
    return jnp.minimum((u * n).astype(jnp.int32), n - 1)


@functools.partial(jax.jit, static_argnums=(0, 7, 8, 9, 10, 11))
def _client_steps(dkey, base, a0, tokens, labels, client, seed, steps,
                  batch, lr, prec, fault):
    d = dict(dkey)
    n = tokens.shape[0]
    zeros = jax.tree.map(jnp.zeros_like, a0)

    def body(carry, s):
        a, m, v = carry
        idx = draw(seed, client, s, n, batch)
        if fault == "half":
            idx = idx[:batch // 2]
        loss, g = jax.value_and_grad(lm_loss)(a, d, base, tokens[idx],
                                              labels[idx], prec)
        t = (s + 1).astype(jnp.float32)
        m = jax.tree.map(lambda m, g: B1 * m + (1 - B1) * g, m, g)
        v = jax.tree.map(lambda v, g: B2 * v + (1 - B2) * g * g, v, g)
        upd = jax.tree.map(
            lambda m, v: (m / (1 - B1 ** t)) / (jnp.sqrt(v / (1 - B2 ** t))
                                                + EPS), m, v)
        if fault != "frozen":
            a = jax.tree.map(lambda a, u: a - lr * u, a, upd)
        return (a, m, v), loss

    (a, m, _), losses = jax.lax.scan(body, (a0, zeros, zeros),
                                     jnp.arange(steps))
    return a, m, losses[-1]


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def _evaluate(dkey, base, adp, tokens, labels, prec, raise_first):
    """Label-head logits at each row's label position -> (loss, f1,
    probabilities)."""
    d = dict(dkey)
    nl = 2
    h = hidden(d, base, adp, tokens, prec)
    pos = jnp.argmax(labels >= 0, axis=1)
    hp = jnp.take_along_axis(h, pos[:, None, None], 1)[:, 0]
    logits = jnp.einsum("bd,dv->bv", hp, _head(d, base)[:, -nl:],
                        precision=prec)
    if raise_first:
        logits = logits.at[:, 0].add(1.0)
    gold = jnp.take_along_axis(labels, pos[:, None], 1)[:, 0] \
        - (d["vocab_size"] - nl)
    logp = jax.nn.log_softmax(logits, -1)
    loss = -jnp.mean(jnp.take_along_axis(logp, gold[:, None], 1))
    pred = jnp.argmax(logits, -1)
    f1s = []
    for c in range(nl):
        tp = jnp.sum((pred == c) & (gold == c))
        fp = jnp.sum((pred == c) & (gold != c))
        fn = jnp.sum((pred != c) & (gold == c))
        prec_c = jnp.where(tp + fp > 0, tp / jnp.maximum(tp + fp, 1), 0.0)
        rec_c = jnp.where(tp + fn > 0, tp / jnp.maximum(tp + fn, 1), 0.0)
        f1s.append(jnp.where(prec_c + rec_c > 0,
                             2 * prec_c * rec_c / (prec_c + rec_c), 0.0))
    return loss, jnp.mean(jnp.stack(f1s)), jax.nn.softmax(logits, -1)


def stage(d: dict, base, a0, shards, weights, *, seed: int, steps: int,
          batch: int, lr: float, rho: float, n_chips: int = 1,
          precision=jax.lax.Precision.HIGHEST, fault: str = ""):
    """One call of the stage from the initial adapters ``a0`` (stacked
    over clients).  ``shards`` is a list of (tokens, labels) numpy pairs.
    Returns host arrays: adapters after the blend and AdamW's first
    moment (lists of per-client leaf lists), and per client the last
    step's training loss, the evaluation loss, F1 and probabilities."""
    dkey = tuple(sorted(d.items()))
    C = len(shards)
    adapters, moments, train = [], [], []
    for c, (toks, labs) in enumerate(shards):
        a0c = jax.tree.map(lambda x: x[c], a0)
        a, m, last = _client_steps(dkey, base, a0c, jnp.asarray(toks),
                                   jnp.asarray(labs), jnp.int32(c),
                                   jnp.int32(seed), steps, batch, lr,
                                   precision, fault)
        adapters.append(jax.device_get(a))
        moments.append(jax.device_get(m))
        train.append(float(last))
    w = np.asarray(weights, np.float64)
    groups = [list(range(C))]
    if fault == "no_exchange":
        per = C // n_chips
        groups = [list(range(i, i + per)) for i in range(0, C, per)]
    blended = [None] * C
    for g in groups:
        wg = w[g] / w[g].sum()
        avg = jax.tree.map(lambda *xs: sum(wi * x for wi, x in zip(wg, xs)),
                           *[adapters[c] for c in g])
        for c in g:
            blended[c] = jax.tree.map(lambda a, m: (1 - rho) * a + rho * m,
                                      adapters[c], avg)
    losses, f1s, probs = [], [], []
    for c, (toks, labs) in enumerate(shards):
        loss, f1, p = _evaluate(dkey, base, blended[c], jnp.asarray(toks),
                                jnp.asarray(labs), precision,
                                fault == "answer" and c == 0)
        losses.append(float(loss))
        f1s.append(float(f1))
        probs.append(np.asarray(p))
    return {"adapters": blended, "moments": moments,
            "train_loss": np.asarray(train), "eval_loss": np.asarray(losses),
            "f1": np.asarray(f1s), "teacher": np.stack(probs)}
