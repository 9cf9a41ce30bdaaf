"""Attention mixers: GQA (full / sliding-window) and MLA.

Training/prefill uses a chunked online-softmax ("flash") implementation in
pure jnp — HLO-compact (double lax.scan) and O(chunk²) memory — so 32k-token
prefill lowers within VMEM/HBM budgets.  The Pallas kernel in
``repro.kernels.flash_attention`` is the TPU fast path; this module is the
lowering-friendly default used by the dry-run (see DESIGN.md §5).

Decode uses a single-dot path over the (possibly seq-sharded) KV cache —
GSPMD turns the softmax normalizers into small all-reduces (flash-decode
equivalent).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (MATMUL_PRECISION, apply_rope, dense,
                                 lora_pair, rms_norm, rope_freqs, weight)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# chunked flash attention (training / prefill)
# ---------------------------------------------------------------------------
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    q_offset: int = 0,
                    q_chunk: int = 512, k_chunk: int = 512,
                    anchor: bool = True) -> jnp.ndarray:
    """q: (B, Sq, H, D); k/v: (B, Sk, KH, Dk/Dv).  GQA via head grouping.

    ``q_offset``: absolute position of q[0] relative to k[0] (for decoder
    tokens attending past a prefix).  ``window`` > 0 enables sliding-window.
    Returns (B, Sq, H, Dv).
    """
    B, Sq, H, D = q.shape
    _, Sk, KH, Dv = v.shape
    G = H // KH
    scale = D ** -0.5

    # largest divisor ≤ requested chunk (encoder lengths like 1500 are not
    # powers of two)
    q_chunk = next(c for c in range(min(q_chunk, Sq), 0, -1) if Sq % c == 0)
    k_chunk = next(c for c in range(min(k_chunk, Sk), 0, -1) if Sk % c == 0)
    nq, nk = Sq // q_chunk, Sk // k_chunk

    qr = (q.reshape(B, nq, q_chunk, KH, G, D)
           .transpose(1, 0, 3, 4, 2, 5))                 # (nq,B,KH,G,qc,D)
    kr = k.reshape(B, nk, k_chunk, KH, D).transpose(1, 0, 3, 2, 4)
    vr = v.reshape(B, nk, k_chunk, KH, Dv).transpose(1, 0, 3, 2, 4)

    # Anchor the loop layout: without explicit constraints the partitioner
    # reshards the grouped-head tensors on EVERY chunk step (≈TB-scale
    # dynamic all-to-all traffic; EXPERIMENTS.md §Perf iteration 1).  Shard
    # heads on 'model' — KH when divisible, else the G (q-groups-per-kv)
    # dim — and batch on ('pod','data').
    from repro.distributed.sharding import (constrain, head_axis_choice,
                                            mesh_axis_size)
    from jax.sharding import PartitionSpec as P
    kh_ax, g_ax = head_axis_choice(KH, G) if anchor else (None, None)
    # neither head dim divisible (e.g. kimi KH=8, G=8 on a 16-way axis):
    # context-parallel fallback — shard the q-chunk dim instead
    qc_ax = None
    if anchor and kh_ax is None and g_ax is None \
            and q_chunk % max(mesh_axis_size("model"), 1) == 0:
        qc_ax = "model"
    _BA = ("pod", "data")
    if anchor:
        qr = constrain(qr, P(None, _BA, kh_ax, g_ax, qc_ax, None))
        kr = constrain(kr, P(None, _BA, kh_ax, None, None))
        vr = constrain(vr, P(None, _BA, kh_ax, None, None))

    q_pos_base = jnp.arange(q_chunk)
    k_pos_base = jnp.arange(k_chunk)

    def q_chunk_body(qi, qc):
        # online softmax over k chunks
        m0 = jnp.full((B, KH, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KH, G, q_chunk), jnp.float32)
        acc0 = jnp.zeros((B, KH, G, q_chunk, Dv), jnp.float32)
        if anchor:
            m0 = constrain(m0, P(_BA, kh_ax, g_ax, qc_ax))
            l0 = constrain(l0, P(_BA, kh_ax, g_ax, qc_ax))
            acc0 = constrain(acc0, P(_BA, kh_ax, g_ax, qc_ax, None))

        def k_chunk_body(carry, kin):
            m, l, acc = carry
            ki, kc, vc = kin
            s = jnp.einsum("bkgqd,bkcd->bkgqc", qc, kc,
                           preferred_element_type=jnp.float32,
                           precision=MATMUL_PRECISION) * scale
            if anchor:
                s = constrain(s, P(_BA, kh_ax, g_ax, qc_ax, None))
            qpos = q_offset + qi * q_chunk + q_pos_base       # (qc,)
            kpos = ki * k_chunk + k_pos_base                  # (kc,)
            mask = jnp.ones((q_chunk, k_chunk), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window:
                mask &= qpos[:, None] - kpos[None, :] < window
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqc,bkcd->bkgqd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32,
                precision=MATMUL_PRECISION)
            return (m_new, l_new, acc_new), None

        (m, l, acc), _ = jax.lax.scan(
            k_chunk_body, (m0, l0, acc0),
            (jnp.arange(nk), kr, vr))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        return out.astype(q.dtype)                       # (B,KH,G,qc,Dv)

    outs = jax.lax.map(lambda args: q_chunk_body(*args),
                       (jnp.arange(nq), qr))             # (nq,B,KH,G,qc,Dv)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, H, Dv)
    return out


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, pos: jnp.ndarray, *,
                     window: int = 0) -> jnp.ndarray:
    """Single-token attention.  q: (B,1,H,D); caches: (B,S,KH,D[v]).

    ``pos``: scalar int32, index of the *current* token (entries > pos are
    masked).  For rolling-window caches S == window and entries are valid by
    construction.  Returns (B,1,H,Dv).
    """
    B, _, H, D = q.shape
    _, S, KH, Dv = v_cache.shape
    G = H // KH
    qr = q.reshape(B, KH, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qr, k_cache,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    idx = jnp.arange(S)
    valid = idx <= pos
    if window:
        valid &= idx > pos - window
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, Dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------
def gqa_params(key, cfg, dtype, cross: bool = False):
    import jax.random as jr
    from repro.models.common import init_dense
    H, KH, D, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    ks = jr.split(key, 4)
    pre = "x" if cross else ""
    return {
        f"{pre}ln": jnp.ones((d,), dtype),
        f"{pre}wq": init_dense(ks[0], (d, H * D), dtype),
        f"{pre}wkv": init_dense(ks[1], (d, 2 * KH * D), dtype),
        f"{pre}wo": init_dense(ks[2], (H * D, d), dtype,
                               scale=0.5 / (d ** 0.5 * cfg.n_layers ** 0.5)),
    }


def gqa_qkv(params, cfg, x, positions, *, rope: bool = True, pre: str = ""):
    H, KH, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    B, S, _ = x.shape
    xn = rms_norm(x, params[f"{pre}ln"], cfg.norm_eps)
    q = dense(xn, weight(params, f"{pre}wq"),
              lora_pair(params, f"{pre}wq", cfg.lora)).reshape(B, S, H, D)
    kv = dense(xn, weight(params, f"{pre}wkv"),
               lora_pair(params, f"{pre}wkv", cfg.lora)).reshape(B, S, 2, KH, D)
    k, v = kv[:, :, 0], kv[:, :, 1]
    if rope:
        freqs = rope_freqs(D, cfg.rope_theta, cfg.mrope_sections)
        q = apply_rope(q, positions, freqs)
        k = apply_rope(k, positions, freqs)
    return xn, q, k, v


def gqa_out(params, cfg, x, attn_out, pre: str = ""):
    B, S, H, D = attn_out.shape
    o = dense(attn_out.reshape(B, S, H * D), weight(params, f"{pre}wo"),
              lora_pair(params, f"{pre}wo", cfg.lora))
    return x + o


def attn_train(params, cfg, x, positions, *, causal=True, window=None,
               anchor=True):
    """Full-sequence GQA layer (train/prefill).  Returns (y, (k, v))."""
    _, q, k, v = gqa_qkv(params, cfg, x, positions)
    w = cfg.sliding_window if window is None else window
    out = flash_attention(q, k, v, causal=causal, window=w, anchor=anchor)
    return gqa_out(params, cfg, x, out), (k, v)


def attn_decode(params, cfg, x, pos, k_cache, v_cache, *, window: int = 0):
    """One-token GQA step.  x: (B,1,d).  Returns (y, (k_cache, v_cache))."""
    positions = pos[None, None] if pos.ndim == 0 else pos
    _, q, k, v = gqa_qkv(params, cfg, x,
                         jnp.broadcast_to(positions, x.shape[:2]))
    S = k_cache.shape[1]
    rolling = bool(window) and S == window
    slot = pos % S if rolling else pos
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k.astype(k_cache.dtype), (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v.astype(v_cache.dtype), (0, slot, 0, 0))
    if rolling:
        # rolling cache: slots wrap; unwritten slots exist only while
        # pos < S, in which case "idx <= pos" is exactly the written set.
        out = decode_attention(q, k_cache, v_cache,
                               jnp.minimum(pos, S - 1), window=0)
    else:
        out = decode_attention(q, k_cache, v_cache, pos, window=window)
    return gqa_out(params, cfg, x, out), (k_cache, v_cache)


def cross_attn_train(params, cfg, x, enc_kv):
    """Decoder cross-attention over encoder output (k, v)."""
    B, S, _ = x.shape
    xn = rms_norm(x, params["xln"], cfg.norm_eps)
    H, D = cfg.n_heads, cfg.head_dim
    q = dense(xn, weight(params, "xwq"),
              lora_pair(params, "xwq", cfg.lora)).reshape(B, S, H, D)
    k, v = enc_kv
    out = flash_attention(q, k, v, causal=False)
    o = dense(out.reshape(B, S, H * D), weight(params, "xwo"),
              lora_pair(params, "xwo", cfg.lora))
    return x + o


def cross_kv(params, cfg, enc_out):
    """Precompute cross-attention K/V from encoder output (prefill)."""
    B, F, _ = enc_out.shape
    KH, D = cfg.n_kv_heads, cfg.head_dim
    kv = dense(enc_out, weight(params, "xwkv"),
               lora_pair(params, "xwkv", cfg.lora)).reshape(B, F, 2, KH, D)
    return kv[:, :, 0], kv[:, :, 1]


def cross_attn_decode(params, cfg, x, xk, xv):
    B, S, _ = x.shape
    xn = rms_norm(x, params["xln"], cfg.norm_eps)
    H, D = cfg.n_heads, cfg.head_dim
    q = dense(xn, weight(params, "xwq"),
              lora_pair(params, "xwq", cfg.lora)).reshape(B, S, H, D)
    out = decode_attention(q, xk, xv, jnp.asarray(xk.shape[1] - 1))
    o = dense(out.reshape(B, S, H * D), weight(params, "xwo"),
              lora_pair(params, "xwo", cfg.lora))
    return x + o


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V2 / MiniCPM3)
# ---------------------------------------------------------------------------
def mla_params(key, cfg, dtype):
    import jax.random as jr
    from repro.models.common import init_dense
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jr.split(key, 6)
    return {
        "ln": jnp.ones((d,), dtype),
        "wq_a": init_dense(ks[0], (d, m.q_lora_rank), dtype),
        "q_norm": jnp.ones((m.q_lora_rank,), dtype),
        "wq_b": init_dense(ks[1], (m.q_lora_rank, H * qk_dim), dtype),
        "wkv_a": init_dense(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim),
                            dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "wkv_b": init_dense(ks[3], (m.kv_lora_rank,
                                    H * (m.qk_nope_head_dim + m.v_head_dim)),
                            dtype),
        "wo": init_dense(ks[4], (H * m.v_head_dim, d), dtype,
                         scale=0.5 / (d ** 0.5 * cfg.n_layers ** 0.5)),
    }


def _mla_q(params, cfg, xn, positions):
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = xn.shape
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    cq = dense(xn, weight(params, "wq_a"), lora_pair(params, "wq_a", cfg.lora))
    cq = rms_norm(cq, params["q_norm"], cfg.norm_eps)
    q = dense(cq, weight(params, "wq_b"),
              lora_pair(params, "wq_b", cfg.lora)).reshape(B, S, H, qk_dim)
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = apply_rope(q[..., m.qk_nope_head_dim:], positions,
                        rope_freqs(m.qk_rope_head_dim, cfg.rope_theta))
    return q_nope, q_rope


def _mla_ckv(params, cfg, xn, positions):
    m = cfg.mla
    ckv_full = dense(xn, weight(params, "wkv_a"), lora_pair(params, "wkv_a", cfg.lora))
    c_kv = rms_norm(ckv_full[..., : m.kv_lora_rank], params["kv_norm"],
                    cfg.norm_eps)
    k_rope = apply_rope(ckv_full[..., m.kv_lora_rank:], positions,
                        rope_freqs(m.qk_rope_head_dim, cfg.rope_theta))
    return c_kv, k_rope


def mla_train(params, cfg, x, positions, *, window: int = 0, anchor=True):
    """Full-sequence MLA.  Materializes per-head K/V from the latent (the
    training-time formulation); cache is the compressed (c_kv, k_rope)."""
    m, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    xn = rms_norm(x, params["ln"], cfg.norm_eps)
    q_nope, q_rope = _mla_q(params, cfg, xn, positions)
    c_kv, k_rope = _mla_ckv(params, cfg, xn, positions)
    kv = dense(c_kv, weight(params, "wkv_b"), lora_pair(params, "wkv_b", cfg.lora))
    kv = kv.reshape(B, S, H, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = kv[..., : m.qk_nope_head_dim], kv[..., m.qk_nope_head_dim:]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.qk_rope_head_dim))], axis=-1)
    out = flash_attention(q, k, v, causal=True, window=window,
                          anchor=anchor)
    o = dense(out.reshape(B, S, H * m.v_head_dim), weight(params, "wo"),
              lora_pair(params, "wo", cfg.lora))
    return x + o, (c_kv, k_rope)


def mla_decode(params, cfg, x, pos, ckv_cache, krope_cache, *,
               window: int = 0):
    """Absorbed-matrix MLA decode: attention runs in the latent space, so the
    cache stays compressed — the family's memory contribution."""
    m, H = cfg.mla, cfg.n_heads
    B = x.shape[0]
    xn = rms_norm(x, params["ln"], cfg.norm_eps)
    positions = jnp.broadcast_to(pos[None, None], x.shape[:2])
    q_nope, q_rope = _mla_q(params, cfg, xn, positions)   # (B,1,H,·)
    c_kv, k_rope = _mla_ckv(params, cfg, xn, positions)   # (B,1,r),(B,1,rope)
    ckv_cache = jax.lax.dynamic_update_slice(
        ckv_cache, c_kv.astype(ckv_cache.dtype), (0, pos, 0))
    krope_cache = jax.lax.dynamic_update_slice(
        krope_cache, k_rope.astype(krope_cache.dtype), (0, pos, 0))

    wkv_b = weight(params, "wkv_b").reshape(
        m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim)
    w_uk = wkv_b[..., : m.qk_nope_head_dim]               # (r,H,nope)
    w_uv = wkv_b[..., m.qk_nope_head_dim:]                # (r,H,v)
    # absorb: q' = q_nope @ W_uk^T  -> latent-space query
    q_lat = jnp.einsum("bihn,rhn->bihr", q_nope, w_uk.astype(q_nope.dtype))
    s = (jnp.einsum("bihr,bsr->bhis", q_lat, ckv_cache,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bihn,bsn->bhis", q_rope, krope_cache,
                      preferred_element_type=jnp.float32))
    s = s * ((m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5)
    S = ckv_cache.shape[1]
    idx = jnp.arange(S)
    valid = idx <= pos
    if window:
        valid &= idx > pos - window
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhis,bsr->bihr", p.astype(ckv_cache.dtype), ckv_cache)
    out = jnp.einsum("bihr,rhv->bihv", ctx, w_uv.astype(ctx.dtype))
    o = dense(out.reshape(B, 1, H * m.v_head_dim).astype(x.dtype),
              weight(params, "wo"), lora_pair(params, "wo", cfg.lora))
    return x + o, (ckv_cache, krope_cache)
