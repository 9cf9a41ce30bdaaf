"""Model assembly: init, forward, train/prefill/serve steps, input specs.

Every model is: embed (+frontend stub prefix) → scan(remat(layer-group))
→ final RMSNorm → (chunked-CE loss | logits).  ``train_step`` is the
paper's technique — LoRA fine-tuning: base weights frozen, adapters + AdamW
trained, with microbatch gradient accumulation so 400B-class configs fit
v5e HBM (DESIGN.md §6.8).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, InputShape
from repro.distributed.sharding import constrain, batch_axes
from repro.models import layers as L
from repro.models.common import (MATMUL_PRECISION, dense, init_dense,
                                 rms_norm)
from repro.optim import adamw
from repro.peft import lora as lora_mod
from repro.telemetry import MODEL_HEAD


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(cfg: ModelConfig, key, dtype=None) -> Dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    params: Dict = {
        "embed": init_dense(keys[0], (cfg.vocab_size, cfg.d_model), dtype,
                            scale=0.02),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(keys[1], (cfg.d_model, cfg.vocab_size),
                                       dtype)
    if cfg.frontend:
        params["proj_frontend"] = init_dense(
            keys[2], (cfg.d_model, cfg.d_model), dtype)

    def stack_layers(key, n_groups, mixer, ffn, cross):
        ks = jax.random.split(key, n_groups)
        return jax.vmap(
            lambda k: L.init_layer_params(k, cfg, mixer, ffn, dtype,
                                          cross=cross))(ks)

    gkeys = jax.random.split(keys[3], len(cfg.pattern))
    params["groups"] = tuple(
        stack_layers(gk, cfg.n_groups, mixer, ffn,
                     cross=cfg.encoder_decoder)
        for gk, (mixer, ffn) in zip(gkeys, cfg.pattern))

    if cfg.encoder_decoder:
        ekeys = jax.random.split(keys[4], len(cfg.pattern))
        params["enc_groups"] = tuple(
            stack_layers(ek, cfg.n_encoder_layers // len(cfg.pattern),
                         mixer, ffn, cross=False)
            for ek, (mixer, ffn) in zip(ekeys, cfg.pattern))
        params["enc_final_norm"] = jnp.ones((cfg.d_model,), dtype)
    if cfg.lora.quantize_base:
        # QLoRA: frozen base weights stored (and all-gathered) as packed
        # int4 + scales; dequantized per use (common.weight)
        params = lora_mod.quantize_stacked_groups(params, cfg.lora.targets)
    return params


def init_adapters(cfg: ModelConfig, key, params: Dict) -> Dict:
    """LoRA adapters mirroring the group structure (stacked over groups)."""
    out: Dict = {}

    def stack_adapters(key, group_stack):
        one = jax.tree.map(lambda x: x[0], group_stack)
        n_groups = jax.tree.leaves(group_stack)[0].shape[0]
        ks = jax.random.split(key, n_groups)
        return jax.vmap(
            lambda k: lora_mod.init_layer_adapters(k, cfg, one))(ks)

    for gk in ("groups", "enc_groups"):
        if gk in params:
            keys = jax.random.split(key, len(params[gk]) + 1)
            key = keys[0]
            out[gk] = tuple(stack_adapters(k, g)
                            for k, g in zip(keys[1:], params[gk]))
    return out


def _merge(base_layer: Dict, adapter_layer: Optional[Dict]) -> Dict:
    if not adapter_layer:
        return base_layer
    return {**base_layer, **adapter_layer}


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class FwdOptions:
    window: Optional[int] = None        # override sliding window
    remat: bool = True
    mlstm_chunkwise: bool = False
    collect_cache: bool = False
    causal: bool = True
    seq_parallel: bool = False          # shard residual stream seq on 'model'
    shard_cache: bool = False           # shard collected caches (prefill)
    attn_anchor: bool = True            # anchor attention-loop shardings


_BA = ("pod", "data")


def _shard_cache_tree(tree, batch: int):
    """Prefill-cache sharding: batch over DP axes, long axes over 'model'."""
    def leaf(x):
        if not hasattr(x, "ndim") or x.ndim == 0:
            return x
        spec = [None] * x.ndim
        if batch > 1 and x.shape[0] == batch:
            spec[0] = _BA
        big = [(i, d) for i, d in enumerate(x.shape) if i > 0 and d >= 2048]
        if big:
            i, _ = max(big, key=lambda t: t[1])
            spec[i] = "model"
        return constrain(x, P(*spec))
    return jax.tree.map(leaf, tree)


def _embed_tokens(cfg, params, tokens):
    return jnp.take(params["embed"], tokens, axis=0)


def _run_stack(cfg, groups_base, groups_adp, x, positions, opts: FwdOptions,
               enc_out=None, pattern=None):
    """Scan the layer-group stack.  Returns (x, balance, caches)."""
    pattern = pattern or cfg.pattern

    def group_fn(x, layer_ins):
        # NOTE (§Perf iteration 3, refuted): releasing the seq-sharding at
        # the group entrance ("Megatron seq-parallel") made XLA store the
        # released full-seq copy for the backward pass — peak 26→58 GiB
        # with no collective win.  The carry keeps whatever sharding
        # scan_body constrained; interior layout is left to the
        # partitioner.
        caches, balance = [], jnp.zeros((), jnp.float32)
        for (mixer, ffn), base_l, adp_l in zip(pattern, layer_ins[0],
                                               layer_ins[1]):
            p = _merge(base_l, adp_l)
            enc_kv = None
            if enc_out is not None:
                from repro.models.attention import cross_kv
                enc_kv = cross_kv(p, cfg, enc_out)
            x, cache, bal = L.apply_layer_train(
                cfg, p, x, positions, mixer, ffn,
                causal=opts.causal, window=opts.window,
                mlstm_chunkwise=opts.mlstm_chunkwise, enc_kv=enc_kv,
                anchor=opts.attn_anchor)
            balance = balance + bal
            if opts.collect_cache:
                if enc_kv is not None:
                    cache = (cache, enc_kv)
                if opts.shard_cache:
                    cache = _shard_cache_tree(cache, x.shape[0])
                caches.append(cache)
            else:
                caches.append(None)
        return x, (tuple(caches), balance)

    fn = jax.checkpoint(group_fn) if opts.remat else group_fn

    def scan_body(x, xs):
        x, ys = fn(x, xs)
        if opts.seq_parallel:
            x = constrain(x, P(_BA, "model", None))
        return x, ys

    x, (caches, balances) = jax.lax.scan(
        scan_body, x, (groups_base, groups_adp))
    return x, balances.sum(), caches


def forward(cfg: ModelConfig, params: Dict, adapters: Dict, batch: Dict,
            opts: FwdOptions = FwdOptions()):
    """Returns (hidden (B,S,d) post-norm over *label-bearing* positions,
    balance_loss, caches)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = _embed_tokens(cfg, params, tokens)
    prefix = 0
    enc_out = None

    if cfg.encoder_decoder:
        frames = batch["frontend"]                     # (B, F, d) stub
        e = dense(frames, params["proj_frontend"]) if cfg.frontend else frames
        e_pos = jnp.broadcast_to(jnp.arange(e.shape[1])[None], e.shape[:2])
        eopts = FwdOptions(remat=opts.remat, causal=False)
        e, _, _ = _run_stack(cfg, params["enc_groups"],
                             adapters.get("enc_groups",
                                          _none_like(params["enc_groups"])),
                             e, e_pos, eopts)
        enc_out = rms_norm(e, params["enc_final_norm"], cfg.norm_eps)
    elif cfg.frontend:
        fe = dense(batch["frontend"], params["proj_frontend"])
        x = jnp.concatenate([fe.astype(x.dtype), x], axis=1)
        prefix = fe.shape[1]

    positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None],
                                 (B, x.shape[1]))
    x, balance, caches = _run_stack(
        cfg, params["groups"],
        adapters.get("groups", _none_like(params["groups"])),
        x, positions, opts, enc_out=enc_out)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if opts.seq_parallel:
        # gather seq before the (vocab-sharded) loss head
        x = constrain(x, P(_BA, None, None))
    if prefix:
        x = x[:, prefix:, :]
    return x, balance, caches


def _none_like(groups):
    # empty adapter dicts: scan-compatible (no leaves), merge-safe
    return tuple({} for _ in groups)


# ---------------------------------------------------------------------------
# chunked cross-entropy
# ---------------------------------------------------------------------------
def chunked_ce(cfg, params, hidden, labels, *, chunk: int = 512):
    """Scan over sequence chunks so (B, chunk, V) logits are the only live
    vocab-sized tensor.  labels < 0 are masked."""
    with jax.named_scope(MODEL_HEAD):
        return _chunked_ce(cfg, params, hidden, labels, chunk)


def _chunked_ce(cfg, params, hidden, labels, chunk):
    B, S, d = hidden.shape
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    chunk = min(chunk, S)
    assert S % chunk == 0
    n = S // chunk

    def body(carry, i):
        tot, cnt = carry
        h = jax.lax.dynamic_slice_in_dim(hidden, i * chunk, chunk, axis=1)
        y = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
        logits = jnp.einsum("bsd,dv->bsv", h, head.astype(h.dtype),
                            precision=MATMUL_PRECISION).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(y, 0)[..., None], axis=-1)[..., 0]
        mask = (y >= 0).astype(jnp.float32)
        tot = tot + jnp.sum((logz - gold) * mask)
        cnt = cnt + jnp.sum(mask)
        return (tot, cnt), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        jnp.arange(n))
    return tot / jnp.maximum(cnt, 1.0)


def logits_last(cfg, params, hidden):
    with jax.named_scope(MODEL_HEAD):
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        h = hidden[:, -1, :]
        return jnp.einsum("bd,dv->bv", h, head.astype(h.dtype),
                          precision=MATMUL_PRECISION).astype(jnp.float32)


# ---------------------------------------------------------------------------
# train step (LoRA fine-tuning — the paper's client-side technique)
# ---------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, *, n_microbatches: int = 1,
                    lr: float = 1e-4, opts: FwdOptions = FwdOptions(),
                    loss_chunk: int = 512):
    """Pure ``(params, adapters, opt_state, batch) → (adapters, opt_state,
    metrics)`` LoRA step.

    The returned function is **vmap/scan-composable**: it closes over
    static config only, touches no host state, and every internal op is
    batchable — so the batched LLM engine (``core/batched_llm.py``) can
    run ``lax.scan`` over steps of ``jax.vmap(step, in_axes=(None, 0, 0,
    0))`` with the frozen base replicated and ``(C, …)`` adapter/AdamW
    stacks on the leading client axis.  Keep it that way: no Python side
    effects, no data-dependent Python control flow, no host callbacks.
    """
    def loss_fn(adapters, params, mb):
        hidden, balance, _ = forward(cfg, params, adapters, mb, opts)
        loss = chunked_ce(cfg, params, hidden, mb["labels"],
                          chunk=loss_chunk)
        if cfg.moe:
            loss = loss + cfg.moe.balance_loss_weight * balance
        return loss

    def train_step(params, adapters, opt_state, batch):
        nm = n_microbatches
        ba = ("pod", "data")

        def split(x):
            if x.ndim == 0:
                return x
            b = x.shape[0]
            xm = x.reshape(nm, b // nm, *x.shape[1:])
            return constrain(xm, P(None, ba, *((None,) * (x.ndim - 1))))

        micro = jax.tree.map(split, batch) if nm > 1 else None

        if nm == 1:
            loss, grads = jax.value_and_grad(loss_fn)(adapters, params, batch)
        else:
            def body(carry, i):
                gacc, lacc = carry
                mb = jax.tree.map(
                    lambda x: (jax.lax.dynamic_index_in_dim(
                        x, i, 0, keepdims=False) if x.ndim else x), micro)
                l, g = jax.value_and_grad(loss_fn)(adapters, params, mb)
                return (jax.tree.map(jnp.add, gacc, g), lacc + l), None

            g0 = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32),
                              adapters)
            (grads, loss_sum), _ = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32)), jnp.arange(nm))
            grads = jax.tree.map(lambda g: g / nm, grads)
            loss = loss_sum / nm

        new_adapters, new_opt = adamw.update(grads, opt_state, adapters,
                                             lr=lr)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        return new_adapters, new_opt, {"loss": loss, "grad_norm": gnorm}

    return train_step


# One jitted train step per static config, shared across every consumer:
# each LLMClient used to jit its own make_train_step closure, so C
# federated clients paid C identical compiles of the same program.
_TRAIN_STEP_CACHE: dict = {}


def get_train_step(cfg: ModelConfig, *, n_microbatches: int = 1,
                   lr: float = 1e-4, opts: FwdOptions = FwdOptions(),
                   loss_chunk: int = 512):
    """Module-cached ``jax.jit(make_train_step(...))``.

    Keyed by the full static configuration (``ModelConfig`` and
    ``FwdOptions`` are frozen dataclasses, hence hashable), so instances
    with the same config share one compilation; jax's own cache then
    specializes per input shape as usual.
    """
    key = (cfg, int(n_microbatches), float(lr), opts, int(loss_chunk))
    if key not in _TRAIN_STEP_CACHE:
        _TRAIN_STEP_CACHE[key] = jax.jit(make_train_step(
            cfg, n_microbatches=n_microbatches, lr=lr, opts=opts,
            loss_chunk=loss_chunk))
    return _TRAIN_STEP_CACHE[key]


# ---------------------------------------------------------------------------
# prefill / serve
# ---------------------------------------------------------------------------
def make_prefill_step(cfg: ModelConfig, opts: FwdOptions = FwdOptions(
        remat=False, collect_cache=True)):
    def prefill(params, adapters, batch):
        hidden, _, caches = forward(cfg, params, adapters, batch, opts)
        return logits_last(cfg, params, hidden), caches
    return prefill


def init_cache(cfg: ModelConfig, batch: int, seq: int, *,
               window: int = 0, dtype=jnp.bfloat16):
    """Decode caches, stacked (n_groups, ...) per pattern position."""
    def one(mixer):
        s = seq
        if mixer in ("attn", "mla") and window:
            s = min(seq, window)
        base = L.cache_struct(cfg, mixer, batch, s, dtype)
        return jax.tree.map(
            lambda x: jnp.zeros((cfg.n_groups,) + x.shape, x.dtype), base)

    caches = tuple(one(mixer) for (mixer, _) in cfg.pattern)
    if cfg.encoder_decoder:
        F = cfg.n_frontend_tokens
        xkv = jnp.zeros((cfg.n_groups, batch, F, cfg.n_kv_heads,
                         cfg.head_dim), dtype)
        caches = (caches, tuple((jnp.copy(xkv), jnp.copy(xkv))
                                for _ in cfg.pattern))
    return caches


def make_serve_step(cfg: ModelConfig, *, window: int = 0):
    """One-token decode: (params, adapters, cache, token (B,1), pos) →
    (logits (B,V), cache)."""
    def serve(params, adapters, cache, token, pos):
        x = _embed_tokens(cfg, params, token)
        self_caches = cache[0] if cfg.encoder_decoder else cache
        cross = cache[1] if cfg.encoder_decoder else None

        adp = adapters.get("groups", _none_like(params["groups"]))
        has_cross = cfg.encoder_decoder

        def group_fn(carry, xs):
            x = carry
            if has_cross:
                base_g, adp_g, cache_g, cross_g = xs
            else:
                base_g, adp_g, cache_g = xs
                cross_g = None
            new_caches = []
            for idx, (mixer, ffn) in enumerate(cfg.pattern):
                p = _merge(base_g[idx], adp_g[idx])
                w = window if mixer in ("attn", "mla") else 0
                ck = cross_g[idx] if cross_g is not None else None
                x, nc = L.apply_layer_decode(
                    cfg, p, x, pos, cache_g[idx], mixer, ffn,
                    window=w, cross_kv=ck)
                new_caches.append(nc)
            return x, tuple(new_caches)

        xs = ((params["groups"], adp, self_caches, cross) if has_cross
              else (params["groups"], adp, self_caches))
        x, new_self = jax.lax.scan(group_fn, x, xs)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = logits_last(cfg, params, x)
        new_cache = ((new_self, cross) if cfg.encoder_decoder else new_self)
        return logits, new_cache

    return serve


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins — no allocation)
# ---------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: InputShape, *,
                window: int = 0) -> Dict:
    """Abstract inputs for lower()/compile() dry-runs."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        batch = {"tokens": sds((B, S), i32), "labels": sds((B, S), i32)}
        if cfg.frontend:
            batch["frontend"] = sds((B, cfg.n_frontend_tokens, cfg.d_model),
                                    jnp.bfloat16)
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": sds((B, S), i32)}
        if cfg.frontend:
            batch["frontend"] = sds((B, cfg.n_frontend_tokens, cfg.d_model),
                                    jnp.bfloat16)
        return batch
    if shape.kind == "decode":
        cache = jax.eval_shape(
            lambda: init_cache(cfg, B, S, window=window))
        return {"token": sds((B, 1), i32), "pos": sds((), i32),
                "cache": cache}
    raise ValueError(shape.kind)
