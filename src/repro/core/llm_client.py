"""Per-client LLM fine-tuning (Alg. 1 Step 1) on the repro.models substrate.

Every client shares a frozen randomly-initialized base LLM (the "pretrained"
model; DESIGN.md §2 — no offline checkpoints) and fine-tunes **LoRA
adapters** on its private shard during round 1 only.  The fine-tuned LLM
then provides:
  - ``eval_loss``     : the reference loss L_LLM^t for optimizer regulation,
  - ``teacher_probs`` : per-example soft class labels for KL distillation,
  - ``f1``            : macro-F1 (paper Fig. 24 benchmark axis).

"Distill LLM using a global model" (Alg. 1 line 8) is realized as adapter
blending toward the weighted FedAvg adapter: a_i ← (1−ρ)·a_i + ρ·a_g.

This module owns the **sequential parity reference** for the fine-tuning
stage: the pure per-client functions (``label_logits``/``masked_label_nll``/
``masked_macro_f1``) plus the thin ``LLMClient`` wrapper that runs them one
client at a time.  ``core/batched_llm.py`` runs the same math stacked over
all clients in one jitted program; both paths draw identically under the
key contract below, so batched == sequential draw-for-draw.

LLM key-derivation contract
---------------------------
Mirroring the quantum stage's ``eval_key(seed, round, client, slot)``
contract, every random draw of the fine-tuning stage derives from

    ``llm_key(llm_root(seed), client, step)``
    = ``fold_in(fold_in(fold_in(PRNGKey(seed), LLM_DOMAIN), client), step)``

where ``client`` is the client's *position* ``0..C-1`` (padding rows on a
mesh take ids ``C..``, appended after every real client — sharding never
renumbers) and ``step`` is the **global fine-tune step index**:

  - minibatch draw of step ``s``   → ``llm_key(root, client, s)``
    (``sample_minibatch_idx``: with-replacement uniform indices — a pure
    function of the key and the shard size, so the batched engine's
    vmapped draw is bitwise the sequential draw),
  - adapter initialization         → ``llm_key(root, client,
    LLM_INIT_STEP)`` (a reserved step id at the top of the range).

``LLM_DOMAIN`` separates this chain from the orchestrator's shot-noise
chain (which folds round indices into the same seed root).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import paper_models
from repro.models import model as M
from repro.optim import adamw
from repro.peft import lora as lora_mod

# Reserved ids of the LLM key contract (module docstring).  LLM_DOMAIN is
# folded once into PRNGKey(seed) so the fine-tune chain and the quantum
# shot-noise chain (fold_in(round)) can never collide; LLM_INIT_STEP is
# the adapter-init draw's reserved step id.
LLM_DOMAIN = 0x4C4C4D            # "LLM"
LLM_INIT_STEP = 0x7FFFFFFF


def llm_root(seed: int) -> jax.Array:
    """Root of the fine-tuning stage's key chain for a run seed."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), LLM_DOMAIN)


def llm_key(root: jax.Array, client, step) -> jax.Array:
    """The contract's key chain; ``client``/``step`` may be traced ints
    (usable under ``jit`` / ``vmap`` / ``lax.scan``)."""
    return jax.random.fold_in(jax.random.fold_in(root, client), step)


def sample_minibatch_idx(key: jax.Array, n, batch_size: int) -> jnp.ndarray:
    """With-replacement uniform minibatch indices in ``[0, n)``.

    ``n`` may be a traced per-client shard size (clamped to >= 1 so inert
    padding clients index row 0 of their padded stack); ``batch_size`` is
    static, so every client draws the same shape and the batched engine
    can vmap this over ``(keys, ns)`` — per-lane draws are bitwise the
    sequential per-client calls.
    """
    u = jax.random.uniform(key, (batch_size,))
    n = jnp.maximum(n, 1)
    return jnp.minimum((u * n).astype(jnp.int32), n - 1)


def task_llm_config(base_name: str, vocab_size: int, seq_len: int):
    """Clone a paper LLM config with the task vocabulary.

    ``tiny-llm`` is the CPU-scale default; pass 'llama3.2-1b' etc. for the
    full paper configs (dry-run scale).
    """
    base = {
        "tiny-llm": paper_models.TINY_LLM,
        "llama3.2-1b": paper_models.LLAMA32_1B,
        "gpt2": paper_models.GPT2,
        "deepseek-llm-7b-base": paper_models.DEEPSEEK_7B,
    }[base_name]
    return dataclasses.replace(base, vocab_size=vocab_size)


# ---------------------------------------------------------------------------
# pure per-client evaluation math (shared by both engines)
# ---------------------------------------------------------------------------
def label_logits(cfg, params: Dict, adapters: Dict, tokens: jnp.ndarray,
                 labels: jnp.ndarray, n_labels: int
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Logits over the label-token block at each example's label position.

    ``tokens``/``labels`` are one client's ``(B, L)`` shard (possibly
    zero/-1 padded rows — a padded row has no ``label >= 0`` position, so
    ``pos`` degenerates to 0 and its gold index is clipped; callers mask
    those rows out).  Returns (logits (B, n_labels) f32, gold (B,)).
    """
    hidden, _, _ = M.forward(cfg, params, adapters, {"tokens": tokens},
                             M.FwdOptions(remat=False))
    pos = jnp.argmax((labels >= 0).astype(jnp.int32), axis=1)        # (B,)
    h = jnp.take_along_axis(hidden, pos[:, None, None], axis=1)[:, 0]
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    label_head = head[:, -n_labels:].astype(jnp.float32)
    # full f32 (the TPU default would round both operands to bfloat16)
    logits = jnp.dot(h.astype(jnp.float32), label_head,
                     precision=jax.lax.Precision.HIGHEST)
    gold_tok = jnp.take_along_axis(labels, pos[:, None], axis=1)[:, 0]
    gold = jnp.clip(gold_tok - (cfg.vocab_size - n_labels), 0,
                    n_labels - 1)
    return logits, gold


def masked_label_nll(logits: jnp.ndarray, gold: jnp.ndarray,
                     mask: jnp.ndarray) -> jnp.ndarray:
    """Classification NLL on the label positions — L_LLM^t.  Mask-weighted
    mean (denominator clamped so an all-padding client stays finite)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, gold[:, None], axis=1)[:, 0]
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def masked_macro_f1(logits: jnp.ndarray, gold: jnp.ndarray,
                    mask: jnp.ndarray, n_labels: int) -> jnp.ndarray:
    """Macro-F1 over masked rows, fully on device (vmap-composable).

    Count accumulation is exact in f32 (integer-valued sums), so this
    matches the old host numpy implementation on unmasked inputs.
    """
    pred = jnp.argmax(logits, axis=-1)
    cls = jnp.arange(n_labels)
    is_p = (pred[:, None] == cls[None, :]).astype(jnp.float32) \
        * mask[:, None]
    is_g = (gold[:, None] == cls[None, :]).astype(jnp.float32) \
        * mask[:, None]
    tp = jnp.sum(is_p * is_g, axis=0)
    fp = jnp.sum(is_p, axis=0) - tp
    fn = jnp.sum(is_g, axis=0) - tp
    p = jnp.where(tp + fp > 0, tp / jnp.maximum(tp + fp, 1.0), 0.0)
    r = jnp.where(tp + fn > 0, tp / jnp.maximum(tp + fn, 1.0), 0.0)
    f1 = jnp.where(p + r > 0, 2 * p * r / jnp.maximum(p + r, 1e-30), 0.0)
    return jnp.mean(f1)


class LLMClient:
    """One client's local LLM: shared frozen base + private LoRA adapters.

    The thin sequential wrapper around the pure functions above — the
    parity reference for ``core/batched_llm.BatchedLLMEngine``.  All C
    instances share **one** jitted train step per config
    (``M.get_train_step``; each instance used to jit its own closure, so
    C clients paid C identical compiles), and every draw follows the
    module's ``llm_key(root, client, step)`` contract.
    """

    def __init__(self, cfg, base_params, key, *, n_labels: int,
                 lr: float = 3e-3, batch_size: int = 16,
                 client_id: int = 0):
        self.cfg = cfg
        self.base = base_params
        self.n_labels = n_labels
        self.lr = lr
        self.batch_size = batch_size
        self.client_id = client_id
        self._root = key                  # llm_root(seed) in federated runs
        self.adapters = M.init_adapters(
            cfg, llm_key(key, client_id, LLM_INIT_STEP), base_params)
        self.opt_state = adamw.init(self.adapters)
        self._step = M.get_train_step(cfg, n_microbatches=1, lr=lr)
        self._n_steps = 0                 # global step counter (contract)

    # -- fine-tuning (round 1 / periodic refresh) ---------------------------
    def fine_tune(self, batch: Dict[str, np.ndarray], *, steps: int = 30
                  ) -> float:
        toks = jnp.asarray(batch["tokens"])
        ys = jnp.asarray(batch["labels"])
        n = toks.shape[0]
        last = float("nan")
        for _ in range(steps):
            k = llm_key(self._root, self.client_id, self._n_steps)
            self._n_steps += 1
            idx = sample_minibatch_idx(k, n, self.batch_size)
            mb = {"tokens": toks[idx], "labels": ys[idx]}
            self.adapters, self.opt_state, metrics = self._step(
                self.base, self.adapters, self.opt_state, mb)
            last = float(metrics["loss"])
        return last

    # -- evaluation ----------------------------------------------------------
    def _label_logits(self, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        toks = jnp.asarray(batch["tokens"])
        ys = jnp.asarray(batch["labels"])
        return label_logits(self.cfg, self.base, self.adapters, toks, ys,
                            self.n_labels)

    def eval_loss(self, batch) -> float:
        """Classification NLL on the label positions — L_LLM^t."""
        logits, gold = self._label_logits(batch)
        mask = jnp.ones((logits.shape[0],), jnp.float32)
        return float(masked_label_nll(logits, gold, mask))

    def teacher_probs(self, batch) -> jnp.ndarray:
        """Soft class labels (B, n_labels) for distillation."""
        logits, _ = self._label_logits(batch)
        return jax.nn.softmax(logits, axis=-1)

    def f1(self, batch) -> float:
        logits, gold = self._label_logits(batch)
        mask = jnp.ones((logits.shape[0],), jnp.float32)
        return float(masked_macro_f1(logits, gold, mask, self.n_labels))


def fedavg_adapters(adapter_list, weights) -> Dict:
    """Weighted average of client adapter pytrees (global LLM teacher)."""
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    out = jax.tree.map(lambda *xs: sum(wi * x for wi, x in zip(w, xs)),
                       *adapter_list)
    return out


def distill_to_global(clients, weights, *, rho: float = 0.25):
    """a_i ← (1−ρ)·a_i + ρ·a_g  (Alg. 1 line 8)."""
    a_g = fedavg_adapters([c.adapters for c in clients], weights)
    for c in clients:
        c.adapters = lora_mod.blend_adapters(c.adapters, a_g, rho)
    return a_g


def run_sequential_stage(task, cfg, base_params, *, seed: int,
                         lr: float = 3e-3, steps: int = 30,
                         batch_size: int = 16, rho: float = 0.25):
    """The whole fine-tuning stage, one client at a time — the parity
    reference for ``core/batched_llm.BatchedLLMEngine`` (the orchestrator's
    ``engine="sequential"`` branch and ``bench_llm_round`` both run this).

    Returns ``(clients, losses, f1s, teachers)`` with evaluations taken
    *after* the distillation blend, matching Alg. 1's ordering.
    """
    root = llm_root(seed)
    clients = []
    for i in range(task.n_clients):
        cl = LLMClient(cfg, base_params, root, client_id=i,
                       n_labels=task.n_classes, lr=lr,
                       batch_size=batch_size)
        cl.fine_tune(task.clients[i].llm_batch, steps=steps)
        clients.append(cl)
    distill_to_global(clients, task.weights, rho=rho)
    losses = [cl.eval_loss(task.clients[i].llm_batch)
              for i, cl in enumerate(clients)]
    f1s = [cl.f1(task.clients[i].llm_batch)
           for i, cl in enumerate(clients)]
    teachers = [cl.teacher_probs(task.clients[i].llm_batch)
                for i, cl in enumerate(clients)]
    return clients, losses, f1s, teachers
