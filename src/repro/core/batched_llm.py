"""Batched LLM fine-tuning engine — Alg. 1 Step 1 as one device program.

The sequential reference (``core/llm_client.LLMClient`` driven by the
orchestrator) fine-tunes clients one at a time: ``llm_steps`` host
dispatches per client, then per-client host evals and a host-side
adapter blend.  This engine runs the **entire fine-tuning stage** — all
C clients' LoRA adapters, every optimizer step, the FedAvg teacher, the
distillation blend, and the label-head evaluations — as a single jitted
program:

  - adapters and AdamW states are stacked into leading-axis ``(C, …)``
    pytrees (``jax.vmap(M.init_adapters)`` / ``jax.vmap(adamw.init)``),
  - the **single shared frozen base is replicated, never stacked** —
    the vmapped train step takes it with ``in_axes=None``,
  - fine-tuning is ``lax.scan`` over ``llm_steps`` of
    ``jax.vmap(M.make_train_step(cfg), in_axes=(None, 0, 0, 0))``,
  - per-client minibatches draw under the ``llm_client.llm_key(root,
    client, step)`` contract via ``sample_minibatch_idx`` — bitwise the
    sequential draws, so batched == sequential draw-for-draw,
  - ``fedavg_adapters`` + ``distill_to_global`` become an on-device
    masked weighted tree reduction
    (``lora.weighted_average_stacked`` + ``lora.blend_adapters``),
  - ``eval_loss`` / ``teacher_probs`` / ``f1`` run as vmapped masked
    label-head evals on the blended adapters.

Padding/mask contract (PR-4 style, two explicit layers)
-------------------------------------------------------
Client shards are ragged in *examples*, and the client count can be
ragged against the device mesh:

  - **example axis**: each client's token shard is padded to
    ``(Nmax, L)`` — tokens with PAD, labels with -1 (so no row mask is
    inferred from content: ``rowmask`` (C, Nmax) is explicit, 1.0 on
    real examples).  Evaluations are mask-weighted with the denominator
    clamped to 1; training minibatches index only rows ``< n_i`` so
    padding never enters the loss.
  - **client axis**: with ``n_devices > 1`` the stacks are padded to a
    multiple of the mesh width (``sharding.pad_client_count``) with
    inert clients — all-zero rowmasks, shard size clamped to 1, zero
    FedAvg weight, PAD-token shards whose all-masked CE is 0, so their
    gradients and AdamW updates are exactly zero.  Padding rows take
    client ids ``C..c_pad-1`` *after* every real client (key folding is
    position-based — sharding never renumbers a real client's draws).

Sharding
--------
With ``n_devices > 1`` the stacks are placed along the 1-D ``'clients'``
mesh (``sharding.put_client_stacks``; adapter/AdamW pytrees via the
strict ``client_tree_specs``) and the base/weights replicated
(``put_replicated``).  GSPMD partitions the jitted program by
computation-follows-data.  Unlike the quantum round program, this
program contains **one deliberate cross-client reduction** — the FedAvg
teacher ``a_g = Σ w_i a_i`` at the distill point — which lowers to a
single all-reduce over adapter-sized tensors; everything before
(fine-tune scan) and after (evals) is collective-free along the client
axis.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry as tel
from repro.core import llm_client as llmc
from repro.data.tokenizer import PAD
from repro.distributed import sharding as shd
from repro.models import model as M
from repro.optim import adamw
from repro.peft import lora as lora_mod

_LLM_ROUND_CACHE: Dict[tuple, object] = {}


@dataclasses.dataclass
class LLMRoundResult:
    """Per-client outputs of the fine-tuning stage (real clients only)."""
    losses: np.ndarray            # (C,)  post-distill eval NLL (L_LLM)
    f1: np.ndarray                # (C,)  post-distill macro-F1
    teacher: np.ndarray           # (C, Nmax, n_labels) soft labels
    final_train_loss: np.ndarray  # (C,)  last fine-tune minibatch loss


def _build_llm_round_fn(cfg, n_labels: int, lr: float, batch_size: int,
                        steps: int, rho: float):
    """Jitted fine-tuning stage → (adapters, opt, a_g, losses, f1,
    teacher, last_train_loss).  Static config closed over; every
    per-round quantity (stacks, keys, weights) is a traced input."""
    # the model's default per-layer remat: at Llama-3.2-1B widths the
    # saved activations of C vmapped clients would not fit one chip
    train_step = M.make_train_step(cfg, n_microbatches=1, lr=lr)
    vstep = jax.vmap(train_step, in_axes=(None, 0, 0, 0))

    def eval_one(params, adp, toks, labs, rmask):
        logits, gold = llmc.label_logits(cfg, params, adp, toks, labs,
                                         n_labels)
        loss = llmc.masked_label_nll(logits, gold, rmask)
        f1 = llmc.masked_macro_f1(logits, gold, rmask, n_labels)
        return loss, f1, jax.nn.softmax(logits, axis=-1)

    veval = jax.vmap(eval_one, in_axes=(None, 0, 0, 0, 0))

    @jax.jit
    def round_fn(base, adapters, opt_state, tokens, labels, rowmask,
                 nvalid, weights, ckeys, step0):
        def body(carry, s):
            adp, opt = carry
            with jax.named_scope(tel.LLM_SAMPLE):
                keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(
                    ckeys, s)
                idx = jax.vmap(llmc.sample_minibatch_idx,
                               in_axes=(0, 0, None))(keys, nvalid,
                                                     batch_size)
                mb = {"tokens": jax.vmap(lambda t, i: t[i])(tokens, idx),
                      "labels": jax.vmap(lambda t, i: t[i])(labels, idx)}
            with jax.named_scope(tel.LLM_STEP):
                adp, opt, metrics = vstep(base, adp, opt, mb)
            return (adp, opt), metrics["loss"]

        # step0 is the GLOBAL step offset (traced — a refresh does not
        # recompile): the contract's ``step`` keeps counting across
        # run() calls, like the sequential wrapper's ``_n_steps``
        (adapters, opt_state), tlosses = jax.lax.scan(
            body, (adapters, opt_state), step0 + jnp.arange(steps))
        # Alg. 1 line 8 on device: FedAvg teacher (the one cross-client
        # reduction of this program) + distillation blend
        with jax.named_scope(tel.LLM_FEDAVG):
            a_g = lora_mod.weighted_average_stacked(adapters, weights)
            adapters = lora_mod.blend_adapters(adapters, a_g, rho)
        with jax.named_scope(tel.LLM_EVAL):
            losses, f1s, teacher = veval(base, adapters, tokens, labels,
                                         rowmask)
        return adapters, opt_state, a_g, losses, f1s, teacher, tlosses[-1]

    return round_fn


def get_llm_round_fn(cfg, *, n_labels: int, lr: float, batch_size: int,
                     steps: int, rho: float):
    """Module-cached program: fresh engine instances (new runs, tests,
    benches) with the same static config reuse one compilation; jax's
    cache then specializes per stack shape."""
    key = (cfg, int(n_labels), float(lr), int(batch_size), int(steps),
           float(rho))
    if key not in _LLM_ROUND_CACHE:
        _LLM_ROUND_CACHE[key] = _build_llm_round_fn(
            cfg, n_labels, lr, batch_size, steps, rho)
    return _LLM_ROUND_CACHE[key]


class BatchedLLMEngine:
    """Stacks all clients' shards/adapters once; runs the stage on device."""

    def __init__(self, task, cfg, base_params, *, seed: int,
                 lr: float = 3e-3, steps: int = 30, batch_size: int = 16,
                 rho: float = 0.25, n_devices: Optional[int] = None,
                 pad_to: Optional[int] = None):
        C = task.n_clients
        n_labels = task.n_classes
        n_max = max(cl.n for cl in task.clients)
        L = task.llm_seq_len

        # ``pad_to`` pads the client axis without a mesh — mesh placement
        # does this automatically; exposed so the padding-inertness
        # contract is testable on a single device.
        self._mesh = None
        c_pad = max(C, int(pad_to)) if pad_to else C
        if n_devices is not None and int(n_devices) > 1:
            self._mesh = shd.client_mesh(int(n_devices))
            c_pad = shd.pad_client_count(c_pad, int(n_devices))

        tokens = np.full((c_pad, n_max, L), PAD, np.int32)
        labels = np.full((c_pad, n_max, L), -1, np.int32)
        rowmask = np.zeros((c_pad, n_max), np.float32)
        nvalid = np.ones((c_pad,), np.int32)     # clamped: padding → 1
        weights = np.zeros((c_pad,), np.float32)
        for i, cl in enumerate(task.clients):
            tokens[i, :cl.n] = cl.llm_batch["tokens"]
            labels[i, :cl.n] = cl.llm_batch["labels"]
            rowmask[i, :cl.n] = 1.0
            nvalid[i] = cl.n
            weights[i] = task.weights[i]
        self._tokens = jnp.asarray(tokens)
        self._labels = jnp.asarray(labels)
        self._rowmask = jnp.asarray(rowmask)
        self._nvalid = jnp.asarray(nvalid)
        self._weights = jnp.asarray(weights)

        # contract keys: real clients keep positions 0..C-1, padding
        # rows fold ids C..c_pad-1 after them (never renumbered)
        root = llmc.llm_root(seed)
        cids = jnp.arange(c_pad)
        self._ckeys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            root, cids)
        ikeys = jax.vmap(llmc.llm_key, in_axes=(None, 0, None))(
            root, cids, llmc.LLM_INIT_STEP)
        self._base = base_params
        self.adapters = jax.vmap(
            lambda k: M.init_adapters(cfg, k, base_params))(ikeys)
        self.opt_state = jax.vmap(adamw.init)(self.adapters)

        if self._mesh is not None:
            flat = (self._tokens, self._labels, self._rowmask,
                    self._nvalid, self._weights, self._ckeys)
            (self._tokens, self._labels, self._rowmask, self._nvalid,
             self._weights, self._ckeys) = shd.put_client_stacks(
                self._mesh, flat, c_pad)
            # adapter/AdamW pytrees: every leaf must be client-stacked —
            # the strict tree placement catches a forgotten vmap(init)
            self.adapters = shd.put_client_tree(self._mesh, self.adapters,
                                                c_pad)
            self.opt_state = shd.put_client_tree(self._mesh,
                                                 self.opt_state, c_pad)
            # the frozen base is REPLICATED, never stacked: its leaves'
            # leading dims (vocab, groups) must not be sharded even if
            # one coincidentally equals c_pad
            self._base = shd.put_replicated(self._mesh, self._base)

        self._n_clients = C
        self._c_pad = c_pad
        self._steps = int(steps)
        self._n_steps = 0             # global step counter (key contract)
        self._calls = 0               # run() calls: the host step number
        self._round = get_llm_round_fn(cfg, n_labels=n_labels, lr=lr,
                                       batch_size=batch_size, steps=steps,
                                       rho=rho)

    def _args(self) -> tuple:
        return (self._base, self.adapters, self.opt_state, self._tokens,
                self._labels, self._rowmask, self._nvalid, self._weights,
                self._ckeys, jnp.int32(self._n_steps))

    def run(self) -> LLMRoundResult:
        """Fine-tune all clients, distill toward the FedAvg teacher, and
        evaluate — one device program.  Updates the engine's stacked
        adapter/optimizer state and advances the global step counter, so
        a later refresh continues from both (draws resume at step
        ``_n_steps``, matching the sequential wrapper's counter)."""
        self._calls += 1
        with jax.profiler.StepTraceAnnotation(tel.LLM_STAGE,
                                              step_num=self._calls):
            with jax.profiler.TraceAnnotation(tel.LLM_STAGE_DISPATCH):
                (self.adapters, self.opt_state, self.a_g, losses, f1s,
                 teacher, tlast) = self._round(*self._args())
            self._n_steps += self._steps
            C = self._n_clients
            with jax.profiler.TraceAnnotation(
                    tel.LLM_STAGE_FETCH,
                    bytes=tel.nbytes((losses, f1s, teacher, tlast))):
                return LLMRoundResult(
                    losses=np.asarray(losses, np.float64)[:C],
                    f1=np.asarray(f1s, np.float64)[:C],
                    teacher=np.asarray(teacher, np.float32)[:C],
                    final_train_loss=np.asarray(tlast, np.float64)[:C])

    def compiled_text(self) -> str:
        """The optimized HLO text of the program ``run()`` executes next
        (lowered on its own arguments; the compilation cache serves it)."""
        return self._round.lower(*self._args()).compile().as_text()

    def teacher_probs_list(self, task, teacher: np.ndarray) -> List:
        """Slice the padded (C, Nmax, n_labels) teacher stack back into
        the orchestrator's ragged per-client list."""
        return [teacher[i, :cl.n] for i, cl in enumerate(task.clients)]
