"""Operation and byte counts of the benchmark's work, from shapes alone.

The yardstick's own arithmetic: it reads configuration files and traffic
parameters, never the program.  A multiply-add counts as 2 FLOP.

LLM stage (dense decoder, LoRA on the five projections of each layer):

- ``params``: every parameter, embeddings included (the hand counts in
  PERF.md are of this).
- ``model_flops``: what the algorithm needs.  A trained token costs the
  forward and the activation gradients of the frozen base (2 + 2 FLOP
  per projection and head weight), 6 FLOP per LoRA weight, and
  attention's QK^T and AV over the whole row (4 S H D per layer forward,
  twice that backward).  An evaluated token costs the forward through
  the layers; its label head is two columns at one position.
  Recomputation does not count.
- ``executed_matmuls``: the matmuls the program runs per call, with the
  forward recomputed by per-layer remat, as (name, FLOP, bytes) rows.
  Bytes are float32 operands and result, the weight read once per
  batched matmul.

Quantum rounds: ``circuit_flops_per_eval`` is the statevector work of
one circuit evaluation of one example: every gate of the tape touches
all 2^q complex amplitudes (a 2x2 complex matrix on each pair: 14 FLOP
per amplitude for a one-qubit gate, 16 for a two-qubit one).
"""
from __future__ import annotations

from benchmarks.chip.llm import shapes

F32 = 4


def layer_matmul_params(d: dict) -> int:
    return d["n_layers"] * sum(i * o for i, o in shapes(d).values())


def lora_params(d: dict) -> int:
    r = d["rank"]
    return d["n_layers"] * sum(r * (i + o) for i, o in shapes(d).values())


def head_params(d: dict) -> int:
    return d["d_model"] * d["vocab_size"]


def params(d: dict) -> int:
    """All base parameters: embeddings, layers with their two norms, the
    final norm and an untied head."""
    p = d["vocab_size"] * d["d_model"] + layer_matmul_params(d)
    p += d["n_layers"] * 2 * d["d_model"] + d["d_model"]
    if not d["tie_embeddings"]:
        p += head_params(d)
    return p


def _attn(d: dict, seq: int) -> int:
    """Forward QK^T + AV FLOP per token over a whole row of ``seq``."""
    return 4 * d["n_layers"] * seq * d["n_heads"] * d["head_dim"]


def model_flops(d: dict, *, train_tokens: int, eval_tokens: int,
                eval_rows: int, seq: int, n_labels: int = 2) -> float:
    n_base = layer_matmul_params(d)
    train = (4 * (n_base + head_params(d)) + 6 * lora_params(d)
             + 3 * _attn(d, seq))
    evals = 2 * (n_base + lora_params(d)) + _attn(d, seq)
    return (train * train_tokens + evals * eval_tokens
            + 2 * d["d_model"] * n_labels * eval_rows)


def executed_matmuls(d: dict, *, clients: int, batch: int, seq: int,
                     steps: int, eval_rows: int, n_labels: int = 2):
    """(name, FLOP, bytes) of the matmuls one stage call runs."""
    r, G = d["rank"], d["n_layers"]
    rows_t = clients * batch * seq          # rows of a batched train matmul
    rows_e = eval_rows * seq
    H, D, S = d["n_heads"], d["head_dim"], seq
    out = []

    def mm(name, rows, i, o, times):
        f = 2 * rows * i * o * times
        b = (rows * (i + o) + i * o) * F32 * times
        out.append((name, f, b))

    for name, (i, o) in shapes(d).items():
        # train: forward, remat recompute, activation gradient
        mm(f"{name}.train", rows_t, i, o, 3 * G * steps)
        mm(f"{name}.eval", rows_e, i, o, G)
        # LoRA x@A and (xA)@B: forward, recompute, two gradients each
        mm(f"{name}.lora_a.train", rows_t, i, r, 4 * G * steps)
        mm(f"{name}.lora_b.train", rows_t, r, o, 4 * G * steps)
        mm(f"{name}.lora_a.eval", rows_e, i, r, G)
        mm(f"{name}.lora_b.eval", rows_e, r, o, G)
    # attention score and value matmuls, per (row, head): forward,
    # recompute and two gradients each in training
    for rows, times, tag in ((clients * batch, 4 * G * steps, "train"),
                             (eval_rows, G, "eval")):
        f = 2 * 2 * rows * H * S * S * D * times
        b = 2 * rows * H * (2 * S * D + S * S) * F32 * times
        out.append((f"attention.{tag}", f, b))
    # LM head over every position in training (forward + input gradient),
    # two label columns at one position per evaluated row
    mm("head.train", rows_t, d["d_model"], d["vocab_size"], 2 * steps)
    mm("head.eval", eval_rows, d["d_model"], n_labels, 1)
    return out


def least_time_s(rows, peak_flops: float, peak_bytes: float) -> float:
    """The least time the chip could take for ``rows`` of (name, FLOP,
    bytes): each matmul at the larger of its compute and memory bounds."""
    return sum(max(f / peak_flops, b / peak_bytes) for _, f, b in rows)


def circuit_flops_per_eval(n_qubits: int, gates_1q: int, gates_2q: int) -> int:
    amps = 2 ** n_qubits
    return amps * (14 * gates_1q + 16 * gates_2q)
