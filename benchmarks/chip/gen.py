"""The benchmark's one traffic generator: a federation made from a seed.

Every cell's inputs come from here, drawn from ``--seed`` and the
parameters of its traffic file; the program receives only the arrays.

The task is a genomic-classification surrogate with the shapes of the
paper's Experiment I: 200-nucleotide sequences of two classes that
differ in base composition and in planted motifs.  Each example is given
in both of the program's representations:

- tokens for the LLM stage: BOS, the sequence's non-overlapping 6-mers,
  then the label token (teacher-forced), padded to ``seq_len``; the
  label row holds the label token at the position that predicts it and
  -1 elsewhere.  The task's ids are drawn from the configuration's whole
  vocabulary: 4 specials, the 4**6 k-mers from id 4, and one token per
  class as the vocabulary's last ids, which the label head reads; the
  vocabulary has to hold at least those 4102 ids.
- four angle features in [0, pi] for the quantum clients.

Every training row of every client differs from every other, so a step
that drops part of a batch changes what it computes.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List

import numpy as np

PAD, BOS = 0, 1
N_SPECIALS = 4
K = 6
N_NT = 200
N_CLASSES = 2
MIN_VOCAB = N_SPECIALS + 4 ** K + N_CLASSES      # 4102
_GC = (0.41, 0.36)
_MOTIFS = (((0, 0, 3, 0, 3, 0), (2, 2, 1, 1, 2, 2)),   # class 0
           ((3, 3, 2, 0, 3, 0), (0, 0, 3, 3, 3, 3)))   # class 1


def sub_seed(seed: int, *path: int) -> int:
    """A 31-bit seed derived from the run seed and a path of ints; any
    whole number, however large, is a valid run seed."""
    ss = np.random.SeedSequence([int(seed) % 2 ** 64, *path])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


@dataclass
class Shard:
    """One client's examples, as the program's task object holds them."""
    qX: np.ndarray                  # (n, 4) float32 angles
    qy: np.ndarray                  # (n,) int32 classes
    llm_batch: dict                 # tokens/labels (n, seq_len) int32
    n: int = 0

    def __post_init__(self):
        self.n = len(self.qy)


@dataclass
class Federation:
    """Duck-types the program's ``FederatedTask``."""
    clients: List[Shard]
    val_qX: np.ndarray
    val_qy: np.ndarray
    test_qX: np.ndarray
    test_qy: np.ndarray
    llm_seq_len: int
    vocab_size: int
    name: str = "genomic"
    n_classes: int = N_CLASSES
    weights: np.ndarray = field(default=None)

    @property
    def n_clients(self) -> int:
        return len(self.clients)


def _sequences(rng, n: int):
    labels = rng.integers(0, N_CLASSES, size=n).astype(np.int32)
    seqs = np.empty((n, N_NT), np.int8)
    for cls in range(N_CLASSES):
        idx = np.nonzero(labels == cls)[0]
        gc = _GC[cls]
        p = [(1 - gc) / 2, gc / 2, gc / 2, (1 - gc) / 2]
        seqs[idx] = rng.choice(4, size=(len(idx), N_NT), p=p)
        for i in idx:
            for m in _MOTIFS[cls]:
                if rng.random() < 0.7:
                    off = rng.integers(0, N_NT - len(m))
                    seqs[i, off:off + len(m)] = m
    return seqs, labels


def _tokens(seqs, labels, seq_len: int, vocab_size: int):
    n = len(labels)
    kmers = seqs[:, :N_NT - N_NT % K].reshape(n, -1, K).astype(np.int64)
    ids = N_SPECIALS + (kmers * 4 ** np.arange(K - 1, -1, -1)).sum(-1)
    width = min(ids.shape[1] + 1, seq_len - 1)   # BOS + k-mers, label after
    toks = np.full((n, seq_len), PAD, np.int32)
    ys = np.full((n, seq_len), -1, np.int32)
    toks[:, 0] = BOS
    toks[:, 1:width] = ids[:, :width - 1]
    label_tok = vocab_size - N_CLASSES + labels
    toks[:, width] = label_tok
    ys[:, width - 1] = label_tok
    return toks, ys


def _features(seqs):
    """GC share, the two classes' motif-start counts and the purine share,
    mapped to angles in [0, pi]."""
    gc = np.isin(seqs, (1, 2)).mean(1)
    purine = np.isin(seqs, (0, 2)).mean(1)
    hits = []
    for cls in range(N_CLASSES):
        c = np.zeros(len(seqs))
        for m in _MOTIFS[cls]:
            win = np.lib.stride_tricks.sliding_window_view(seqs, len(m), 1)
            c += (win == np.asarray(m, np.int8)).all(-1).sum(1)
        hits.append(c)
    raw = np.stack([(gc - 0.30) / 0.18, hits[0] / 3.0, hits[1] / 3.0,
                    (purine - 0.40) / 0.20], 1)
    return (np.pi * np.clip(raw, 0.0, 1.0)).astype(np.float32)


def federation(params: dict, seed: int, vocab_size: int) -> Federation:
    """The federation a traffic file describes: ``n_clients`` clients of
    ``examples_per_client`` training examples each, ``n_val``/``n_test``
    held-out examples, token rows of ``seq_len``, ids in a vocabulary of
    ``vocab_size``."""
    if vocab_size < MIN_VOCAB:
        raise ValueError(f"a vocabulary of {vocab_size} cannot hold the "
                         f"task's {MIN_VOCAB} ids")
    C = int(params["n_clients"])
    n = int(params["examples_per_client"])
    n_val, n_test = int(params.get("n_val", 0)), int(params.get("n_test", 0))
    L = int(params["seq_len"])
    rng = np.random.default_rng(sub_seed(seed, 1))
    seqs, labels = _sequences(rng, C * n + n_val + n_test)
    toks, ys = _tokens(seqs, labels, L, vocab_size)
    if len(np.unique(toks[:C * n], axis=0)) != C * n:
        raise ValueError("two training rows are equal; the batch-fault "
                         "check needs distinct rows")
    qX = _features(seqs)
    clients = []
    for c in range(C):
        s = slice(c * n, (c + 1) * n)
        clients.append(Shard(qX=qX[s], qy=labels[s],
                             llm_batch={"tokens": toks[s], "labels": ys[s]}))
    v = slice(C * n, C * n + n_val)
    t = slice(C * n + n_val, C * n + n_val + n_test)
    fed = Federation(clients=clients, val_qX=qX[v], val_qy=labels[v],
                     test_qX=qX[t], test_qy=labels[t], llm_seq_len=L,
                     vocab_size=vocab_size)
    fed.weights = np.full(C, 1.0 / C)
    return fed
