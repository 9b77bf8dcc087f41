"""Traffic generators, read by the mixes in ``mixes/`` by ``generator``.

``zipf_clusters`` is a copy of the program's synthetic LM stream
(``repro.data.synthetic.zipf_token_stream`` behind
``repro.data.pipeline.lm_batch_fn``), kept here so that the yardstick does
not move when the program's generator does. Tokens follow a Zipf law over a
permuted vocabulary; each next token stays in the previous token's cluster
with probability ``stay``. Every batch is a pure function of
``(seed, index)``.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def zipf_cluster_tokens(vocab: int, batch: int, seq_len: int, seed: int,
                        zipf_a: float, n_clusters: int,
                        stay: float) -> np.ndarray:
    """(batch, seq_len) int32 tokens of one stream position ``seed``."""
    base = np.random.default_rng(seed)
    cluster_of = base.integers(0, n_clusters, vocab)
    members = [np.where(cluster_of == i)[0] for i in range(n_clusters)]
    members = [m if len(m) else np.array([0]) for m in members]
    ranks = np.arange(1, vocab + 1, dtype=np.float64) ** (-zipf_a)
    p_unigram = ranks / ranks.sum()
    perm = base.permutation(vocab)
    r = np.random.default_rng((seed, 0))
    toks = np.empty((batch, seq_len), np.int64)
    toks[:, 0] = perm[r.choice(vocab, size=batch, p=p_unigram)]
    stays = r.random((batch, seq_len)) < stay
    fresh = perm[r.choice(vocab, size=(batch, seq_len), p=p_unigram)]
    for t in range(1, seq_len):
        prev_cluster = cluster_of[toks[:, t - 1]]
        pick = r.integers(0, 1 << 30, batch)
        in_cluster = np.array([members[pc][pk % len(members[pc])]
                               for pc, pk in zip(prev_cluster, pick)])
        toks[:, t] = np.where(stays[:, t], in_cluster, fresh[:, t])
    return toks.astype(np.int32)


def train_batch(mix: Dict[str, Any], vocab: int, seed: int,
                index: int) -> Dict[str, np.ndarray]:
    """Batch ``index`` of a training mix: next-token labels, full mask.

    The stream position is ``seed * 1_000_003 + index`` (as in the
    program's ``lm_batch_fn``), so batches of one seed all differ."""
    if mix["generator"] != "zipf_clusters":
        raise ValueError(f"unknown training generator {mix['generator']!r}")
    b, s = int(mix["batch"]), int(mix["seq_len"])
    toks = zipf_cluster_tokens(vocab, b, s + 1, seed * 1_000_003 + index,
                               float(mix["zipf_a"]), int(mix["n_clusters"]),
                               float(mix["stay"]))
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:].astype(np.int32),
            "mask": np.ones((b, s), np.float32)}
