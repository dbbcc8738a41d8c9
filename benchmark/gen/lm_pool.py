"""A pool of seeded token batches for a next-token learner.

Text-shaped ids without text: token ranks are Zipf (``p(rank) ~ rank^-a``)
over the vocabulary rows the model holds, and which id has which rank is a
permutation drawn from the seed, so the frequent ids differ from seed to
seed as they do from corpus to corpus. Documents have log-normal lengths
(median and sigma from the traffic file, cut at the sequence length) and are
packed end to end with the vocabulary's last id between them: no padding, no
masking across documents. A sequence is ``unroll_len`` ids and its labels
are the next ids, so each row is cut from ``unroll_len + 1`` ids of the
stream.
"""
from __future__ import annotations

from typing import Dict, Iterator, List

import numpy as np


def token_batch(rng: np.random.Generator, batch_size: int, seq_len: int, vocab: int,
                rank_to_id: np.ndarray, p: dict) -> Dict[str, np.ndarray]:
    n = batch_size * (seq_len + 1)
    weight = np.arange(1, vocab) ** -float(p["zipf_exponent"])      # ranks 1 .. vocab-1
    stream = rank_to_id[rng.choice(vocab - 1, size=n, p=weight / weight.sum())]
    # enough documents to cover the stream, then the separator after each
    mean_len = p["doc_len_median"] * np.exp(p["doc_len_sigma"] ** 2 / 2.0)
    lens = np.zeros((0,), np.int64)
    while lens.sum() + len(lens) < n:
        draw = rng.lognormal(np.log(p["doc_len_median"]), p["doc_len_sigma"],
                             size=int(2 * n / mean_len) + 8)
        lens = np.concatenate([lens, np.clip(draw.astype(np.int64), 1, seq_len)])
    ends = np.cumsum(lens + 1) - 1
    stream[ends[ends < n]] = vocab - 1
    rows = stream.reshape(batch_size, seq_len + 1).astype(np.int32)
    return {"tokens": np.ascontiguousarray(rows[:, :-1]), "labels": np.ascontiguousarray(rows[:, 1:])}


def build(seed: int, params: dict, model_cfg=None, **_) -> List[Dict]:
    """``params['pool']`` batches from ``seed``; the same seed gives the same
    bytes. The vocabulary is the model's (the rows it holds)."""
    rng = np.random.default_rng(seed)
    vocab = int(model_cfg["vocab_size"]) if model_cfg is not None else int(params["vocab_size"])
    rank_to_id = rng.permutation(vocab - 1)
    return [token_batch(rng, params["batch_size"], params["unroll_len"], vocab, rank_to_id, params)
            for _ in range(params["pool"])]


def cycle(pool: List[Dict]) -> Iterator[Dict]:
    """Serve the pool round-robin, for ever, each batch as a fresh dict."""
    i = 0
    while True:
        yield dict(pool[i % len(pool)])
        i += 1
