"""The one token generator every traffic mix feeds through.

A mix (``bench/traffic/<name>.json``) fixes the nodes, the sequence length,
the rows per node and the heterogeneity of the nodes' data; this module
draws the token batches from ``--seed``.  Its semantics are those of the
synthetic Zipf stream the trainer is developed on: tokens follow a Zipf
law (probability ~ 1/rank) over the vocabulary, and with heterogeneity
h, node i draws from that law reweighted by (1 - h) + h * V * [token in
node i's slice], the vocabulary cut into one contiguous slice per node
(the last slice takes the remainder).  h = 1 is the paper's ``sorted``
setting, where every node sees only its own slice.  Rows hold seq_len + 1
tokens: inputs are the first seq_len, labels the last seq_len.  Every
batch is a fresh draw, so no two steps see the same rows.
"""
from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


def node_probs(vocab: int, nodes: int, heterogeneity: float) -> np.ndarray:
    """(nodes, vocab) sampling distribution of each node."""
    base = 1.0 / np.arange(1, vocab + 1)
    probs = np.tile(base, (nodes, 1))
    if heterogeneity > 0:
        width = vocab // nodes
        for i in range(nodes):
            mask = np.zeros(vocab)
            hi = (i + 1) * width if i < nodes - 1 else vocab
            mask[i * width:hi] = 1.0
            probs[i] = base * ((1 - heterogeneity) + heterogeneity * vocab
                               * mask)
    return probs / probs.sum(axis=1, keepdims=True)


def batches(traffic: Dict, vocab: int, seed: int) -> Iterator[Dict]:
    """Endless batches ``{"tokens", "labels"}`` of shape
    (nodes, batch_per_node, seq_len), int32, drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    n, rows, seq = (traffic["nodes"], traffic["batch_per_node"],
                    traffic["seq_len"])
    cdf = np.cumsum(node_probs(vocab, n, traffic["heterogeneity"]), axis=1)
    cdf /= cdf[:, -1:]
    while True:
        toks = np.empty((n, rows, seq + 1), np.int32)
        for i in range(n):
            u = rng.random((rows, seq + 1))
            toks[i] = np.minimum(np.searchsorted(cdf[i], u, side="right"),
                                 vocab - 1)
        yield {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
