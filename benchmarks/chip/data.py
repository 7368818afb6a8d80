"""Client text shards from a traffic file's parameters and a seed.

Token ids follow a Zipf law over the whole vocabulary and each client's
shard size a log-normal law (heavy-tailed, as LEAF's natural partitions
are), clipped to at least one batch so every client trains at the full
batch size.  All draws are vectorised numpy from one generator seeded by
``seed``: the same seed gives the same shards.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

# separates this stream from any other use of the same seed
_STREAM = 0x0DA7A


def zipf_tokens(rng: np.random.Generator, shape: Tuple[int, ...], vocab: int,
                exponent: float) -> np.ndarray:
    """Token ids with P(id = k) proportional to (k + 1) ** -exponent."""
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(exponent)
    cdf = np.cumsum(p)
    cdf /= cdf[-1]
    u = rng.random(shape)
    return np.minimum(np.searchsorted(cdf, u, side="right"),
                      vocab - 1).astype(np.int32)


def shard_sizes(rng: np.random.Generator, clients: int, shards: dict
                ) -> np.ndarray:
    sizes = rng.lognormal(np.log(shards["median"]), shards["sigma"], clients)
    return np.clip(np.round(sizes), shards["min"], shards["max"]).astype(
        np.int64)


def client_text(traffic: dict, seed: int, vocab: int
                ) -> Tuple[np.ndarray, np.ndarray, List[np.ndarray],
                           Dict[str, np.ndarray]]:
    """``(x, y, parts, test)``: next-token inputs and labels
    ``(S, seq_len)`` for all clients' sequences, each client's row
    indices, and the evaluation batch."""
    rng = np.random.default_rng([_STREAM, int(seed)])
    seq = int(traffic["seq_len"])
    sizes = shard_sizes(rng, int(traffic["engine"]["num_clients"]),
                        traffic["shards"])
    n_test = int(traffic["eval_sequences"])
    total = int(sizes.sum()) + n_test
    tokens = zipf_tokens(rng, (total, seq + 1), vocab, traffic["zipf_exponent"])
    x = np.ascontiguousarray(tokens[:, :-1])
    y = np.ascontiguousarray(tokens[:, 1:])
    ends = np.cumsum(sizes)
    parts = [np.arange(e - s, e) for s, e in zip(sizes, ends)]
    test = {"tokens": x[total - n_test:], "labels": y[total - n_test:]}
    return x, y, parts, test

