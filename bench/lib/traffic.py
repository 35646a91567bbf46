"""The one traffic generator: request streams from a traffic file's
parameters and the seed.

Lengths are drawn so that every seed gets the same set of sizes in
another order: ``n`` lengths are the distribution's quantiles at
``(j + 1/2) / n``, clipped, and the seed only shuffles them.  A run's
work then does not swing with the seed; token ids and the order do.

A length distribution is ``{"dist": "lognormal", "median": m, "sigma":
s, "min": lo, "max": hi}`` or ``{"dist": "fixed", "value": v}``.  A
closed-loop mix (``"kind": "closed_loop"``) gives ``clients`` streams of
``requests_per_client`` requests each; an optional ``documents`` block
(``count``, ``length``, ``zipf_s``) prefixes every prompt with one of a
fixed set of documents picked by Zipf popularity, and the prompt's own
length is then that of the question after it.
"""
from __future__ import annotations

from statistics import NormalDist
from typing import Dict, List

import numpy as np


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's mid-quantiles, clipped."""
    if dist["dist"] == "fixed":
        return np.full(n, int(dist["value"]))
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([NormalDist().inv_cdf((j + 0.5) / n) for j in range(n)])
    x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(int)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 63), stream])


def zipf_weights(count: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, count + 1) ** s
    return w / w.sum()


def closed_loop(t: dict, seed: int, vocab: int) -> Dict[str, object]:
    """Requests of a closed-loop mix: ``{"clients": [[(prompt, max_new),
    ...] per client], "documents": [doc tokens, ...]}``.  Token ids are
    in ``[1, vocab)``."""
    c, k = t["clients"], t["requests_per_client"]
    n = c * k
    rng = rng_for(seed, 0)
    plen = rng.permutation(quantile_lengths(t["prompt"], n))
    olen = rng.permutation(quantile_lengths(t["output"], n))
    docs: List[List[int]] = []
    pick = np.full(n, -1)
    d = t.get("documents")
    if d:
        docs = [rng.integers(1, vocab, d["length"]).tolist()
                for _ in range(d["count"])]
        pick = rng.choice(d["count"], n, p=zipf_weights(d["count"],
                                                         d["zipf_s"]))
    reqs = []
    for j in range(n):
        own = rng.integers(1, vocab, int(plen[j])).tolist()
        prompt = (docs[pick[j]] + own) if pick[j] >= 0 else own
        reqs.append((prompt, int(olen[j])))
    clients = [reqs[i * k:(i + 1) * k] for i in range(c)]
    # the first request of client i keeps (i + 1) / c of its output, so
    # the lanes start staggered, as in a loop that has run a while
    lo = t["output"].get("min", 1)
    for i, cl in enumerate(clients):
        p, o = cl[0]
        cl[0] = (p, max(lo, round(o * (i + 1) / c)))
    return {"clients": clients, "documents": docs}
