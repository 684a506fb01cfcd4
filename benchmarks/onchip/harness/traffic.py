"""Traffic generator: one reader for every mix file under ``traffic/``.

A mix file holds parameters only::

    {"arrival": "backlog", "requests": 512, "block": 16,
     "temperature": 1.0,
     "prompt": {"median": 256, "sigma": 0.6, "min": 32, "max": 1024},
     "output": {"median": 128, "sigma": 0.5, "min": 16, "max": 512}}

Every seed gets the same work in another order.  Lengths are the
``block`` quantiles of the clipped lognormal, so each block of ``block``
requests holds every prompt length and every output length once, paired
by a permutation drawn from the seed.  The whole backlog is due when
the window opens.  Only the token ids and the order depend on the seed,
so set-up (one compiled prefill per distinct prompt length) and the work
in a window do not.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


# warm-up requests take ids from here on, apart from the timed ones
WARMUP_RID = 1 << 24


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int


def levels(dist: Dict[str, Any], n: int) -> List[int]:
    """The ``n`` quantiles of the clipped lognormal, at probabilities
    (i + 0.5) / n, rounded to whole tokens."""
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = dist["median"] * math.exp(dist["sigma"] * z)
        out.append(int(min(dist["max"], max(dist["min"], round(v)))))
    return out


def generate(mix: Dict[str, Any], seed: int, vocab: int) -> List[Request]:
    rng = np.random.default_rng(seed)
    n, block = int(mix["requests"]), int(mix["block"])
    plen, olen = levels(mix["prompt"], block), levels(mix["output"], block)
    if mix["arrival"] != "backlog":
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    reqs: List[Request] = []
    for b0 in range(0, n, block):
        pi, oi = rng.permutation(block), rng.permutation(block)
        for j in range(min(block, n - b0)):
            p = rng.integers(0, vocab, plen[pi[j]]).tolist()
            reqs.append(Request(rid=b0 + j, prompt=p, max_new=olen[oi[j]]))
    return reqs


def warmup(mix: Dict[str, Any], seed: int, vocab: int, max_new: int
           ) -> List[Request]:
    """One request per distinct prompt length, each ``max_new`` tokens
    long: serving them compiles every prefill the mix can ask for.
    Token ids come from a stream of their own, apart from the timed
    requests'."""
    rng = np.random.default_rng([seed, 1])
    block = int(mix["block"])
    return [Request(rid=WARMUP_RID + i,
                    prompt=rng.integers(0, vocab, n).tolist(),
                    max_new=max_new)
            for i, n in enumerate(sorted(set(levels(mix["prompt"], block))))]
