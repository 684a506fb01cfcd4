"""The numbers that decide ``correct``: served output against the plain
float32 reference, teacher-forced over each sampled prompt with its
served tokens.

* ``greedy_gap`` (temperature 0): the widest gap by which a served
  token's reference logit lies below the reference's best logit at that
  position.  A greedy server picks the argmax, so only rounding near a
  tie may cost a little.
* ``loglik_gap`` (temperature > 0): |mean over served tokens of
  log p(y) - E_p[log p]| under the reference distribution p.  Tokens
  drawn from p read about 0, give or take 1/sqrt(n); tokens drawn from
  anything else (another model, a changed token, a greedy pick) read
  about the logits' spread, near 1.
* ``kv_rel_err``: the relative Frobenius error of the keys and values
  that the timed path left in the paged caches of the requests in flight
  at the close of the window, at the last layer of target and draft,
  against the reference's — the worst over requests, K/V and models.
  The last layer's keys carry every earlier layer's error, and they are
  what prefill and the verify forwards wrote.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _token_stats(logits: jax.Array, y: jax.Array, ctrl_arg: jax.Array):
    """Per position: best logit, the served token's logit, log p(y),
    E_p[log p], and the reference logit of ``ctrl_arg``."""
    best = logits.max(-1)
    lse = jax.nn.logsumexp(logits, -1)
    ly = jnp.take_along_axis(logits, y[:, None], -1)[:, 0]
    p = jax.nn.softmax(logits, -1)
    elogp = (p * logits).sum(-1) - lse
    lc = jnp.take_along_axis(logits, ctrl_arg[:, None], -1)[:, 0]
    return best, ly, ly - lse, elogp, lc


def token_stats(ref_logits: jax.Array, ctrl_logits: Optional[jax.Array],
                seq: Sequence[int], n_prompt: int) -> Dict[str, np.ndarray]:
    """Statistics at the positions that predict the served tokens
    ``seq[n_prompt:]``; ``ref_logits`` covers ``seq`` (T, V)."""
    pos = np.arange(n_prompt - 1, len(seq) - 1)
    y = np.asarray(seq[n_prompt:], np.int32)
    lg = ref_logits[pos]
    carg = (jnp.argmax(ctrl_logits[pos], -1) if ctrl_logits is not None
            else jnp.zeros(len(pos), jnp.int32))
    best, ly, logp, elogp, lc = (np.asarray(a) for a in _token_stats(
        lg, jnp.asarray(y), carg.astype(jnp.int32)))
    return {"gap": best - ly, "ctrl_gap": best - lc, "logp": logp,
            "elogp": elogp, "y": y}


def greedy_gap(stats: List[Dict[str, np.ndarray]], key: str = "gap"
               ) -> float:
    return float(max(float(s[key].max()) for s in stats if len(s[key])))


def loglik_gap(stats: List[Dict[str, np.ndarray]]) -> float:
    d = np.concatenate([s["logp"] - s["elogp"] for s in stats])
    return float(abs(d.mean()))


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


def kv_rel_err(pairs: List[Tuple[np.ndarray, np.ndarray]]) -> float:
    return max(rel_err(g, w) for g, w in pairs)


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """Every number at or under its limit; a number without a limit, or
    one that could not be read (NaN), fails."""
    out, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        good = (limit is not None and np.isfinite(value)
                and value <= limit)
        ok = ok and good
        out[name] = {"value": value, "limit": limit}
    return ok and bool(numbers), out
