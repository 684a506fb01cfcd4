"""Latency and rate arithmetic over a window's records (host clock,
seconds).

A request's record holds when it was due, when it was admitted, and its
deliveries: (time, tokens) for every round that committed tokens to it.
Tails are taken over every request or every gap of the window, never from
medians of chunks.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclasses.dataclass
class ReqRecord:
    due: float
    prompt_len: int = 0
    admitted: Optional[float] = None
    deliveries: List[Tuple[float, int]] = dataclasses.field(
        default_factory=list)


@dataclasses.dataclass
class RoundRecord:
    start: float
    end: float
    rows: int              # requests active in the round
    committed: int         # tokens committed by the round


@dataclasses.dataclass
class Window:
    t0: float              # the window opens
    t_due_end: float       # requests due before this belong to it
    t_end: float           # the last round started in it has ended
    reqs: Dict[int, ReqRecord]
    rounds: List[RoundRecord]

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    if len(values) == 0:
        return float("nan")
    return float(np.percentile(np.asarray(values, np.float64), q))


def window_rounds(w: Window) -> List[RoundRecord]:
    return [r for r in w.rounds if r.start >= w.t0 and r.end <= w.t_end]


def output_tokens_per_s(w: Window) -> float:
    """Tokens committed by the window's rounds over the window's
    length."""
    return sum(r.committed for r in window_rounds(w)) / w.seconds


def ttfts(w: Window) -> List[float]:
    """Due-to-first-token of every request due in the window; one that
    never got its first token has no entry and must be counted failed
    by the caller."""
    return [r.deliveries[0][0] - r.due for r in w.reqs.values()
            if w.t0 <= r.due < w.t_due_end and r.deliveries]


def itls(w: Window) -> List[float]:
    """Every gap between two successive deliveries of the same request,
    both inside the window."""
    out = []
    for r in w.reqs.values():
        ts = [t for t, _ in r.deliveries if w.t0 <= t <= w.t_end]
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return out


def tokens_per_row_round(w: Window) -> float:
    rs = window_rounds(w)
    rows = sum(r.rows for r in rs)
    return sum(r.committed for r in rs) / rows if rows else float("nan")


def round_ms_p50(w: Window) -> float:
    return 1e3 * percentile([r.end - r.start for r in window_rounds(w)], 50)
