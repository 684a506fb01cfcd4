"""Run the cell's correctness check once the window has closed and the
engine is freed: pick the sample, run the plain float32 reference over
each prompt with its served tokens, and reduce to the numbers of
``check``.

The sample, drawn from the seed: the longest request the window
finished, then others it finished in a seeded order until
``check.min_tokens`` served tokens are covered, plus every request still
in flight (their caches are what ``kv_rel_err`` reads).  With
``control=True`` (``tools/calibrate.py``, never a benchmark run) it also
returns the readings of the control (the reference in int8 in the
program's place) and of a changed token (every served token + 1).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Tuple

import numpy as np

from harness import check as C
from harness import registry


def _sample(finished: Dict[int, List[int]], min_tokens: int, seed: int
            ) -> List[int]:
    if not finished:
        return []
    rng = np.random.default_rng([seed, 3])
    longest = max(finished, key=lambda r: (len(finished[r]), -r))
    rest = [r for r in sorted(finished) if r != longest]
    out, n = [longest], len(finished[longest])
    for i in rng.permutation(len(rest)):
        if n >= min_tokens:
            break
        out.append(rest[i])
        n += len(finished[rest[i]])
    return out


def check(cell: Dict[str, Any], params, dims, seed: int,
          finished: Dict[int, List[int]], prompts: Dict[int, List[int]],
          flight: List[Dict[str, Any]], root: str, control: bool = False
          ) -> Tuple[Dict[str, float], Dict[str, Any]]:
    ref = registry.load_module(root, "references",
                               cell["config_spec"]["reference"])
    greedy = float(cell["traffic_spec"]["temperature"]) == 0.0
    t0 = time.monotonic()
    chosen = _sample(finished, int(cell["check"]["min_tokens"]), seed)
    seqs = [(prompts[r] + finished[r], len(prompts[r]), None)
            for r in chosen]
    seqs += [(f["tokens"], f["n_prompt"], f) for f in flight]
    stats, ctrl_stats, kv, kv_ctrl = [], [], [], []
    for toks, n_prompt, f in seqs:
        lg, k, v = ref.forward(params["target"], dims["target"],
                               np.asarray(toks, np.int32))
        lq = kq = vq = None
        if control:
            lq, kq, vq = ref.forward(params["target"], dims["target"],
                                     np.asarray(toks, np.int32),
                                     quant="int8")
        if len(toks) > n_prompt:
            stats.append(C.token_stats(lg, lq, toks, n_prompt))
        if f is None:
            continue
        n, kp, vp = f["target"]
        k, v = np.asarray(k[:n]), np.asarray(v[:n])
        kv += [(kp, k), (vp, v)]
        if control:
            kv_ctrl += [(np.asarray(kq[:n]), k), (np.asarray(vq[:n]), v)]
        n, kp, vp = f["draft"]
        if n == 0:
            continue
        _, kd, vd = ref.forward(params["draft"], dims["draft"],
                                np.asarray(toks[:n], np.int32))
        kd, vd = np.asarray(kd), np.asarray(vd)
        kv += [(kp, kd), (vp, vd)]
        if control:
            _, kq, vq = ref.forward(params["draft"], dims["draft"],
                                    np.asarray(toks[:n], np.int32),
                                    quant="int8")
            kv_ctrl += [(np.asarray(kq), kd), (np.asarray(vq), vd)]
    numbers: Dict[str, float] = {}
    numbers["kv_rel_err"] = C.kv_rel_err(kv) if kv else float("nan")
    served = sum(len(s["y"]) for s in stats)
    if greedy:
        numbers["greedy_gap"] = (C.greedy_gap(stats) if served
                                 else float("nan"))
    else:
        numbers["loglik_gap"] = (C.loglik_gap(stats) if served
                                 else float("nan"))
    info = {"finished_requests": len(chosen), "in_flight": len(flight),
            "served_tokens": served,
            "reference_s": round(time.monotonic() - t0, 3)}
    if control:
        info["control"] = {
            "kv_rel_err": C.kv_rel_err(kv_ctrl) if kv_ctrl else None,
            "greedy_gap": C.greedy_gap(stats, "ctrl_gap") if served
            else None,
            **(_changed_token(ref, params, dims, seqs) if served else {})}
    return numbers, info


def _changed_token(ref, params, dims, seqs) -> Dict[str, float]:
    """greedy_gap and loglik_gap of the served tokens each changed to
    token + 1, read by the reference in the program's place (the fault
    'a token altered where it is produced')."""
    stats = []
    V = dims["target"].vocab
    for toks, n_prompt, _ in seqs:
        if len(toks) <= n_prompt:
            continue
        bad = list(toks[:n_prompt]) + [(t + 1) % V for t in toks[n_prompt:]]
        lg, _, _ = ref.forward(params["target"], dims["target"],
                               np.asarray(bad, np.int32))
        stats.append(C.token_stats(lg, None, bad, n_prompt))
    return {"greedy_gap_changed_token": C.greedy_gap(stats),
            "loglik_gap_changed_token": C.loglik_gap(stats)}
