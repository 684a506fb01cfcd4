"""Serving throughput sweep: batch size x request-arrival rate, sequential
vs continuous-batching, JSON report.

For each (max_batch, arrival_interval) cell the same request set runs
through both paths:

  * sequential — runtime/scheduler.py round-robin (one request at a time;
    a request arriving mid-generation waits for every earlier request);
  * batched    — repro.serving continuous batching (token-level batching
    with the paged KV pool; physically paged attention storage by default,
    ``--attn-backend dense`` for the reference layout).

Throughput is modeled tokens-per-cost (runtime/cost_model.py, t = 1);
sequential completion accounts for arrival gaps the same way the batched
scheduler does (the clock idles until the next arrival).  Run with
--pair trained for the cached Zipf-Markov pair, or the default random
tiny pair for a fast smoke sweep.

Batched cells additionally record the device-resident loop's host-boundary
traffic (DESIGN.md §7.7): per-step host-transfer bytes (a deterministic
count — the engines tally every device_get) and wall-clock step-latency
p50/p95.  ``--check-baseline`` diffs the measured transfer bytes against a
committed baseline JSON (benchmarks/baselines/serving_transfer_cpu.json)
and exits non-zero when the loop regresses to >2x the committed post-PR
bytes or loses the >=10x reduction over the recorded pre-PR host loop —
the CI bench-smoke gate.

``--draft-mode sequential|parallel`` threads the drafting discipline
(DESIGN.md §7.12) through the sweep cells; ``--draft-mode-sweep
OUT.json`` additionally runs the first batch-size cell under both modes
and reports device dispatches/round, acceptance rate and draft-phase
wall per mode, and ``--draft-mode-gate`` turns that into the CI smoke
gate (parallel must collapse to <=2 dispatches/round and cut draft wall
at <= --draft-mode-margin acceptance loss).

``--spec-predictor on|off|oracle`` threads the acceptance-history
speculation controller (runtime/predictor.py, DESIGN.md §7.11) through
the sweep cells; ``--predictor-sweep OUT.json`` additionally runs the
first batch-size cell with the predictor off/on/oracle and reports
rollback tokens/request per mode, and ``--predictor-gate`` turns that
into the CI smoke gate (predictor-on must reduce rollback tokens/request
without losing throughput).

Usage:
  PYTHONPATH=src python benchmarks/serving_throughput.py \
      --out serving_sweep.json [--check-baseline benchmarks/baselines/...]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

from repro.data.synthetic import ZipfMarkov  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.obs import (NULL_RECORDER, TraceRecorder,  # noqa: E402
                       write_metrics, write_trace)
from repro.models.config import ModelConfig, dense_pattern  # noqa: E402
from repro.runtime.cost_model import CostModel  # noqa: E402
from repro.runtime.engines import EngineConfig  # noqa: E402
from repro.runtime.scheduler import sequential_arrival_cost  # noqa: E402
from repro.runtime.specbranch import SpecBranchEngine  # noqa: E402
from repro.serving import (BatchedSpecBranchEngine,  # noqa: E402
                           ContinuousBatchScheduler, ServeRequest)


def tiny_pair(vocab: int = 64):
    def cfg(name, layers, d, heads):
        return ModelConfig(name=name, family="dense", num_layers=layers,
                           d_model=d, num_heads=heads,
                           num_kv_heads=max(1, heads // 2), d_ff=4 * d,
                           vocab_size=vocab, pattern=dense_pattern(0),
                           dtype="float32")
    tcfg = cfg("bench-t", 2, 64, 2)
    dcfg = cfg("bench-d", 1, 32, 2)
    return (M.init_params(jax.random.PRNGKey(1), dcfg), dcfg,
            M.init_params(jax.random.PRNGKey(0), tcfg), tcfg)


def run_sequential(dp, dcfg, tp, tcfg, ecfg, prompts, n_new, interval,
                   cost, draft_heads=None) -> dict:
    eng = SpecBranchEngine(dp, dcfg, tp, tcfg, ecfg,
                           draft_heads=draft_heads)
    timelines, total_tokens = [], 0
    key = jax.random.PRNGKey(0)
    for p in prompts:
        key, sub = jax.random.split(key)
        r = eng.generate(p, n_new, sub)
        timelines.append(r.timeline)
        total_tokens += len(r.tokens)
    clock = sequential_arrival_cost(timelines, cost, interval)
    return {"total_tokens": total_tokens, "total_cost": clock,
            "tokens_per_cost": total_tokens / max(clock, 1e-9)}


def run_batched(dp, dcfg, tp, tcfg, ecfg, prompts, n_new, interval,
                max_batch, attn_backend="paged", rec=NULL_RECORDER,
                mesh=None, draft_heads=None, prefix_cache=False,
                t_prefill=0.0) -> dict:
    eng = BatchedSpecBranchEngine(dp, dcfg, tp, tcfg, ecfg,
                                  max_batch=max_batch, page_size=16,
                                  attn_backend=attn_backend, mesh=mesh,
                                  draft_heads=draft_heads,
                                  prefix_cache=prefix_cache)
    # price prefill on the modeled clock (prefix-cache cells set this for
    # BOTH cache-on and cache-off, so the TTFT comparison is apples to
    # apples; the default 0.0 keeps every other cell bitwise unchanged)
    eng.cost.t_prefill = t_prefill
    eng.set_recorder(rec)
    sched = ContinuousBatchScheduler(eng)
    reqs = [ServeRequest(rid=i, prompt=p, max_new_tokens=n_new,
                         arrival=i * interval)
            for i, p in enumerate(prompts)]
    sched.run(reqs)
    rep = sched.report()
    out = {k: rep[k] for k in
           ("total_tokens", "total_cost", "tokens_per_cost",
            "ttft_p50", "ttft_p95", "itl_p50", "itl_p95",
            "pool_occupancy_peak", "preemptions", "rounds",
            "host_transfer_bytes", "host_fetches",
            "per_step_transfer_bytes", "step_wall_p50",
            "step_wall_p95")} | {
        "reclaimed_speculative_pages":
            rep["pool"]["reclaimed_speculative_pages"],
        "dispatches_per_round": rep.get("dispatches_per_round")}
    # physical occupancy counts each shared page ONCE; the logical view
    # sums table-bound pages, so logical - physical is the sharing win
    for k in ("pool_logical_occupancy_peak", "shared_pages_peak"):
        if k in rep:
            out[k] = rep[k]
    if "prefix_cache" in rep:
        out["prefix_cache"] = rep["prefix_cache"]
    return out


def overhead_gate(dp, dcfg, tp, tcfg, ecfg, prompts, n_new, max_batch,
                  attn_backend, draft_heads=None) -> TraceRecorder:
    """Tracing-overhead gate (ISSUE 6 satellite 5): after a jit warm-up
    run, interleave untraced (NullRecorder) and traced runs and compare
    best-of-2 wall clocks — fail (exit 1) if tracing costs >10%.  The
    modeled tokens_per_cost must be bit-identical between the two paths
    (the recorder must never change scheduling decisions).  Returns the
    last traced recorder so its trace/metrics can be dumped as CI
    artifacts without an extra run."""
    def one(rec):
        t0 = time.time()
        rep = run_batched(dp, dcfg, tp, tcfg, ecfg, prompts, n_new, 0.0,
                          max_batch, attn_backend=attn_backend, rec=rec,
                          draft_heads=draft_heads)
        return time.time() - t0, rep["tokens_per_cost"]

    one(NULL_RECORDER)                      # jit warm-up, discarded
    walls_off, walls_on = [], []
    rec = NULL_RECORDER
    tpc_off = tpc_on = None
    for _ in range(2):                       # interleaved: fair vs drift
        w, tpc_off = one(NULL_RECORDER)
        walls_off.append(w)
        rec = TraceRecorder()
        w, tpc_on = one(rec)
        walls_on.append(w)
    best_off, best_on = min(walls_off), min(walls_on)
    ratio = best_on / max(best_off, 1e-9)
    print(f"overhead gate: untraced {best_off:.3f}s vs traced "
          f"{best_on:.3f}s (x{ratio:.3f}, {len(rec.events)} events)")
    if tpc_on != tpc_off:
        print(f"  FAIL: tracing changed the modeled schedule "
              f"(tokens_per_cost {tpc_on} != {tpc_off})")
        sys.exit(1)
    if ratio > 1.10:
        print("  FAIL: tracing-enabled run >10% slower than untraced")
        sys.exit(1)
    print("overhead gate passed")
    return rec


def predictor_sweep(dp, dcfg, tp, tcfg, args, prompts, out_path: str,
                    gate: bool = False, tol: float = 0.05) -> None:
    """Rollback sweep (ISSUE 8 / DESIGN.md §7.11): the same request set
    through the batched SpecBranch engine with the acceptance-history
    predictor off / on / oracle.  Per mode: rollback tokens per finished
    request (trace-registry totals — the same host packets the engine
    consumes) and modeled tokens-per-cost.  With ``gate``: exit 1 unless
    predictor-on keeps throughput within ``tol`` of predictor-off AND
    strictly reduces rollback tokens/request — the CI bench-smoke gate."""
    mb = args.batch_sizes[0]
    modes = {}
    for mode in ("off", "on", "oracle"):
        ecfg = EngineConfig(gamma=args.gamma, c=args.c, temperature=0.0,
                            epsilon=0.4, signal_temperature=0.5,
                            spec_predictor=mode, max_len=512)
        rec = TraceRecorder()
        t0 = time.time()
        rep = run_batched(dp, dcfg, tp, tcfg, ecfg, prompts,
                          args.new_tokens, 0.0, mb, rec=rec,
                          attn_backend=args.attn_backend)
        reg = rec.registry
        n_req = max(reg.counter("requests_finished_total").value, 1)
        rb = reg.counter("rollback_tokens_total").value
        modes[mode] = {
            "tokens_per_cost": rep["tokens_per_cost"],
            "rollback_tokens_total": rb,
            "rollback_tokens_per_request": rb / n_req,
            "drafted_tokens_total":
                reg.counter("tokens_drafted_total").value,
            "pred_decisions": reg.counter("pred_decisions_total").value,
            "requests_finished": n_req,
            "wall_s": time.time() - t0,
        }
        print(f"predictor={mode:6s}: {rep['tokens_per_cost']:.3f} tok/cost  "
              f"rollback/req {modes[mode]['rollback_tokens_per_request']:.2f}"
              f"  drafted {modes[mode]['drafted_tokens_total']}")
    off, on = modes["off"], modes["on"]
    report = {
        "engine": "specbranch", "mode": "batched", "max_batch": mb,
        "pair": "trained-misaligned" if args.pair == "trained" else args.pair,
        "requests": args.requests, "new_tokens": args.new_tokens,
        "gamma": args.gamma, "c": args.c, "gate_tol": tol,
        "modes": modes,
        "rollback_reduction_per_request":
            off["rollback_tokens_per_request"]
            - on["rollback_tokens_per_request"],
        "throughput_ratio_on_vs_off":
            on["tokens_per_cost"] / max(off["tokens_per_cost"], 1e-9),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, default=float)
    print(f"wrote {out_path}")
    if gate:
        ok = True
        if on["tokens_per_cost"] < (1.0 - tol) * off["tokens_per_cost"]:
            print(f"  FAIL: predictor-on throughput "
                  f"{on['tokens_per_cost']:.3f} regressed >"
                  f"{tol:.0%} below off {off['tokens_per_cost']:.3f}")
            ok = False
        if (on["rollback_tokens_per_request"]
                >= off["rollback_tokens_per_request"]):
            print(f"  FAIL: predictor-on rollback/req "
                  f"{on['rollback_tokens_per_request']:.2f} did not reduce "
                  f"off {off['rollback_tokens_per_request']:.2f}")
            ok = False
        if not ok:
            sys.exit(1)
        print("predictor gate passed: rollback/req "
              f"{off['rollback_tokens_per_request']:.2f} -> "
              f"{on['rollback_tokens_per_request']:.2f} at "
              f"{report['throughput_ratio_on_vs_off']:.3f}x throughput")


def _draft_heads_for_sweep(args, dp, dcfg, K: int):
    """Multi-position draft heads for parallel-mode bench cells: the
    trained heads that ride the cached pair for --pair trained, random
    init (engine mechanics, not model quality) otherwise."""
    if args.pair == "trained":
        from repro.training.pairs import draft_heads_for
        return draft_heads_for("misaligned", K=max(K, 4))
    return M.init_draft_heads(jax.random.PRNGKey(7), dcfg, K)


def draft_mode_sweep(dp, dcfg, tp, tcfg, args, prompts, out_path: str,
                     gate: bool = False, margin: float = 0.1) -> None:
    """Draft-mode sweep (DESIGN.md §7.12): the same request set through
    the batched SpecBranch engine with ``draft_mode`` sequential (one
    device dispatch per drafted token) vs parallel (the whole chunk from
    one masked forward).  Per mode: modeled tokens-per-cost, device
    dispatches per round, acceptance rate (accepted/drafted from the
    trace registry) and draft-phase wall seconds (sum of lane=="draft"
    trace spans, measured on a jit-warmed second run).  With ``gate``:
    exit 1 unless parallel reaches <=2 dispatches/round, cuts the
    draft-phase wall, and keeps the acceptance rate within ``margin``
    of sequential — the CI bench-smoke gate for the 1+gamma -> 2
    dispatch collapse."""
    mb = args.batch_sizes[0]
    modes = {}
    for mode in ("sequential", "parallel"):
        ecfg = EngineConfig(gamma=args.gamma, c=args.c, temperature=0.0,
                            epsilon=0.4, signal_temperature=0.5,
                            draft_mode=mode, max_len=512)
        heads = None
        if mode == "parallel":
            heads = _draft_heads_for_sweep(
                args, dp, dcfg, max(ecfg.gamma, ecfg.gamma_branch))
        # warm-up run: jit compile time would otherwise land inside the
        # first round's draft span and poison the wall comparison
        run_batched(dp, dcfg, tp, tcfg, ecfg, prompts, args.new_tokens,
                    0.0, mb, attn_backend=args.attn_backend,
                    draft_heads=heads)
        rec = TraceRecorder()
        t0 = time.time()
        rep = run_batched(dp, dcfg, tp, tcfg, ecfg, prompts,
                          args.new_tokens, 0.0, mb, rec=rec,
                          attn_backend=args.attn_backend,
                          draft_heads=heads)
        reg = rec.registry
        drafted = reg.counter("tokens_drafted_total").value
        accepted = reg.counter("tokens_accepted_total").value
        rb = reg.counter("rollback_tokens_total").value
        draft_wall = sum(e["wall1"] - e["wall0"] for e in rec.events
                         if e["kind"] == "span" and e["lane"] == "draft")
        modes[mode] = {
            "tokens_per_cost": rep["tokens_per_cost"],
            "total_tokens": rep["total_tokens"],
            "dispatches_per_round": rep["dispatches_per_round"],
            "drafted_tokens_total": drafted,
            "accepted_tokens_total": accepted,
            "rollback_tokens_total": rb,
            "acceptance_rate": accepted / max(drafted, 1),
            "draft_wall_s": draft_wall,
            "rounds": rep["rounds"],
            "wall_s": time.time() - t0,
        }
        print(f"draft_mode={mode:10s}: {rep['tokens_per_cost']:.3f} "
              f"tok/cost  dispatches/round "
              f"{modes[mode]['dispatches_per_round']:.2f}  accept "
              f"{modes[mode]['acceptance_rate']:.3f}  draft wall "
              f"{draft_wall * 1e3:.1f}ms")
    seq, par = modes["sequential"], modes["parallel"]
    report = {
        "engine": "specbranch", "mode": "batched", "max_batch": mb,
        "pair": "trained-misaligned" if args.pair == "trained" else args.pair,
        "attn_backend": args.attn_backend,
        "requests": args.requests, "new_tokens": args.new_tokens,
        "gamma": args.gamma, "c": args.c, "gate_margin": margin,
        "modes": modes,
        "dispatch_reduction": (seq["dispatches_per_round"]
                               - par["dispatches_per_round"]),
        "draft_wall_ratio_par_vs_seq":
            par["draft_wall_s"] / max(seq["draft_wall_s"], 1e-9),
        "acceptance_drop": seq["acceptance_rate"] - par["acceptance_rate"],
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, default=float)
    print(f"wrote {out_path}")
    if gate:
        ok = True
        if par["dispatches_per_round"] > 2.0 + 1e-9:
            print(f"  FAIL: parallel dispatches/round "
                  f"{par['dispatches_per_round']:.2f} > 2 (the round "
                  f"did not collapse to draft + verify)")
            ok = False
        if par["draft_wall_s"] >= seq["draft_wall_s"]:
            print(f"  FAIL: parallel draft wall {par['draft_wall_s']:.3f}s"
                  f" did not cut sequential {seq['draft_wall_s']:.3f}s")
            ok = False
        if report["acceptance_drop"] > margin:
            print(f"  FAIL: acceptance rate dropped "
                  f"{report['acceptance_drop']:.3f} > margin {margin:.3f} "
                  f"({seq['acceptance_rate']:.3f} -> "
                  f"{par['acceptance_rate']:.3f})")
            ok = False
        if not ok:
            sys.exit(1)
        print("draft-mode gate passed: dispatches/round "
              f"{seq['dispatches_per_round']:.2f} -> "
              f"{par['dispatches_per_round']:.2f}, draft wall x"
              f"{report['draft_wall_ratio_par_vs_seq']:.2f}, acceptance "
              f"{seq['acceptance_rate']:.3f} -> "
              f"{par['acceptance_rate']:.3f}")


def prefix_cache_sweep(dp, dcfg, tp, tcfg, args, vocab, out_path: str,
                       gate: bool = False, tol: float = 0.05) -> None:
    """Prefix-cache sweep (DESIGN.md §7.13): two request traces through
    the batched SpecBranch engine with the cross-request prefix cache off
    vs on.

      * **shared** — every request opens with the same long system prompt
        (3 KV pages) and diverges in a short unique suffix, arriving far
        enough apart that each admission sees the previous request's
        published run;
      * **nosharing** — same shape, fully distinct prompts (the cache can
        only add overhead here).

    Both cells of a pair price prefill identically on the modeled clock
    (``t_prefill``; default cells leave it 0), so TTFT differences come
    from WHAT was staged, not how it was priced.  Per cell: TTFT p50/p95,
    prefill forwards, prefix hit/saved-token counts and the physical vs
    logical pool occupancy peaks.  With ``gate``: exit 1 unless cache-on
    cuts TTFT p50 on the shared trace AND holds no-sharing throughput
    within ``tol`` — the CI bench-smoke gate."""
    mb = args.batch_sizes[0]
    ecfg = EngineConfig(gamma=args.gamma, c=args.c, temperature=0.0,
                        epsilon=0.4, signal_temperature=0.5, max_len=512)
    zm = ZipfMarkov(vocab=vocab, seed=7)
    shared_prefix = list(map(int, zm.prompts(1, 48, seed=5)[0]))
    suffixes = [list(map(int, p)) for p in zm.prompts(args.requests, 8,
                                                      seed=11)]
    traces = {
        "shared": [shared_prefix + s for s in suffixes],
        "nosharing": [list(map(int, p))
                      for p in zm.prompts(args.requests, 56, seed=13)],
    }
    # arrivals far apart: request i retires (and publishes its prefix)
    # before i+1 arrives, so every later shared admission can hit
    interval = 400.0
    t_prefill = 1.0
    cells = {}
    for tname, prompts in traces.items():
        for cache in (False, True):
            rec = TraceRecorder()
            t0 = time.time()
            rep = run_batched(dp, dcfg, tp, tcfg, ecfg, prompts,
                              args.new_tokens, interval, mb, rec=rec,
                              attn_backend="paged", prefix_cache=cache,
                              t_prefill=t_prefill)
            reg = rec.registry
            cell = {
                "tokens_per_cost": rep["tokens_per_cost"],
                "total_tokens": rep["total_tokens"],
                "ttft_p50": rep["ttft_p50"],
                "ttft_p95": rep["ttft_p95"],
                "prefill_forwards":
                    reg.counter("prefill_forwards_total").value,
                # real tokens ingested across prefill forwards: a cached
                # admission stages only its uncached suffix, so this —
                # not the forward count, which is one target + one draft
                # per solo admission either way — carries the rung win
                "prefill_tokens":
                    sum(e["tokens"] for e in rec.events
                        if e["kind"] == "prefill"),
                "pool_occupancy_peak": rep["pool_occupancy_peak"],
                "pool_logical_occupancy_peak":
                    rep.get("pool_logical_occupancy_peak"),
                "shared_pages_peak": rep.get("shared_pages_peak"),
                "wall_s": time.time() - t0,
            }
            if cache:
                cell["prefix_cache"] = rep["prefix_cache"]
            cells[f"{tname}_{'on' if cache else 'off'}"] = cell
            print(f"trace={tname:9s} cache={'on ' if cache else 'off'}: "
                  f"ttft p50 {cell['ttft_p50']:.1f}  "
                  f"{cell['tokens_per_cost']:.3f} tok/cost  "
                  f"prefill {cell['prefill_tokens']} tok / "
                  f"{cell['prefill_forwards']} fwds")
    s_off, s_on = cells["shared_off"], cells["shared_on"]
    n_off, n_on = cells["nosharing_off"], cells["nosharing_on"]
    report = {
        "engine": "specbranch", "mode": "batched", "max_batch": mb,
        "attn_backend": "paged", "requests": args.requests,
        "new_tokens": args.new_tokens, "gamma": args.gamma, "c": args.c,
        "shared_prefix_tokens": len(shared_prefix),
        "arrival_interval": interval, "t_prefill": t_prefill,
        "gate_tol": tol, "cells": cells,
        "shared_ttft_ratio_on_vs_off":
            s_on["ttft_p50"] / max(s_off["ttft_p50"], 1e-9),
        "nosharing_throughput_ratio_on_vs_off":
            n_on["tokens_per_cost"] / max(n_off["tokens_per_cost"], 1e-9),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2, default=float)
    print(f"wrote {out_path}")
    if gate:
        ok = True
        if s_on["ttft_p50"] >= s_off["ttft_p50"]:
            print(f"  FAIL: cache-on TTFT p50 {s_on['ttft_p50']:.1f} did "
                  f"not cut cache-off {s_off['ttft_p50']:.1f} on the "
                  f"shared-prompt trace")
            ok = False
        if s_on["prefill_tokens"] >= s_off["prefill_tokens"]:
            print(f"  FAIL: cache-on staged prefill tokens "
                  f"{s_on['prefill_tokens']} did not drop below "
                  f"cache-off {s_off['prefill_tokens']}")
            ok = False
        hits = s_on.get("prefix_cache", {}).get("hits", 0)
        if hits < args.requests - 1:
            print(f"  FAIL: only {hits} prefix hits on the shared trace "
                  f"(expected {args.requests - 1})")
            ok = False
        if n_on["tokens_per_cost"] < (1.0 - tol) * n_off["tokens_per_cost"]:
            print(f"  FAIL: cache-on no-sharing throughput "
                  f"{n_on['tokens_per_cost']:.3f} regressed >{tol:.0%} "
                  f"below off {n_off['tokens_per_cost']:.3f}")
            ok = False
        if not ok:
            sys.exit(1)
        print("prefix-cache gate passed: shared TTFT p50 "
              f"{s_off['ttft_p50']:.1f} -> {s_on['ttft_p50']:.1f} "
              f"({hits} hits, "
              f"{s_on['prefix_cache']['saved_tokens']} tokens bound "
              f"zero-copy) at "
              f"{report['nosharing_throughput_ratio_on_vs_off']:.3f}x "
              "no-sharing throughput")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", default="random", choices=["random", "trained"])
    ap.add_argument("--hybrid", action="store_true",
                    help="sweep an SSM-bearing (jamba-shaped) pair instead: "
                    "batched decode runs on the checkpoint-ring SSM cache "
                    "(DESIGN.md §7.6) — the hybrid-serving bench smoke")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--batch-sizes", type=int, nargs="+", default=[1, 2, 4])
    ap.add_argument("--arrival-intervals", type=float, nargs="+",
                    default=[0.0, 10.0])
    ap.add_argument("--gamma", type=int, default=3)
    ap.add_argument("--c", type=float, default=4.0)
    ap.add_argument("--spec-predictor", default="off",
                    choices=["off", "on", "oracle"],
                    help="acceptance-history speculation controller for "
                    "the main sweep cells (runtime/predictor.py); off is "
                    "today's static knobs, bit-for-bit")
    ap.add_argument("--predictor-sweep", default=None, metavar="JSON",
                    help="also run the rollback sweep: the first "
                    "batch-size cell with the predictor off/on/oracle, "
                    "reporting rollback tokens/request per mode to JSON")
    ap.add_argument("--predictor-gate", action="store_true",
                    help="with --predictor-sweep: exit 1 unless "
                    "predictor-on holds throughput within 5%% of off AND "
                    "reduces rollback tokens/request (CI smoke gate)")
    ap.add_argument("--draft-mode", default="sequential",
                    choices=["sequential", "parallel"],
                    help="drafting discipline for the main sweep cells "
                    "(DESIGN.md §7.12): sequential is one device dispatch "
                    "per drafted token; parallel emits the whole chunk "
                    "from one masked multi-position forward (2 dispatches "
                    "per round).  Parallel trains/loads multi-position "
                    "draft heads for --pair trained, random-init heads "
                    "otherwise")
    ap.add_argument("--draft-mode-sweep", default=None, metavar="JSON",
                    help="also run the first batch-size cell with "
                    "draft_mode sequential vs parallel, reporting "
                    "dispatches/round, acceptance rate and draft-phase "
                    "wall per mode to JSON")
    ap.add_argument("--draft-mode-gate", action="store_true",
                    help="with --draft-mode-sweep: exit 1 unless parallel "
                    "reaches <=2 dispatches/round, cuts draft-phase wall, "
                    "and keeps acceptance within --draft-mode-margin of "
                    "sequential (CI smoke gate)")
    ap.add_argument("--draft-mode-margin", type=float, default=0.1,
                    help="max tolerated acceptance-rate drop for the "
                    "draft-mode gate (default 0.1)")
    ap.add_argument("--prefix-cache", default="off",
                    choices=["off", "on"],
                    help="cross-request radix prefix cache for the main "
                    "sweep's batched cells (DESIGN.md §7.13; paged "
                    "backend only).  off is today's path, bit-for-bit")
    ap.add_argument("--prefix-cache-sweep", default=None, metavar="JSON",
                    help="also run the prefix-cache sweep: a shared-"
                    "system-prompt trace and a no-sharing trace with the "
                    "cache off vs on, reporting TTFT, prefill forwards, "
                    "hit/saved-token counts and physical vs logical pool "
                    "occupancy to JSON")
    ap.add_argument("--prefix-cache-gate", action="store_true",
                    help="with --prefix-cache-sweep: exit 1 unless "
                    "cache-on cuts TTFT p50 (and prefill forwards) on "
                    "the shared-prompt trace and holds no-sharing "
                    "throughput within 5%% (CI smoke gate)")
    ap.add_argument("--attn-backend", default="paged",
                    choices=["dense", "paged"],
                    help="batched-cell KV storage (default: paged, the "
                    "serving default backend; dense is the reference "
                    "oracle).  Hybrid sweeps run SSM rings next to the "
                    "chosen attention backend")
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="run the batched cells on a serving device mesh "
                    "(DESIGN.md §7.10): TP-sharded verify + per-device "
                    "KV-pool shards.  Needs DP*TP visible devices — on "
                    "CPU set XLA_FLAGS=--xla_force_host_platform_device_"
                    "count=N (the simulated-mesh CI tier does)")
    ap.add_argument("--out", default="serving_sweep.json")
    ap.add_argument("--check-baseline", default=None, metavar="JSON",
                    help="diff per-step host-transfer bytes against this "
                    "committed baseline; exit 1 on >2x regression or on "
                    "losing the >=10x reduction vs the pre-PR host loop")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Perfetto trace.json from a traced run "
                    "of the first sweep cell")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the traced run's metrics registry "
                    "(.json -> JSON, else plain text)")
    ap.add_argument("--overhead-gate", action="store_true",
                    help="interleave traced/untraced runs of the first "
                    "cell and exit 1 if tracing costs >10% wall or "
                    "changes the modeled schedule")
    args = ap.parse_args()
    enable_compile_cache()

    if args.hybrid and args.pair != "random":
        ap.error("--hybrid selects its own (jamba-shaped) pair; "
                 "drop --pair " + args.pair)
    if args.prefix_cache == "on" and args.attn_backend == "dense":
        ap.error("--prefix-cache on needs --attn-backend paged (dense "
                 "rows have no page runs to share)")
    if args.hybrid:
        from repro.training.pairs import hybrid_pair
        dp, dcfg, tp, tcfg = hybrid_pair("jamba-shaped")
        vocab = tcfg.vocab_size
    elif args.pair == "trained":
        from repro.training.pairs import VOCAB, get_pair
        dp, dcfg, tp, tcfg = get_pair("misaligned")
        vocab = VOCAB
    else:
        dp, dcfg, tp, tcfg = tiny_pair()
        vocab = tcfg.vocab_size
    mesh = None
    if args.mesh:
        from repro.launch import mesh as MESH
        try:
            mdp, mtp = MESH.parse_mesh_arg(args.mesh)
            MESH.validate_serving_mesh(mdp, mtp, configs=(dcfg, tcfg))
        except ValueError as e:
            raise SystemExit(str(e))
        if (mdp, mtp) != (1, 1):
            mesh = MESH.make_serving_mesh(mdp, mtp)
    ecfg = EngineConfig(gamma=args.gamma, c=args.c, temperature=0.0,
                        epsilon=0.4, signal_temperature=0.5,
                        spec_predictor=args.spec_predictor,
                        draft_mode=args.draft_mode, max_len=512)
    draft_heads = None
    if args.draft_mode == "parallel":
        if args.hybrid:
            ap.error("--draft-mode parallel needs an attention-only "
                     "draft; drop --hybrid")
        draft_heads = _draft_heads_for_sweep(
            args, dp, dcfg, max(ecfg.gamma, ecfg.gamma_branch))
    cost = CostModel(c=args.c)
    zm = ZipfMarkov(vocab=vocab, seed=7)
    prompts = [list(map(int, p))
               for p in zm.prompts(args.requests, 8, seed=3)]

    grid = []
    for interval in args.arrival_intervals:
        t0 = time.time()
        seq = run_sequential(dp, dcfg, tp, tcfg, ecfg, prompts,
                             args.new_tokens, interval, cost,
                             draft_heads=draft_heads)
        seq["wall_s"] = time.time() - t0
        for mb in args.batch_sizes:
            t0 = time.time()
            bat = run_batched(dp, dcfg, tp, tcfg, ecfg, prompts,
                              args.new_tokens, interval, mb,
                              attn_backend=args.attn_backend, mesh=mesh,
                              draft_heads=draft_heads,
                              prefix_cache=(args.prefix_cache == "on"))
            bat["wall_s"] = time.time() - t0
            cell = {
                "max_batch": mb,
                "arrival_interval": interval,
                "sequential": seq,
                "batched": bat,
                "throughput_gain": (bat["tokens_per_cost"]
                                    / max(seq["tokens_per_cost"], 1e-9)),
            }
            grid.append(cell)
            print(f"interval={interval:5.1f} max_batch={mb}: "
                  f"seq {seq['tokens_per_cost']:.3f} tok/cost -> batched "
                  f"{bat['tokens_per_cost']:.3f} "
                  f"({cell['throughput_gain']:.2f}x)  "
                  f"xfer/step {bat['per_step_transfer_bytes']:.0f}B  "
                  f"step p50 {bat['step_wall_p50'] * 1e3:.1f}ms")

    report = {
        "engine": "specbranch",
        "pair": "jamba-shaped" if args.hybrid else args.pair,
        "hybrid": bool(args.hybrid),
        "attn_backend": args.attn_backend,
        "mesh": args.mesh or "1,1",
        "draft_mode": args.draft_mode,
        "target_pattern": [list(s) for s in tcfg.pattern],
        "requests": args.requests,
        "new_tokens": args.new_tokens,
        "gamma": args.gamma,
        "c": args.c,
        "grid": grid,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, default=float)
    print(f"wrote {args.out} ({len(grid)} cells)")

    if args.overhead_gate or args.trace or args.metrics_out:
        mb0 = args.batch_sizes[0]
        if args.overhead_gate:
            rec = overhead_gate(dp, dcfg, tp, tcfg, ecfg, prompts,
                                args.new_tokens, mb0, args.attn_backend,
                                draft_heads=draft_heads)
        else:
            rec = TraceRecorder()
            run_batched(dp, dcfg, tp, tcfg, ecfg, prompts,
                        args.new_tokens, 0.0, mb0,
                        attn_backend=args.attn_backend, rec=rec,
                        draft_heads=draft_heads)
        if args.trace:
            write_trace(rec, args.trace)
            print(f"trace written to {args.trace} ({len(rec.events)} "
                  f"events)")
        if args.metrics_out:
            write_metrics(rec.registry, args.metrics_out)
            print(f"metrics written to {args.metrics_out}")

    if args.predictor_sweep:
        predictor_sweep(dp, dcfg, tp, tcfg, args, prompts,
                        args.predictor_sweep, gate=args.predictor_gate)

    if args.draft_mode_sweep:
        if args.hybrid:
            ap.error("--draft-mode-sweep needs an attention-only draft; "
                     "drop --hybrid")
        draft_mode_sweep(dp, dcfg, tp, tcfg, args, prompts,
                         args.draft_mode_sweep, gate=args.draft_mode_gate,
                         margin=args.draft_mode_margin)

    if args.prefix_cache_sweep:
        prefix_cache_sweep(dp, dcfg, tp, tcfg, args, vocab,
                           args.prefix_cache_sweep,
                           gate=args.prefix_cache_gate)

    if args.check_baseline:
        if not os.path.exists(args.check_baseline):
            # a missing baseline is a misconfigured gate, not a crash: say
            # so in one line and fail the job cleanly
            print(f"FAIL: --check-baseline file not found: "
                  f"{args.check_baseline}")
            sys.exit(1)
        with open(args.check_baseline) as f:
            base = json.load(f)
        base_intervals = base.get("sweep", {}).get("arrival_intervals")
        ok = True
        for cell in grid:
            key = str(cell["max_batch"])
            if key not in base.get("per_step_transfer_bytes", {}):
                continue
            if (base_intervals is not None
                    and cell["arrival_interval"] not in base_intervals):
                continue            # baseline bytes are per-interval
            got = cell["batched"]["per_step_transfer_bytes"]
            committed = base["per_step_transfer_bytes"][key]
            pre = base.get("pre_pr_per_step_transfer_bytes", {}).get(key)
            vs_pre = ("" if pre is None else
                      f" (pre-PR host loop {pre:.0f}B, "
                      f"{pre / max(got, 1e-9):.0f}x reduction)")
            print(f"baseline max_batch={key}: {got:.0f}B/step vs committed "
                  f"{committed:.0f}B{vs_pre}")
            if got > 2.0 * committed:
                print(f"  FAIL: >2x transfer-bytes regression over the "
                      f"committed baseline ({got:.0f} > 2*{committed:.0f})")
                ok = False
            if pre is not None and got * 10.0 > pre:
                print(f"  FAIL: lost the >=10x reduction vs the pre-PR "
                      f"host loop ({got:.0f} * 10 > {pre:.0f})")
                ok = False
        if not ok:
            sys.exit(1)
        print("baseline check passed")


if __name__ == "__main__":
    main()
