"""Serving driver: SpS / SpecBranch over batched requests.

Two modes (DESIGN.md §7):

  * ``--mode sequential`` — the original request-level round-robin baseline
    (runtime/scheduler.py): each request runs its own engine to completion
    in arrival order.
  * ``--mode batched``   — the continuous-batching subsystem
    (repro.serving): token-level batching with per-decoder paged KV pools,
    rollback-aware page reclamation, step-granularity admission/retirement
    with batched bucketed prefill, preemption + paged swap, and
    per-request streaming.  The default storage backend is **paged**
    (DESIGN.md §7.5/§7.8); ``--attn-backend dense`` keeps the N-row
    reference caches as the equivalence oracle.  SSM/hybrid pairs
    (``--pair falcon-shaped|jamba-shaped``) batch on either backend:
    mamba state rides the per-row checkpoint ring (DESIGN.md §7.6) next
    to dense rows or paged tables, so rollback stays O(1) and there is no
    sequential fallback for recurrent models.

Speeds are reported on the modeled clock (runtime/cost_model.py; wall
time is printed with the platform it ran on); both modes print the same
``aggregate tokens/s`` metric so they compare directly on an identical
request set.  On real hardware the same engines run with draft/target
sharded on disjoint mesh sub-axes (DESIGN.md §3).

Usage:
  PYTHONPATH=src python -m repro.launch.serve --engine specbranch \
      --mode batched --requests 8 --new-tokens 48
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.data.synthetic import ZipfMarkov
from repro.launch import mesh as MESH
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import (NULL_RECORDER, TraceRecorder, profiler_session,
                       write_metrics, write_trace)
from repro.obs.trace import set_trace_annotations
from repro.runtime.cost_model import CostModel
from repro.runtime.engines import (AdaEDLEngine, AutoregressiveEngine,
                                   EngineConfig, LookaheadEngine, PEARLEngine,
                                   SpSEngine)
from repro.runtime.scheduler import (Request, Scheduler,
                                     sequential_arrival_cost)
from repro.runtime.specbranch import SpecBranchEngine
from repro.serving import (BatchedSpecBranchEngine, BatchedSpSEngine,
                           ContinuousBatchScheduler, ServeRequest)
from repro.training.pairs import (HYBRID_KINDS, LLAMA_KINDS, VOCAB,
                                  get_pair, hybrid_pair, llama_configs,
                                  llama_pair)

ENGINES = {
    "autoregressive": AutoregressiveEngine,
    "sps": SpSEngine,
    "adaedl": AdaEDLEngine,
    "lookahead": LookaheadEngine,
    "pearl": PEARLEngine,
    "specbranch": SpecBranchEngine,
}

BATCHED_ENGINES = {
    "sps": BatchedSpSEngine,
    "specbranch": BatchedSpecBranchEngine,
}


def load_pair(kind: str):
    """Trained Zipf-Markov pairs, random-init SSM-bearing pairs for the
    hybrid serving path (falcon-shaped / jamba-shaped), or the paper's
    LLaMA-68M / LLaMA-7B pair at published widths (llama)."""
    if kind in HYBRID_KINDS:
        return hybrid_pair(kind)
    if kind in LLAMA_KINDS:
        return llama_pair()
    return get_pair(kind)


def make_prompts(kind: str, n: int, length: int = 16, seed: int = 3
                 ) -> list:
    """``n`` prompts of ``length`` tokens for ``--pair kind``: the Zipf-
    Markov language the toy pairs were trained on, or uniform ids over
    the target vocabulary for the random-weight LLaMA pair."""
    if kind in LLAMA_KINDS:
        vocab = llama_configs()[1].vocab_size
        rng = np.random.default_rng(seed)
        return [list(map(int, p))
                for p in rng.integers(0, vocab, (n, length))]
    zm = ZipfMarkov(vocab=VOCAB, seed=7)
    return [list(map(int, p)) for p in zm.prompts(n, length, seed=seed)]


def build_engine(name: str, ecfg: EngineConfig, pair_kind: str = "misaligned",
                 hrad_params=None, draft_heads=None):
    dp, dcfg, tp, tcfg = load_pair(pair_kind)
    cls = ENGINES[name]
    if name in ("autoregressive", "lookahead"):
        return cls(tp, tcfg, ecfg)
    if name == "specbranch":
        return cls(dp, dcfg, tp, tcfg, ecfg, hrad_params=hrad_params,
                   draft_heads=draft_heads)
    return cls(dp, dcfg, tp, tcfg, ecfg, draft_heads=draft_heads)


def load_draft_heads(args, ecfg: EngineConfig):
    """Multi-position draft heads for --draft-mode parallel (DESIGN.md
    §7.12): trained-and-cached alongside the pair.  None in sequential
    mode (the heads are inert there)."""
    if args.draft_mode != "parallel":
        return None
    if args.pair in HYBRID_KINDS:
        raise SystemExit("--draft-mode parallel needs an attention-only "
                         f"draft model; --pair {args.pair} has mamba "
                         "layers")
    if args.engine not in ("sps", "specbranch"):
        raise SystemExit("--draft-mode parallel requires a drafting "
                         f"engine (sps/specbranch), not {args.engine}")
    from repro.training.pairs import draft_heads_for
    return draft_heads_for(args.pair,
                           K=max(ecfg.gamma, ecfg.gamma_branch, 4))


def run_sequential(args, ecfg, prompts, rec=NULL_RECORDER) -> dict:
    engine = build_engine(args.engine, ecfg, args.pair,
                          draft_heads=load_draft_heads(args, ecfg))
    engine.set_recorder(rec)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=args.new_tokens)
            for i, p in enumerate(prompts)]
    sched = Scheduler(engine)
    t0 = time.time()
    done = sched.run(reqs, key=jax.random.PRNGKey(0))
    wall = time.time() - t0
    cost = CostModel(c=args.c)
    agg = sched.aggregate(done, cost)
    if args.arrival_interval > 0:
        clock = sequential_arrival_cost(
            [r.result.timeline for r in done], cost, args.arrival_interval)
        agg["total_cost"] = clock
        agg["tokens_per_cost"] = agg["total_tokens"] / max(clock, 1e-9)
    print(f"\n== sequential {args.engine} on {args.pair} pair: "
          f"{len(done)} requests, {wall:.1f}s wall "
          f"({jax.devices()[0].platform}) ==")
    for r in done:
        rep = r.result.report(cost)
        print(f"req {r.rid}: {rep['tokens']} tok  M={rep['M']:.2f} "
              f"speedup={rep['speedup']:.2f}x  RB={rep['rollback_rate']:.2f}")
    print(f"wall per request: p50={agg['wall_p50']:.2f}s "
          f"p95={agg['wall_p95']:.2f}s")
    print(f"aggregate tokens/s (modeled, t=1): "
          f"{agg['tokens_per_cost']:.4f}")
    return agg


def build_batched_engine(args, ecfg, pair=None, rec=NULL_RECORDER):
    """The batched engine ``--mode batched`` serves with.  ``pair`` is
    ``load_pair(args.pair)``'s tuple when the caller already holds it."""
    if args.engine not in BATCHED_ENGINES:
        raise SystemExit(
            f"--mode batched supports {sorted(BATCHED_ENGINES)}; "
            f"run --engine {args.engine} with --mode sequential")
    dp, dcfg, tp, tcfg = pair if pair is not None else load_pair(args.pair)
    mesh = None
    if args.mesh:
        try:
            mdp, mtp = MESH.parse_mesh_arg(args.mesh)
            MESH.validate_serving_mesh(mdp, mtp, configs=(dcfg, tcfg))
        except ValueError as e:
            raise SystemExit(str(e))
        if (mdp, mtp) != (1, 1):
            mesh = MESH.make_serving_mesh(mdp, mtp)
    eng = BATCHED_ENGINES[args.engine](
        dp, dcfg, tp, tcfg, ecfg,
        max_batch=args.max_batch,
        page_size=args.page_size,
        pool_pages=args.pool_pages,
        swap_pages=args.swap_pages,
        attn_backend=args.attn_backend,
        prefix_cache=(args.prefix_cache == "on"),
        mesh=mesh,
        draft_heads=load_draft_heads(args, ecfg))
    eng.set_recorder(rec)        # before a scheduler grabs engine.rec
    return eng


def serve_batched(eng, args, prompts, first_rid: int = 0):
    """Serve ``prompts`` (request ids from ``first_rid``) on ``eng``.
    Returns (results by rid, scheduler report, wall seconds); the clock
    stops once the engine's device buffers are ready."""
    sched = ContinuousBatchScheduler(eng)
    reqs = [ServeRequest(rid=first_rid + i, prompt=p,
                         max_new_tokens=args.new_tokens,
                         arrival=i * args.arrival_interval)
            for i, p in enumerate(prompts)]
    t0 = time.time()
    results = sched.run(reqs)
    jax.block_until_ready((eng.tgt_dec.cache, eng.dft_dec.cache))
    wall = time.time() - t0
    return results, sched.report(), wall


def run_batched(args, ecfg, prompts, rec=NULL_RECORDER) -> dict:
    eng = build_batched_engine(args, ecfg, rec=rec)
    results, rep, wall = serve_batched(eng, args, prompts)
    print(f"\n== batched {args.engine} on {args.pair} pair: "
          f"{len(results)} requests, max_batch={args.max_batch}, "
          f"{wall:.1f}s wall ({jax.devices()[0].platform}) ==")
    for rid in sorted(results):
        r = results[rid]
        print(f"req {rid}: {len(r.tokens)} tok  M={r.stats.mean_accepted:.2f}"
              f"  RB={r.stats.rollback_rate:.2f}")
    pool = rep["pool"]
    print(f"rounds: {rep['rounds']}  preemptions: {rep['preemptions']}")
    print(f"TTFT p50/p95 (modeled): {rep['ttft_p50']:.1f}/"
          f"{rep['ttft_p95']:.1f}   ITL p50/p95: {rep['itl_p50']:.1f}/"
          f"{rep['itl_p95']:.1f}")
    print(f"pool occupancy: mean={rep['pool_occupancy_mean']:.2f} "
          f"peak={rep['pool_occupancy_peak']:.2f}  "
          f"(pages={eng.pool.num_pages} x {eng.pool.page_size} tok)")
    print(f"reclaimed pages: rollback={pool['reclaimed_rollback_pages']} "
          f"branch={pool['reclaimed_branch_pages']} "
          f"prune={pool['reclaimed_prune_pages']} "
          f"preempt={pool['reclaimed_preempt_pages']} "
          f"retire={pool['reclaimed_retire_pages']}  "
          f"(cow_copies={pool['cow_copies']})")
    if "prefix_cache" in rep:
        pc = rep["prefix_cache"]
        print(f"prefix cache: hits={pc['hits']}/{pc['lookups']} "
              f"saved_tokens={pc['saved_tokens']} "
              f"published={pc['published_runs']} "
              f"evicted={pc['evicted_runs']}")
    print(f"aggregate tokens/s (modeled, t=1): "
          f"{rep['tokens_per_cost']:.4f}")
    return rep


def parse_args(argv=None) -> argparse.Namespace:
    """Parse and cross-check the serving options (before any model
    loads)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", default="specbranch", choices=list(ENGINES))
    ap.add_argument("--mode", default=None,
                    choices=["sequential", "batched"],
                    help="default: batched for engines with a batched "
                    "implementation, sequential otherwise")
    ap.add_argument("--pair", default="misaligned",
                    choices=["misaligned", "aligned", *HYBRID_KINDS,
                             *LLAMA_KINDS],
                    help="misaligned/aligned: trained attention pairs; "
                    "falcon-shaped/jamba-shaped: random-init SSM/hybrid "
                    "pairs — batched mode serves them via the checkpoint-"
                    "ring SSM cache, no sequential fallback; llama: "
                    "LLaMA-68M draft / LLaMA-7B target at published "
                    "widths, random bf16 weights, target cut to 16 of 32 "
                    "layers (one chip's share)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=48)
    ap.add_argument("--gamma", type=int, default=4)
    ap.add_argument("--c", type=float, default=10.0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--spec-predictor", default="off",
                    choices=["off", "on", "oracle"],
                    help="per-request acceptance-history speculation "
                    "controller (runtime/predictor.py): 2-bit saturating "
                    "counters + a global pattern-history table pick "
                    "gamma/branch-cap/epsilon each round from past verify "
                    "outcomes.  off (default): today's static knobs, "
                    "bit-for-bit; on: the hardware-style predictor; "
                    "oracle: exact per-request acceptance EMA (upper "
                    "bound).  Lossless either way — the predictor never "
                    "touches accept/reject decisions")
    ap.add_argument("--draft-mode", default="sequential",
                    choices=["sequential", "parallel"],
                    help="drafting discipline (DESIGN.md §7.12).  "
                    "sequential (default): one draft forward per drafted "
                    "token, bit-for-bit today's path.  parallel: the "
                    "whole chunk from ONE masked multi-position forward "
                    "(K trained draft heads, cached next to the pair) — "
                    "a round collapses to two device dispatches (draft + "
                    "verify).  Same verdict packets, same per-row PRNG, "
                    "lossless verification; only the draft distribution "
                    "differs.  Needs an attention-only draft pair and a "
                    "drafting engine (sps/specbranch)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="KV pool pages (default: sized for max_batch "
                    "full-length requests; smaller values exercise "
                    "preemption)")
    ap.add_argument("--swap-pages", type=int, default=256,
                    help="paged swap-store pages for preempted requests")
    ap.add_argument("--attn-backend", default="paged",
                    choices=["dense", "paged"],
                    help="batched-mode KV storage (default: paged — "
                    "physically paged KV attended in place through the "
                    "pool page tables via the Pallas paged-attention "
                    "kernel; SSM/hybrid configs ride per-row checkpoint "
                    "rings next to the pages).  dense keeps the N-row "
                    "reference caches — the equivalence oracle")
    ap.add_argument("--prefix-cache", default="off",
                    choices=["off", "on"],
                    help="cross-request radix prefix cache over the COW "
                    "page pool (batched + paged only, DESIGN.md §7.13): "
                    "retired prompts publish their page-aligned KV runs "
                    "into a token trie; admissions sharing that prefix "
                    "bind the pages zero-copy and prefill only the "
                    "uncached suffix.  off (default): today's path, "
                    "bit-for-bit")
    ap.add_argument("--mesh", default=None, metavar="DP,TP",
                    help="serving device mesh (batched mode): DP-way data "
                    "parallelism over dense cache rows x TP-way tensor "
                    "parallelism over attention heads / MLP hidden, with "
                    "per-device shards of the paged KV pool (DESIGN.md "
                    "§7.10).  TP must divide both models' head counts and "
                    "DP*TP must fit the visible devices (on CPU force a "
                    "simulated mesh with XLA_FLAGS=--xla_force_host_"
                    "platform_device_count=N).  Default/1,1: today's "
                    "single-device path, bit-for-bit")
    ap.add_argument("--arrival-interval", type=float, default=0.0,
                    help="modeled time units between request arrivals")
    ap.add_argument("--max-len", type=int, default=0,
                    help="decode-cache length; 0 = auto-size to the "
                    "request shape (prompt + new tokens + speculation "
                    "headroom), min 512")
    ap.add_argument("--json", default=None,
                    help="write the aggregate report to this path")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace.json of the run "
                    "(open at https://ui.perfetto.dev): draft/verify/"
                    "commit lanes, per-round spans, per-request "
                    "speculation + rollback-attribution events")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the metrics registry (counters/gauges/"
                    "histograms); .json -> JSON, else plain text")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="also run a jax.profiler trace into DIR with the "
                    "engine.* phase spans on (TensorBoard/Perfetto)")
    args = ap.parse_args(argv)
    if args.mode is None:
        args.mode = ("batched" if args.engine in BATCHED_ENGINES
                     else "sequential")
    if args.prefix_cache == "on":
        if args.mode != "batched":
            raise SystemExit("--prefix-cache on requires --mode batched "
                             "(sequential engines have no page pool)")
        if args.attn_backend == "dense":
            raise SystemExit(
                "--prefix-cache on is incompatible with --attn-backend "
                "dense: dense rows hold a private KV copy per request, so "
                "there are no page runs to share zero-copy.  Use "
                "--attn-backend paged (the default), or drop "
                "--prefix-cache to keep the dense equivalence oracle.")
    if args.mesh:
        if args.mode != "batched":
            raise SystemExit("--mesh requires --mode batched")
        try:
            # fail fast (syntax + device count) BEFORE the pair loads;
            # the head-divisibility check runs in run_batched once the
            # model configs are known
            MESH.validate_serving_mesh(*MESH.parse_mesh_arg(args.mesh))
        except ValueError as e:
            raise SystemExit(str(e))
    return args


def engine_config(args, prompts) -> EngineConfig:
    max_len = args.max_len
    if max_len <= 0:
        need = (max(len(p) for p in prompts) + args.new_tokens
                + 4 * (args.gamma + int(args.c)))
        max_len = max(512, 1 << (need - 1).bit_length())
    return EngineConfig(gamma=args.gamma, c=args.c,
                        temperature=args.temperature,
                        spec_predictor=args.spec_predictor,
                        draft_mode=args.draft_mode, max_len=max_len)


def main() -> None:
    args = parse_args()
    enable_compile_cache()
    prompts = make_prompts(args.pair, args.requests)
    ecfg = engine_config(args, prompts)
    tracing = bool(args.trace or args.metrics_out or args.profile_dir)
    rec = TraceRecorder() if tracing else NULL_RECORDER
    if args.profile_dir:
        set_trace_annotations(True)
    with profiler_session(args.profile_dir):
        if args.mode == "sequential":
            rep = run_sequential(args, ecfg, prompts, rec)
        else:
            rep = run_batched(args, ecfg, prompts, rec)
    if args.trace:
        write_trace(rec, args.trace)
        print(f"trace written to {args.trace} "
              f"({len(rec.events)} events; open at https://ui.perfetto.dev)")
    if args.metrics_out:
        write_metrics(rec.registry, args.metrics_out)
        print(f"metrics written to {args.metrics_out}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rep, f, indent=2, default=float)
        print(f"report written to {args.json}")


if __name__ == "__main__":
    main()
