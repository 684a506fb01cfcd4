#!/usr/bin/env python3
"""Serve the batched SpecBranch path on a TPU and check what comes out.

Run from the root of a checkout:

    python3 chip_smoke.py              # one chip: phases (a), (b), (c)
    python3 chip_smoke.py --mesh 1,4   # four chips: the LLaMA pair on a
                                       # 1x4 serving mesh vs device 0

Every phase goes through ``launch/serve.py``'s batched path (SpecBranch,
paged KV backend), all in this one process:

  (a) the trained tiny misaligned pair tracked in ``.cache/pairs`` at
      temperature 0: every stream must equal autoregressive greedy
      decoding of the target (``runner.greedy_reference``) exactly;
  (b) the paper's LLaMA-68M / LLaMA-7B pair at published widths (random
      bf16 weights, target cut to 16 layers — ``training/pairs.py``) at
      temperature 0: one paged verify forward's logits must match the
      dense ``model.forward`` within ``LOGIT_TOL``, and each stream must
      equal the dense greedy decode up to the first position where the
      reference's top-2 logit gap is under ``LOGIT_TOL``;
  (c) the same pair at temperature 1, which takes the TPU verify route
      (the fused Pallas ``verify_accept`` kernel): exact token counts, ids
      inside the vocabulary, finite logits, and the kernel's packets equal
      to its XLA twin's on the same logits.

Each phase prints its set-up seconds, a first (compiling) run, a timed
second run (host clock, stopped after ``block_until_ready``), tokens
served, the device's ``peak_bytes_in_use`` and the kernel routes taken.
The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script exits non-zero, before printing it, when JAX's first device is
not a TPU or when any check fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.runtime.runner import greedy_reference  # noqa: E402
from repro.serving import device_loop as DL  # noqa: E402
from repro.serving.batched_engine import BatchedDecoder  # noqa: E402
from repro.serving.kv_pool import PagedKVPool  # noqa: E402
from repro.training import pairs  # noqa: E402

# environment overrides of kernel routes; the smoke run must take the
# routes the platform picks, so it refuses to start under either
ROUTE_ENV = ("REPRO_VERIFY_BACKEND", "REPRO_PAGED_BACKEND")

# Paged vs dense logits of the bf16 LLaMA pair.  The logits are the bf16
# product of the final hidden state and lm_head, cast to f32; at the
# random-weight scale (|logit| < 8) one bf16 step is at most 2^-5.  The
# paged path (Pallas kernel over pages) and the dense path (one XLA
# attention over the whole sequence) sum attention in different orders
# and round each layer's output to bf16 at different values, and those
# roundings (relative 2^-8 each) carry through 16 residual layers into
# the logits.  0.25 = eight bf16 steps at the top binade: far above that
# rounding noise, far below the O(1) change a wrong key, position or
# mask makes.  The same bound marks a greedy near-tie: where the dense
# reference's top-2 gap is under it, the two paths may rightly disagree.
LOGIT_TOL = 0.25
GAMMA = 4
# a few requests in one prompt-length bucket keep a cold run to minutes
N_REQUESTS = 4
NEW_TOKENS = 32


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def _routes(eng) -> str:
    """The kernel routes ``eng`` serves through (the engine's own rules:
    batched_engine.BatchedDecoder, device_loop.kernel_route)."""
    interp = "interpret" if ops.default_interpret() else "compiled"
    paged = "xla-twin" if eng.mesh is not None else f"pallas-{interp}"
    use_kernel = DL.kernel_route(eng.ecfg.temperature,
                                 eng.ecfg.draft_temperature)
    verify = f"pallas-{interp}" if use_kernel else "xla-probs-twin"
    return f"paged_attention={paged} verify={verify}"


def serve_phase(name: str, pair_kind: str, pair, prompts, *,
                new_tokens: int, temperature: float, mesh: str = "",
                max_len: int = 0):
    """Serve ``prompts`` through launch/serve.py's batched SpecBranch path
    on the paged backend: one engine, a first run that compiles, then a
    timed run of the same requests.  Returns (the timed run's streams,
    the engine)."""
    argv = ["--engine", "specbranch", "--mode", "batched",
            "--pair", pair_kind, "--attn-backend", "paged",
            "--requests", str(len(prompts)),
            "--new-tokens", str(new_tokens), "--gamma", str(GAMMA),
            "--temperature", str(temperature),
            "--max-batch", str(len(prompts)), "--max-len", str(max_len)]
    if mesh:
        argv += ["--mesh", mesh]
    args = serve.parse_args(argv)
    ecfg = serve.engine_config(args, prompts)
    t0 = time.time()
    eng = serve.build_batched_engine(args, ecfg, pair)
    setup = time.time() - t0
    _, _, first = serve.serve_batched(eng, args, prompts)
    n = len(prompts)
    results, rep, wall = serve.serve_batched(eng, args, prompts,
                                             first_rid=n)
    streams = [list(results[n + i].tokens) for i in range(n)]
    served = sum(len(s) for s in streams)
    print(f"[{name}] setup_s={setup:.3f} first_run_s(incl compile)="
          f"{first:.3f} wall_s={wall:.3f} tokens={served} "
          f"rounds={rep['rounds']} peak_bytes_in_use={_peak_bytes()} "
          f"routes: {_routes(eng)}")
    return streams, eng


def phase_trained(n_requests: int = 4, new_tokens: int = 32,
                  max_len: int = 0) -> None:
    """(a) The tracked trained pair: streams == AR greedy, exactly.  The
    pair is float32; XLA on TPU multiplies f32 in one bf16 pass by
    default, so both sides run at "highest" precision — the claim under
    test is that the engine is lossless, not that two roundings agree."""
    pair = pairs.get_pair("misaligned", train=False)
    _dp, _dcfg, tp, tcfg = pair
    prompts = serve.make_prompts("misaligned", n_requests)
    with jax.default_matmul_precision("highest"):
        streams = serve_phase("a trained-tiny t=0", "misaligned", pair,
                              prompts, new_tokens=new_tokens,
                              temperature=0.0, max_len=max_len)[0]
        refs = [greedy_reference(tp, tcfg, p, new_tokens, max_len=128)
                for p in prompts]
    for i, (got, want) in enumerate(zip(streams, refs)):
        check(got == want, f"(a) request {i}: served {got} != greedy "
              f"reference {want}")
    print(f"[a trained-tiny t=0] {n_requests} streams == AR greedy")


def paged_verify_logits(params, cfg, prompts, chunks, *, mesh=None,
                        page_size: int = 16) -> np.ndarray:
    """Target logits of one paged verify forward, the call a serving round
    makes: each prompt is prefilled into a row of a paged decoder, then
    ``chunks`` (B, T) is verified in one forward after it."""
    B, T = len(prompts), len(chunks[0])
    plen = len(prompts[0])
    max_len = DL.prefill_bucket(plen + T, 8)
    pool = PagedKVPool(B * -(-max_len // page_size), page_size)
    dec = BatchedDecoder(params, cfg, n_rows=B, max_len=max_len,
                         paged=pool, mesh=mesh)
    for row, p in enumerate(prompts):
        pool.open(row)
        pool.extend(row, len(p))
        dec.bind_row(row, row)
    dec.prefill_rows(list(enumerate(prompts)))
    for row in range(B):
        pool.extend(row, T)
    logits, _ = dec.step(np.asarray(chunks, np.int32),
                         np.full(B, plen, np.int32))
    return np.asarray(logits, np.float32)


def dense_logits(params, cfg, seqs) -> np.ndarray:
    """Dense reference: ``model.forward`` over whole sequences, no cache."""
    fwd = jax.jit(lambda p, t: M.forward(p, cfg, t)[0])
    return np.asarray(fwd(params, jnp.asarray(seqs, jnp.int32)))


def dense_greedy(params, cfg, prompts, n_new: int):
    """Greedy decode through the dense oracle (no cache, one fixed-width
    forward per step; later pad positions cannot reach earlier ones under
    the causal mask).  Returns tokens and the top-2 logit gap of each
    step's choice, both (B, n_new)."""
    B, plen = len(prompts), len(prompts[0])

    @jax.jit
    def step(p, toks, i):
        lg = M.forward(p, cfg, toks)[0]
        last = jax.lax.dynamic_index_in_dim(lg, plen + i - 1, 1, False)
        top2 = jax.lax.top_k(last, 2)[0]
        return jnp.argmax(last, -1), top2[:, 0] - top2[:, 1]

    buf = np.zeros((B, plen + n_new), np.int32)
    buf[:, :plen] = prompts
    gaps = np.zeros((B, n_new), np.float32)
    for i in range(n_new):
        nxt, gap = step(params, jnp.asarray(buf), i)
        buf[:, plen + i] = np.asarray(nxt)
        gaps[:, i] = np.asarray(gap)
    return buf[:, plen:], gaps


def agreed_prefix(got, want) -> int:
    n = 0
    while n < min(len(got), len(want)) and got[n] == want[n]:
        n += 1
    return n


def check_greedy_streams(tag: str, streams, ref_toks, gaps,
                         tol: float) -> list:
    """Each stream must equal the reference up to its first near-tie (top-2
    gap under ``tol``).  Returns the agreed prefix lengths."""
    agreed = []
    for i, got in enumerate(streams):
        want = [int(t) for t in ref_toks[i]]
        n = agreed_prefix(got, want)
        ties = np.flatnonzero(gaps[i] < tol)
        first_tie = int(ties[0]) if ties.size else len(want)
        check(n >= min(first_tie, len(want)),
              f"{tag} request {i}: stream leaves the dense greedy decode at "
              f"{n}, before the first near-tie at {first_tie}")
        agreed.append(n)
        print(f"{tag} request {i}: agreed greedy prefix {n}/{len(want)} "
              f"(first reference top-2 gap < {tol} at {first_tie})")
    return agreed


def check_verify_logits(tag: str, params, cfg, prompts, streams, tol: float,
                        mesh=None) -> None:
    """One paged verify forward over GAMMA + 1 served tokens vs the dense
    forward over the same tokens."""
    T = min(GAMMA + 1, min(len(s) for s in streams))
    chunks = [s[:T] for s in streams]
    got = paged_verify_logits(params, cfg, prompts, chunks, mesh=mesh)
    seqs = [list(p) + list(c) for p, c in zip(prompts, chunks)]
    plen = len(prompts[0])
    want = dense_logits(params, cfg, seqs)[:, plen:plen + T]
    check(np.isfinite(got).all(), f"{tag} paged verify logits not finite")
    err = float(np.abs(got - want).max())
    print(f"{tag} paged verify forward vs dense: max |logit diff| = "
          f"{err:.6f} (tolerance {tol}, max |logit| "
          f"{float(np.abs(want).max()):.3f})")
    check(err <= tol, f"{tag} paged verify logits differ from dense by "
          f"{err} > {tol}")


def phase_greedy(pair, prompts, *, new_tokens: int = 32,
                 tol: float = LOGIT_TOL, max_len: int = 0) -> None:
    """(b) The full-width pair at temperature 0."""
    _dp, _dcfg, tp, tcfg = pair
    tag = "[b full-width t=0]"
    streams = serve_phase("b full-width t=0", "llama", pair, prompts,
                          new_tokens=new_tokens, temperature=0.0,
                          max_len=max_len)[0]
    check_verify_logits(tag, tp, tcfg, prompts, streams, tol)
    ref_toks, gaps = dense_greedy(tp, tcfg, prompts, new_tokens)
    check_greedy_streams(tag, streams, ref_toks, gaps, tol)


def phase_sampled(pair, prompts, *, new_tokens: int = 32,
                  max_len: int = 0) -> None:
    """(c) The full-width pair at temperature 1: the TPU verify route."""
    _dp, _dcfg, tp, tcfg = pair
    tag = "[c full-width t=1]"
    streams = serve_phase("c full-width t=1", "llama", pair, prompts,
                          new_tokens=new_tokens, temperature=1.0,
                          max_len=max_len)[0]
    V = tcfg.vocab_size
    for i, s in enumerate(streams):
        check(len(s) == new_tokens,
              f"{tag} request {i}: {len(s)} tokens, want {new_tokens}")
        check(all(0 <= t < V for t in s),
              f"{tag} request {i}: token id outside [0, {V})")
    T = min(GAMMA + 1, new_tokens)
    logits = paged_verify_logits(tp, tcfg, prompts, [s[:T] for s in streams])
    check(np.isfinite(logits).all(), f"{tag} verify logits not finite")
    print(f"{tag} {len(prompts)} streams x {new_tokens} tokens, ids in "
          f"[0, {V}), verify logits finite")
    check_verify_kernel(tag, V)


def check_verify_kernel(tag: str, vocab: int, B: int = 4) -> None:
    """The fused verify kernel on this device vs its XLA twin, same inputs:
    accept flags and residual tokens equal, probabilities close."""
    R = GAMMA + 1
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    p = jax.random.normal(ks[0], (B, R, vocab)) * 2
    q = jax.random.normal(ks[1], (B, R, vocab)) * 2
    toks = jax.random.randint(ks[2], (B, R), 0, vocab)
    lens = jax.random.randint(ks[3], (B,), 1, R + 1)
    u = jax.random.uniform(ks[4], (B, R))
    w = jax.random.uniform(ks[5], (B, R))
    got = ops.verify_accept_batched(p, q, toks, lens, u, w,
                                    backend="pallas")
    want = ops.verify_accept_batched(p, q, toks, lens, u, w, backend="xla")
    names = ("accept", "residual", "p_tok", "q_tok")
    for name, g, wv in zip(names, got, want):
        g, wv = np.asarray(g), np.asarray(wv)
        if name in ("accept", "residual"):
            check(np.array_equal(g, wv),
                  f"{tag} verify kernel {name} != XLA twin: {g} vs {wv}")
        else:
            check(np.allclose(g, wv, rtol=1e-4, atol=1e-7),
                  f"{tag} verify kernel {name} != XLA twin")
    print(f"{tag} verify kernel (B={B}, R={R}, V={vocab}) packets == XLA "
          f"twin")


def _bytes_per_device(tree):
    """(bytes each device holds, bytes of the whole tree)."""
    per, total = {}, 0
    for leaf in jax.tree.leaves(tree):
        total += leaf.nbytes
        for s in leaf.addressable_shards:
            per[s.device] = per.get(s.device, 0) + s.data.nbytes
    return per, total


def phase_mesh(pair, prompts, mesh_arg: str, *, new_tokens: int = 32,
               tol: float = LOGIT_TOL, max_len: int = 0) -> None:
    """The full-width pair on a dp x tp serving mesh at temperature 0,
    against the same requests unmeshed on device 0."""
    _dp, _dcfg, tp, tcfg = pair
    tag = f"[mesh {mesh_arg}]"
    base = serve_phase("mesh baseline: unmeshed, device 0", "llama", pair,
                       prompts, new_tokens=new_tokens, temperature=0.0,
                       max_len=max_len)[0]
    streams, eng = serve_phase(f"mesh {mesh_arg}", "llama", pair, prompts,
                               new_tokens=new_tokens, temperature=0.0,
                               mesh=mesh_arg, max_len=max_len)
    mesh = eng.mesh
    n_dev = mesh.devices.size
    for name, tree in (("target params", eng.tgt_dec.params),
                       ("target KV pool", eng.tgt_dec.cache),
                       ("draft params", eng.dft_dec.params)):
        per, total = _bytes_per_device(tree)
        most = max(per.values())
        print(f"{tag} {name}: {total} bytes over {len(per)} devices, at "
              f"most {most} ({most / total:.3f}) on one")
        # sharded, not replicated: no device holds even half of the tree
        check(len(per) == n_dev and most <= total / 2,
              f"{tag} {name} is not spread over {n_dev} devices")
    del eng                      # free its pools before two more decoders
    T = min(GAMMA + 1, new_tokens)
    chunks = [s[:T] for s in base]
    single = paged_verify_logits(tp, tcfg, prompts, chunks)
    meshed = paged_verify_logits(tp, tcfg, prompts, chunks, mesh=mesh)
    err = float(np.abs(single - meshed).max())
    print(f"{tag} verify logits, mesh vs device 0: max |diff| = {err:.6f} "
          f"(tolerance {tol})")
    check(err <= tol, f"{tag} mesh verify logits differ by {err} > {tol}")
    ref_toks, gaps = dense_greedy(tp, tcfg, prompts, new_tokens)
    check_greedy_streams(tag, streams, ref_toks, gaps, tol)
    for i, (a, b) in enumerate(zip(streams, base)):
        print(f"{tag} request {i}: agreed prefix with the unmeshed run "
              f"{agreed_prefix(a, b)}/{len(b)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", default="", metavar="DP,TP",
                    help="run only the full-width pair on this serving "
                    "mesh and its unmeshed comparison (e.g. 1,4)")
    args = ap.parse_args(argv)
    bad = [k for k in ROUTE_ENV if k in os.environ]
    if bad:
        print(f"refusing to run with {', '.join(bad)} set: the smoke run "
              "takes the kernel routes the platform picks", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform!r} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    os.chdir(ROOT)                 # .cache/pairs is relative to the root
    cache_dir = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x "
          f"{len(jax.devices())}; compile cache: {cache_dir}")

    n, new = N_REQUESTS, NEW_TOKENS
    if not args.mesh:
        phase_trained(n, new)
    t0 = time.time()
    pair = pairs.llama_pair()
    jax.block_until_ready(pair)
    print(f"[llama pair] built at published widths (target "
          f"{pair[3].num_layers} layers) in {time.time() - t0:.3f}s; "
          f"peak_bytes_in_use={_peak_bytes()}")
    prompts = serve.make_prompts("llama", n)
    if args.mesh:
        phase_mesh(pair, prompts, args.mesh, new_tokens=new)
    else:
        phase_greedy(pair, prompts, new_tokens=new)
        phase_sampled(pair, prompts, new_tokens=new)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
