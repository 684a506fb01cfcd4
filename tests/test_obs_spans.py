"""The engine's profiler spans (obs/trace.py ``annotate``, DESIGN.md §7.9).

Pins, on a CPU ``jax.profiler`` trace of a tiny batched engine served to
completion with the spans on:

  * every ``engine.*`` span the round kind has appears in the trace;
  * the phase spans nest inside ``engine.round``;
  * the ``engine.fetch`` spans of the rounds number exactly the change in
    the engine's ``host_fetches`` tally (one span per device -> host
    sync);
  * with the spans off no ``jax.profiler`` annotation is constructed, and
    the served streams, fetch count and transfer bytes equal the spans-on
    run's bitwise.
"""
import functools
import glob
import os
import tempfile

import jax
import pytest
from jax.profiler import ProfileData

from repro.data.synthetic import ZipfMarkov
from repro.models import model as M
from repro.models.config import ModelConfig, dense_pattern
from repro.obs import trace as OT
from repro.runtime.engines import EngineConfig
from repro.serving import BatchedSpecBranchEngine, BatchedSpSEngine

VOCAB = 61
PHASES = {"engine.make_room", "engine.branch_fork", "engine.verify_dispatch",
          "engine.draft_tick", "engine.draft_resolve", "engine.commit"}
SPANS = PHASES | {"engine.round", "engine.fetch", "engine.decoder_step",
                  "engine.admit", "engine.retire"}
ROUNDS = [("specbranch", "sequential"), ("specbranch", "parallel"),
          ("sps", "sequential"), ("sps", "parallel")]


@functools.lru_cache(maxsize=1)
def _pair():
    def cfg(name, layers, d):
        return ModelConfig(name=name, family="dense", num_layers=layers,
                           d_model=d, num_heads=2, num_kv_heads=1,
                           d_ff=2 * d, vocab_size=VOCAB,
                           pattern=dense_pattern(0), dtype="float32")
    tcfg, dcfg = cfg("spans-t", 2, 32), cfg("spans-d", 1, 32)
    return (M.init_params(jax.random.PRNGKey(1), dcfg), dcfg,
            M.init_params(jax.random.PRNGKey(0), tcfg), tcfg)


def _serve(engine: str, mode: str, trace_dir=None):
    """Admit two requests and serve them to completion, the way the
    serving loop calls the engine; with ``trace_dir`` the spans are on
    and a profiler session covers admission, rounds and retirement.
    Returns (streams by rid, fetch-tally change over the rounds,
    host transfer bytes)."""
    dp, dcfg, tp, tcfg = _pair()
    ecfg = EngineConfig(gamma=3, c=4.0, temperature=1.0, max_len=128,
                        draft_mode=mode)
    heads = (M.init_draft_heads(jax.random.PRNGKey(7), dcfg, 8)
             if mode == "parallel" else None)
    cls = {"specbranch": BatchedSpecBranchEngine,
           "sps": BatchedSpSEngine}[engine]
    eng = cls(dp, dcfg, tp, tcfg, ecfg, max_batch=2, page_size=8,
              draft_heads=heads)
    zm = ZipfMarkov(vocab=VOCAB, seed=7)
    for rid, p in enumerate(zm.prompts(2, 6, seed=3)):
        eng.reserve(rid, list(map(int, p)), 10)
    if trace_dir is not None:
        OT.set_trace_annotations(True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    streams = {}
    try:
        eng.commit_admissions()
        fetches0 = eng.host_fetches
        for _ in range(60):
            if not eng.active:
                break
            eng.step_round()
            for seq, res in eng.retire_done():
                streams[seq.rid] = list(res.tokens)
        fetches = eng.host_fetches - fetches0
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
            OT.set_trace_annotations(False)
    assert not eng.active and sorted(streams) == [0, 1]
    return streams, fetches, eng.host_transfer_bytes


@functools.lru_cache(maxsize=None)
def _traced(engine: str, mode: str):
    """The spans-on run and the ``engine.*`` events of its trace."""
    with tempfile.TemporaryDirectory() as d:
        out = _serve(engine, mode, trace_dir=d)
        path = sorted(glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                                recursive=True))[-1]
        spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                 for plane in ProfileData.from_file(path).planes
                 for line in plane.lines for e in line.events
                 if e.name.startswith("engine.")]
    return out, spans


@pytest.mark.parametrize("engine,mode", ROUNDS)
def test_spans_cover_the_round(engine, mode):
    (_, fetches, _), spans = _traced(engine, mode)
    want = SPANS if engine == "specbranch" else \
        SPANS - {"engine.branch_fork", "engine.draft_resolve"}
    assert {n for n, _, _ in spans} == want
    rounds = [(s, e) for n, s, e in spans if n == "engine.round"]
    for n, s, e in spans:
        if n in PHASES:
            assert any(r0 <= s and e <= r1 for r0, r1 in rounds), n
    # one engine.fetch span per device -> host sync of the rounds, and
    # none during admission (this pair has no recurrent state to fetch)
    admit = [(s, e) for n, s, e in spans if n == "engine.admit"]
    fetch = [(s, e) for n, s, e in spans if n == "engine.fetch"]
    assert len(fetch) == fetches > 0
    assert not any(a0 <= s < a1 for s, _ in fetch for a0, a1 in admit)


@pytest.mark.parametrize("engine,mode", ROUNDS)
def test_spans_off_construct_nothing_and_change_nothing(engine, mode,
                                                        monkeypatch):
    traced, _ = _traced(engine, mode)

    def refuse(*a, **k):
        raise AssertionError("profiler annotation built with spans off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", refuse)
    assert _serve(engine, mode) == traced
