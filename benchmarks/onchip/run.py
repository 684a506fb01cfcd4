"""On-chip benchmark of the batched SpecBranch serving engine.

    python3 benchmarks/onchip/run.py --workload dsc33b.backlog \
        --seed 12345 --seconds 10 --trace 0

One run: build the cell's draft/target pair on the device from the seed,
build the engine through ``launch/serve.build_batched_engine`` (paged
backend), warm every shape the cell's traffic uses, serve the seeded
traffic for ``--seconds`` on the host's wall clock, then check what the
window served against the plain float32 reference.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics from a profiled window), ``device``,
``breakdown`` (traced runs) and ``checks``, each compared number beside
its limit.  The checks are also the last lines of standard error.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.  The compile cache lives at ``.cache/jax`` in the
checkout, so only a checkout's first run compiles.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()          # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(CHECKOUT, "src"))
# the persistent compile cache sits at a fixed path inside the checkout,
# a directory of the benchmark's own
CACHE_DIR = os.path.join(CHECKOUT, ".cache", "onchip-jax")


class NoChip(RuntimeError):
    pass


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def start(workload: str, *, root: str = HERE, bench=None,
          require_tpu: bool = True):
    """Load the cell, check the chip, turn the compile cache on (chip
    runs only).  Returns (bench, cell, device, peak)."""
    if require_tpu:
        # the program's launch/compile_cache takes the directory from here
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    from harness import registry, system
    from harness.peaks import peaks
    from repro.launch.compile_cache import enable_compile_cache
    bench = bench if bench is not None else registry.load_benchmark()
    entry = next(w for w in bench["workloads"] if w["name"] == workload)
    cell = registry.load_cell(root, workload)
    device = system.device_info()
    if require_tpu and (device["platform"] != "tpu"
                        or device["count"] < entry["chips"]):
        raise NoChip(f"cell {workload} needs {entry['chips']} TPU "
                     f"chip(s); JAX found {device}")
    peak = None
    if require_tpu:
        peak = peaks(device["kind"])
        jax.config.update("jax_compilation_cache_dir", enable_compile_cache())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return bench, cell, device, peak


class _Compiles:
    """Counts compilations (and compile-cache loads) while ``on``."""

    def __init__(self):
        import jax
        self.n, self.s, self.on = 0, 0.0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event in (
                "/jax/core/compile/backend_compile_duration",
                "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.n += 1
            self.s += duration


def serve_once(cell, seed: int, seconds: float, *, trace: bool = False,
               fault=None, t_process: float = T_PROCESS) -> dict:
    """Set up (weights, engine, warm-up), serve one measured window, read
    the device's peak memory and what the check needs, and free the
    engine.  ``fault(engine)`` breaks the timed path (tests only)."""
    import jax
    from harness import system
    from harness import traffic as TR
    from harness import weights as W
    from harness.frontend import Frontend
    from repro.serving import device_loop as DL

    compiles = _Compiles()
    stamps = [("start", time.monotonic())]
    conf, mix, knobs = (cell["config_spec"], cell["traffic_spec"],
                        cell["engine"])
    dims = {side: W.dims(conf[side]) for side in ("draft", "target")}
    params = {side: W.make_params(W.key_of(seed, i), dims[side])
              for i, side in enumerate(("draft", "target"))}
    jax.block_until_ready(params)
    stamps.append(("weights", time.monotonic()))
    vocab = dims["target"].vocab
    requests = TR.generate(mix, seed, vocab)
    max_len = system.max_len_for(
        (max(TR.levels(mix["prompt"], mix["block"])),
         max(TR.levels(mix["output"], mix["block"]))),
        knobs["gamma"], int(conf["speed_ratio_c"]))
    eng = system.build(cell, params, seed, max_len)
    if fault is not None:
        fault(eng)
    stamps.append(("engine", time.monotonic()))
    system.warm_decode_widths(eng, knobs["warm_widths"])
    stamps.append(("decode widths", time.monotonic()))
    fe = Frontend(eng)
    fe.serve(TR.warmup(mix, seed, vocab, knobs["warmup_new_tokens"]),
             t0=time.monotonic(), seconds=knobs["warmup_s_max"],
             idle_to_close=False)
    if eng.active:
        raise RuntimeError("warm-up requests did not finish in "
                           f"{knobs['warmup_s_max']} s")
    fe.outputs.clear()
    fe.pages_peak.clear()
    system.device_sync(eng)
    gc.collect()
    stamps.append(("warm-up requests", time.monotonic()))
    print("set-up: " + ", ".join(
        f"{name} {t - t_prev:.3f} s" for (_, t_prev), (name, t)
        in zip(stamps, stamps[1:])), file=sys.stderr)

    calls = trace_dir = None
    if trace:
        DL.set_trace_annotations(True)
        calls = system.CallLog(eng)
        calls.on = True
        trace_dir = tempfile.mkdtemp(prefix="onchip_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans: TraceMe only
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles.on = True
    t0 = time.monotonic()
    setup_s = t0 - t_process
    with jax.profiler.TraceAnnotation("bench.window"):
        window = fe.serve(requests, t0=t0, seconds=seconds)
        system.device_sync(eng)
    compiles.on = False
    reduced = None
    if trace:
        jax.profiler.stop_trace()
        calls.on = False
        DL.set_trace_annotations(False)
        from harness import trace as TRC
        reduced = TRC.reduce(TRC.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = {"dims": dims, "params": params, "window": window,
           "setup_s": setup_s, "reduced": reduced, "calls": calls,
           "compiles": compiles.n, "compile_s": compiles.s,
           "memory_peak_bytes": system.memory_peak_bytes(),
           "flight": system.in_flight(eng),
           "finished": {rid: toks for rid, toks in fe.outputs.items()
                        if rid in window.reqs},
           "prompts": fe.prompts, "preemptions": fe.preemptions,
           "kv_held": system.pages_held(eng, fe.pages_peak, dims)}
    del eng, fe
    gc.collect()
    return out


def main(argv=None, *, root: str = HERE, bench=None,
         require_tpu: bool = True, fault=None) -> dict:
    """Run one cell; returns the result dict (also printed).  ``root``,
    ``bench`` and ``require_tpu`` let a CPU test drive a tiny cell;
    ``fault(engine)`` lets it break the timed path underneath."""
    args = parse_args(argv)
    bench, cell, device, peak = start(args.workload, root=root, bench=bench,
                                      require_tpu=require_tpu)
    from harness import registry
    from harness.check import verdict
    from harness.check_run import check
    from harness.context import Context

    run = serve_once(cell, args.seed, args.seconds, trace=bool(args.trace),
                     fault=fault)
    window, reduced = run["window"], run["reduced"]
    numbers, info = check(cell, run["params"], run["dims"], args.seed,
                          run["finished"], run["prompts"], run["flight"],
                          root)
    ok, checks = verdict(numbers, cell["check"]["limits"])

    ctx = Context(window=window, cell=cell, dims=run["dims"], peak=peak,
                  setup_s=run["setup_s"], reduced=reduced,
                  calls=run["calls"])
    metrics = {}
    for m in registry.cell_metrics(bench, args.workload, bool(args.trace)):
        value = registry.load_module(root, "metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # a request still in flight at the close is served on, not failed
    failed = 0
    dev = dict(device, memory_peak_bytes=run["memory_peak_bytes"])
    if reduced is not None:
        dev.update(busy_s=reduced.busy_s(), window_s=reduced.window_s)
    checks["compiles_in_window"] = {"value": run["compiles"], "limit": 0}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    out = {"correct": bool(ok and failed == 0 and run["compiles"] == 0),
           "attempted": ctx.attempted(), "failed": failed,
           "metrics": metrics, "device": dev}
    if reduced is not None:
        out["breakdown"] = reduced.breakdown()
    out["checks"] = checks
    for k, v in info.items():
        print(f"sample {k}: {v}", file=sys.stderr)
    print(f"window: {window.seconds:.3f} s, setup {run['setup_s']:.3f} s, "
          f"rounds {len(window.rounds)}, preemptions {run['preemptions']}, "
          f"compile time in window {run['compile_s']:.3f} s",
          file=sys.stderr)
    for side, (held, pages, nbytes) in run["kv_held"].items():
        print(f"kv pages held at peak, {side}: {held} of {pages} "
              f"({held * nbytes} of {pages * nbytes} bytes)",
              file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out))
    sys.stdout.flush()
    return out


if __name__ == "__main__":
    try:
        main()
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        sys.exit(3)
