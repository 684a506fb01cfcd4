"""A whole run at a tiny size on the CPU, past the harness's look for a
chip: a sound run comes out correct, and one whose served tokens are
altered where they are produced, or whose decoder steps leave their
cache unchanged, comes out not correct, greedy and sampled.  Without a chip the command prints no result and fails."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from harness import registry as R

SIDE = {"source": "tiny", "hidden_size": 256, "intermediate_size": 512,
        "num_attention_heads": 4, "num_key_value_heads": 1,
        "num_hidden_layers": 2, "vocab_size": 256, "rms_norm_eps": 1e-6,
        "rope_theta": 10000}
BENCH = {"workloads": [{"name": f"tiny.t{t}", "config": "tiny",
                        "traffic": f"tiny-t{t}", "chips": 1}
                       for t in (0, 1)],
         "end_to_end": [{"name": "output_tokens_per_s", "unit": "tokens/s"},
                        {"name": "setup_s", "unit": "s"}],
         "per_layer": []}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench")
    for kind in ("configs", "traffic", "workloads"):
        (root / kind).mkdir()
    for kind in ("metrics", "references"):
        shutil.copytree(os.path.join(R.ROOT, kind), root / kind)
    (root / "configs" / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "source": "tiny", "dtype": "bfloat16",
         "speed_ratio_c": 4, "reference": "llama",
         "draft": dict(SIDE, hidden_size=128, num_hidden_layers=1),
         "target": SIDE}))
    for t in (0, 1):
        (root / "traffic" / f"tiny-t{t}.json").write_text(json.dumps(
            {"arrival": "backlog", "requests": 16, "block": 2,
             "temperature": float(t),
             "prompt": {"median": 9, "sigma": 0.2, "min": 4, "max": 16},
             "output": {"median": 8, "sigma": 0.2, "min": 4, "max": 16}}))
        (root / "workloads" / f"tiny.t{t}.json").write_text(json.dumps(
            {"config": "tiny", "traffic": f"tiny-t{t}",
             "engine": {"max_batch": 2, "page_size": 16, "pool_pages": 64,
                        "gamma": 4, "k_max": 6, "epsilon": 0.3,
                        "warm_widths": [1, 2, 4, 8],
                        "warmup_new_tokens": 2, "warmup_s_max": 600},
             "check": {"min_tokens": 48,
                       "limits": {"kv_rel_err": 0.01, "greedy_gap": 0.1,
                                  "loglik_gap": 0.45}}}))
    return str(root)


def _run(root, cell, fault=None):
    sys.path.insert(0, R.ROOT)
    import run
    return run.main(["--workload", cell, "--seed", str(2 ** 33 + 11),
                     "--seconds", "8"], root=root, bench=BENCH,
                    require_tpu=False, fault=fault)


def _alter_tokens(eng):
    """Every committed token changed to token + 1 where it is produced."""
    commit, V = eng._commit, eng.tcfg.vocab_size

    def bad(seq, tokens, now):
        commit(seq, [(int(t) + 1) % V for t in tokens], now)
    eng._commit = bad


def _stale_state(eng):
    """Every decoder step returns its paged cache unchanged (a copy: the
    step donates the buffers it is given)."""
    import jax
    import jax.numpy as jnp
    for dec in (eng.tgt_dec, eng.dft_dec):
        def stale(tokens, pos, dec=dec, step=dec.step):
            cache = jax.tree.map(jnp.copy, dec.cache)
            out = step(tokens, pos)
            dec.cache = cache
            return out
        dec.step = stale


def test_sound_run_is_correct(root):
    out = _run(root, "tiny.t0")
    assert out["correct"], out["checks"]
    assert list(out["checks"])[:2] == ["kv_rel_err", "greedy_gap"]
    assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", ["tiny.t0", "tiny.t1"])
def test_altered_tokens_are_not_correct(root, cell):
    out = _run(root, cell, fault=_alter_tokens)
    assert not out["correct"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values()), \
        out["checks"]


@pytest.mark.parametrize("cell", ["tiny.t0", "tiny.t1"])
def test_stale_step_state_is_not_correct(root, cell):
    out = _run(root, cell, fault=_stale_state)
    assert not out["correct"]
    assert out["checks"]["kv_rel_err"]["value"] > \
        out["checks"]["kv_rel_err"]["limit"], out["checks"]


def test_no_chip_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, os.path.join(R.ROOT, "run.py"), "--workload",
           "dsc33b.backlog", "--seed", "5", "--seconds", "1"]
    p = subprocess.run(cmd, cwd=R.CHECKOUT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    # a checkout that holds only BENCHMARK.json and the benchmark's files
    bare = tmp_path / "bare"
    shutil.copytree(R.ROOT, bare / "benchmarks" / "onchip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(R.CHECKOUT, "BENCHMARK.json"), bare)
    cmd[1] = str(bare / "benchmarks" / "onchip" / "run.py")
    p = subprocess.run(cmd, cwd=bare, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("cell", ["tiny.t0", "tiny.t1"])
def test_control_is_not_correct(root, cell):
    """The reference computed in int8 in the program's place fails one of
    the cell's numbers; a served token changed to token + 1 fails the
    sampled cell's loglik_gap."""
    sys.path.insert(0, R.ROOT)
    import run
    from harness.check import verdict
    from harness.check_run import check
    _, c, _, _ = run.start(cell, root=root, bench=BENCH, require_tpu=False)
    r = run.serve_once(c, 2 ** 33 + 12, 8.0)
    numbers, info = check(c, r["params"], r["dims"], 2 ** 33 + 12,
                          r["finished"], r["prompts"], r["flight"], root,
                          control=True)
    limits = c["check"]["limits"]
    assert verdict(numbers, limits)[0], numbers
    ctrl = {k: v for k, v in info["control"].items()
            if k in numbers and v is not None}
    assert ctrl and not verdict(ctrl, limits)[0], (ctrl, limits)
    if cell == "tiny.t1":
        assert info["control"]["loglik_gap_changed_token"] > \
            numbers["loglik_gap"]
