"""chip_smoke.py's phases at tiny size on the CPU.

``main`` insists on a TPU and is only checked for refusing here; the
phase functions it runs are driven with small shapes: the tracked trained
pair for phase (a), and a bf16 LLaMA-family pair cut to tiny widths in
place of the full-width one for phases (b) and (c).
"""
import dataclasses
import importlib.util
import os

import jax
import numpy as np
import pytest

from repro.models import model as M
from repro.training import pairs

ROOT = os.path.join(os.path.dirname(__file__), "..")
MAX_LEN = 128


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny_llama():
    """The LLaMA pair's configs at tiny widths, bf16, random weights."""
    dcfg, tcfg = pairs.llama_configs()
    small = dict(d_model=64, num_heads=4, num_kv_heads=4, vocab_size=256)
    dcfg = dataclasses.replace(dcfg, d_ff=128, **small)
    tcfg = dataclasses.replace(tcfg, num_layers=2, d_ff=172, **small)
    tp = M.init_params(jax.random.PRNGKey(0), tcfg)
    dp = M.init_params(jax.random.PRNGKey(1), dcfg)
    rng = np.random.default_rng(3)
    prompts = [list(map(int, p)) for p in rng.integers(0, 256, (2, 16))]
    return (dp, dcfg, tp, tcfg), prompts


def test_phase_trained_tiny(smoke, capsys):
    smoke.phase_trained(n_requests=2, new_tokens=4, max_len=MAX_LEN)
    out = capsys.readouterr().out
    assert "2 streams == AR greedy" in out
    assert "routes: paged_attention=pallas-interpret" in out


def test_phase_greedy_tiny(smoke, tiny_llama, capsys):
    pair, prompts = tiny_llama
    smoke.phase_greedy(pair, prompts, new_tokens=4, max_len=MAX_LEN)
    out = capsys.readouterr().out
    assert "paged verify forward vs dense" in out
    assert out.count("agreed greedy prefix") == len(prompts)


def test_phase_sampled_tiny(smoke, tiny_llama, capsys):
    pair, prompts = tiny_llama
    smoke.phase_sampled(pair, prompts, new_tokens=4, max_len=MAX_LEN)
    out = capsys.readouterr().out
    assert "verify=xla-probs-twin" in out       # the kernel route is TPU's
    assert "packets == XLA twin" in out


def test_greedy_check_rejects_divergence_before_a_near_tie(smoke):
    ref = np.asarray([[5, 6, 7, 8]])
    gaps = np.asarray([[1.0, 1.0, 0.01, 1.0]], np.float32)
    # leaving the reference at the near-tie (index 2) is allowed ...
    assert smoke.check_greedy_streams("t", [[5, 6, 9, 9]], ref, gaps,
                                      0.25) == [2]
    # ... leaving it before is a failure
    with pytest.raises(smoke.SmokeFailure):
        smoke.check_greedy_streams("t", [[5, 9, 7, 8]], ref, gaps, 0.25)


def test_main_refuses_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err


def test_main_refuses_route_overrides(smoke, monkeypatch, capsys):
    monkeypatch.setenv("REPRO_PAGED_BACKEND", "xla")
    assert smoke.main([]) != 0
    assert "REPRO_PAGED_BACKEND" in capsys.readouterr().err


def test_compile_cache_location(monkeypatch):
    """Unset JAX_COMPILATION_CACHE_DIR: the cache goes to the fixed
    <checkout>/.cache/jax.  Set: JAX reads it itself and the helper sets
    no other directory."""
    from repro.launch import compile_cache as CC
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(os.path.abspath(ROOT), ".cache", "jax")
        assert CC.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        jax.config.update("jax_compilation_cache_dir", was)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert CC.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
