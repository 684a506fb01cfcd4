"""Training substrate: loss decreases on the Zipf-Markov language;
checkpoint roundtrip; optimizer math."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import ZipfMarkov
from repro.models import model as M
from repro.models.config import ModelConfig, dense_pattern
from repro.training import checkpoint as ckpt
from repro.training import optim
from repro.training.optim import AdamWConfig
from repro.training.train import TrainConfig, lm_loss, train_lm

TINY = ModelConfig(name="t", family="dense", num_layers=2, d_model=48,
                   num_heads=2, num_kv_heads=1, d_ff=96, vocab_size=67,
                   pattern=dense_pattern(0), dtype="float32")


def test_zipf_markov_statistics():
    zm = ZipfMarkov(vocab=67, seed=0)
    np.testing.assert_allclose(zm.T.sum(-1), 1.0, atol=1e-9)
    seq = zm.sample(np.random.default_rng(0), 500)
    assert seq.min() >= 0 and seq.max() < 67
    # Zipfian head: most-common token clearly above uniform
    counts = np.bincount(seq, minlength=67)
    assert counts.max() > 3 * (500 / 67)


def test_loss_decreases():
    zm = ZipfMarkov(vocab=67, seed=0)
    data = zm.batch_iter(8, 32, seed=1)
    first = next(data)
    params0 = M.init_params(jax.random.PRNGKey(0), TINY)
    loss0, _ = lm_loss(params0, TINY, jnp.asarray(first))
    tc = TrainConfig(steps=60, batch=8, seq_len=32,
                     optim=AdamWConfig(lr=2e-3, total_steps=60))
    params, metrics = train_lm(TINY, data, tc, verbose=False)
    assert metrics["final_loss"] < float(loss0) - 0.3


def test_checkpoint_roundtrip():
    params = M.init_params(jax.random.PRNGKey(0), TINY)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "p.npz")
        ckpt.save(path, params)
        restored = ckpt.load(path, params)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pair_cache_retrains_only_missing_or_stale_files(tmp_path):
    """The pair loaders fall back to training for a missing file or one
    written for another architecture, and for nothing else."""
    from repro.training.pairs import _load_cached
    params = M.init_params(jax.random.PRNGKey(0), TINY)
    assert _load_cached(str(tmp_path / "none.npz"), params) is None
    other = M.init_params(jax.random.PRNGKey(0),
                          ModelConfig(**{**TINY.__dict__, "d_model": 32}))
    stale = str(tmp_path / "stale.npz")
    ckpt.save(stale, other)
    with pytest.raises(ckpt.StaleCheckpoint):
        ckpt.load(stale, params)
    assert _load_cached(stale, params) is None
    broken = tmp_path / "broken.npz"
    broken.write_bytes(b"not an npz")
    with pytest.raises(ValueError) as err:      # unreadable: propagates
        _load_cached(str(broken), params)
    assert not isinstance(err.value, ckpt.StaleCheckpoint)


def test_adamw_schedule():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110,
                      min_lr_ratio=0.1)
    assert float(optim.schedule(cfg, jnp.asarray(0))) < 0.2
    mid = float(optim.schedule(cfg, jnp.asarray(10)))
    assert mid == 1.0
    end = float(optim.schedule(cfg, jnp.asarray(109)))
    assert end < 0.15


def test_grad_clip_bounds_update():
    params = {"w": jnp.ones((4,))}
    grads = {"w": jnp.full((4,), 1e6)}
    state = optim.init(params)
    cfg = AdamWConfig(lr=0.1, clip_norm=1.0, warmup_steps=0, total_steps=10,
                      weight_decay=0.0)
    new, _ = optim.apply(cfg, params, grads, state)
    assert float(jnp.abs(new["w"] - params["w"]).max()) < 0.2
