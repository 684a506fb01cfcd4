"""The plain float32 reference computes what the served model computes:
at a tiny size on the CPU, the program's own forward in float32 over the
benchmark's weights agrees with it; the int8 control does not."""
import jax
import jax.numpy as jnp
import numpy as np

from harness import registry as R
from harness import system
from harness import weights as W

SIDE = {"source": "tiny", "hidden_size": 64, "intermediate_size": 160,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 3, "vocab_size": 97, "rms_norm_eps": 1e-6,
        "rope_theta": 100000}


def _ref():
    return R.load_module(R.ROOT, "references", "llama")


def test_reference_matches_the_program_forward_in_float32():
    from repro.models import model as M
    m = W.dims(SIDE)
    params = W.make_params(W.key_of(3, 0), m)
    cfg = system.model_config(SIDE, "tiny", "float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    toks = np.random.default_rng(0).integers(0, 97, 37).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _, _ = M.forward(p32, cfg, jnp.asarray(toks)[None])
    got, k, v = _ref().forward(params, m, toks)
    assert got.shape == (37, 97) and k.shape == (37, 2, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               rtol=2e-4, atol=2e-4)
    ctrl, kq, _ = _ref().forward(params, m, toks, quant="int8")
    err = np.abs(np.asarray(ctrl) - np.asarray(got)).max()
    assert err > 100 * np.abs(np.asarray(got) - np.asarray(want[0])).max()


def test_reference_keys_match_the_program_paged_cache():
    """Prefill through the paged cache (the served layout): the last
    layer's keys and values equal the reference's to bf16 rounding."""
    from repro.models import model as M
    m = W.dims(SIDE)
    params = W.make_params(W.key_of(4, 0), m)
    cfg = system.model_config(SIDE, "tiny", "bfloat16")
    T, ps = 40, 16
    cache = M.init_paged_cache(cfg, 4, ps)
    toks = np.random.default_rng(1).integers(0, 97, T).astype(np.int32)
    table = jnp.asarray([[2, 0, 3]], jnp.int32)
    lens = jnp.asarray([T], jnp.int32)
    _, cache, _ = M.forward(params, cfg, jnp.asarray(toks)[None],
                            cache=cache, paged=(table, lens))
    kp = cache["blocks"][0]["k_pages"][-1][np.array([2, 0, 3])]
    kp = np.asarray(jnp.transpose(kp, (0, 2, 1, 3)).reshape(-1, 2, 16)[:T],
                    np.float32)
    _, k, _ = _ref().forward(params, m, toks)
    from harness.check import rel_err
    assert rel_err(kp, np.asarray(k)) < 0.02
