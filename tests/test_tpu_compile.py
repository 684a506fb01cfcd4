"""Ahead-of-time compiles, for a described TPU v5e, of the served path's
kernels at LLaMA-7B widths (32 KV heads of 128, 16-token pages, bf16 KV,
32000-token vocabulary).

Nothing runs: each test lowers the kernel for one chip of a described
``v5e:2x2`` topology and asserts the compiled program holds the Mosaic
kernel (``tpu_custom_call``).  That catches what interpret mode cannot —
block shapes the TPU tiling refuses, unsupported in-kernel primitives,
scoped-VMEM overflow.  The topology is described inside a module fixture
(never at import): one process at a time may load the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged as PG
from repro.kernels import paged_attention as PA
from repro.kernels import verify_accept as VA

H = KV = 32                 # LLaMA-7B heads (no GQA)
HD = 128
PS = 16                     # serving page size (launch/serve.py default)
ROWS = 8                    # serving rows (--max-batch default)
N_TABLE = 512 // PS         # page-table width at the default max_len 512
PAGES = ROWS * N_TABLE + 1  # + the trash page
VOCAB = 32000
R = 8                       # verify width: gamma + 1 = 5 on the 1/2/4/8 ladder


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("T", [R, 16], ids=["verify", "prefill"])
def test_paged_attention_compiles(one_chip, T):
    def f(q, k, v, table, lens, q_start):
        return PA.paged_decode_attention(q, k, v, table, lens, q_start,
                                         interpret=False)

    pages = _spec((PAGES, KV, PS, HD), jnp.bfloat16, one_chip)
    compiled = jax.jit(f).lower(
        _spec((ROWS, T, H, HD), jnp.bfloat16, one_chip), pages, pages,
        _spec((ROWS, N_TABLE), jnp.int32, one_chip),
        _spec((ROWS,), jnp.int32, one_chip),
        _spec((ROWS,), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)


def test_verify_accept_batched_compiles(one_chip):
    def f(p, q, toks, lens, u, w):
        return VA.verify_accept_batched(p, q, toks, lens, u, w,
                                        interpret=False)

    logits = _spec((ROWS, R, VOCAB), jnp.float32, one_chip)
    grid = _spec((ROWS, R), jnp.float32, one_chip)
    compiled = jax.jit(f).lower(
        logits, logits, _spec((ROWS, R), jnp.int32, one_chip),
        _spec((ROWS,), jnp.int32, one_chip), grid, grid).compile()
    _assert_kernel(compiled)


# the preemption swap store's rows: 16 layers x (K, V) x 4096 floats
@pytest.mark.parametrize("dim,dtype", [(KV * HD, jnp.bfloat16),
                                       (16 * 2 * KV * HD, jnp.float32)],
                         ids=["one-layer-bf16", "swap-row-f32"])
def test_paged_gather_compiles(one_chip, dim, dtype):
    def f(pages, table, valid_len):
        return PG.paged_gather(pages, table, valid_len, interpret=False)

    compiled = jax.jit(f).lower(
        _spec((256, PS, dim), dtype, one_chip),
        _spec((N_TABLE,), jnp.int32, one_chip),
        _spec((), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)
