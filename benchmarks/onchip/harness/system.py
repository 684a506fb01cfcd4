"""The system under test: the batched SpecBranch engine of ``src/repro``,
built through ``launch/serve.build_batched_engine`` on the paged backend,
from a cell's configuration and engine knobs.

This is the only module that imports the program.  Besides the engine it
reads what the program already exposes: the paged caches and the pool's
page tables (for the KV check), and the decoders' calls (for the kernel
rooflines of a traced run).
"""
from __future__ import annotations

import argparse
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import weights as W


def model_config(side: Dict[str, Any], name: str, dtype: str):
    from repro.models.config import ModelConfig, dense_pattern
    m = W.dims(side)
    return ModelConfig(
        name=name, family="dense", num_layers=m.layers, d_model=m.d,
        num_heads=m.heads, num_kv_heads=m.kv_heads, d_ff=m.ff,
        vocab_size=m.vocab, pattern=dense_pattern(0), head_dim=m.head_dim,
        rope_theta=m.theta, norm_eps=m.eps, tie_embeddings=False,
        dtype=dtype, source=side["source"])


def max_len_for(mix_levels: Tuple[int, int], gamma: int, c: int) -> int:
    """Decode-cache length: a power of two above the longest request plus
    the engine's speculation headroom (as ``serve.engine_config``, with
    room for the admission check's round margin)."""
    need = sum(mix_levels) + 8 * (gamma + c) + 16
    return max(512, 1 << (need - 1).bit_length())


def build(cell: Dict[str, Any], params: Dict[str, Any], seed: int,
          max_len: int):
    """The engine for ``cell`` over ``params`` ({"draft", "target"})."""
    from repro.launch.serve import build_batched_engine
    from repro.runtime.engines import EngineConfig
    conf = cell["config_spec"]
    knobs = cell["engine"]
    dcfg = model_config(conf["draft"], conf["name"] + ".draft",
                        conf["dtype"])
    tcfg = model_config(conf["target"], conf["name"] + ".target",
                        conf["dtype"])
    ecfg = EngineConfig(
        gamma=knobs["gamma"], k_max=knobs["k_max"],
        epsilon=knobs["epsilon"], c=float(conf["speed_ratio_c"]),
        temperature=float(cell["traffic_spec"]["temperature"]),
        max_len=max_len, seed=int(np.random.SeedSequence(
            [seed, 2]).generate_state(1)[0]) & 0x7FFFFFFF)
    args = argparse.Namespace(
        engine="specbranch", mesh=None, max_batch=knobs["max_batch"],
        page_size=knobs["page_size"], pool_pages=knobs["pool_pages"],
        swap_pages=0, attn_backend="paged", prefix_cache="off",
        draft_mode="sequential", pair=None)
    return build_batched_engine(
        args, ecfg, pair=(params["draft"], dcfg, params["target"], tcfg))


def warm_decode_widths(eng, widths: List[int]) -> None:
    """Compile each decoder's batched step at every token width the
    rounds can ask for; with no row bound, every write lands on the trash
    page and the logits are dropped."""
    for dec in (eng.tgt_dec, eng.dft_dec):
        pos = np.zeros(dec.n_rows, np.int32)
        for T in widths:
            lg, _ = dec.step(np.zeros((dec.n_rows, T), np.int32), pos)
            lg.block_until_ready()


def pages_held(eng, peak: Dict[str, int], dims: Dict[str, Any]
               ) -> Dict[str, Tuple[int, int, int]]:
    """Per side: the most pages the window held, the pages the engine's
    pool has, and the bytes of one page (keys and values, bf16)."""
    out = {}
    for side, which in (("target", "t"), ("draft", "d")):
        pool, m = eng.pools[which], dims[side]
        nbytes = m.layers * m.kv_heads * m.head_dim * 2 * 2 * pool.page_size
        out[side] = (peak.get(which, 0), pool.num_pages, nbytes)
    return out


def device_sync(eng) -> None:
    jax.block_until_ready((eng.tgt_dec.cache, eng.dft_dec.cache))


def _last_layer_kv(dec, pool, key, n: int) -> Tuple[np.ndarray, np.ndarray]:
    slot = dec.cache["blocks"][0]
    ps = slot["k_pages"].shape[3]
    pages = jnp.asarray(pool.table(key)[:-(-n // ps)], jnp.int32)

    def rows(a):                 # (L, P, KV, ps, hd) -> (n, KV, hd)
        t = a[-1][pages]                                # (np, KV, ps, hd)
        t = jnp.transpose(t, (0, 2, 1, 3))
        return np.asarray(t.reshape(-1, *t.shape[2:])[:n], np.float32)

    return rows(slot["k_pages"]), rows(slot["v_pages"])


def in_flight(eng) -> List[Dict[str, Any]]:
    """For each request in flight: its tokens so far (prompt + served)
    and the last layer's keys and values that target and draft hold for
    the committed positions, read back from the paged caches."""
    out = []
    for s in eng.active:
        toks = list(s.prompt) + list(s.out)
        rec = {"rid": s.rid, "tokens": toks, "n_prompt": len(s.prompt)}
        for side, dec, st, key in (
                ("target", eng.tgt_dec, s.tgt, ("t", s.rid)),
                ("draft", eng.dft_dec, s.dft, ("d", s.rid))):
            n = min(st.ing, len(toks))
            rec[side] = (n, *_last_layer_kv(dec, eng.pools[key[0]], key, n))
        out.append(rec)
    return out


def device_info() -> Dict[str, Any]:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def memory_peak_bytes() -> int:
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


class CallLog:
    """Records, per decoder forward, what the paged-attention kernel was
    asked: token width, each row's first query position and key count.
    Installed only for a traced run (wraps the decoders' ``step`` and
    ``prefill_rows`` and their page-table views; values are the host
    arrays the engine already built, so nothing syncs)."""

    def __init__(self, eng):
        self.calls: List[Tuple[str, int, np.ndarray, np.ndarray]] = []
        self.on = False
        for side, dec in (("target", eng.tgt_dec), ("draft", eng.dft_dec)):
            self._wrap(side, dec)

    def _wrap(self, side: str, dec) -> None:
        log, state = self, dec.state
        view, step, prefill = state.table_view, dec.step, dec.prefill_rows
        last = {}

        def table_view(*a, **k):
            tab, lens = view(*a, **k)
            last["lens"] = lens
            return tab, lens

        def step_(tokens, pos):
            out = step(tokens, pos)
            if log.on:
                log.calls.append((side, int(tokens.shape[1]),
                                  np.asarray(pos), last["lens"].copy()))
            return out

        def prefill_(parts):
            out = prefill(parts)
            if log.on:
                T = max(len(t) for _, t in parts)
                lens = last["lens"].copy()
                log.calls.append((side, T, np.zeros_like(lens), lens))
            return out

        state.table_view, dec.step, dec.prefill_rows = (table_view, step_,
                                                        prefill_)
