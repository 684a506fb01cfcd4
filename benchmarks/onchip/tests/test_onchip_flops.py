"""FLOP and byte counts against hand counts; the peak table."""
import json

import pytest

from harness import flops as F
from harness import peaks as P
from harness import weights as W

# a 2-layer model: d 8, 2 heads of 4, 1 KV head, ff 16, vocab 10
M = W.Dims(layers=2, d=8, heads=2, kv_heads=1, head_dim=4, ff=16, vocab=10,
           eps=1e-6, theta=1e4)


def test_matmul_params_by_hand():
    # per layer: q 8*8 + k,v 2*8*4 + o 8*8 + mlp 3*8*16 = 576; head 80
    assert F.matmul_params(M) == 2 * 576 + 80


def test_forward_flops_by_hand():
    # two tokens attending 3 and 4 keys
    want = 2 * (2 * 576 + 80) * 2 + 4 * 2 * 2 * 4 * (3 + 4)
    assert F.forward_flops(M, [3, 4]) == want


def test_paged_attention_cost_by_hand():
    # row 0: 5 keys, queries at positions 3 and 4 (both real): 4 + 5 pairs
    # row 1: 2 keys, queries at 2, 3 (both padding); row 2: unbound
    f, b = F.paged_attention_cost(M, q_start=[3, 2, 0], lens=[5, 2, 0], T=2)
    assert f == 2 * 4 * 2 * 4 * (4 + 5)
    kv = 2 * 1 * 4 * 2 * 5           # K and V of row 0's 5 keys, bf16
    qo = 2 * 2 * 4 * 2 * 2           # its 2 queries in, 2 outputs out
    assert b == 2 * (kv + qo)
    # a prefill lane: 3 real tokens from position 0 -> 1 + 2 + 3 pairs
    f, _ = F.paged_attention_cost(M, q_start=[0], lens=[3], T=8)
    assert f == 2 * 4 * 2 * 4 * 6


def test_verify_cost_and_least_time():
    assert F.verify_accept_cost(3, 100) == (3000.0, 2400.0)
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert F.least_time(1000.0, 50.0, peak) == (10.0, "compute")
    assert F.least_time(100.0, 50.0, peak) == (5.0, "memory")


def test_peaks_for_v5e_and_unknown_device_is_an_error(tmp_path):
    p = P.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com/tpu/docs/v5e" in p["source"]
    with pytest.raises(P.UnknownDevice):
        P.peaks("TPU v9 imaginary")
    t = tmp_path / "peaks.json"
    t.write_text(json.dumps({"X": {"bf16_flops_per_s": 1}}))
    assert P.peaks("X", table=str(t)) == {"bf16_flops_per_s": 1}
    with pytest.raises(P.UnknownDevice):
        P.peaks("TPU v5 lite", table=str(t))


def test_weight_bytes_of_the_configurations():
    for name, gb in (("dsc-1.3b_33b-8l", (2.69, 9.41)),):
        conf = json.load(open(f"{P.TABLE[:-len('peaks.json')]}configs/"
                              f"{name}.json"))
        got = [W.param_bytes(W.dims(conf[s])) / 1e9
               for s in ("draft", "target")]
        assert got == pytest.approx(list(gb), abs=0.01)
