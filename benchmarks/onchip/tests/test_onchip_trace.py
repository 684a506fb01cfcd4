"""The trace reduction: busy union, idle gaps and their host attribution,
kernel time; and the reduction of a small trace recorded on a v5e."""
import os

import pytest

from harness import trace as T

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def test_union_and_gaps_by_hand():
    iv = [(0, 10), (5, 12), (20, 30), (29, 31), (40, 45)]
    assert T.union(iv, 0, 50) == 12 + 11 + 5
    assert T.union(iv, 8, 25) == 4 + 5
    assert T.gaps(iv, 0, 50) == [(12, 20), (31, 40), (45, 50)]
    assert T.gaps([], 3, 7) == [(3, 7)]
    assert T.gaps([(0, 100)], 10, 20) == []


def _reduced():
    ops = [T.Op("fusion.1", "jit_step", 100, 300, 0),
           T.Op("custom-call.7", "jit_step", 300, 400, 0),
           T.Op("fusion.1", "jit_step", 700, 800, 0)]
    host = [("bench.window", 100, 1000), ("bench.step_round", 100, 900),
            ("PjitFunction(step)", 120, 150),
            ("np.asarray(jax.Array)", 450, 650),
            ("bench.retire_done", 900, 1000)]
    return T.Reduced(window=(100, 1000), ops=ops, host=host, chips=1)


def test_idle_share_kernel_time_and_breakdown():
    r = _reduced()
    assert r.window_s == pytest.approx(900e-9)
    assert r.busy_s() == pytest.approx(400e-9)
    assert r.idle_share() == pytest.approx(1 - 4 / 9)
    assert r.kernel_s(r"custom-call") == pytest.approx(100e-9)
    b = r.breakdown()
    assert b["device_ops"][0] == ["jit_step/fusion.1", pytest.approx(3e-7)]
    gaps = dict((k, v) for k, v in b["idle_gaps"])
    # 400-700: the host was waiting on a fetch; 800-1000: step_round then
    # retire_done (the midpoint, 900, is where both touch: innermost wins)
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(300e-9)
    assert sum(gaps.values()) == pytest.approx(500e-9)


def test_recorded_v5e_trace():
    """A trace recorded on one v5e by ``tools/record_trace.py``: one paged
    attention call, one fused verify call and a 2048^3 bf16 matmul."""
    r = T.reduce(os.path.join(DATA, "v5e_probe.xplane.pb"), window=None)
    assert r.chips == 1
    names = {o.name for o in r.ops}
    assert {"paged_decode_attention.1", "verify_accept_batched.1",
            "fusion"} <= names
    assert {o.module for o in r.ops} >= {"jit_paged_decode_attention",
                                         "jit_verify_accept_batched"}
    assert r.kernel_s(r"^paged_decode_attention") == pytest.approx(
        58497e-9)
    assert r.kernel_s(r"^verify_accept_batched") == pytest.approx(30946e-9)
    # the matmul: 2 * 2048^3 FLOPs in one fusion, under the chip's peak
    t_mm = r.kernel_s(r"^fusion$")
    assert 0.9 * 197e12 < 2 * 2048 ** 3 / t_mm < 197e12
    # three programs run back to back with host work between them
    assert 0.5 < r.idle_share() < 1.0
    b = r.breakdown()
    assert b["device_ops"][0][0] == "jit__lambda/fusion"
    assert b["idle_gaps"][0][0] == "(no host span)"
