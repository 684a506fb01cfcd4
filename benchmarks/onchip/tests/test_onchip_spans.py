"""The readers of the program's ``engine.*`` spans, on a hand-built trace
reduction: a round's own host time, syncs per round, and the idle time
put down to the engine's host work."""
import pytest

from harness import registry as R
from harness import trace as T
from harness.context import Context

READERS = ("round_host_ms_p50", "host_syncs_per_round",
           "device_idle_host_share")

# two rounds in a 10 us window (ns); the first holds two overlapping
# fetches, the second a commit with a JAX dispatch event beneath it
HOST = [("bench.window", 0, 10000),
        ("bench.step_round", 1000, 5000), ("engine.round", 1000, 5000),
        ("engine.make_room", 1000, 1200),
        ("engine.fetch", 1500, 2500), ("engine.fetch", 2000, 3000),
        ("bench.retire_done", 5000, 6000), ("engine.retire", 5000, 5400),
        ("bench.step_round", 6000, 8000), ("engine.round", 6000, 8000),
        ("engine.fetch", 6100, 6400), ("engine.commit", 6500, 7800),
        ("PjitFunction(convert_element_type)", 7000, 7500)]
# chip 0's idle gaps, the event each falls under, and whether it counts
GAPS = {"make_room": ((1050, 1150), True),
        "fetch": ((2100, 2300), False),
        "round_itself": ((3500, 3700), True),
        "no_engine_span": ((5500, 5600), False),
        "jax_event_in_commit": ((7100, 7300), True)}


def _reduced(host=HOST, idle=()):
    ops, t = [], 0
    for a, b in sorted(idle):
        ops.append(T.Op("fusion", "jit_step", t, a, 0))
        t = b
    ops.append(T.Op("fusion", "jit_step", t, 10000, 0))
    return T.Reduced(window=(0, 10000), ops=ops, host=list(host), chips=1)


def _read(name, reduced):
    ctx = Context(window=None, cell={}, dims={}, peak=None, setup_s=0.0,
                  reduced=reduced)
    return R.load_module(R.ROOT, "metrics", name).read(ctx)


def test_round_host_time_subtracts_overlapping_fetches_once():
    # round 1 (1000-5000): idle 1050-1150 under make_room counts 100 ns;
    # idle 1400-3100 under the two overlapping fetches (union 1500-3000)
    # counts 1700 - 1500 = 200; idle 4900-5100 counts its 100 inside the
    # round.  Round 2 (6000-8000): idle 6200-6300 under its fetch counts
    # nothing, idle 7100-7300 under commit 200.  The chip's busy time
    # counts nowhere, nor idle 5500-5600 between the rounds.  Median of
    # 400 and 200 ns:
    idle = [(1050, 1150), (1400, 3100), (4900, 5100), (5500, 5600),
            (6200, 6300), (7100, 7300)]
    assert _read("round_host_ms_p50", _reduced(idle=idle)) == \
        pytest.approx(0.0003)


def test_round_host_time_leaves_out_blocked_host_time():
    # a round with no idle stretch reads 0 however its time splits into
    # fetches and other host calls: blocked calls hold the chip busy
    assert _read("round_host_ms_p50", _reduced()) == 0.0


def test_syncs_per_round():
    assert _read("host_syncs_per_round", _reduced()) == pytest.approx(1.5)


@pytest.mark.parametrize("gap", sorted(GAPS))
def test_idle_gap_attribution(gap):
    (a, b), counted = GAPS[gap]
    share = _read("device_idle_host_share", _reduced(idle=[(a, b)]))
    assert share == pytest.approx(100.0 * (b - a) / 10000 if counted
                                  else 0.0)


def test_idle_host_share_sums_the_counted_gaps():
    r = _reduced(idle=[g for g, _ in GAPS.values()])
    assert _read("device_idle_host_share", r) == pytest.approx(
        100.0 * sum(b - a for (a, b), c in GAPS.values() if c) / 10000)
    # the harness's own breakdown names the JAX event, not the phase
    assert dict(r.breakdown()["idle_gaps"])[
        "PjitFunction(convert_element_type)"] == pytest.approx(200e-9)


@pytest.mark.parametrize("name", READERS)
def test_no_engine_round_reads_none(name):
    host = [ev for ev in HOST if ev[0] != "engine.round"]
    assert _read(name, _reduced(host=host, idle=[(3500, 3700)])) is None
    assert _read(name, None) is None
