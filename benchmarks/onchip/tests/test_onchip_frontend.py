"""The wall-clock front end against a fake engine on a fake clock: FIFO
admission of the backlog, the engine calls in the scheduler's order, the
window's end, and the pages the pools held."""
import types

import pytest

from harness import stats as S
from harness.frontend import Frontend
from harness.traffic import Request


class FakeEngine:
    """One row at a time; each round takes 0.1 s and commits one token."""

    def __init__(self, clock, rows=1):
        self.clock, self.rows = clock, rows
        self.active, self.calls = [], []

    def resume_out_len(self, rid):
        return 0

    def can_admit(self, prompt_len, max_new):
        return len(self.active) < self.rows

    def reserve(self, rid, prompt, max_new):
        self.calls.append(("reserve", rid))
        self.active.append(types.SimpleNamespace(
            rid=rid, out=[], max_new=max_new, done=False))

    def commit_admissions(self):
        self.calls.append(("commit_admissions",))

    def step_round(self):
        self.calls.append(("step_round",))
        self.clock.t += 0.1
        committed = {}
        for s in self.active:
            s.out.append(7)
            s.done = len(s.out) >= s.max_new
            committed[s.rid] = 1
        return {"committed": committed, "preempted": []}

    def retire_done(self):
        done = [s for s in self.active if s.done]
        self.active = [s for s in self.active if not s.done]
        return [(s, types.SimpleNamespace(tokens=list(s.out))) for s in done]


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


def test_fifo_backlog_in_the_scheduler_order():
    clk = Clock()
    eng = FakeEngine(clk)
    fe = Frontend(eng, clock=clk, sleep=clk.sleep)
    reqs = [Request(0, [1, 2], 3), Request(1, [1, 2], 2)]
    w = fe.serve(reqs, t0=0.0, seconds=1.0)
    # request 0 runs rounds ending at .1 .2 .3; request 1 waits for the
    # row, admitted at .3, delivers at .4 .5
    assert w.reqs[0].deliveries == [(pytest.approx(0.1), 1),
                                    (pytest.approx(0.2), 1),
                                    (pytest.approx(0.3), 1)]
    assert w.reqs[1].admitted == pytest.approx(0.3)
    assert sorted(S.ttfts(w)) == pytest.approx([0.1, 0.4])
    assert fe.outputs == {0: [7, 7, 7], 1: [7, 7]}
    # the backlog drained: the window idles to its close, and the rate is
    # over the whole window
    assert w.t_end == pytest.approx(1.0)
    assert S.output_tokens_per_s(w) == pytest.approx(5.0)
    # the scheduler's order: reserve the admissible head, commit, rounds
    assert eng.calls[:3] == [("reserve", 0), ("commit_admissions",),
                             ("step_round",)]


def test_backlog_ends_with_the_window():
    clk = Clock()
    eng = FakeEngine(clk, rows=2)
    fe = Frontend(eng, clock=clk, sleep=clk.sleep)
    w = fe.serve([Request(0, [1, 2], 100)], t0=0.0, seconds=0.95)
    # rounds start until the close; the last, starting at 0.9, ends the
    # window at 1.0
    assert w.reqs[0].admitted == pytest.approx(0.0)
    assert w.t_end == pytest.approx(1.0)
    assert len(w.rounds) == 10
    assert S.output_tokens_per_s(w) == pytest.approx(10.0)


def test_pages_peak_is_the_most_any_round_held():
    clk = Clock()
    eng = FakeEngine(clk, rows=2)
    held = iter([3, 5, 4] + [4] * 20)

    class Pool:
        @property
        def pages_in_use(self):
            return next(held)

    eng.pools = {"t": Pool()}
    fe = Frontend(eng, clock=clk, sleep=clk.sleep)
    fe.serve([Request(0, [1, 2], 3)], t0=0.0, seconds=1.0)
    assert fe.pages_peak == {"t": 5}


def test_request_that_can_never_fit_is_an_error():
    clk = Clock()
    eng = FakeEngine(clk, rows=0)
    fe = Frontend(eng, clock=clk, sleep=clk.sleep)
    with pytest.raises(RuntimeError, match="never be admitted"):
        fe.serve([Request(0, [1, 2], 2)], t0=0.0, seconds=1.0)
