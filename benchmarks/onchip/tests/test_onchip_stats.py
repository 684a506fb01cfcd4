"""TTFT, inter-token gaps and rates from a window's records, by hand."""
import pytest

from harness import stats as S


def _window(stall: float = 0.0) -> S.Window:
    """Two requests, rounds every 0.1 s from t=0; with ``stall`` the
    round ending at 0.5 s takes that much longer (an admission
    prefill), and every later delivery slips by it."""
    reqs = {0: S.ReqRecord(due=0.0, prompt_len=10),
            1: S.ReqRecord(due=0.25, prompt_len=20)}
    rounds, t = [], 0.0
    for i in range(10):
        end = t + 0.1 + (stall if i == 4 else 0.0)
        n = [rid for rid in reqs if reqs[rid].due <= t]
        for rid in n:
            reqs[rid].deliveries.append((end, 1))
        rounds.append(S.RoundRecord(start=t, end=end, rows=len(n),
                                    committed=len(n)))
        t = end
    return S.Window(t0=0.0, t_due_end=1.0, t_end=t, reqs=reqs,
                    rounds=rounds)


def test_rate_ttft_itl_by_hand():
    w = _window()
    assert w.seconds == pytest.approx(1.0)
    # request 0: 10 tokens; request 1 due 0.25, served from the round
    # starting at 0.3: 7 tokens
    assert S.output_tokens_per_s(w) == pytest.approx(17.0)
    assert sorted(S.ttfts(w)) == pytest.approx([0.1, 0.15])
    assert S.itls(w) == pytest.approx([0.1] * 15)
    assert S.tokens_per_row_round(w) == pytest.approx(1.0)
    assert S.round_ms_p50(w) == pytest.approx(100.0)


def test_a_stall_moves_the_tail_not_the_median():
    base, st = _window(), _window(stall=0.5)
    assert S.percentile(S.itls(st), 95) > S.percentile(S.itls(base), 95) \
        + 0.3
    assert S.percentile(S.itls(st), 50) == pytest.approx(
        S.percentile(S.itls(base), 50))
    assert S.output_tokens_per_s(st) == pytest.approx(17.0 / 1.5)


def test_percentile_is_linear_and_empty_is_nan():
    assert S.percentile([1, 2, 3, 4, 5], 90) == pytest.approx(4.6)
    assert S.percentile([], 90) != S.percentile([], 90)


def test_rounds_past_the_window_are_left_out():
    w = _window()
    w.t_end = 0.5                   # rounds ending later fall outside
    assert S.output_tokens_per_s(w) == pytest.approx((5 + 2) / 0.5)
