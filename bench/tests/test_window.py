"""Window arithmetic: the token-weighted time per output token, the
percentiles and the window cut."""
from types import SimpleNamespace

import numpy as np
import pytest

from bench import window as W


def _request(due, times):
    return {"due": due, "times": list(times)}


def test_stalled_short_request_moves_tpot_by_its_share_of_gaps():
    step, stall = 0.08, 0.286
    # nine long requests: 101 tokens each, one every decode step
    long = [_request(0.0, [1.0 + i * step for i in range(101)])
            for _ in range(9)]
    # one 3-token request whose first decode sat behind a prefill stall
    short = _request(0.0, [1.0, 1.0 + stall, 1.0 + stall + step])
    recs = long + [short]
    gaps = 9 * 100 + 2
    got = W.window_metrics(recs, 0.0, 100.0)["tpot_ms"] / 1e3
    assert got == pytest.approx(step + (stall - step) / gaps)
    # a mean of per-request means would move by 1/N of the requests
    mean_of_means = np.mean([np.mean(np.diff(r["times"])) for r in recs])
    assert mean_of_means - step == pytest.approx(
        ((stall + step) / 2 - step) / len(recs))
    assert (got - step) < (mean_of_means - step) / 40


def test_gaps_count_only_inside_the_window():
    r = _request(0.0, [0.5, 1.5, 2.5, 3.5])
    assert W.gaps_in([r], 1.0, 3.0) == [1.0]
    assert W.gaps_in([r], 0.0, 3.5) == [1.0, 1.0, 1.0]
    assert W.tokens_in([r], 1.0, 3.5) == 2          # [w0, w1)
    assert W.window_metrics([r], 1.6, 2.4)["tpot_ms"] is None


def test_arrivals_and_ttft_use_due_time():
    recs = [_request(0.9, [1.2]), _request(1.0, [1.3, 1.4]),
            _request(2.0, []), _request(3.0, [3.5])]
    m = W.window_metrics(recs, 1.0, 3.0)
    assert m["arrived"] == 2
    assert m["without_first_token"] == 1
    assert m["ttft_p50_s"] == pytest.approx(0.3)
    assert m["output_tokens_per_s"] == pytest.approx(3 / 2.0)


@pytest.mark.parametrize("n", [1, 2, 7, 51, 400])
def test_percentile_matches_numpy_linear(n):
    v = np.random.default_rng(n).lognormal(size=n)
    for q in (0, 50, 90, 95, 99, 100):
        assert W.percentile(list(v), q) == pytest.approx(np.percentile(v, q))


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        W.percentile([], 90)


def test_stamp_spreads_a_sync_over_its_tokens():
    t = [1.0]
    W.stamp(t, 4, 2.0)
    assert t == pytest.approx([1.0, 1.25, 1.5, 1.75, 2.0])
    W.stamp(t, 0, 3.0)
    assert len(t) == 5


class _Engine:
    """Hands back scripted ``step()`` deltas; its own first-token stamp
    lies far in the past, where no client saw the token."""

    def __init__(self, rounds):
        self.rounds = list(rounds)
        self.queue = []
        self.prefill_tokens = 0

    def submit(self, prompt, max_new_tokens):
        self.queue.append(SimpleNamespace(t_first_token=-1e6, done=False))
        return len(self.queue)

    def step(self):
        return self.rounds.pop(0)


def test_first_token_is_stamped_when_step_hands_it_over(monkeypatch):
    from bench import drive

    clock = iter([10.0, 10.25, 10.5, 11.0, 11.25])
    monkeypatch.setattr(drive.time, "time", lambda: next(clock))
    eng = _Engine([{1: [7, 8]}, {1: [9]}])
    rec = drive.Recorder(eng, [{"output_len": 3}], [np.arange(4)])
    rec.submit(0, due=9.0)
    rec.step()
    rec.step()
    r = rec.records[0]
    # prefill and the round's decode step both reach the client at 10.5
    assert r["times"] == [10.5, 10.5, 11.25]
    assert r["output"] == [7, 8, 9]
    assert W.ttfts([r]) == [1.5]
