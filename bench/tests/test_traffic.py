"""The traffic stream is the cell's: the same schedule under every seed."""
import json

import numpy as np
import pytest

from bench import traffic as T
from bench.cells import BENCH

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_schedule_is_fixed_by_the_traffic_file(name):
    t = _mix(name)
    a, b = T.schedule(t), T.schedule(t)
    assert a == b
    for r in a:
        lo, hi = t["prompt_tokens"]["min"], t["prompt_tokens"]["max"]
        assert lo <= r["prompt_len"] <= hi
        assert 1 <= r["output_len"] <= t["output_tokens"]["max"]
        assert r["prompt_len"] + r["output_len"] <= t["context_limit"]


@pytest.mark.parametrize("name", MIXES)
def test_seed_changes_token_ids_only(name):
    t = _mix(name)
    s = T.schedule(t)
    p1 = T.prompts(s, 32000, 2147483813)
    p2 = T.prompts(s, 32000, 7)
    assert [len(p) for p in p1] == [r["prompt_len"] for r in s]
    assert [len(p) for p in p1] == [len(p) for p in p2]
    assert any(not np.array_equal(a, b) for a, b in zip(p1, p2))
    again = T.prompts(s, 32000, 2147483813)
    assert all(np.array_equal(a, b) for a, b in zip(p1, again))
    assert max(int(p.max()) for p in p1) < 32000


def test_open_loop_rate_and_chat_lengths():
    t = _mix("chat")
    s = T.schedule(t)
    offsets = np.array([r["offset"] for r in s])
    assert np.all(np.diff(offsets) > 0)
    assert len(s) / offsets[-1] == pytest.approx(t["rate_per_s"], rel=0.15)
    assert np.mean([r["prompt_len"] for r in s]) == pytest.approx(245, rel=0.1)
    assert np.mean([r["output_len"] for r in s]) == pytest.approx(280, rel=0.1)


def test_closed_loop_clients():
    t = _mix("chat-closed")
    assert T.clients(t, 32) == 48
    assert all(r["offset"] == 0.0 for r in T.schedule(t))
