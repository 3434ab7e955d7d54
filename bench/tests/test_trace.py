"""Trace reduction: a small recorded chip trace and a hand-made one."""
import json
import pathlib

import pytest

from bench import trace as TR

DATA = pathlib.Path(__file__).resolve().parent / "data" / "trace_small.json"


def test_hand_made_trace():
    ev = {
        "window": [0.0, 10.0],
        "devices": [{
            "modules": [["jit__prefill_bucket(1)", 1.0, 3.0],
                        ["jit__decode_multi(2)", 4.0, 6.0],
                        ["jit__insert(3)", 6.0, 6.5]],
            "ops": [["%psq_matmul_kernel.1 = f32[256,4096]", 1.0, 2.0],
                    ["%while.5 = (f32[4])", 4.0, 6.0],
                    ["%psq_matmul_kernel.2 = f32[32,4096]", 4.0, 5.0],
                    ["%fusion.3 = f32[32]", 5.0, 6.0],
                    ["%copy.4 = f32[8]", 6.0, 6.5],
                    ["%fusion.3 = f32[32]", 9.5, 11.0]],
        }],
        "host": [["step", 0.5, 7.0], ["wait_arrival", 7.0, 9.5]],
    }
    r = TR.reduce(ev)
    assert r["window_s"] == 10.0
    assert r["busy_s"] == pytest.approx(1.0 + 2.5 + 0.5)   # clipped at 10
    assert r["phase_s"] == {"decode": 2.0, "prefill": 2.0, "other": 0.5}
    assert r["psq_s"] == {"decode": 1.0, "prefill": 1.0, "other": 0.0}
    assert r["psq_calls"]["decode"] == 1
    ops = dict(r["device_ops"])
    assert "while.5" not in ops                  # containers are not ops
    assert ops["fusion.3"] == pytest.approx(1.5)
    gaps = r["idle_gaps"]
    assert gaps[0] == ["wait_arrival", pytest.approx(3.0)]   # 6.5 .. 9.5
    assert ["step", pytest.approx(2.0)] in gaps               # 2.0 .. 4.0
    assert sum(g for _, g in gaps) == pytest.approx(10.0 - r["busy_s"])


def test_recorded_chip_trace():
    ev = json.loads(DATA.read_text())
    r = TR.reduce(ev)
    w = r["window_s"]
    assert 0 < r["busy_s"] <= w
    assert r["phase_calls"]["prefill"] == 1 and r["phase_calls"]["decode"] == 3
    phases = r["phase_s"]["decode"] + r["phase_s"]["prefill"]
    assert phases <= r["busy_s"] + 1e-9
    for ph in ("decode", "prefill"):
        assert 0 < r["psq_s"][ph] <= r["phase_s"][ph]
    # 4 layers x 7 projections per prefill call and per decode step
    assert r["psq_calls"]["prefill"] == 28
    assert r["psq_calls"]["decode"] == 3 * 28
    names = [n for n, _ in r["device_ops"]]
    assert names[0].startswith("psq_matmul_kernel")
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) <= 10
    assert all(label in ("step", "submit", "untracked")
               for label, _ in r["idle_gaps"])


def test_op_name():
    assert TR.op_name("%psq_matmul_kernel.53 = f32[32,14336]{1,0} x") == \
        "psq_matmul_kernel.53"
