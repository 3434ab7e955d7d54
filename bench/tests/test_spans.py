"""The program's spans and scopes: a hand-made trace, a small recorded
chip trace, and the scopes in a compiled decode program."""
import json
import pathlib

import numpy as np
import pytest

from bench import spans as SP
from bench import trace as TR

DATA = pathlib.Path(__file__).resolve().parent / "data" / "trace_spans.json"


def _hand_made():
    events = {
        "window": [0.0, 10.0],
        "devices": [{
            "modules": [["jit__prefill_bucket(1)", 1.0, 3.0],
                        ["jit__decode_multi(2)", 4.0, 6.0],
                        ["jit__insert(3)", 6.0, 6.5]],
            "ops": [["%fusion.1 = f32[256,4096]", 1.0, 1.5],
                    ["%fusion.2 = f32[256,4096]", 1.5, 3.0],
                    ["%while.5 = (f32[4])", 4.0, 6.0],
                    ["%fusion.3 = f32[32]", 4.0, 5.0],
                    ["%copy.4 = f32[8]", 5.0, 6.0],
                    ["%copy.4 = f32[8]", 6.0, 6.5],
                    ["%fusion.3 = f32[32]", 9.5, 11.0]],
        }],
        "host": [["step", 0.5, 7.0], ["wait_arrival", 7.0, 9.5]],
    }
    spans = [["serve.step", 0.6, 6.9], ["serve.admit", 0.7, 3.5],
             ["serve.prefill.wait", 1.2, 3.1],
             ["serve.decode.dispatch", 3.5, 3.9],
             ["serve.decode.wait", 3.9, 6.1],
             ["serve.decode.drain", 6.1, 6.8],
             ["serve.step", 10.5, 11.0]]
    names = {
        "jit__decode_multi": {
            "fusion.3": "jit(_decode_multi)/while/body/ffn/mul",
            "copy.4": ""},
        "jit__prefill_bucket": {
            "fusion.1": "jit(_prefill_bucket)/qkv/dot_general",
            "fusion.2": "jit(_prefill_bucket)/attention/attention/exp"},
    }
    return events, spans, names


def test_hand_made_trace():
    events, spans, names = _hand_made()
    r = SP.reduce(events, spans, names)
    # one step in the window, 6.3 s, of which 1.9 + 2.2 s blocked
    assert r["host_ms_per_step"] == pytest.approx(1e3 * (6.3 - 4.1))
    # idle gaps 0..1, 3..4, 6.5..9.5; the program's spans cover
    # 0.6..1, 3..4 and 6.5..6.9 of them
    assert r["idle_engine_s"] == pytest.approx(0.4 + 1.0 + 0.4)
    # longest first; the two of 1 s in time order
    assert r["idle_gaps"] == [["wait_arrival", pytest.approx(3.0)],
                              ["step", pytest.approx(1.0)],
                              ["serve.decode.dispatch", pytest.approx(1.0)]]
    s = r["scope_s"]
    assert s["prefill"] == pytest.approx({"qkv": 0.5, "attention": 1.5})
    # the while loop is a container; copy.4 has no op_name
    assert s["decode"] == pytest.approx({"ffn": 1.0, "unscoped": 1.0})
    # no text for jit__insert; the op past the last module is "other"
    assert s["other"] == pytest.approx({"unmapped": 1.0})
    # what trace.reduce reads is untouched by the program's spans
    assert TR.reduce(events)["idle_gaps"][0] == ["wait_arrival",
                                                 pytest.approx(3.0)]


def test_innermost_label_prefers_program_spans():
    spans = [("serve.step", 0.0, 10.0), ("serve.decode.wait", 2.0, 4.0)]
    host = [("step", 0.0, 10.0)]
    gaps = [(2.5, 3.5), (5.0, 6.0), (11.0, 12.0)]
    assert SP.label_gaps(gaps, spans, host) == [
        ["serve.decode.wait", 1.0], ["serve.step", 1.0], ["untracked", 1.0]]


def test_no_program_spans_keeps_harness_labels():
    """On a trace without the program's spans (an older program), the
    labels are the harness's, as ``trace.reduce`` gives them."""
    events, _, _ = _hand_made()
    r = SP.reduce(events, [])
    assert r["host_ms_per_step"] is None
    assert r["idle_engine_s"] == 0.0
    assert r["idle_gaps"] == TR.reduce(events)["idle_gaps"]
    assert set(r["scope_s"]["decode"]) == {"unmapped"}


@pytest.mark.parametrize("op_name,scope", [
    ("jit(_decode_multi)/while/body/kv_commit/vmap(vmap())/transpose",
     "kv_commit"),
    ("jit(_decode_multi)/while/body/ffn/ffn/mul", "ffn"),
    ("jit(_prefill_bucket)/qkv/dot_general", "qkv"),
    ("jit(_decode_multi)/while/body/sample/argmax", "sample"),
    ("jit(_decode_multi)/while/body/while/body/dynamic_slice", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of(op_name, scope):
    assert SP.scope_of(op_name) == scope


def test_op_names_from_compiled_text():
    text = """ENTRY %main.1 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %copy.51 = f32[4]{0} copy(%x.1)
  ROOT %fusion.7 = f32[4]{0} fusion(%copy.51), kind=kLoop, calls=%f, metadata={op_name="jit(f)/ffn/mul" stack_frame_id=2}
}"""
    assert SP.op_names(text) == {"x.1": "x", "copy.51": "",
                                 "fusion.7": "jit(f)/ffn/mul"}


def test_recorded_chip_trace():
    """One admission and three decode steps of mistral-7b widths at 4
    layers, 32 slots, on a v5e: the program's spans share the device's
    clock, and the decode program's scopes carry device time."""
    rec = json.loads(DATA.read_text())
    spans = [tuple(s) for s in rec["spans"]]
    assert {s[0] for s in spans} == set(SP.SPANS)
    decode = [m for m in rec["devices"][0]["modules"]
              if m[0].startswith(TR.DECODE_MODULE)]
    waits = [s for s in spans if s[0] == "serve.decode.wait"]
    assert len(waits) == len(decode) == 3
    for _, a, b in waits:
        assert any(m[1] < b and a < m[2] for m in decode)
    r = SP.reduce(rec, spans, rec["op_names"])
    dec = r["scope_s"]["decode"]
    assert dec["kv_commit"] > 0 and dec["attention"] > 0
    assert set(dec) <= set(SP.SCOPES) | {SP.UNSCOPED}
    assert dec["ffn"] == max(dec.values())
    assert 0 < r["host_ms_per_step"]
    busy = TR.reduce(rec)["busy_s"]
    w0, w1 = rec["window"]
    assert 0 < r["idle_engine_s"] <= (w1 - w0) - busy + 1e-9
    assert {g[0] for g in r["idle_gaps"]} <= set(SP.SPANS) | {"step"}


@pytest.fixture(scope="module")
def tiny_engine():
    """The m7b-chat engine at CPU size, every shape served once."""
    from bench import drive
    from bench.tests.tiny import tiny_cell

    cell = tiny_cell()
    cfg, model = cell["config"], cell["model"]
    arch, params = model.program(cfg, model.draw(cfg, 7), "reference")
    eng = drive.make_engine(arch, params, cfg, cell["traffic"])
    eng.submit(np.arange(1, 10), max_new_tokens=3)
    eng.run()
    return eng


def test_load_reads_the_program_spans(tiny_engine, tmp_path):
    """``load`` finds the engine's spans in a profile (here on the CPU),
    in time order."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        tiny_engine.submit(np.arange(1, 10), max_new_tokens=3)
        tiny_engine.run()
    finally:
        jax.profiler.stop_trace()
    spans = SP.load(str(tmp_path))
    assert {s[0] for s in spans} == set(SP.SPANS)
    assert [s[1] for s in spans] == sorted(s[1] for s in spans)
    assert SP.host_ms_per_step(spans, spans[0][1], spans[-1][2]) > 0


def test_compiled_decode_program_carries_the_scopes(tiny_engine):
    """The decode program as the engine serves it names its parts in
    the op metadata ``decode_text`` reads."""
    names = SP.op_names(SP.decode_text(tiny_engine))
    scopes = {SP.scope_of(v) for v in names.values()}
    assert set(SP.SCOPES) - {"sample"} <= scopes
    assert SP.UNSCOPED in scopes
