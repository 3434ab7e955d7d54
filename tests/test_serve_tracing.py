"""The serving engine's host spans and counters.

Each scheduling round is annotated with ``serve.*`` spans on the
profiler's clock (docs/serving.md). These tests profile a tiny engine
on the CPU, read the host plane back with ``ProfileData``, and check
that the spans nest as documented for every continuous executor. The
counters are checked against what a known submit sequence must give.
"""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import init_model
from repro.serve import EngineConfig, ServeEngine

jax.config.update("jax_platform_name", "cpu")

SPANS = ("serve.step", "serve.admit", "serve.prefill.wait",
         "serve.decode.dispatch", "serve.decode.wait", "serve.decode.drain")
DECODE = SPANS[3:]


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama-1.1b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    dcfg = dataclasses.replace(cfg, n_layers=1)
    dparams = init_model(jax.random.PRNGKey(1), dcfg)
    return cfg, params, dcfg, dparams


def _engine(tiny, kind: str, **kw) -> ServeEngine:
    cfg, params, dcfg, dparams = tiny
    base = dict(max_batch=2, max_len=64)
    if kind == "host_loop":
        base["device_loop"] = False
    elif kind == "spec":
        base.update(spec_k=2, draft_config=dcfg)
    elif kind == "paged":
        base.update(paged=True, block_size=8)
    base.update(kw)
    return ServeEngine(params, cfg, EngineConfig(**base),
                       draft_params=dparams if kind == "spec" else None)


def _prompts(cfg, sizes, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, size=n) for n in sizes]


def _serve(eng, prompts, max_new):
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    return eng.run()


def _host_spans(trace_dir):
    """``{name: [(start_ns, end_ns), ...]}`` of the ``serve.*`` spans on
    the host plane of the newest trace under ``trace_dir``."""
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {n: [] for n in SPANS}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in out:
                    out[e.name].append((e.start_ns,
                                        e.start_ns + e.duration_ns))
    return out


def _inside(inner, outers):
    return any(a <= inner[0] and inner[1] <= b for a, b in outers)


@pytest.mark.parametrize("kind", ["horizon", "host_loop", "spec", "paged"])
def test_spans_nest_as_documented(tiny, tmp_path, kind):
    """serve.admit and the three decode spans sit inside serve.step, in
    the order admit, dispatch, wait, drain; serve.prefill.wait sits
    inside serve.admit. Every executor traces the same names."""
    cfg = tiny[0]
    eng = _engine(tiny, kind)
    prompts = _prompts(cfg, (5, 6, 7))
    _serve(eng, prompts, 4)               # compile every shape first
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(eng, prompts, 4)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    assert all(spans[n] for n in SPANS), {n: len(v) for n, v in
                                          spans.items()}
    steps = spans["serve.step"]
    for a in spans["serve.admit"]:
        assert _inside(a, steps)
    for w in spans["serve.prefill.wait"]:
        assert _inside(w, spans["serve.admit"])
    for s0, s1 in steps:
        admit = [a for a in spans["serve.admit"] if s0 <= a[0] < s1]
        dec = [[d for d in spans[n] if s0 <= d[0] < s1] for n in DECODE]
        assert len(admit) <= 1
        # one decode round per step, nothing of it outside the step
        assert [len(d) for d in dec] == [1, 1, 1]
        chain = admit + [d[0] for d in dec]
        for (_, b), (a, _) in zip(chain, chain[1:]):
            assert b <= a
        assert chain[-1][1] <= s1
    # a decode round runs only inside a step
    assert sum(len(spans[n]) for n in DECODE) == 3 * len(steps)


def test_prefill_padded_tokens_count_rows_times_bucket(tiny):
    """Each prefill call adds its rows x bucket width: rows are the pow2
    of the requests it takes (capped by prefill_batch), the width the
    pow2 of the head's prompt length (at least min_bucket)."""
    cfg = tiny[0]
    eng = _engine(tiny, "horizon", max_batch=8, prefill_batch=4)
    _serve(eng, _prompts(cfg, (5,)), 2)           # 1 x 8
    _serve(eng, _prompts(cfg, (10, 12, 3)), 2)    # 2 x 16, then 1 x 8
    _serve(eng, _prompts(cfg, (20, 21, 30)), 2)   # 4 x 32
    s = eng.stats()
    assert s["prefill_calls"] == 4
    assert s["prefill_tokens"] == 5 + 10 + 12 + 3 + 20 + 21 + 30
    assert s["prefill_padded_tokens"] == 8 + 32 + 8 + 128
    eng.reset_stats()
    assert eng.stats()["prefill_padded_tokens"] == 0


def test_static_prefill_padded_tokens(tiny):
    cfg, params = tiny[:2]
    eng = ServeEngine(params, cfg, EngineConfig(max_batch=4, max_len=64,
                                                mode="static"))
    _serve(eng, _prompts(cfg, (9, 3, 4)), 3)      # 4 rows x 16
    assert eng.stats()["prefill_padded_tokens"] == 64


@pytest.mark.parametrize("kind", ["horizon", "host_loop", "spec"])
def test_queue_wait_and_admitted(tiny, kind):
    """admitted counts the requests bound to a slot (a request whose
    prefill token ends it never is); queue_wait_s sums their time from
    submit to leaving the queue."""
    cfg = tiny[0]
    eng = _engine(tiny, kind)
    prompts = _prompts(cfg, (4, 9, 6, 5, 7), seed=1)
    budgets = (3, 1, 5, 1, 2)
    for p, m in zip(prompts, budgets):
        eng.submit(p, max_new_tokens=m)
    done = eng.run()
    bound = [r for r in done if r.admit_step >= 0]
    assert len(bound) == sum(m > 1 for m in budgets)
    assert all(r.slot >= 0 for r in bound)
    assert all(r.slot == -1 for r in done if r.admit_step < 0)
    for r in done:
        assert r.t_enqueue <= r.t_admit <= r.t_first_token
    s = eng.stats()
    assert s["admitted"] == s["admissions"] == len(bound)
    assert s["queue_wait_s"] >= 0.0
    assert s["queue_wait_s"] == pytest.approx(
        sum(r.t_admit - r.t_enqueue for r in bound))
    assert s["device_wait_s"] > 0.0
    eng.reset_stats()
    s = eng.stats()
    assert (s["admitted"], s["queue_wait_s"], s["device_wait_s"]) == \
        (0, 0.0, 0.0)


@pytest.mark.parametrize("kind,horizon", [("horizon", 1), ("horizon", 4),
                                          ("host_loop", 1), ("spec", 1)])
def test_occupied_slot_steps(tiny, kind, horizon):
    """A bound slot is live for one decode step per token after its
    prefill token (one round per step for spec, which may emit more),
    and mean_slot_occupancy is the live share of slot-steps."""
    cfg = tiny[0]
    eng = _engine(tiny, kind, max_batch=3, decode_horizon=horizon)
    done = []
    for p, m in zip(_prompts(cfg, (4, 9, 6, 5), seed=2), (6, 3, 8, 4)):
        eng.submit(p, max_new_tokens=m)
    done = eng.run()
    s = eng.stats()
    if kind != "spec":
        assert s["occupied_slot_steps"] == sum(len(r.output) - 1
                                               for r in done)
    assert 0 < s["occupied_slot_steps"] <= 3 * s["decode_steps"]
    assert s["mean_slot_occupancy"] == pytest.approx(
        s["occupied_slot_steps"] / (3 * s["decode_steps"]))


def test_static_occupancy(tiny):
    """Static batches count each alive member per lockstep decode step."""
    cfg, params = tiny[:2]
    eng = ServeEngine(params, cfg, EngineConfig(max_batch=4, max_len=64,
                                                mode="static"))
    done = _serve(eng, _prompts(cfg, (5, 6), seed=3), 4)
    s = eng.stats()
    assert s["decode_steps"] == 3
    assert s["occupied_slot_steps"] == sum(len(r.output) - 1 for r in done)
    assert s["mean_slot_occupancy"] == pytest.approx(6 / (3 * 4))


def test_telemetry_is_scalar(tiny):
    """No engine counter grows with the steps or requests served."""
    cfg = tiny[0]
    eng = _engine(tiny, "horizon")
    _serve(eng, _prompts(cfg, (5, 6, 7)), 5)
    for k in ("decode_steps", "occupied_slot_steps", "admitted",
              "queue_wait_s", "device_wait_s", "prefill_padded_tokens"):
        assert np.isscalar(getattr(eng, k)), k
    assert not hasattr(eng, "step_occupancy")
    assert not hasattr(eng, "admissions")
