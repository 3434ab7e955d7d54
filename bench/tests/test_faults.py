"""A whole run at CPU size, without the chip: sound, it is correct; with
the timed path broken underneath, ``correct`` comes out false."""
import jax.numpy as jnp

from bench import compare, run
from bench.tests.tiny import tiny_cell
from repro.models import decode as D

LIMITS = compare.limits("m7b-chat")


def _run(seed):
    return run.run_cell(tiny_cell(), "tiny", seed, 1.5, False, LIMITS,
                        backend="reference")


def _wrap(monkeypatch, change):
    real = D.decode_multi_step

    def broken(params, cfg, cache, *args):
        out = real(params, cfg, cache, *args)
        return change(out, cache, cfg)

    monkeypatch.setattr(D, "decode_multi_step", broken)


def test_sound_run_is_correct():
    r = _run(2147483813)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"ttft_p90_s", "tpot_ms", "itl_p99_ms",
                                 "setup_s"}
    assert list(r)[-1] == "checks"


def test_token_altered_where_produced(monkeypatch):
    # every decoded token is replaced by its neighbour in the vocabulary
    _wrap(monkeypatch, lambda out, cache, cfg: (
        jnp.where(out[0] >= 0, (out[0] + 1) % cfg.vocab_size, out[0]),)
        + tuple(out[1:]))
    r = _run(2147483813)
    assert not r["correct"]
    assert r["checks"]["mean_logit_gap"]["value"] > LIMITS["mean_logit_gap"]


def test_step_returns_its_state_unchanged(monkeypatch):
    # the decode step hands back the cache it was given: no KV is written
    _wrap(monkeypatch, lambda out, cache, cfg: out[:4] + (cache, out[5]))
    r = _run(2147483813)
    assert not r["correct"]
    assert r["checks"]["mean_logit_gap"]["value"] > LIMITS["mean_logit_gap"]
