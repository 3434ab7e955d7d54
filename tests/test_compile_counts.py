"""One block compile per (family, phase) — the scan-over-layers pin.

Every forward stacks its layer params and runs them under ``lax.scan``
(models/transformer.layer_scan), so jit traces each transformer block
ONCE per engine phase regardless of depth. This suite pins the
consequence at the serving boundary: a single-bucket trace leaves every
phase closure (prefill / insert / decode) at jit cache size exactly 1
for each family, and the unrolled ``scan_layers=False`` oracle obeys
the same contract (it re-traces the block per layer inside ONE compile,
it does not compile per layer).

Two extensions of the same contract:

- side-input families (encdec cross-KV pools, VLM patch embeds) admit
  through the SAME bucketed prefill closure — the per-slot side-input
  scatter must not add a compile per admission wave;
- speculative decoding adds exactly TWO compiles on top of admission
  (the draft's scanned propose step and the masked verify forward),
  and a second admission wave replays both from cache.

The shared ``compile_counts`` fixture (tests/conftest.py) reads the
``_cache_size`` introspection hook; see docs/testing.md for the test
taxonomy this belongs to.
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_model
from repro.serve import EngineConfig, ServeEngine

import jax

# one arch per layer-stacked family without side inputs; encdec/vlm
# need per-request side-input rows, so they get their own suite below
ARCHS = ("tinyllama-1.1b", "granite-moe-3b-a800m", "zamba2-7b",
         "xlstm-350m")
SIDE_ARCHS = ("whisper-large-v3", "llava-next-mistral-7b")


@pytest.fixture(scope="module")
def models():
    out = {}
    for arch in ARCHS + SIDE_ARCHS:
        cfg = get_config(arch).reduced()
        out[arch] = (cfg, init_model(jax.random.PRNGKey(0), cfg))
    return out


def _side_inputs(cfg, n=4, seed=7):
    rng = np.random.RandomState(seed)
    if cfg.family == "encdec":
        return {"enc_embeds": (rng.randn(n, 8, cfg.d_model)
                               * 0.1).astype(np.float32)}
    return {"patch_embeds": (rng.randn(n, cfg.frontend_len, cfg.d_model)
                             * 0.1).astype(np.float32)}


def _single_bucket_trace(cfg, n=4, seed=0):
    # one admission wave of one shape: n == slot-pool size, prompt
    # lengths 4..8 all land in the smallest (8-token) prefill bucket,
    # and equal decode budgets retire every slot together — so
    # prefill/insert/decode each see exactly one (bucket, batch) shape
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, cfg.vocab_size, size=int(rng.randint(4, 9))), 4)
            for _ in range(n)]


def _serve(eng, trace):
    for p, mn in trace:
        eng.submit(p, max_new_tokens=mn)
    return {r.uid: r.output for r in eng.run()}


class TestOneCompilePerFamilyPhase:
    @pytest.mark.parametrize("arch", ARCHS)
    def test_scan_path_one_compile_per_phase(self, models, arch,
                                             compile_counts):
        cfg, params = models[arch]
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=4, max_len=48))
        _serve(eng, _single_bucket_trace(cfg))
        fns = [eng._prefill_bucket, eng._insert, eng._decode_multi]
        assert compile_counts(*fns) == [1, 1, 1], \
            f"{arch}: each engine phase must compile exactly one block"

    @pytest.mark.parametrize("arch", ("tinyllama-1.1b",
                                      "granite-moe-3b-a800m"))
    def test_unrolled_oracle_same_phase_counts(self, models, arch,
                                               compile_counts):
        """scan_layers=False swaps lax.scan for a Python loop over the
        same stacked params: slower to trace, but still ONE jit compile
        per phase — and token-identical to the scan engine (the full
        six-family parity matrix lives in tests/test_golden_parity.py).
        """
        cfg, params = models[arch]
        trace = _single_bucket_trace(cfg, seed=1)
        scan = _serve(ServeEngine(params, cfg,
                                  EngineConfig(max_batch=4, max_len=48)),
                      trace)
        loop_cfg = dataclasses.replace(cfg, scan_layers=False)
        eng = ServeEngine(params, loop_cfg,
                          EngineConfig(max_batch=4, max_len=48))
        assert _serve(eng, trace) == scan, \
            f"{arch}: unrolled oracle diverged from the scan path"
        fns = [eng._prefill_bucket, eng._insert, eng._decode_multi]
        assert compile_counts(*fns) == [1, 1, 1]

    @pytest.mark.parametrize("arch", SIDE_ARCHS)
    def test_side_input_admission_one_compile_per_phase(self, models,
                                                        arch,
                                                        compile_counts):
        """encdec/VLM continuous admission gathers per-request side
        inputs into the bucketed prefill batch and scatters them into
        per-slot pools on insert — still exactly one compile per phase
        for a single-bucket trace."""
        cfg, params = models[arch]
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=4, max_len=48),
                          extra_inputs=_side_inputs(cfg))
        assert eng.mode == "continuous"
        _serve(eng, _single_bucket_trace(cfg))
        fns = [eng._prefill_bucket, eng._insert, eng._decode_multi]
        assert compile_counts(*fns) == [1, 1, 1], \
            f"{arch}: side-input admission must not add compiles"

    def test_spec_decode_two_extra_compiles(self, models, compile_counts):
        """Speculative decoding compiles exactly two closures beyond
        admission — the draft's k-step scanned propose and the masked
        width-(k+1) verify forward — and a SECOND admission wave of the
        same shapes adds zero compilations anywhere (warm == rerun).
        The per-token decode-step closure stays cold: spec rounds
        replace it entirely."""
        cfg, params = models["tinyllama-1.1b"]
        dcfg = dataclasses.replace(cfg, n_layers=1)
        eng = ServeEngine(
            params, cfg,
            EngineConfig(max_batch=4, max_len=48, spec_k=2,
                         draft_config=dcfg),
            draft_params=init_model(jax.random.PRNGKey(1), dcfg))
        trace = _single_bucket_trace(cfg)
        _serve(eng, trace)
        spec_fns = [eng._draft_propose, eng._verify]
        assert compile_counts(*spec_fns) == [1, 1], \
            "spec decode must cost exactly two extra compiles"
        fns = spec_fns + [eng._prefill_bucket, eng._insert,
                          eng._draft_prefill, eng._draft_insert]
        warm = compile_counts(*fns)
        assert warm == [1, 1, 1, 1, 1, 1]
        assert compile_counts(eng._decode_multi) == [0], \
            "spec rounds must not fall back to the per-token step"
        _serve(eng, trace)                     # readmission wave
        assert compile_counts(*fns) == warm, \
            "a second admission wave re-traced a spec-engine phase"
