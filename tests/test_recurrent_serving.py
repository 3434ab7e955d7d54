"""Recurrent-state families (SSM / xLSTM / hybrid) on the serve engine.

PR 2 left ssm/xlstm/hybrid on the static fallback because recurrent
prefill folded right-pad tokens into the state. Masked-length prefill
(``models/decode.prefill`` + per-layer ``lengths`` masking) makes padded
positions exact state no-ops, so these families now run the continuous
slot pool — this module pins bit-exact greedy parity across sequential /
static / continuous / sharded execution, slot-reuse state isolation, jit
stability, and the hoisted decode constants.

Three recurrent architectures cover the three state flavors:

* ``xlstm-350m`` — family "ssm": mLSTM matrix memory + sLSTM scalars,
* ``zamba2-7b`` — family "hybrid": Mamba2 SSD states + shared attention,
* a pure-Mamba variant (zamba2 layout with no attention slots) — SSD
  states only, no KV at all.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import init_model
from repro.serve import EngineConfig, ServeEngine

jax.config.update("jax_platform_name", "cpu")

ARCHS = ("xlstm-350m", "zamba2-7b", "mamba-pure")


def _arch_cfg(name):
    if name == "mamba-pure":
        # hybrid layout with attn_every > n_layers: every layer lands in
        # the Mamba2 tail — a pure-SSM decoder with no attention block
        return dataclasses.replace(
            get_config("zamba2-7b").reduced(), n_layers=3, attn_every=4
        )
    return get_config(name).reduced()


@pytest.fixture(scope="module")
def models():
    return {
        a: (lambda c: (c, init_model(jax.random.PRNGKey(0), c)))(_arch_cfg(a))
        for a in ARCHS
    }


def _run(params, cfg, prompts, mode="auto", max_batch=4, max_new=6,
         mesh=None, max_len=64):
    eng = ServeEngine(
        params, cfg,
        EngineConfig(max_batch=max_batch, max_len=max_len, mode=mode),
        mesh=mesh,
    )
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    return {r.uid: r.output for r in eng.run()}, eng


def _prompts(cfg, sizes=(3, 9, 5, 14), seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, size=n) for n in sizes]


class TestContinuousParity:
    """Greedy decode is bit-exact across schedulers for every recurrent
    state flavor — the masked-length prefill contract, end to end."""

    @pytest.mark.parametrize("arch", ARCHS)
    def test_auto_resolves_continuous(self, models, arch):
        cfg, params = models[arch]
        _, eng = _run(params, cfg, _prompts(cfg, sizes=(4,)), max_new=2)
        assert eng.mode == "continuous"

    @pytest.mark.parametrize("arch", ARCHS)
    def test_continuous_vs_sequential(self, models, arch):
        """Mixed-length slot pool == one-at-a-time decoding, token for
        token (more requests than slots: retirement + re-admission)."""
        cfg, params = models[arch]
        prompts = _prompts(cfg, sizes=(3, 9, 5, 14, 7))
        batched, _ = _run(params, cfg, prompts, "continuous", max_batch=2)
        for uid, p in zip(sorted(batched), prompts):
            seq, _ = _run(params, cfg, [p], "static", max_batch=1)
            assert batched[uid] == seq[1], \
                f"{arch} request {uid} diverged from sequential decode"

    @pytest.mark.parametrize("arch", ARCHS)
    def test_static_vs_continuous(self, models, arch):
        """The static fallback right-pads with per-row lengths, so the
        two schedulers agree bit for bit on a mixed-length batch."""
        cfg, params = models[arch]
        prompts = _prompts(cfg)
        cont, _ = _run(params, cfg, prompts, "continuous")
        stat, _ = _run(params, cfg, prompts, "static")
        assert cont == stat, f"{arch}: static diverged from continuous"

    @pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices")
    @pytest.mark.parametrize("arch", ARCHS)
    def test_2way_data_mesh_parity(self, models, arch):
        """Recurrent state pools shard over the data axis
        (``recurrent_state`` rule) without changing a single token."""
        cfg, params = models[arch]
        prompts = _prompts(cfg)
        base, _ = _run(params, cfg, prompts, "continuous")
        mesh = make_mesh((2, 1), ("data", "model"))
        out, eng = _run(params, cfg, prompts, "continuous", mesh=mesh)
        assert out == base, f"{arch}: 2-way data mesh diverged"
        assert eng.stats()["mesh"] == "data=2xmodel=1"


class TestSlotReuse:
    @pytest.mark.parametrize("arch", ARCHS)
    def test_retire_then_readmit_state_isolation(self, models, arch):
        """A retired slot's stale recurrent state must not bleed into
        the request re-admitted into it: with ONE slot, every request
        decodes in the previous retiree's slot."""
        cfg, params = models[arch]
        prompts = _prompts(cfg, sizes=(6, 11, 4), seed=3)
        pooled, eng = _run(params, cfg, prompts, "continuous", max_batch=1,
                           max_new=5)
        # every admission really went through the same slot
        assert {r.slot for r in eng.finished} == {0}
        assert eng.admitted == len(prompts)
        for uid, p in zip(sorted(pooled), prompts):
            seq, _ = _run(params, cfg, [p], "static", max_batch=1, max_new=5)
            assert pooled[uid] == seq[1], \
                f"{arch}: state bled through slot reuse (request {uid})"


class TestJitStability:
    @pytest.mark.parametrize("arch", ("xlstm-350m", "zamba2-7b"))
    def test_no_recompile_after_warmup(self, models, arch, compile_counts):
        cfg, params = models[arch]
        eng = ServeEngine(params, cfg, EngineConfig(max_batch=4, max_len=64))
        fns = [eng._decode_multi, eng._prefill_bucket, eng._insert]
        rng = np.random.RandomState(1)
        trace = [(rng.randint(0, cfg.vocab_size, size=int(rng.randint(2, 17))),
                  int(rng.randint(2, 9))) for _ in range(8)]
        for p, mn in trace:
            eng.submit(p, max_new_tokens=mn)
        eng.run()
        warm = compile_counts(*fns)
        assert warm[0] == 1, "recurrent decode loop must compile exactly once"
        for p, mn in trace:
            eng.submit(p, max_new_tokens=mn)
        eng.run()
        assert compile_counts(*fns) == warm, \
            "re-running an already-seen workload must not recompile"

    def test_static_prefill_buckets_batch_and_length(self, models,
                                                     compile_counts):
        """The static path pow2-buckets the admitted batch dim (and, for
        recurrent right-pad, the prompt length), so uneven final batches
        reuse the full-batch compile instead of recompiling per size."""
        cfg, params = models["xlstm-350m"]
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=4, max_len=64,
                                       mode="static"))
        rng = np.random.RandomState(0)
        # 7 requests, prompt lengths all inside the 8-bucket: batches of
        # 4 and 3 — the 3-batch pads to 4 and hits the same compile
        for _ in range(7):
            eng.submit(rng.randint(0, cfg.vocab_size, size=6),
                       max_new_tokens=3)
        eng.run()
        assert compile_counts(eng._prefill_full) == [1], \
            "static prefill must compile once per (batch, length) bucket"


class TestDecodeConstantHoisting:
    """Satellite: decode_mamba2 stops re-deriving A = -exp(A_log) every
    token — the engine folds it into the served params at load."""

    def test_engine_hoists_mamba_constants(self, models):
        cfg, params = models["zamba2-7b"]
        eng = ServeEngine(params, cfg, EngineConfig(max_batch=2, max_len=32))
        assert "A" in eng.params["mamba_groups"]["mamba"]
        np.testing.assert_array_equal(
            np.asarray(eng.params["mamba_groups"]["mamba"]["A"]),
            np.asarray(-jnp.exp(params["mamba_groups"]["mamba"]["A_log"])),
        )

    def test_hoisted_decode_step_drops_weight_exp_ops(self, models):
        """The compiled decode step contains strictly fewer exponential
        ops with hoisted params — and produces identical logits."""
        from repro.models import decode as D

        cfg, params = models["mamba-pure"]
        hoisted = D.hoist_decode_params(params, cfg)
        tok = jnp.zeros((2, 1), jnp.int32)

        def compiled(p):
            cache = D.cache_init(p, cfg, 2, 32, dtype=jnp.float32)
            fn = jax.jit(lambda pp, t, c: D.decode_step(pp, cfg, t, c))
            return fn.lower(p, tok, cache).compile(), cache

        raw_exe, raw_cache = compiled(params)
        hst_exe, hst_cache = compiled(hoisted)
        n_raw = raw_exe.as_text().count("exponential")
        n_hst = hst_exe.as_text().count("exponential")
        assert n_hst < n_raw, \
            f"hoisting must remove exp(A_log) from the step ({n_hst} vs {n_raw})"
        lg_raw, _ = raw_exe(params, tok, raw_cache)
        lg_hst, _ = hst_exe(hoisted, tok, hst_cache)
        np.testing.assert_array_equal(np.asarray(lg_raw), np.asarray(lg_hst))
