"""Continuous-batching engine: slot lifecycle, parity, telemetry, jit."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import init_model
from repro.serve import EngineConfig, Request, ServeEngine, throughput_stats

jax.config.update("jax_platform_name", "cpu")


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama-1.1b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _greedy_outputs(cfg, params, prompt, max_new, **ecfg_kw):
    eng = ServeEngine(params, cfg,
                      EngineConfig(max_batch=1, max_len=64, **ecfg_kw))
    eng.submit(prompt, max_new_tokens=max_new)
    return eng.run()[0].output


class TestContinuousScheduling:
    def test_eos_retirement_frees_slot_for_queued_request(self, tiny):
        """A sequence hitting EOS retires at that decode step, and the
        freed slot is filled by a queued request while the other slot's
        sequence is still mid-flight."""
        cfg, params = tiny
        rng = np.random.RandomState(3)
        # find a prompt whose 2nd greedy token differs from its 1st, so
        # EOS fires at a decode step (not at prefill)
        for _ in range(10):
            prompt_a = rng.randint(0, cfg.vocab_size, size=6)
            probe = _greedy_outputs(cfg, params, prompt_a, 3)
            if probe[1] != probe[0]:
                break
        else:
            pytest.skip("no prompt with distinct first tokens found")
        eos = probe[1]

        eng = ServeEngine(params, cfg, EngineConfig(max_batch=2, max_len=64))
        assert eng.mode == "continuous"
        # all three share the length bucket, so admission is strictly
        # FIFO: a+b fill both slots, c queues until a slot frees
        uid_a = eng.submit(prompt_a, max_new_tokens=12, eos_id=eos)
        uid_b = eng.submit(rng.randint(0, cfg.vocab_size, size=7),
                           max_new_tokens=12)
        uid_c = eng.submit(rng.randint(0, cfg.vocab_size, size=5),
                           max_new_tokens=4)
        done = {r.uid: r for r in eng.run()}
        assert set(done) == {uid_a, uid_b, uid_c}

        # a retired via EOS, early
        assert done[uid_a].output[-1] == eos
        assert len(done[uid_a].output) == 2 < 12
        # b ran to its full budget, c to its own
        assert len(done[uid_b].output) == 12
        assert len(done[uid_c].output) == 4
        # c was admitted mid-flight into a freed slot (both slots were
        # taken at step 0), before b finished
        assert done[uid_c].admit_step > 0
        assert done[uid_c].slot == done[uid_a].slot
        assert done[uid_c].t_first_token < done[uid_b].t_done

    def test_batched_vs_sequential_greedy_parity(self, tiny):
        """Per-slot lengths + right-padded bucketed prefill make the slot
        pool exact: batched greedy outputs match one-at-a-time decoding
        token for token."""
        cfg, params = tiny
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, cfg.vocab_size, size=n)
                   for n in (3, 9, 5, 14)]

        eng = ServeEngine(params, cfg, EngineConfig(max_batch=4, max_len=64))
        for p in prompts:
            eng.submit(p, max_new_tokens=8)
        batched = {r.uid: r.output for r in eng.run()}

        for uid, p in zip(sorted(batched), prompts):
            assert batched[uid] == _greedy_outputs(cfg, params, p, 8), \
                f"request {uid} diverged from sequential decode"

    def test_no_recompile_after_warmup(self, tiny, compile_counts):
        """Fixed shapes: decode compiles once; prefill/insert compile per
        (bucket length, bucket batch) pair; a repeat of the same workload
        adds zero compilations."""
        cfg, params = tiny
        eng = ServeEngine(params, cfg, EngineConfig(max_batch=4, max_len=64))
        fns = [eng._decode_multi, eng._prefill_bucket, eng._insert]

        rng = np.random.RandomState(1)
        trace = [(rng.randint(0, cfg.vocab_size, size=int(rng.randint(2, 17))),
                  int(rng.randint(2, 9))) for _ in range(8)]
        for p, mn in trace:
            eng.submit(p, max_new_tokens=mn)
        eng.run()
        warm = compile_counts(*fns)
        assert warm[0] == 1, "decode loop must compile exactly once"

        for p, mn in trace:
            eng.submit(p, max_new_tokens=mn)
        eng.run()
        assert compile_counts(*fns) == warm, \
            "re-running an already-seen workload must not recompile"

    def test_occupancy_and_scheduler_stats(self, tiny):
        cfg, params = tiny
        eng = ServeEngine(params, cfg, EngineConfig(max_batch=2, max_len=48))
        rng = np.random.RandomState(2)
        for _ in range(6):
            eng.submit(rng.randint(0, cfg.vocab_size, size=4),
                       max_new_tokens=5)
        eng.run()
        s = eng.stats()
        assert s["mode"] == "continuous"
        assert s["admissions"] == 6
        assert s["decode_steps"] > 0 and s["prefill_calls"] > 0
        # equal-length equal-budget requests on a saturated queue keep
        # the pool essentially full
        assert s["mean_slot_occupancy"] > 0.8

    def test_static_right_pad_gives_short_prompt_full_budget(self, tiny):
        """The static path right-pads with per-row lengths, so each row's
        KV writes are bounded by its OWN prompt + budget (the historical
        left-pad layout shifted every row to the longest prompt and had
        to truncate the short one's decode budget)."""
        cfg, params = tiny
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=2, max_len=16,
                                       mode="static"))
        rng = np.random.RandomState(0)
        long_p = rng.randint(0, cfg.vocab_size, size=12)
        short_p = rng.randint(0, cfg.vocab_size, size=2)
        uid_a = eng.submit(long_p, max_new_tokens=2)
        uid_b = eng.submit(short_p, max_new_tokens=12)  # 2 + 12 <= 16
        done = {r.uid: r for r in eng.run()}
        assert len(done[uid_a].output) == 2
        assert len(done[uid_b].output) == 12
        assert all(r.done for r in done.values())
        # and the mixed-length batch is exact, not just full-length
        assert done[uid_a].output == _greedy_outputs(cfg, params, long_p, 2)
        assert done[uid_b].output == _greedy_outputs(cfg, params, short_p, 12)

    def test_submit_rejects_overlong_request(self, tiny):
        cfg, params = tiny
        eng = ServeEngine(params, cfg, EngineConfig(max_batch=1, max_len=16))
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(np.arange(10), max_new_tokens=10)

    def test_engine_config_eos_id_is_live(self, tiny):
        """Regression: EngineConfig.eos_id used to be dead config —
        submit() hardcoded its own -1 default and never consulted it.
        The config value must now apply to submits without an explicit
        eos_id, and an explicit per-request value must win over it."""
        cfg, params = tiny
        rng = np.random.RandomState(5)
        prompt = rng.randint(0, cfg.vocab_size, size=6)
        ref = _greedy_outputs(cfg, params, prompt, 12)
        eos, cut = None, None
        for k in range(1, len(ref)):
            if ref[k] not in ref[:k]:
                eos, cut = ref[k], k
                break
        if eos is None:
            pytest.skip("degenerate greedy output: no usable EOS token")
        # config default reaches the request: output truncates at EOS
        assert _greedy_outputs(cfg, params, prompt, 12,
                               eos_id=eos) == ref[:cut + 1]
        # explicit per-request eos_id overrides the config
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=1, max_len=64, eos_id=eos))
        eng.submit(prompt, max_new_tokens=12, eos_id=-1)
        assert eng.run()[0].output == ref


class TestStaticScheduling:
    def test_static_prefill_buckets_the_batch_dim(self, tiny, compile_counts):
        """_prefill_full pow2-buckets the admitted batch size: a trailing
        batch of 3 pads to the 4-bucket and reuses the full-batch
        compile, and a repeat workload adds zero compilations."""
        cfg, params = tiny
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=4, max_len=64,
                                       mode="static"))
        rng = np.random.RandomState(0)
        for _ in range(7):                      # batches of 4 then 3
            eng.submit(rng.randint(0, cfg.vocab_size, size=6),
                       max_new_tokens=3)
        eng.run()
        assert compile_counts(eng._prefill_full) == [1], \
            "batches of 4 and 3 must share one (batch-bucket, len) compile"
        for _ in range(7):
            eng.submit(rng.randint(0, cfg.vocab_size, size=6),
                       max_new_tokens=3)
        eng.run()
        assert compile_counts(eng._prefill_full) == [1]

    def test_encdec_batches_get_their_own_side_inputs(self):
        """Side inputs are positional by submission order: request i must
        be prefilled against its OWN enc_embeds row, not batch-local row
        0 (the old head-slice handed every batch the first rows)."""
        from repro.models import init_model as _init

        cfg = get_config("whisper-large-v3").reduced()
        params = _init(jax.random.PRNGKey(0), cfg)
        rng = np.random.RandomState(0)
        prompt = rng.randint(0, cfg.vocab_size, size=5)
        enc = (rng.randn(2, 6, cfg.d_model) * 0.1).astype(np.float32)
        eng = ServeEngine(params, cfg, EngineConfig(max_batch=1, max_len=32),
                          extra_inputs={"enc_embeds": enc})
        eng.submit(prompt, max_new_tokens=4)
        eng.submit(prompt, max_new_tokens=4)    # second single-req batch
        out = {r.uid: r.output for r in eng.run()}

        ref_eng = ServeEngine(params, cfg,
                              EngineConfig(max_batch=1, max_len=32),
                              extra_inputs={"enc_embeds": enc[1:]})
        ref_eng.submit(prompt, max_new_tokens=4)
        ref = ref_eng.run()[0].output
        assert out[2] == ref, \
            "request 2 must decode against enc_embeds row 1, not row 0"


class TestShardedServing:
    """Mesh-sharded engine == single-device engine, token for token."""

    @staticmethod
    def _run(params, cfg, prompts, mesh=None, max_new=6):
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=4, max_len=64), mesh=mesh)
        for p in prompts:
            eng.submit(p, max_new_tokens=max_new)
        return {r.uid: r.output for r in eng.run()}, eng

    @pytest.fixture(scope="class")
    def prompts(self, tiny):
        cfg, _ = tiny
        rng = np.random.RandomState(7)
        return [rng.randint(0, cfg.vocab_size, size=n) for n in (3, 9, 5, 14)]

    @pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices")
    @pytest.mark.parametrize("shape", [(2, 1), (1, 2)])
    def test_fp_decode_parity_2way(self, tiny, prompts, shape):
        cfg, params = tiny
        base, _ = self._run(params, cfg, prompts)
        mesh = make_mesh(shape, ("data", "model"))
        out, eng = self._run(params, cfg, prompts, mesh=mesh)
        assert out == base, f"mesh {shape} diverged from single-device"
        assert eng.stats()["mesh"] == f"data={shape[0]}xmodel={shape[1]}"

    @pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >= 4 devices")
    def test_psq_packed_decode_parity_4way(self, tiny, prompts):
        """The full HCiM datapath — packed codes, int4 planes, DCiM scale
        factors column-sharded over `model`, slots over `data` — decodes
        bit-identically to the single-device engine."""
        import dataclasses

        from repro.core.config import PSQ_TERNARY
        from repro.serve import PackedModelCache, pack_tree_psq

        cfg, _ = tiny
        qcfg = dataclasses.replace(PSQ_TERNARY, kernel_backend="reference",
                                   xbar_rows=64)
        qc = cfg.with_quant(qcfg)
        params = init_model(jax.random.PRNGKey(0), qc)
        cache = PackedModelCache()
        packed = pack_tree_psq(params, qcfg, cache)
        base, _ = self._run(packed, qc, prompts, max_new=4)

        mesh = make_mesh((2, 2), ("data", "model"))
        packed_sh = pack_tree_psq(params, qcfg, cache, mesh=mesh)
        # sharded packing of identical weights is a pure cache hit
        assert cache.stats()["packs"] == cache.stats()["layers"]
        assert cache.stats()["hits"] == cache.stats()["layers"]
        out, _ = self._run(packed_sh, qc, prompts, mesh=mesh, max_new=4)
        assert out == base

    @pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices")
    def test_sharded_engine_stays_jit_stable(self, tiny, prompts,
                                             compile_counts):
        """The no-recompile contract survives sharding: decode compiles
        once, a repeated workload adds zero compilations."""
        cfg, params = tiny
        mesh = make_mesh((2, 1), ("data", "model"))
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=4, max_len=64), mesh=mesh)
        fns = [eng._decode_multi, eng._prefill_bucket, eng._insert]
        for p in prompts:
            eng.submit(p, max_new_tokens=5)
        eng.run()
        warm = compile_counts(*fns)
        # sharded decode may compile twice at warm-up: the first step
        # canonicalizes the eagerly-placed cache's shardings (XLA drops
        # size-1 mesh-axis entries), the second traces the steady state
        assert warm[0] <= 2
        for p in prompts:
            eng.submit(p, max_new_tokens=5)
        eng.run()
        assert compile_counts(*fns) == warm, \
            "re-running an already-seen workload must not recompile"


class TestModeResolution:
    """mode="auto" across every family × (paged, mesh, prefix_reuse),
    including the error paths (nothing may silently fall through)."""

    # family -> expected auto resolution (no side inputs submitted)
    AUTO = {
        "tinyllama-1.1b": "continuous",        # dense
        "granite-moe-3b-a800m": "continuous",  # moe
        "llava-next-mistral-7b": "continuous",  # vlm without patch embeds
        "xlstm-350m": "continuous",            # ssm: mLSTM/sLSTM state
        "zamba2-7b": "continuous",             # hybrid: Mamba2 + attn
        "whisper-large-v3": "continuous",      # encdec: per-slot cross-KV
    }

    @pytest.mark.parametrize("arch,expect", sorted(AUTO.items()))
    def test_auto_resolution_by_family(self, arch, expect):
        cfg = get_config(arch).reduced()
        eng = ServeEngine(None, cfg, EngineConfig())
        assert eng.mode == expect, arch

    @pytest.mark.parametrize("arch,expect", sorted(AUTO.items()))
    @pytest.mark.parametrize("prefix_reuse", [False, True])
    @pytest.mark.parametrize("with_mesh", [False, True])
    def test_auto_matrix_paged_mesh_reuse(self, arch, expect, prefix_reuse,
                                          with_mesh):
        """paged/mesh/prefix_reuse flags never change what auto resolves
        to; the invalid paged combinations raise their specific message
        instead of falling through to a broken engine."""
        mesh = None
        if with_mesh:
            if len(jax.devices()) < 2:
                pytest.skip("needs >= 2 devices")
            mesh = make_mesh((2, 1), ("data", "model"))
        cfg = get_config(arch).reduced()
        eng = ServeEngine(None, cfg,
                          EngineConfig(max_batch=2, max_len=32,
                                       prefix_reuse=prefix_reuse),
                          mesh=mesh)
        assert eng.mode == expect, arch

        paged_kw = dict(max_batch=2, max_len=32, paged=True, block_size=16,
                        prefix_reuse=prefix_reuse)
        if cfg.family in ("hybrid", "ssm", "encdec"):
            # recurrent state has nothing to page; encdec cross-KV has
            # no pages — both must say why (and name the contiguous
            # continuous scheduler as the way out)
            with pytest.raises(ValueError, match="paged KV cache"):
                ServeEngine(None, cfg, EngineConfig(**paged_kw), mesh=mesh)
        else:
            eng = ServeEngine(None, cfg, EngineConfig(**paged_kw), mesh=mesh)
            assert eng.mode == "continuous"

    def test_paged_on_recurrent_family_names_the_reason(self):
        cfg = get_config("xlstm-350m").reduced()
        with pytest.raises(ValueError, match="no sequence axis to page"):
            ServeEngine(None, cfg,
                        EngineConfig(paged=True, max_len=32, block_size=16))

    def test_paged_with_side_inputs_raises_scheduler_error(self):
        # vlm IS a paged family, but the radix prefix index keys on
        # token ids alone, so per-request patch embeds could alias a
        # reused prefix page — the engine must reject the combination,
        # not half-configure pages
        cfg = get_config("llava-next-mistral-7b").reduced()
        with pytest.raises(ValueError, match="continuous scheduler"):
            ServeEngine(None, cfg,
                        EngineConfig(paged=True, max_len=32, block_size=16),
                        extra_inputs={"patch_embeds": np.zeros((1, 2, 4))})

    def test_side_inputs_stay_continuous(self):
        # patch/enc side inputs ride per-slot pools now: they no longer
        # force (or even permit forcing back to) the static fallback
        cfg = get_config("llava-next-mistral-7b").reduced()
        eng = ServeEngine(None, cfg, EngineConfig(),
                          extra_inputs={"patch_embeds": np.zeros((1, 2, 4))})
        assert eng.mode == "continuous"

    def test_unknown_mode_raises(self):
        cfg = get_config("tinyllama-1.1b").reduced()
        with pytest.raises(ValueError, match="unknown engine mode"):
            ServeEngine(None, cfg, EngineConfig(mode="banana"))


class TestThroughputStats:
    def test_empty(self):
        assert throughput_stats([]) == {}

    def test_zero_output_request(self):
        r = Request(1, np.arange(3), t_enqueue=10.0)
        r.t_done = 11.0
        s = throughput_stats([r])
        assert s["total_tokens"] == 0
        assert s["tokens_per_s"] == 0.0
        assert s["started"] == 0
        assert s["mean_ttft_s"] == 0.0

    def test_tokens_without_finish_timestamps(self):
        # mid-flight inspection: tokens exist but nothing finished yet —
        # rate must be 0.0, not total_tokens / epsilon
        r = Request(1, np.arange(3), t_enqueue=10.0)
        r.output = [5, 6]
        r.t_first_token = 10.2
        s = throughput_stats([r])
        assert s["tokens_per_s"] == 0.0
        assert s["total_tokens"] == 2

    def test_never_started_request_mixed_with_finished(self):
        ok = Request(1, np.arange(3), t_enqueue=10.0)
        ok.output = [5, 6]
        ok.t_first_token, ok.t_done = 10.5, 11.0
        never = Request(2, np.arange(4), t_enqueue=10.0)   # no timestamps
        s = throughput_stats([ok, never])
        assert s["requests"] == 2 and s["started"] == 1
        assert s["total_tokens"] == 2
        assert s["mean_ttft_s"] == pytest.approx(0.5)
        assert np.isfinite(s["tokens_per_s"]) and s["tokens_per_s"] > 0


class TestEnergyTelemetry:
    """Modeled hwmodel energy attribution in stats() (docs/energy.md)."""

    ENERGY_KEYS = ("energy_pj_per_token", "energy_pj_total",
                   "energy_pj_per_request", "edap_total", "mean_occupancy")

    def test_counters_finite_and_monotone_across_runs(self, tiny):
        import math

        cfg, params = tiny
        eng = ServeEngine(params, cfg, EngineConfig(max_batch=2, max_len=64))
        s0 = eng.stats()
        assert s0["energy_tokens"] == 0
        assert s0["energy_pj_total"] == 0.0 and s0["edap_total"] == 0.0

        rng = np.random.RandomState(11)
        eng.submit(rng.randint(0, cfg.vocab_size, size=6), max_new_tokens=4)
        eng.run()
        s1 = eng.stats()
        assert s1["energy_tokens"] > 0
        for k in self.ENERGY_KEYS:
            assert math.isfinite(s1[k]), k
        assert s1["energy_pj_per_token"] > 0.0
        assert s1["energy_pj_total"] > 0.0
        assert s1["energy_pj_per_request"] > 0.0
        assert s1["edap_total"] > 0.0

        eng.submit(rng.randint(0, cfg.vocab_size, size=5), max_new_tokens=3)
        eng.run()
        s2 = eng.stats()
        assert s2["energy_tokens"] > s1["energy_tokens"]
        assert s2["energy_pj_total"] > s1["energy_pj_total"]
        # per-token cost is a property of the served model, not the trace
        assert s2["energy_pj_per_token"] == s1["energy_pj_per_token"]

    def test_reset_counters_zeroes_energy(self, tiny):
        cfg, params = tiny
        eng = ServeEngine(params, cfg, EngineConfig(max_batch=1, max_len=64))
        eng.submit(np.arange(4) % cfg.vocab_size, max_new_tokens=3)
        eng.run()
        before = eng.stats()
        assert before["energy_pj_total"] > 0.0
        eng.reset_counters()
        after = eng.stats()
        assert after["energy_tokens"] == 0
        assert after["energy_pj_total"] == 0.0
        assert after["energy_pj_per_request"] == 0.0
        assert after["edap_total"] == 0.0
        # the per-token model survives the reset (engine state, not trace)
        assert after["energy_pj_per_token"] == before["energy_pj_per_token"]

    def test_never_started_engine_reports_zeros(self, tiny):
        cfg, params = tiny
        eng = ServeEngine(params, cfg, EngineConfig(max_batch=1, max_len=64))
        s = eng.stats()
        assert s["energy_tokens"] == 0
        assert s["energy_pj_total"] == 0.0
        assert s["energy_pj_per_request"] == 0.0
        assert s["edap_total"] == 0.0

    def test_zero_output_run_keeps_per_request_finite(self, tiny):
        """run() with no submissions: no division by an empty finished
        list, all totals stay zero."""
        cfg, params = tiny
        eng = ServeEngine(params, cfg, EngineConfig(max_batch=1, max_len=64))
        assert eng.run() == []
        s = eng.stats()
        assert s["energy_pj_per_request"] == 0.0 and s["energy_tokens"] == 0

    def test_energy_style_is_live(self, tiny):
        cfg, params = tiny
        pj = {}
        for style in ("hcim", "adc"):
            eng = ServeEngine(params, cfg,
                              EngineConfig(max_batch=1, max_len=64,
                                           energy_style=style))
            assert eng.stats()["energy_style"] == style
            pj[style] = eng.stats()["energy_pj_per_token"]
        assert pj["adc"] > pj["hcim"]

    def test_unknown_energy_style_raises(self, tiny):
        cfg, params = tiny
        with pytest.raises(ValueError, match="energy_style"):
            ServeEngine(params, cfg,
                        EngineConfig(max_batch=1, max_len=64,
                                     energy_style="dram"))
