"""On-device multi-step decode loop (models.decode.decode_multi_step):
greedy bit-parity vs the host loop across horizons, families, paged and
sharded layouts; mid-horizon retirement; host-sync accounting; jit
stability (one compile per horizon value)."""
import math

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import init_model
from repro.serve import EngineConfig, ServeEngine, throughput_stats

jax.config.update("jax_platform_name", "cpu")

# one KV-cache family + both recurrent-state families: the loop's
# retirement mask must freeze KV writes AND recurrent state
ARCHS = ("tinyllama-1.1b", "xlstm-350m", "zamba2-7b")
HORIZONS = (1, 4, 32)


@pytest.fixture(scope="module")
def models():
    out = {}
    for a in ARCHS:
        cfg = get_config(a).reduced()
        out[a] = (cfg, init_model(jax.random.PRNGKey(0), cfg))
    return out


def _trace(cfg, n=6, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, cfg.vocab_size, size=int(rng.randint(3, 15))),
             int(rng.randint(3, 13))) for _ in range(n)]


def _run(cfg, params, trace, mesh=None, **kw):
    eng = ServeEngine(params, cfg,
                      EngineConfig(max_batch=2, max_len=64, **kw), mesh=mesh)
    for p, mn in trace:
        eng.submit(p, max_new_tokens=mn)
    return {r.uid: r.output for r in eng.run()}, eng


@pytest.fixture(scope="module")
def host_refs(models):
    """Per-arch reference outputs from the legacy per-token host loop
    (device_loop=False keeps greedy on the host-sampled path)."""
    refs = {}
    for a, (cfg, params) in models.items():
        refs[a], _ = _run(cfg, params, _trace(cfg), device_loop=False)
    return refs


class TestHorizonParity:
    @pytest.mark.parametrize("h", HORIZONS)
    @pytest.mark.parametrize("arch", ARCHS)
    def test_greedy_bit_parity_vs_host_loop(self, models, host_refs, arch, h):
        """decode_horizon ∈ {1, 4, 32} is token-for-token identical to
        the per-token host loop for KV and recurrent families."""
        cfg, params = models[arch]
        out, eng = _run(cfg, params, _trace(cfg), decode_horizon=h)
        assert out == host_refs[arch], f"{arch} diverged at horizon {h}"
        assert eng._use_device_loop

    @pytest.mark.parametrize("h", HORIZONS)
    def test_paged_horizon_parity(self, models, host_refs, h):
        """The paged loop (block tables pre-grown min(h, budget) steps via
        prepare_append) matches the host loop bit-for-bit too."""
        cfg, params = models["tinyllama-1.1b"]
        out, _ = _run(cfg, params, _trace(cfg), decode_horizon=h,
                      paged=True, block_size=8)
        assert out == host_refs["tinyllama-1.1b"]

    @pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices")
    @pytest.mark.parametrize("arch", ("tinyllama-1.1b", "zamba2-7b"))
    def test_two_way_mesh_parity(self, models, host_refs, arch):
        """The data-sharded slot pool (batch/recurrent_state -> data)
        decodes identically under the device loop on a 2-way mesh."""
        cfg, params = models[arch]
        mesh = make_mesh((2, 1), ("data", "model"))
        out, _ = _run(cfg, params, _trace(cfg), decode_horizon=4, mesh=mesh)
        assert out == host_refs[arch]


class TestRetirement:
    def _single_ref(self, cfg, params, prompt, max_new):
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=1, max_len=64,
                                       device_loop=False))
        eng.submit(prompt, max_new_tokens=max_new)
        return eng.run()[0].output

    def test_mid_horizon_eos_retirement(self, models):
        """A slot hitting EOS inside the horizon stops emitting there —
        the retirement mask keeps its later (masked) steps out of the
        output and the cache."""
        cfg, params = models["tinyllama-1.1b"]
        rng = np.random.RandomState(7)
        prompt = rng.randint(0, cfg.vocab_size, size=6)
        ref = self._single_ref(cfg, params, prompt, 12)
        eos, cut = None, None
        for k in range(1, len(ref)):
            if ref[k] not in ref[:k]:
                eos, cut = ref[k], k
                break
        if eos is None:
            pytest.skip("degenerate greedy output: no usable EOS token")
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=1, max_len=64,
                                       decode_horizon=32))
        eng.submit(prompt, max_new_tokens=12, eos_id=eos)
        out = eng.run()[0].output
        assert out == ref[:cut + 1]
        # EOS fell mid-horizon: the whole request took one boundary sync
        assert eng.host_syncs == 1

    def test_finish_exactly_at_horizon_boundary(self, models):
        """max_new_tokens = 1 (prefill) + horizon decode steps: the
        request retires exactly when the loop's step count hits h."""
        cfg, params = models["tinyllama-1.1b"]
        rng = np.random.RandomState(8)
        prompt = rng.randint(0, cfg.vocab_size, size=5)
        h = 4
        ref = self._single_ref(cfg, params, prompt, h + 1)
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=1, max_len=64,
                                       decode_horizon=h))
        eng.submit(prompt, max_new_tokens=h + 1)
        out = eng.run()[0].output
        assert out == ref and len(out) == h + 1
        assert eng.host_syncs == 1


class TestSyncAccounting:
    def test_host_syncs_drop_o_tokens_to_o_tokens_over_h(self, models):
        """stats()['host_syncs'] is the round-trip counter: per-token at
        h=1, ~tokens/h at larger horizons, same decode-token output."""
        cfg, params = models["tinyllama-1.1b"]
        rng = np.random.RandomState(9)
        prompt = rng.randint(0, cfg.vocab_size, size=4)
        n_decode = 32          # 33 output tokens = 1 prefill + 32 decode
        syncs = {}
        for h in (1, 8):
            eng = ServeEngine(params, cfg,
                              EngineConfig(max_batch=1, max_len=64,
                                           decode_horizon=h))
            eng.submit(prompt, max_new_tokens=n_decode + 1)
            out = eng.run()[0].output
            assert len(out) == n_decode + 1
            assert eng.stats()["host_syncs"] == eng.host_syncs
            syncs[h] = eng.host_syncs
        assert syncs[1] == n_decode
        assert syncs[8] == math.ceil(n_decode / 8)

    def test_stats_finite_and_monotone_with_horizon(self, models):
        """Timestamps come from real horizon boundaries, never fabricated
        per token: every request has t_enqueue <= t_first_token <= t_done
        and the aggregate latency stats stay finite at h > 1."""
        cfg, params = models["tinyllama-1.1b"]
        _, eng = _run(cfg, params, _trace(cfg, seed=2), decode_horizon=4)
        for r in eng.finished:
            assert r.t_enqueue <= r.t_first_token <= r.t_done
        ts = throughput_stats(eng.finished)
        for key in ("tokens_per_s", "mean_ttft_s", "mean_tpot_s"):
            assert np.isfinite(ts[key]) and ts[key] >= 0.0
        assert ts["mean_tpot_s"] > 0.0
        sched = eng.stats()
        assert np.isfinite(sched["decode_wall_s"])
        assert sched["decode_wall_s"] > 0.0
        assert 0 < sched["host_syncs"] <= sched["decode_steps"]


class TestCompileStability:
    def test_one_compile_per_horizon_value(self, models, compile_counts):
        """horizon is a static argnum: the loop compiles once per
        configured horizon and a repeated workload adds nothing."""
        cfg, params = models["tinyllama-1.1b"]
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=2, max_len=64,
                                       decode_horizon=8))
        trace = _trace(cfg, seed=3)
        for p, mn in trace:
            eng.submit(p, max_new_tokens=mn)
        eng.run()
        assert compile_counts(eng._decode_multi) == [1]
        for p, mn in trace:
            eng.submit(p, max_new_tokens=mn)
        eng.run()
        assert compile_counts(eng._decode_multi) == [1]

    def test_one_compile_per_horizon_value_paged(self, models,
                                                 compile_counts):
        cfg, params = models["tinyllama-1.1b"]
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=2, max_len=64,
                                       decode_horizon=8, paged=True,
                                       block_size=8))
        trace = _trace(cfg, seed=4)
        for p, mn in trace:
            eng.submit(p, max_new_tokens=mn)
        eng.run()
        assert compile_counts(eng._decode_multi_paged) == [1]
        for p, mn in trace:
            eng.submit(p, max_new_tokens=mn)
        eng.run()
        assert compile_counts(eng._decode_multi_paged) == [1]


class TestConfigValidation:
    def test_horizon_with_temperature_raises(self, models):
        cfg, params = models["tinyllama-1.1b"]
        with pytest.raises(ValueError, match="temperature"):
            ServeEngine(params, cfg,
                        EngineConfig(decode_horizon=4, temperature=0.7))

    def test_nonpositive_horizon_raises(self, models):
        cfg, params = models["tinyllama-1.1b"]
        with pytest.raises(ValueError, match="decode_horizon"):
            ServeEngine(params, cfg, EngineConfig(decode_horizon=0))

    def test_horizon_without_device_loop_raises(self, models):
        cfg, params = models["tinyllama-1.1b"]
        with pytest.raises(ValueError, match="device_loop"):
            ServeEngine(params, cfg,
                        EngineConfig(decode_horizon=4, device_loop=False))

    def test_temperature_falls_back_to_host_path(self, models):
        """temperature > 0 keeps the legacy host-sampled per-token loop
        (the device loop is greedy-only)."""
        cfg, params = models["tinyllama-1.1b"]
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=2, max_len=64,
                                       temperature=0.7))
        assert not eng._use_device_loop
