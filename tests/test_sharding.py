"""Sharding rules: logical axes, divisibility guards, param specs, serve."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.parallel.sharding import (
    RULES_2D, axis_rules, constrain, logical_to_pspec,
    packed_layer_pspecs, shard_packed_layer, shard_packed_tree, tp_axes,
)

jax.config.update("jax_platform_name", "cpu")

needs_devices = lambda n: pytest.mark.skipif(
    len(jax.devices()) < n,
    reason=f"needs >= {n} devices (tests/conftest.py forges 4 on CPU)",
)


class TestLogicalRules:
    def test_noop_without_rules(self):
        x = jnp.ones((4, 8))
        y = constrain(x, "batch", "embed")
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_pspec_mapping(self):
        mesh = make_mesh((1, 1), ("data", "model"))
        with axis_rules(RULES_2D, mesh):
            spec = logical_to_pspec(["batch", "seq", "ffn"], shape=(4, 8, 16))
        assert spec == P("data", None, "model")

    def test_divisibility_guard(self):
        mesh = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
        with axis_rules(RULES_2D, mesh):
            # 7 not divisible by model=2 -> unsharded
            spec = logical_to_pspec(["batch", "ffn"], shape=(4, 7))
        assert spec == P("data")

    def test_duplicate_axis_dedup(self):
        """Two logical dims mapping to the same mesh axis: first wins."""
        mesh = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
        with axis_rules(RULES_2D, mesh):
            spec = logical_to_pspec(
                ["experts", None, "expert_ffn"], shape=(4, 2, 8)
            )
        assert spec == P("model")  # expert_ffn dropped, no duplicates


class TestParamSpecs:
    def test_qkv_and_down_proj_rules(self):
        from repro.launch.specs import param_pspec

        mesh = make_mesh((1, 1), ("data", "model"))

        class Leaf:
            def __init__(self, shape):
                self.shape = shape
                self.ndim = len(shape)

        class K:
            def __init__(self, key):
                self.key = key

        spec = param_pspec([K("blocks"), K("attn"), K("wq"), K("w")],
                           Leaf((22, 128, 64)), mesh)
        assert spec == P(None, None, "model")
        spec = param_pspec([K("blocks"), K("mlp"), K("down"), K("w")],
                           Leaf((22, 256, 128)), mesh)
        assert spec == P(None, "model")
        spec = param_pspec([K("blocks"), K("norm1"), K("scale")],
                           Leaf((128,)), mesh)
        assert spec == P()

    def test_moe_expert_parallel_vs_ffn_sharding(self):
        from repro.launch.specs import param_pspec

        class Leaf:
            def __init__(self, shape):
                self.shape = shape
                self.ndim = len(shape)

        class K:
            def __init__(self, key):
                self.key = key

        mesh = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
        # 128 experts divisible by 2 -> EP
        spec = param_pspec([K("moe"), K("w_gate")], Leaf((35, 128, 64, 32)),
                           mesh)
        assert spec == P(None, "model")
        # 41 experts not divisible -> shard expert ffn dim
        spec = param_pspec([K("moe"), K("w_gate")], Leaf((35, 41, 64, 32)),
                           mesh)
        assert spec == P(None, None, None, "model")


def _packed_layer(k_in=64, n_out=8, use_bias=True, seed=0, **qkw):
    from repro.core.config import QuantConfig
    from repro.core.psq_linear import init_linear
    from repro.serve.cache import PackedLayer

    cfg = QuantConfig(mode="psq", xbar_rows=32, kernel_backend="reference",
                      **qkw)
    params = init_linear(jax.random.PRNGKey(seed), k_in, n_out, cfg,
                         use_bias=use_bias)
    return PackedLayer.pack(params, cfg), cfg


class TestPackedLayerSpecs:
    def test_column_dims_follow_sf_out_rule(self):
        layer, _ = _packed_layer()
        mesh = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
        specs = packed_layer_pspecs(layer, rules=RULES_2D, mesh=mesh)
        assert specs.w_codes == P(None, "model")
        assert specs.w_packed == P(None, "model")
        assert specs.sf_q == P(None, None, None, "model")
        assert specs.bias == P("model")
        # scalars / bit-significance vectors replicate — even when their
        # length happens to equal a shardable size
        assert specs.alpha == P() and specs.step_x == P()
        assert specs.sigma == P() and specs.kappa == P()
        assert specs.s_w == P()          # per-layer LSQ step: scalar

    def test_reduced_granularity_sf_stays_replicated(self):
        layer, _ = _packed_layer(sf_granularity="per_tile")
        mesh = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
        specs = packed_layer_pspecs(layer, rules=RULES_2D, mesh=mesh)
        assert layer.sf_q.shape[-1] == 1
        assert specs.sf_q == P()         # size-1 dim: divisibility guard

    def test_non_divisible_columns_fall_back_unsharded(self):
        layer, _ = _packed_layer(n_out=6)
        mesh = jax.sharding.AbstractMesh((1, 4), ("data", "model"))
        specs = packed_layer_pspecs(layer, rules=RULES_2D, mesh=mesh)
        assert specs.w_codes == P()
        assert specs.bias == P()

    def test_stacked_layers_get_leading_layer_axis(self):
        from repro.core.config import QuantConfig
        from repro.core.psq_linear import init_linear
        from repro.serve.cache import PackedLayer

        cfg = QuantConfig(mode="psq", xbar_rows=32,
                          kernel_backend="reference")
        stacked = jax.vmap(
            lambda k: PackedLayer.pack(init_linear(k, 64, 8, cfg), cfg)
        )(jax.random.split(jax.random.PRNGKey(0), 3))
        mesh = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
        specs = packed_layer_pspecs(stacked, rules=RULES_2D, mesh=mesh)
        assert stacked.w_codes.ndim == 3
        assert specs.w_codes == P(None, None, "model")
        assert specs.sf_q == P(None, None, None, None, "model")
        assert specs.s_w == P()          # (L,) stacked scalar: replicated

    def test_tp_axes_activation(self):
        assert tp_axes() is None                       # no rules active
        mesh1 = make_mesh((1, 1), ("data", "model"))
        with axis_rules(RULES_2D, mesh1):
            assert tp_axes() is None                   # model axis size 1
        with axis_rules(RULES_2D, None):
            assert tp_axes() is None                   # rules without mesh
        amesh = jax.sharding.AbstractMesh((1, 2), ("data", "model"))
        with axis_rules(RULES_2D, amesh):
            assert tp_axes() is None                   # abstract: no shard_map

    @needs_devices(2)
    def test_tp_axes_on_real_mesh(self):
        mesh = make_mesh((1, 2), ("data", "model"))
        with axis_rules(RULES_2D, mesh):
            assert tp_axes() == (mesh, "model")


class TestTensorParallelPSQ:
    """Sharded-vs-single-device bit-exactness of the packed PSQ matmul."""

    @needs_devices(2)
    @pytest.mark.parametrize("model_parallel", [2, 4])
    def test_psq_linear_tp_bit_exact(self, model_parallel):
        if len(jax.devices()) < model_parallel:
            pytest.skip(f"needs {model_parallel} devices")
        from repro.core.psq_linear import apply_linear

        layer, qcfg = _packed_layer(k_in=64, n_out=8)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 3, 64))
        y_ref, _ = layer.apply_serving(x)

        mesh = make_mesh((1, model_parallel), ("data", "model"))
        with axis_rules(RULES_2D, mesh):
            y_tp, _ = apply_linear(layer, x, qcfg)
        np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_tp))

    @needs_devices(4)
    def test_tp_divisibility_fallback_still_exact(self):
        from repro.core.psq_linear import apply_linear

        layer, qcfg = _packed_layer(n_out=6)     # 6 % 4 != 0
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 64))
        y_ref, _ = layer.apply_serving(x)
        mesh = make_mesh((1, 4), ("data", "model"))
        with axis_rules(RULES_2D, mesh):
            y, _ = apply_linear(layer, x, qcfg)
        np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y))

    @needs_devices(4)
    def test_tp_under_jit_and_data_axis(self):
        from repro.core.psq_linear import apply_linear

        layer, qcfg = _packed_layer(k_in=64, n_out=16)
        x = jax.random.normal(jax.random.PRNGKey(3), (4, 64))
        y_ref, _ = layer.apply_serving(x)
        mesh = make_mesh((2, 2), ("data", "model"))

        def fwd(lyr, xx):
            with axis_rules(RULES_2D, mesh):
                return apply_linear(lyr, xx, qcfg)[0]

        y = jax.jit(fwd)(layer, x)
        np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y))

    @needs_devices(2)
    def test_shard_packed_layer_placement(self):
        from jax.sharding import NamedSharding

        layer, _ = _packed_layer(n_out=8)
        mesh = make_mesh((1, 2), ("data", "model"))
        placed = shard_packed_layer(layer, mesh)
        assert placed.w_codes.sharding == NamedSharding(
            mesh, P(None, "model"))
        assert placed.alpha.sharding == NamedSharding(mesh, P())
        np.testing.assert_array_equal(
            np.asarray(layer.w_codes), np.asarray(placed.w_codes))

    @needs_devices(2)
    def test_pack_cache_placement_is_per_call_not_sticky(self):
        """A meshed pack must not leak its sharding into later no-mesh
        packs of the same weights — the cache stores unplaced state and
        applies placement per call (fingerprint-stable: all hits)."""
        from repro.core.config import QuantConfig
        from repro.core.psq_linear import init_linear
        from repro.serve.cache import PackedModelCache, pack_tree_psq

        qcfg = QuantConfig(mode="psq", xbar_rows=32,
                           kernel_backend="reference")
        tree = {"mlp": init_linear(jax.random.PRNGKey(0), 64, 8, qcfg)}
        cache = PackedModelCache()
        mesh = make_mesh((1, 2), ("data", "model"))

        sharded = pack_tree_psq(tree, qcfg, cache, mesh=mesh)
        assert sharded["mlp"].w_codes.sharding.spec == P(None, "model")
        plain = pack_tree_psq(tree, qcfg, cache)            # no mesh
        assert not isinstance(
            plain["mlp"].w_codes.sharding, jax.sharding.NamedSharding
        ) or plain["mlp"].w_codes.sharding.spec != P(None, "model")
        assert cache.stats() == {"layers": 1, "packs": 1, "hits": 1}

    @needs_devices(2)
    def test_shard_packed_tree_passes_non_packed_through(self):
        layer, _ = _packed_layer(n_out=8)
        norm = {"scale": jnp.ones((64,))}
        tree = {"mlp": layer, "norm": norm, "depth": [layer]}
        mesh = make_mesh((1, 2), ("data", "model"))
        out = shard_packed_tree(tree, mesh)
        assert out["norm"]["scale"] is norm["scale"]   # leaf passes through
        assert out["mlp"].w_codes.sharding.spec == P(None, "model")
        assert out["depth"][0].w_codes.sharding.spec == P(None, "model")


class TestServeEngine:
    def test_batched_requests_complete(self):
        import numpy as np

        from repro.configs import get_config
        from repro.models import init_model
        from repro.serve import EngineConfig, ServeEngine, throughput_stats

        cfg = get_config("tinyllama-1.1b").reduced()
        params = init_model(jax.random.PRNGKey(0), cfg)
        eng = ServeEngine(params, cfg, EngineConfig(max_batch=3, max_len=48))
        rng = np.random.RandomState(0)
        for _ in range(5):
            eng.submit(rng.randint(0, cfg.vocab_size, size=rng.randint(2, 8)),
                       max_new_tokens=6)
        done = eng.run()
        assert len(done) == 5
        assert all(len(r.output) == 6 for r in done)
        stats = throughput_stats(done)
        assert stats["total_tokens"] == 30 and stats["tokens_per_s"] > 0

    def test_int4_serving_matches_greedy_mostly(self):
        import numpy as np

        from repro.configs import get_config
        from repro.core.psq_linear import pack_tree_for_serving
        from repro.models import init_model
        from repro.serve import EngineConfig, ServeEngine

        cfg = get_config("tinyllama-1.1b").reduced()
        params = init_model(jax.random.PRNGKey(0), cfg)
        prompt = np.arange(5) % cfg.vocab_size
        outs = {}
        for name, p in [("fp", params), ("int4", pack_tree_for_serving(params))]:
            eng = ServeEngine(p, cfg, EngineConfig(max_batch=1, max_len=32))
            eng.submit(prompt, max_new_tokens=4)
            outs[name] = eng.run()[0].output
        assert len(outs["fp"]) == len(outs["int4"]) == 4
