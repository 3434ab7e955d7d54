"""Conformance suite: every registered kernel backend vs the core/psq.py
reference, plus the weight-stationary PackedLayer serving cache.

Accuracy in the HCiM pipeline hinges on exact scale-factor / partial-sum
arithmetic (see PAPERS.md: arXiv:2502.07842, arXiv:2505.07490), so
backends must stay bit-exact against the jnp reference while we optimize.
The grid deliberately includes K not divisible by ``xbar_rows`` and M/N
not divisible by the Pallas block sizes, both comparator levels, the ADC
baseline, and the fused-bit-plane MXU variant.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import psq
from repro.core.config import QuantConfig
from repro.core.psq_linear import apply_linear, init_linear
from repro.kernels import ops, registry
from repro.kernels.int4_matmul import pack_int4
from repro.kernels.ref import int4_matmul_ref, psq_matmul_ref
from repro.serve import cache as serve_cache

jax.config.update("jax_platform_name", "cpu")

BACKENDS = registry.registered_backends()

# (B, K, O, R): ragged K vs xbar_rows, ragged B vs block_b (8), ragged O
# vs block_o (128), single-tile, gemv-like, small crossbar.
SHAPES = [
    (4, 200, 17, 64),     # K % R != 0, O % 128 != 0
    (16, 256, 130, 128),  # multi-tile, O % 128 != 0
    (3, 64, 64, 64),      # single tile, B % 8 != 0
    (1, 128, 256, 128),   # gemv-like decode shape
    (9, 300, 40, 32),     # small crossbar, everything ragged
]


def _backend_or_skip(name):
    try:
        return registry.get_backend(name)
    except RuntimeError as e:
        pytest.skip(str(e))


def _int_inputs(B, K, O, R, n_a=4, n_w=4, seed=0):
    T = math.ceil(K / R)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    lo_a, hi_a = -(2 ** (n_a - 1)), 2 ** (n_a - 1) - 1
    lo_w, hi_w = -(2 ** (n_w - 1)), 2 ** (n_w - 1) - 1
    x = jnp.round(jax.random.uniform(k1, (B, K), minval=lo_a, maxval=hi_a))
    w = jnp.round(jax.random.uniform(k2, (K, O), minval=lo_w, maxval=hi_w))
    sf = jnp.round(jax.random.uniform(k3, (T, n_a, n_w, O), maxval=15)) * 0.5
    return x, w, sf


class TestIntegerLevelParity:
    """Backend contract vs the bit-exact jnp oracle, integer I/O."""

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("levels", ["ternary", "binary", "adc"])
    @pytest.mark.parametrize("shape", SHAPES)
    def test_psq_matmul(self, backend, levels, shape):
        impl = _backend_or_skip(backend)
        B, K, O, R = shape
        x, w, sf = _int_inputs(B, K, O, R)
        alpha = jnp.array(5.0)
        kw = dict(n_a=4, n_w=4, levels=levels, adc_bits=4, xbar_rows=R)
        y = impl.psq_matmul(x, w, sf, alpha, **kw)
        y_ref = psq_matmul_ref(x, w, sf, alpha, **kw)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-3)

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("levels", ["ternary", "binary"])
    def test_fuse_planes_identical(self, backend, levels):
        impl = _backend_or_skip(backend)
        B, K, O, R = 8, 256, 96, 128
        x, w, sf = _int_inputs(B, K, O, R)
        alpha = jnp.array(4.0)
        kw = dict(n_a=4, n_w=4, levels=levels, adc_bits=4, xbar_rows=R)
        y_loop = impl.psq_matmul(x, w, sf, alpha, fuse_planes=False, **kw)
        y_fused = impl.psq_matmul(x, w, sf, alpha, fuse_planes=True, **kw)
        np.testing.assert_array_equal(np.asarray(y_loop), np.asarray(y_fused))

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("shape", [(4, 200, 17), (16, 256, 130),
                                       (1, 128, 256)])
    def test_int4_matmul(self, backend, shape):
        impl = _backend_or_skip(backend)
        B, K, O = shape
        # activations on a 1/16 grid with |x| < 8: exactly representable
        # in bf16, so the kernel's MXU dot is exact and parity is bitwise
        x = jnp.round(
            jax.random.uniform(jax.random.PRNGKey(1), (B, K),
                               minval=-8, maxval=8) * 16
        ) / 16
        w_int = jnp.round(
            jax.random.uniform(jax.random.PRNGKey(2), (K, O),
                               minval=-8, maxval=7)
        )
        packed = pack_int4(w_int)
        scale = jax.random.uniform(jax.random.PRNGKey(3), (O,)) + 0.1
        y = impl.int4_matmul(x, packed, scale)
        y_ref = int4_matmul_ref(packed, scale, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-4)


class TestQATLevelParity:
    """ops.psq_matmul (registry-dispatched) vs core/psq.py, LSQ included."""

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("levels", ["ternary", "binary"])
    @pytest.mark.parametrize("shape", [(5, 200, 17, 64), (3, 64, 33, 64)])
    def test_matches_jnp_reference(self, backend, levels, shape):
        _backend_or_skip(backend)
        B, K, O, R = shape
        cfg = QuantConfig(mode="psq", psq_levels=levels, xbar_rows=R,
                          kernel_backend=backend)
        p = init_linear(jax.random.PRNGKey(0), K, O, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (B, K))
        y_ref, _ = psq.psq_matmul(x, p["w"], p, cfg)
        y_kernel, _ = ops.psq_matmul(x, p["w"], p, cfg)
        np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_ref),
                                   atol=1e-4, rtol=1e-5)
        y_oracle = psq.psq_matmul_dequant_reference(x, p["w"], p, cfg)
        np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_oracle),
                                   atol=1e-4, rtol=1e-5)

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, backend, dtype):
        _backend_or_skip(backend)
        cfg = QuantConfig(mode="psq", xbar_rows=64, kernel_backend=backend)
        p = init_linear(jax.random.PRNGKey(0), 128, 48, cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 128)).astype(dtype)
        y_ref, _ = psq.psq_matmul(x, p["w"], p, cfg)
        y_kernel, _ = ops.psq_matmul(x, p["w"], p, cfg)
        np.testing.assert_allclose(
            np.asarray(y_kernel, np.float32), np.asarray(y_ref, np.float32),
            atol=1e-3, rtol=1e-3,
        )


class TestRegistry:
    def test_unknown_backend_raises(self):
        with pytest.raises(KeyError, match="registered"):
            registry.get_backend("no-such-backend")

    def test_unavailable_backend_raises_or_resolves(self):
        impl = registry._REGISTRY["pallas"]
        if jax.default_backend() == "cpu":
            assert "pallas" not in registry.available_backends()
            with pytest.raises(RuntimeError, match="not.*available"):
                registry.get_backend("pallas")
        else:
            assert impl.is_available()

    def test_reference_always_available(self):
        avail = registry.available_backends()
        assert "reference" in avail and "pallas-interpret" in avail

    def test_env_var_overrides_default(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "reference")
        assert registry.default_backend() == "reference"
        assert registry.get_backend(None).name == "reference"

    def test_set_default_backend(self, monkeypatch):
        monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
        platform_default = registry.default_backend()
        try:
            registry.set_default_backend("reference")
            assert registry.default_backend() == "reference"
        finally:
            registry.set_default_backend(None)
        assert registry.default_backend() == platform_default
        with pytest.raises(KeyError):
            registry.set_default_backend("no-such-backend")

    @pytest.mark.parametrize("platform,expected", [
        ("tpu", "pallas"),
        ("cpu", "pallas-interpret"),
    ])
    def test_platform_default(self, monkeypatch, platform, expected):
        """Unpinned kernel configs run compiled Pallas on a TPU — never
        the interpreter or the oracle — and interpret mode elsewhere."""
        monkeypatch.delenv("REPRO_KERNEL_BACKEND", raising=False)
        monkeypatch.setattr(registry, "_DEFAULT_NAME", None)
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        assert registry.default_backend() == expected
        unpinned = QuantConfig(mode="psq", use_kernel=True)
        assert registry.resolve_backend(unpinned).name == expected
        assert ("pallas" in registry.available_backends()) == (
            platform == "tpu")

    def test_config_kernel_path_property(self):
        assert not QuantConfig(mode="psq").kernel_path
        assert QuantConfig(mode="psq", use_kernel=True).kernel_path
        assert QuantConfig(mode="psq", kernel_backend="reference").kernel_path


class TestPackedLayerCache:
    CFG = QuantConfig(mode="psq", psq_levels="ternary", xbar_rows=64,
                      kernel_backend="reference")

    def _layer(self, K=200, O=33, bias=True):
        return init_linear(jax.random.PRNGKey(0), K, O, self.CFG,
                           use_bias=bias)

    def test_identical_to_uncached_path(self):
        p = self._layer()
        x = jax.random.normal(jax.random.PRNGKey(1), (5, 200))
        y_uncached, _ = apply_linear(p, x, self.CFG)
        packed = serve_cache.PackedLayer.pack(p, self.CFG)
        y_packed, _ = packed.apply_serving(x)
        np.testing.assert_array_equal(np.asarray(y_packed),
                                      np.asarray(y_uncached))
        # and through apply_linear's duck-typed dispatch
        y_dispatch, _ = apply_linear(packed, x, self.CFG)
        np.testing.assert_array_equal(np.asarray(y_dispatch),
                                      np.asarray(y_packed))

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_identical_across_backends(self, backend):
        _backend_or_skip(backend)
        cfg = dataclasses.replace(self.CFG, kernel_backend=backend)
        p = self._layer(bias=False)
        x = jax.random.normal(jax.random.PRNGKey(1), (5, 200))
        y_ref, _ = psq.psq_matmul(x, p["w"], p, cfg)
        y_packed, _ = serve_cache.PackedLayer.pack(p, cfg).apply_serving(x)
        np.testing.assert_allclose(np.asarray(y_packed), np.asarray(y_ref),
                                   atol=1e-4, rtol=1e-5)

    def test_not_repacked_across_calls(self):
        p = self._layer()
        x = jax.random.normal(jax.random.PRNGKey(1), (5, 200))
        packed = serve_cache.PackedLayer.pack(p, self.CFG)
        before = serve_cache.PACK_EVENTS
        for _ in range(4):
            packed.apply_serving(x)
            packed.apply_int4(x)
        assert serve_cache.PACK_EVENTS == before, \
            "serving calls must not re-quantize/re-pack cached state"

    def test_model_cache_counts_packs_and_hits(self):
        p = self._layer()
        tree = {"blocks": [{"attn": {"wq": p}}, {"mlp": {"fc": p}}],
                "final_norm": {"scale": jnp.ones((8,))}}
        cache = serve_cache.PackedModelCache()
        t1 = serve_cache.pack_tree_psq(tree, self.CFG, cache)
        assert cache.stats() == {"layers": 2, "packs": 2, "hits": 0}
        t2 = serve_cache.pack_tree_psq(tree, self.CFG, cache)
        assert cache.stats() == {"layers": 2, "packs": 2, "hits": 2}
        # reused objects, not re-derived ones
        assert t1["blocks"][0]["attn"]["wq"] is t2["blocks"][0]["attn"]["wq"]
        # non-linear leaves untouched
        np.testing.assert_array_equal(
            np.asarray(t1["final_norm"]["scale"]), np.ones((8,)))

    def test_reloaded_weights_repack_not_stale(self):
        """Same path, different weights: the cache must re-pack, never
        serve the old model's packed state."""
        p1 = self._layer()
        p2 = {**p1, "w": p1["w"] + 1.0}
        x = jax.random.normal(jax.random.PRNGKey(1), (5, 200))
        cache = serve_cache.PackedModelCache()
        tree1 = serve_cache.pack_tree_psq({"lin": p1}, self.CFG, cache)
        tree2 = serve_cache.pack_tree_psq({"lin": p2}, self.CFG, cache)
        assert cache.packs == 2 and cache.hits == 0
        y2, _ = tree2["lin"].apply_serving(x)
        y2_ref, _ = apply_linear(p2, x, self.CFG)
        np.testing.assert_array_equal(np.asarray(y2), np.asarray(y2_ref))
        # unchanged weights still hit
        serve_cache.pack_tree_psq({"lin": p2}, self.CFG, cache)
        assert cache.hits == 1

    def test_stacked_layers_pack_and_scan(self):
        """vmapped pack keeps the leading layer axis lax.scan slices."""
        n_layers, K = 3, 64
        cfg = self.CFG
        stacked = jax.vmap(
            lambda k: init_linear(k, K, K, cfg)
        )(jax.random.split(jax.random.PRNGKey(0), n_layers))
        packed = serve_cache.pack_tree_psq({"lin": stacked}, cfg)["lin"]
        assert packed.w_codes.shape == (n_layers, K, K)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, K))

        def body(x, layer):
            y, _ = apply_linear(layer, x, cfg)
            return jnp.tanh(y), None

        y_scan, _ = jax.lax.scan(body, x, packed)
        # reference: apply each layer's uncached path in sequence
        y_ref = x
        for i in range(n_layers):
            lp = jax.tree.map(lambda a: a[i], stacked)
            y, _ = apply_linear(lp, y_ref, cfg)
            y_ref = jnp.tanh(y)
        np.testing.assert_allclose(np.asarray(y_scan), np.asarray(y_ref),
                                   atol=1e-5)

    def test_warm_calls_faster_than_packing(self):
        """Re-packing every call must cost more than cached serving."""
        import time

        K, O = 512, 256
        p = init_linear(jax.random.PRNGKey(0), K, O, self.CFG)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, K))
        packed = serve_cache.PackedLayer.pack(p, self.CFG)
        f = jax.jit(lambda layer, x: layer.apply_serving(x)[0])
        jax.block_until_ready(f(packed, x))  # warm-up: compile once

        t0 = time.perf_counter()
        for _ in range(5):
            jax.block_until_ready(f(packed, x))
        warm = time.perf_counter() - t0

        t0 = time.perf_counter()
        for _ in range(5):
            repacked = serve_cache.PackedLayer.pack(p, self.CFG)
            jax.block_until_ready(f(repacked, x))
        cold = time.perf_counter() - t0
        assert warm < cold, (warm, cold)
