"""Ternary-sparsity-aware serving: skip path bit-exactness + plumbing.

The sparsity-skipping path (docs/energy.md) may only ever change WHAT
work runs, never the numbers: a kernel given pack-time column-occupancy
metadata must return bit-identical outputs to its own dense execution,
on every registered backend, across the occupancy grid, both comparator
levels and the ADC baseline, ragged shapes included. This module pins
that invariant plus the metadata plumbing around it: pack-time
recording on :class:`PackedLayer`, pytree/mesh round-trips, the engine
greedy-parity with the skip toggled, and the benchmark-harness smoke
knobs (``benchmarks/run.py --smoke --sparsities --json``).
"""
import dataclasses
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import QuantConfig
from repro.core.psq_linear import init_linear
from repro.kernels import registry
from repro.kernels.occupancy import (
    META_BLOCK, ColumnOccupancy, column_occupancy, kernel_block_flags,
    occupancy_for_kernel, shard_occupancy,
)
from repro.kernels.ref import psq_matmul_ref
from repro.launch.mesh import make_mesh
from repro.serve.cache import PackedLayer, PackedModelCache, pack_tree_psq

from tests._hypothesis_compat import given, settings, st

jax.config.update("jax_platform_name", "cpu")

BACKENDS = registry.registered_backends()
OCCUPANCY_GRID = (0.0, 0.25, 0.5, 0.9, 1.0)

needs_devices = lambda n: pytest.mark.skipif(
    len(jax.devices()) < n,
    reason=f"needs >= {n} devices (tests/conftest.py forges 4 on CPU)",
)


def _backend_or_skip(name):
    try:
        return registry.get_backend(name)
    except RuntimeError as e:
        pytest.skip(str(e))


def _sparse_weight(K, O, zero_frac, block=META_BLOCK, seed=0, n_w=4):
    """Integer weight codes with ``round(zero_frac * n_blocks)`` whole
    ``block``-wide column blocks zeroed (the structure the pack-time
    metadata can actually exploit — scattered zero columns never empty
    a whole metadata block)."""
    rng = np.random.RandomState(seed)
    lo, hi = -(2 ** (n_w - 1)), 2 ** (n_w - 1) - 1
    w = rng.randint(lo, hi + 1, size=(K, O)).astype(np.float32)
    nb = math.ceil(O / block)
    for bi in range(int(round(zero_frac * nb))):
        w[:, bi * block:(bi + 1) * block] = 0.0
    return w


def _kernel_inputs(B, K, O, R, n_a=4, n_w=4, seed=0):
    T = math.ceil(K / R)
    rng = np.random.RandomState(seed + 1)
    lo_a, hi_a = -(2 ** (n_a - 1)), 2 ** (n_a - 1) - 1
    x = rng.randint(lo_a, hi_a + 1, size=(B, K)).astype(np.float32)
    sf = (rng.randint(0, 16, size=(T, n_a, n_w, O)) * 0.5).astype(np.float32)
    return jnp.asarray(x), jnp.asarray(sf)


class TestOccupancyMetadata:
    def test_records_zero_blocks_per_tile(self):
        w = _sparse_weight(100, 96, 0.0, block=32)       # T=2 at R=64
        w[:, 32:64] = 0.0                                # block 1: all tiles
        w[:64, 0:32] = 0.0                               # block 0: tile 0 only
        occ = column_occupancy(w, xbar_rows=64, n_w=4, block=32)
        assert occ.n_tiles == 2 and occ.n_blocks == 3
        zb = occ.zero_blocks_np()
        assert zb.tolist() == [[True, True, False], [False, True, False]]
        assert occ.matches(96, 64, 100)
        assert not occ.matches(96, 128, 100)

    def test_mean_zero_fraction_is_column_weighted(self):
        # ragged last block (O=40, block=32): 32 zero cols of 40, per tile
        w = _sparse_weight(64, 40, 0.0, block=32)
        w[:, :32] = 0.0
        occ = column_occupancy(w, xbar_rows=64, n_w=4, block=32)
        assert occ.mean_zero_fraction == pytest.approx(32 / 40)
        assert occ.skippable_block_fraction == pytest.approx(0.5)

    def test_rejects_non_2d(self):
        with pytest.raises(ValueError):
            column_occupancy(np.zeros((2, 8, 8)), xbar_rows=64, n_w=4)

    def test_kernel_flags_conservative_and_padding(self):
        w = _sparse_weight(64, 96, 0.0, block=32)
        w[:, 0:32] = 0.0          # metadata block 0 zero, block 1 dense
        occ = column_occupancy(w, xbar_rows=64, n_w=4, block=32)
        # kernel block 0 covers metadata blocks 0+1 -> AND -> not skippable
        flags = kernel_block_flags(occ, block_o=64, o_pad=128)
        assert flags.shape == (1, 2)
        assert flags[0, 0] == 0
        # kernel block 1 covers cols 64..127: metadata block 2 is dense,
        # cols 96..127 are pure padding (skippable) -> AND -> 0
        assert flags[0, 1] == 0
        # padding-only kernel block is always skippable
        flags_wide = kernel_block_flags(occ, block_o=32, o_pad=128)
        assert flags_wide[0].tolist() == [1, 0, 0, 1]

    def test_for_kernel_guards(self):
        w = _sparse_weight(64, 64, 1.0, block=32)
        occ = column_occupancy(w, xbar_rows=64, n_w=4, block=32)
        assert occupancy_for_kernel(occ, 64, 64, 64) is occ
        assert occupancy_for_kernel(occ, 32, 64, 64) is None    # TP shard O
        assert occupancy_for_kernel(occ, 64, 128, 64) is None   # wrong K
        assert occupancy_for_kernel(None, 64, 64, 64) is None
        dense = column_occupancy(_sparse_weight(64, 64, 0.0, block=32),
                                 xbar_rows=64, n_w=4, block=32)
        assert occupancy_for_kernel(dense, 64, 64, 64) is None  # nothing to skip


class TestSkipBitExact:
    """Skip vs dense, same backend: must be bitwise identical."""

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("levels", ["ternary", "binary", "adc"])
    def test_occupancy_grid(self, backend, levels):
        impl = _backend_or_skip(backend)
        B, K, O, R = 5, 200, 4 * META_BLOCK, 64        # ragged K, 4 blocks
        x, sf = _kernel_inputs(B, K, O, R)
        alpha = jnp.array(5.0)
        kw = dict(n_a=4, n_w=4, levels=levels, adc_bits=4, xbar_rows=R)
        for frac in OCCUPANCY_GRID:
            w = _sparse_weight(K, O, frac, seed=int(frac * 100))
            occ = column_occupancy(w, xbar_rows=R, n_w=4)
            wj = jnp.asarray(w)
            y_dense = impl.psq_matmul(x, wj, sf, alpha, **kw)
            y_skip = impl.psq_matmul(x, wj, sf, alpha, occupancy=occ, **kw)
            np.testing.assert_array_equal(
                np.asarray(y_dense), np.asarray(y_skip),
                err_msg=f"{backend}/{levels} differs at zero_frac={frac}")

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("levels", ["ternary", "binary"])
    def test_fused_planes_skip_exact(self, backend, levels):
        impl = _backend_or_skip(backend)
        B, K, O, R = 4, 128, 2 * META_BLOCK, 64
        w = _sparse_weight(K, O, 0.5, seed=7)
        occ = column_occupancy(w, xbar_rows=R, n_w=4)
        x, sf = _kernel_inputs(B, K, O, R)
        alpha = jnp.array(3.0)
        kw = dict(n_a=4, n_w=4, levels=levels, adc_bits=4, xbar_rows=R,
                  fuse_planes=True)
        y_dense = impl.psq_matmul(x, jnp.asarray(w), sf, alpha, **kw)
        y_skip = impl.psq_matmul(x, jnp.asarray(w), sf, alpha,
                                 occupancy=occ, **kw)
        np.testing.assert_array_equal(np.asarray(y_dense), np.asarray(y_skip))

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_all_zero_layer(self, backend):
        impl = _backend_or_skip(backend)
        B, K, O, R = 3, 96, META_BLOCK, 32
        w = np.zeros((K, O), np.float32)
        occ = column_occupancy(w, xbar_rows=R, n_w=4)
        assert occ.mean_zero_fraction == 1.0
        x, sf = _kernel_inputs(B, K, O, R)
        alpha = jnp.array(2.0)
        for levels in ("ternary", "binary", "adc"):
            kw = dict(n_a=4, n_w=4, levels=levels, adc_bits=4, xbar_rows=R)
            y_dense = impl.psq_matmul(x, jnp.asarray(w), sf, alpha, **kw)
            y_skip = impl.psq_matmul(x, jnp.asarray(w), sf, alpha,
                                     occupancy=occ, **kw)
            np.testing.assert_array_equal(np.asarray(y_dense),
                                          np.asarray(y_skip))

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_single_column_block_ragged(self, backend):
        impl = _backend_or_skip(backend)
        B, K, O, R = 2, 130, 40, 64          # one metadata block, O < 128
        w = _sparse_weight(K, O, 0.0, seed=3)
        w[:64, :] = 0.0                      # tile 0 fully zero, tile 1 dense
        occ = column_occupancy(w, xbar_rows=R, n_w=4)
        assert occ.zero_blocks_np().tolist() == [[True], [False], [False]]
        x, sf = _kernel_inputs(B, K, O, R)
        alpha = jnp.array(4.0)
        kw = dict(n_a=4, n_w=4, levels="ternary", adc_bits=4, xbar_rows=R)
        y_dense = impl.psq_matmul(x, jnp.asarray(w), sf, alpha, **kw)
        y_skip = impl.psq_matmul(x, jnp.asarray(w), sf, alpha,
                                 occupancy=occ, **kw)
        np.testing.assert_array_equal(np.asarray(y_dense), np.asarray(y_skip))

    @settings(max_examples=20, deadline=None)
    @given(
        b=st.integers(1, 6),
        k=st.integers(33, 260),
        nb=st.integers(1, 4),
        r=st.sampled_from([32, 64, 128]),
        levels=st.sampled_from(["ternary", "binary", "adc"]),
        frac=st.sampled_from(OCCUPANCY_GRID),
        seed=st.integers(0, 2 ** 16),
    )
    def test_property_skip_invariance(self, b, k, nb, r, levels, frac, seed):
        """Random ragged shapes x occupancy grid, reference backend:
        pallas-interpret is exercised by the parametrized tests above
        (too slow per-example for hypothesis)."""
        O = nb * META_BLOCK - (seed % META_BLOCK)     # ragged last block
        w = _sparse_weight(k, O, frac, seed=seed)
        occ = column_occupancy(w, xbar_rows=r, n_w=4)
        x, sf = _kernel_inputs(b, k, O, r, seed=seed)
        alpha = jnp.array(float(1 + seed % 7))
        kw = dict(n_a=4, n_w=4, levels=levels, adc_bits=4, xbar_rows=r)
        y_dense = psq_matmul_ref(x, jnp.asarray(w), sf, alpha, **kw)
        y_skip = psq_matmul_ref(x, jnp.asarray(w), sf, alpha,
                                occupancy=occ, **kw)
        np.testing.assert_array_equal(np.asarray(y_dense), np.asarray(y_skip))


def _sparse_packed_layer(zero_frac, k_in=96, n_out=2 * META_BLOCK,
                         seed=0, **qkw):
    cfg = QuantConfig(mode="psq", xbar_rows=32, kernel_backend="reference",
                      **qkw)
    params = init_linear(jax.random.PRNGKey(seed), k_in, n_out, cfg,
                         use_bias=True)
    w = np.asarray(params["w"]).copy()
    nb = math.ceil(n_out / META_BLOCK)
    for bi in range(int(round(zero_frac * nb))):
        w[:, bi * META_BLOCK:(bi + 1) * META_BLOCK] = 0.0
    params["w"] = jnp.asarray(w)
    return PackedLayer.pack(params, cfg), cfg


class TestPackedOccupancy:
    def test_pack_records_occupancy(self):
        layer, cfg = _sparse_packed_layer(0.5)
        occ = layer.occupancy
        assert isinstance(occ, ColumnOccupancy)
        k, o = layer.w_codes.shape
        assert occ.matches(o, cfg.xbar_rows, k)
        assert occ.mean_zero_fraction >= 0.5    # zeroed blocks stay zero codes
        assert occ.skippable_block_fraction >= 0.5

    def test_dense_pack_has_empty_occupancy(self):
        layer, _ = _sparse_packed_layer(0.0)
        assert layer.occupancy is not None
        assert layer.occupancy.skippable_block_fraction == 0.0

    def test_occupancy_survives_pytree_roundtrip(self):
        layer, _ = _sparse_packed_layer(0.5)
        leaves, treedef = jax.tree_util.tree_flatten(layer)
        rebuilt = jax.tree_util.tree_unflatten(treedef, leaves)
        assert rebuilt.occupancy == layer.occupancy
        mapped = jax.tree_util.tree_map(lambda a: a, layer)
        assert mapped.occupancy == layer.occupancy

    def test_occupancy_survives_pack_tree_and_cache_hit(self):
        cfg = QuantConfig(mode="psq", xbar_rows=32,
                          kernel_backend="reference")
        params = init_linear(jax.random.PRNGKey(0), 96, 2 * META_BLOCK, cfg)
        w = np.asarray(params["w"]).copy()
        w[:, :META_BLOCK] = 0.0
        params["w"] = jnp.asarray(w)
        tree = {"mlp": params}
        cache = PackedModelCache()
        packed = pack_tree_psq(tree, cfg, cache)
        assert packed["mlp"].occupancy.skippable_block_fraction == 0.5
        again = pack_tree_psq(tree, cfg, cache)      # cache hit path
        assert again["mlp"].occupancy == packed["mlp"].occupancy
        assert cache.stats()["hits"] >= 1

    @needs_devices(2)
    def test_occupancy_survives_mesh_placement(self):
        cfg = QuantConfig(mode="psq", xbar_rows=32,
                          kernel_backend="reference")
        params = init_linear(jax.random.PRNGKey(0), 96, 2 * META_BLOCK, cfg)
        w = np.asarray(params["w"]).copy()
        w[:, :META_BLOCK] = 0.0
        params["w"] = jnp.asarray(w)
        cache = PackedModelCache()
        mesh = make_mesh((1, 2), ("data", "model"))
        placed = pack_tree_psq({"mlp": params}, cfg, cache, mesh=mesh)
        assert placed["mlp"].occupancy is not None
        assert placed["mlp"].occupancy.skippable_block_fraction == 0.5

    @pytest.mark.parametrize("zero_frac", [0.5, 1.0])
    def test_apply_serving_skip_toggle_bit_exact(self, zero_frac):
        layer, cfg = _sparse_packed_layer(zero_frac)
        assert cfg.sparsity_skip                     # default on
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 96))
        y_skip, _ = layer.apply_serving(x)
        dense_layer = dataclasses.replace(
            layer, cfg=dataclasses.replace(cfg, sparsity_skip=False))
        y_dense, _ = dense_layer.apply_serving(x)
        np.testing.assert_array_equal(np.asarray(y_skip),
                                      np.asarray(y_dense))


def _block_sparsify_tree(node):
    """Zero the first META_BLOCK-wide column block of every 2-D linear
    weight wide enough to have one — structured sparsity the pack-time
    metadata can see, applied before packing."""
    if isinstance(node, dict):
        out = {}
        for k, v in node.items():
            if (k == "w" and hasattr(v, "ndim") and v.ndim in (2, 3)
                    and v.shape[-1] >= 2 * META_BLOCK):
                w = np.asarray(v).copy()
                w[..., :META_BLOCK] = 0.0       # all stacked layers: the
                out[k] = jnp.asarray(w)         # merged metadata sees it
            else:
                out[k] = _block_sparsify_tree(v)
        return out
    if isinstance(node, (list, tuple)):
        return type(node)(_block_sparsify_tree(v) for v in node)
    return node


class TestEngineSkipParity:
    def test_greedy_decode_parity_skip_on_off(self):
        """The served model's greedy tokens must not change when the
        sparsity skip is enabled — end-to-end over the packed engine."""
        from repro.configs import get_config
        from repro.core.config import PSQ_TERNARY
        from repro.models import init_model
        from repro.serve import EngineConfig, ServeEngine

        base = get_config("tinyllama-1.1b").reduced()
        outs = {}
        for skip in (True, False):
            qcfg = dataclasses.replace(PSQ_TERNARY,
                                       kernel_backend="reference",
                                       xbar_rows=64, sparsity_skip=skip)
            cfg = base.with_quant(qcfg)
            params = _block_sparsify_tree(
                init_model(jax.random.PRNGKey(0), cfg))
            packed = pack_tree_psq(params, qcfg, PackedModelCache())
            if skip:    # the structured zeros must be visible to the skip
                occs = [
                    lyr.occupancy.skippable_block_fraction
                    for lyr in jax.tree_util.tree_leaves(
                        packed, is_leaf=lambda n: hasattr(n, "w_codes"))
                    if hasattr(lyr, "w_codes") and lyr.occupancy is not None
                ]
                assert any(o > 0 for o in occs)
            eng = ServeEngine(params=packed, cfg=cfg,
                              ecfg=EngineConfig(max_batch=2, max_len=48))
            rng = np.random.RandomState(5)
            for _ in range(3):
                eng.submit(rng.randint(0, cfg.vocab_size, size=6),
                           max_new_tokens=5)
            outs[skip] = [r.output for r in eng.run()]
        assert outs[True] == outs[False]


class TestShardOccupancy:
    """Per-shard metadata re-slicing for tensor parallelism
    (:func:`repro.kernels.occupancy.shard_occupancy`)."""

    def test_reslice_and_conservative_merge(self):
        # O=4 blocks of 32; blocks 0 and 2 zero -> each 2-way shard
        # half has its FIRST local block zero -> merged local block 0
        # is skippable, local block 1 is not
        w = _sparse_weight(64, 128, 0.0, block=32)
        w[:, 0:32] = 0.0
        w[:, 64:96] = 0.0
        occ = column_occupancy(w, xbar_rows=64, n_w=4, block=32)
        s = shard_occupancy(occ, 2)
        assert s is not None and s.n_cols == 64 and s.n_blocks == 2
        assert s.zero_blocks_np().tolist() == [[True, False]]
        # the re-sliced metadata passes the kernel guard the global
        # metadata fails on a shard's local problem
        assert occupancy_for_kernel(occ, 64, 64, 64) is None
        assert occupancy_for_kernel(s, 64, 64, 64) is s

    def test_merge_drops_shard_disagreement(self):
        # only shard 0's half is zero -> AND across shards leaves
        # nothing skippable
        w = _sparse_weight(64, 128, 0.0, block=32)
        w[:, 0:64] = 0.0
        occ = column_occupancy(w, xbar_rows=64, n_w=4, block=32)
        s = shard_occupancy(occ, 2)
        assert s is not None
        assert not any(any(row) for row in s.zero_blocks)
        # fractions are the per-shard minimum, never an average
        assert s.zero_col_frac == ((0.0, 0.0),)

    def test_unrepresentable_splits_return_none(self):
        w = _sparse_weight(64, 96, 1.0, block=32)
        occ = column_occupancy(w, xbar_rows=64, n_w=4, block=32)
        assert shard_occupancy(occ, 5) is None      # 96 % 5 != 0
        # 96/2 = 48 puts a shard boundary inside a 32-wide block
        assert shard_occupancy(occ, 2) is None
        assert shard_occupancy(occ, 1) is occ
        assert shard_occupancy(None, 2) is None

    @needs_devices(2)
    def test_tp_skip_vs_dense_bit_exact(self):
        """2-way model mesh: shards must sparsity-skip (not fall back
        dense) and still match the dense single-device forward bit for
        bit."""
        from repro.core.psq_linear import apply_linear
        from repro.kernels.occupancy import shard_occupancy as shard_occ
        from repro.parallel.sharding import RULES_2D, axis_rules

        # zero the first block of EACH shard half so the conservative
        # cross-shard merge keeps a skippable block
        layer, qcfg = _sparse_packed_layer(0.0, n_out=4 * META_BLOCK)
        w = np.asarray(layer.w_codes).copy()
        w[:, :META_BLOCK] = 0
        w[:, 2 * META_BLOCK:3 * META_BLOCK] = 0
        layer = dataclasses.replace(
            layer, w_codes=jnp.asarray(w),
            occupancy=column_occupancy(w, xbar_rows=qcfg.xbar_rows,
                                       n_w=qcfg.spec.n_bits_w))
        s = shard_occ(layer.occupancy, 2)
        assert s is not None and s.skippable_block_fraction > 0

        dense_layer = dataclasses.replace(
            layer, cfg=dataclasses.replace(qcfg, sparsity_skip=False))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 96))
        y_ref, _ = dense_layer.apply_serving(x)     # single-device dense
        mesh = make_mesh((1, 2), ("data", "model"))
        with axis_rules(RULES_2D, mesh):
            y_skip, _ = apply_linear(layer, x, qcfg)
            y_dense, _ = apply_linear(dense_layer, x, dense_layer.cfg)
        np.testing.assert_array_equal(np.asarray(y_ref),
                                      np.asarray(y_skip))
        np.testing.assert_array_equal(np.asarray(y_ref),
                                      np.asarray(y_dense))

    @needs_devices(2)
    def test_engine_tp_skip_parity(self):
        """Served greedy tokens on a 2-way model mesh are identical with
        the sparsity skip on and off, with shard-aligned structured
        zeros that keep the re-sliced metadata skippable."""
        from repro.configs import get_config
        from repro.core.config import PSQ_TERNARY
        from repro.models import init_model
        from repro.serve import EngineConfig, ServeEngine

        def shard_aligned_sparsify(node):
            # zero the first META_BLOCK columns of each 2-way shard half
            if isinstance(node, dict):
                out = {}
                for k, v in node.items():
                    if (k == "w" and hasattr(v, "ndim") and v.ndim in (2, 3)
                            and v.shape[-1] >= 4 * META_BLOCK
                            and v.shape[-1] % (2 * META_BLOCK) == 0):
                        w = np.asarray(v).copy()
                        half = w.shape[-1] // 2
                        w[..., :META_BLOCK] = 0.0
                        w[..., half:half + META_BLOCK] = 0.0
                        out[k] = jnp.asarray(w)
                    else:
                        out[k] = shard_aligned_sparsify(v)
                return out
            if isinstance(node, (list, tuple)):
                return type(node)(shard_aligned_sparsify(v) for v in node)
            return node

        base = get_config("tinyllama-1.1b").reduced()
        mesh = make_mesh((1, 2), ("data", "model"))
        outs = {}
        for skip in (True, False):
            qcfg = dataclasses.replace(PSQ_TERNARY,
                                       kernel_backend="reference",
                                       xbar_rows=64, sparsity_skip=skip)
            cfg = base.with_quant(qcfg)
            params = shard_aligned_sparsify(
                init_model(jax.random.PRNGKey(0), cfg))
            packed = pack_tree_psq(params, qcfg, PackedModelCache(),
                                   mesh=mesh)
            eng = ServeEngine(params=packed, cfg=cfg,
                              ecfg=EngineConfig(max_batch=2, max_len=48),
                              mesh=mesh)
            rng = np.random.RandomState(5)
            for _ in range(3):
                eng.submit(rng.randint(0, cfg.vocab_size, size=6),
                           max_new_tokens=5)
            outs[skip] = [r.output for r in eng.run()]
        assert outs[True] == outs[False]


class TestBenchHarnessSmoke:
    def test_fig5a_sparsities_knob(self):
        from benchmarks.fig5a_sparsity import rows_to_json, run
        rows = run(sparsities=[0.0, 0.5])
        assert len(rows) == 2
        parsed = rows_to_json(rows)
        assert parsed[0]["reduction"] == 0.0
        assert parsed[1]["reduction"] > 0.2      # paper: 24% at 50%

    def test_run_py_smoke_emits_valid_json(self, tmp_path):
        out = tmp_path / "bench.json"
        env = dict(os.environ)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(repo, "src"), repo,
             env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--smoke",
             "--only", "fig5a", "--json", str(out)],
            cwd=repo, env=env, capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr
        data = json.loads(out.read_text())
        assert data["failed"] == []
        names = [r["name"] for r in data["rows"]]
        # --smoke shrinks the grid to the three-point smoke grid
        assert names == ["fig5a/sparsity_00", "fig5a/sparsity_50",
                         "fig5a/sparsity_90"]
