"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode never checks Mosaic's tiling rules; these compiles do,
with no chip attached: the TPU compiler lowers each kernel for a
``v5e:2x2`` topology description. Shapes are tinyllama-1.1b's published
widths (d_model 2048, 4 KV heads of 64, d_ff 5632) at 8 decode rows and
one prefill size, whole and as one shard of a 2-way ``model`` axis.

The topology is described inside a module fixture, never at import:
only the process running this file loads the TPU library.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.int4_matmul import int4_matmul_kernel
from repro.kernels.occupancy import column_occupancy
from repro.kernels.psq_matmul import psq_matmul_kernel

# (K, O) of tinyllama-1.1b's projections: q/o, k/v, gate/up, down
TINYLLAMA_KO = [(2048, 2048), (2048, 256), (2048, 5632), (5632, 2048)]
DECODE_ROWS, PREFILL_ROWS = 8, 2048
XBAR_ROWS = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _skip_occupancy(k, o):
    """Occupancy with every 4th (tile, column block) weight slab zero."""
    codes = np.ones((k, o), np.int8)
    for t in range(k // XBAR_ROWS):
        for c in range(o // 128):
            if (t + c) % 4 == 0:
                codes[t * XBAR_ROWS:(t + 1) * XBAR_ROWS,
                      c * 128:(c + 1) * 128] = 0
    return column_occupancy(codes, xbar_rows=XBAR_ROWS, n_w=4)


def _compile_psq(one_chip, rows, k, o, *, skip=False, fuse_planes=False):
    t = -(-k // XBAR_ROWS)
    compiled = psq_matmul_kernel.lower(
        _sds((rows, k), one_chip), _sds((k, o), one_chip),
        _sds((t, 4, 4, o), one_chip), _sds((), one_chip),
        n_a=4, n_w=4, levels="ternary", xbar_rows=XBAR_ROWS,
        fuse_planes=fuse_planes,
        occupancy=_skip_occupancy(k, o) if skip else None,
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("skip", [False, True], ids=["dense", "skip"])
@pytest.mark.parametrize("rows", [DECODE_ROWS, PREFILL_ROWS])
@pytest.mark.parametrize("k,o", TINYLLAMA_KO)
def test_psq_matmul_compiles(one_chip, k, o, rows, skip):
    _compile_psq(one_chip, rows, k, o, skip=skip)


@pytest.mark.parametrize("rows", [DECODE_ROWS, PREFILL_ROWS])
def test_psq_matmul_fused_planes_compiles(one_chip, rows):
    _compile_psq(one_chip, rows, 2048, 5632, fuse_planes=True)


@pytest.mark.parametrize("k,o", TINYLLAMA_KO)
def test_psq_matmul_model_shard_compiles(one_chip, k, o):
    """One device's columns under a 2-way ``model`` axis (O/2)."""
    _compile_psq(one_chip, DECODE_ROWS, k, o // 2)


def test_psq_matmul_compiles_under_highest_precision(one_chip):
    """The in-kernel {0,1} dots pin their precision: a caller's
    ``default_matmul_precision("highest")`` must not reach Mosaic."""
    with jax.default_matmul_precision("highest"):
        _compile_psq(one_chip, DECODE_ROWS, 2048, 2048)


@pytest.mark.parametrize("rows", [DECODE_ROWS, PREFILL_ROWS])
@pytest.mark.parametrize("k,o", [(2048, 5632), (5632, 2048)])
def test_int4_matmul_compiles(one_chip, k, o, rows):
    compiled = int4_matmul_kernel.lower(
        _sds((rows, k), one_chip), _sds((k // 2, o), one_chip, jnp.int8),
        _sds((o,), one_chip), interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
