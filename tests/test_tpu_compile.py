"""Compile the main-path Pallas kernels for a described TPU v5e.

Interpret mode never checks Mosaic's tiling rules; these compiles do,
with no chip attached: the TPU compiler lowers each kernel for a
``v5e:2x2`` topology description. Shapes are tinyllama-1.1b's published
widths (d_model 2048, 4 KV heads of 64, d_ff 5632) at 8 decode rows and
one prefill size, whole and as one shard of a 2-way ``model`` axis.
The decode program itself is compiled at the benchmark's widths to
count the copies XLA makes of the whole KV slot pool.

The topology is described inside a module fixture, never at import:
only the process running this file loads the TPU library.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.int4_matmul import int4_matmul_kernel
from repro.kernels.occupancy import column_occupancy
from repro.kernels.psq_matmul import psq_matmul_kernel
from repro.models import decode as D

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


# -- the decode program's KV slot pool ---------------------------------------

# widths of the benchmark's configurations (d_model, d_ff, heads, KV heads,
# head_dim, context), 4 layers, 32 slots, vocabulary 32000
POOL_WIDTHS = {
    "mistral-7b": (4096, 14336, 32, 8, 128, 1792),
    "h2o-danube-3-4b": (3840, 10240, 32, 8, 120, 2048),
}
POOL_LAYERS, POOL_SLOTS, POOL_VOCAB = 4, 32, 32000


def _psq_decoder(one_chip, d, ff, heads, kv_heads, head_dim):
    """(ArchConfig, abstract params) of a dense decoder whose projections
    are scan-stacked ``PackedLayer``s served by the reference backend
    (Pallas cannot lower on a CPU host; the pool's copies do not depend
    on the projection kernel)."""
    from repro.configs.base import ArchConfig
    from repro.core.config import QuantConfig
    from repro.core.quant import QuantSpec
    from repro.serve.cache import PackedLayer

    n, n_bits = POOL_LAYERS, 4
    qcfg = QuantConfig(mode="psq", psq_levels="ternary",
                       xbar_rows=XBAR_ROWS,
                       spec=QuantSpec(n_bits_a=n_bits, n_bits_w=n_bits,
                                      n_bits_sf=n_bits),
                       kernel_backend="reference")
    arch = ArchConfig(name="pool", family="dense", n_layers=n, d_model=d,
                      n_heads=heads, n_kv_heads=kv_heads, d_ff=ff,
                      vocab_size=POOL_VOCAB, head_dim=head_dim, quant=qcfg)

    def packed(k, o):
        return PackedLayer(
            cfg=qcfg, w_codes=_sds((n, k, o), one_chip, jnp.int8),
            s_w=_sds((n,), one_chip),
            sf_q=_sds((n, -(-k // XBAR_ROWS), n_bits, n_bits, o), one_chip),
            alpha=_sds((n,), one_chip), step_x=_sds((n,), one_chip),
            sigma=_sds((n, n_bits), one_chip),
            kappa=_sds((n, n_bits), one_chip))

    q, kv = heads * head_dim, kv_heads * head_dim
    params = {
        "embed": {"table": _sds((POOL_VOCAB, d), one_chip)},
        "final_norm": {"scale": _sds((d,), one_chip)},
        "lm_head": {"w": _sds((d, POOL_VOCAB), one_chip)},
        "blocks": {
            "norm1": {"scale": _sds((n, d), one_chip)},
            "norm2": {"scale": _sds((n, d), one_chip)},
            "attn": {"wq": packed(d, q), "wk": packed(d, kv),
                     "wv": packed(d, kv), "wo": packed(q, d)},
            "mlp": {"gate": packed(d, ff), "up": packed(d, ff),
                    "down": packed(ff, d)},
        },
    }
    return arch, params


def _pool_copies(one_chip, config):
    """Ops of the compiled ``decode_multi_step`` (horizon 1, cache
    donated, as the engine runs it) that produce a whole-pool array by
    a copy, a transpose or a copy fusion."""
    d, ff, heads, kv_heads, head_dim, seq = POOL_WIDTHS[config]
    arch, params = _psq_decoder(one_chip, d, ff, heads, kv_heads, head_dim)
    cache = jax.eval_shape(lambda: D.cache_init(
        None, arch, POOL_SLOTS, seq, dtype=jnp.float32))
    cache = jax.tree.map(lambda a: _sds(a.shape, one_chip, a.dtype), cache)
    vec = lambda dt: _sds((POOL_SLOTS,), one_chip, dt)
    step = jax.jit(
        lambda p, c, last, live, eos, budget: D.decode_multi_step(
            p, arch, c, last, live, eos, budget, 1),
        donate_argnums=(1,))
    text = step.lower(params, cache, vec(jnp.int32), vec(jnp.bool_),
                      vec(jnp.int32), vec(jnp.int32)).compile().as_text()
    pool = "f32[%d,%d,%d,%d,%d]" % (POOL_LAYERS, POOL_SLOTS, seq, kv_heads,
                                    head_dim)
    op = re.compile(r"%([\w.-]+) = " + re.escape(pool) + r"\{[^}]*\} "
                    r"([\w-]+)\(")
    copies = []
    for name, opcode in op.findall(text):
        if (opcode in ("copy", "transpose") or
                (opcode == "fusion" and ("copy" in name
                                         or "transpose" in name))):
            copies.append(name)
    return copies


def test_decode_commits_kv_without_copying_the_pool(one_chip):
    """At 128-wide heads the pool keeps one layout through the decode
    loop: the per-slot commit is an in-place scatter, so neither it nor
    the loop's entry and exit copy the pool."""
    assert _pool_copies(one_chip, "mistral-7b") == []


def test_decode_pool_copies_fall_at_120_wide_heads(one_chip, monkeypatch):
    """A 120-wide minor dim gets the sequence axis as its default minor
    layout, which the scatter does not take: the layout copies at the
    loop's entry and exit stay, the commit's transposes go."""
    from test_kv_commit import _commit_kv_vmapped

    now = _pool_copies(one_chip, "h2o-danube-3-4b")
    monkeypatch.setattr(D, "_commit_kv", _commit_kv_vmapped)
    before = _pool_copies(one_chip, "h2o-danube-3-4b")
    assert len(now) < len(before)
