"""The slot pool's K/V commit: one scatter in the pool's own layout.

``models.decode._commit_kv`` writes each slot's new rows at its own
length with one scatter per leaf. Before that it was a ``vmap`` of
``dynamic_update_slice`` over the slot axis (with a read-back of the old
row for retired slots), which XLA lowers by transposing the whole pool.
That formulation stays here as the oracle: the scatter must match it
bit for bit, on random pools, for every shape a caller commits.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import decode as D

jax.config.update("jax_platform_name", "cpu")


def _commit_kv_vmapped(kv, upd, length, step_mask=None):
    """The former vector-length commit: a vmapped per-slot update.

    The retired-slot read-back takes the update's width ``W``; at
    ``W = 1``, the only width its callers masked, this is the former
    code exactly.
    """
    width = upd["k_new"].shape[2]
    if step_mask is not None:
        read = jax.vmap(
            lambda c, l: jax.lax.dynamic_slice_in_dim(c, l, width, axis=1),
            in_axes=(1, 0), out_axes=1,
        )
        m = step_mask[None, :, None, None, None]
        upd = {
            "k_new": jnp.where(m, upd["k_new"], read(kv["k"], length)),
            "v_new": jnp.where(m, upd["v_new"], read(kv["v"], length)),
        }
    write = jax.vmap(
        lambda c, u, l: jax.lax.dynamic_update_slice(c, u, (0, l, 0, 0)),
        in_axes=(1, 1, 0), out_axes=1,
    )
    return {
        "k": write(kv["k"], upd["k_new"], length),
        "v": write(kv["v"], upd["v_new"], length),
    }


def _pool_shape(arch: str, leaf: str, slots: int = 5, max_len: int = 16):
    """The (layers or groups, slots, S, kv_heads, head_dim) shape that
    ``cache_init`` gives ``leaf`` for ``arch`` at the reduced size."""
    cfg = get_config(arch).reduced()
    cache = jax.eval_shape(
        lambda: D.cache_init(None, cfg, slots, max_len, dtype=jnp.float32))
    return cache[leaf]["k"].shape


SHAPES = {
    "dense": ("tinyllama-1.1b", "kv"),
    "hybrid_kv_shared": ("zamba2-7b", "kv_shared"),
}


def _case(shape, width, lengths, masked, seed):
    rng = np.random.default_rng(seed)
    n, b, s, hk, d = shape
    kv = {k: jnp.asarray(rng.standard_normal(shape), jnp.float32)
          for k in ("k", "v")}
    upd = {k: jnp.asarray(rng.standard_normal((n, b, width, hk, d)),
                          jnp.float32) for k in ("k_new", "v_new")}
    length = jnp.asarray(lengths, jnp.int32)
    mask = None
    if masked:
        live = rng.random(b) < 0.5
        live[:2] = False, True          # at least one of each
        mask = jnp.asarray(live)
    return kv, upd, length, mask


def _lengths(kind, b, s, width, seed):
    rng = np.random.default_rng(seed + 1)
    if kind == "random":
        return rng.integers(0, s - width + 1, size=b)
    if kind == "last_row":
        return np.full(b, s - 1)
    # past the last start: dynamic_update_slice clamps these to S - W
    return np.asarray([s - 1, s, s + 3] + [s - width] * (b - 3))


@pytest.mark.parametrize("lengths", ["random", "last_row", "past_end"])
@pytest.mark.parametrize("masked", [False, True], ids=["all_live", "masked"])
@pytest.mark.parametrize("width", [1, 3])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_scatter_commit_matches_vmapped_update(shape, width, masked, lengths):
    pool = _pool_shape(*SHAPES[shape])
    seed = 10 * width + masked
    ln = _lengths(lengths, pool[1], pool[2], width, seed)
    kv, upd, length, mask = _case(pool, width, ln, masked, seed)

    got = jax.jit(D._commit_kv)(kv, upd, length, mask)
    want = jax.jit(_commit_kv_vmapped)(kv, upd, length, mask)
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got[leaf]),
                                      np.asarray(want[leaf]))
        if mask is not None:
            # retired slots: every row of the pool left as it was
            off = ~np.asarray(mask)
            np.testing.assert_array_equal(np.asarray(got[leaf])[:, off],
                                          np.asarray(kv[leaf])[:, off])


def test_scalar_length_keeps_dynamic_update_slice():
    """Static batching: one position for the whole batch, unchanged."""
    pool = _pool_shape("tinyllama-1.1b", "kv")
    kv, upd, _, _ = _case(pool, 1, np.zeros(pool[1]), False, 11)
    got = D._commit_kv(kv, upd, jnp.asarray(4, jnp.int32))
    want = jax.lax.dynamic_update_slice(kv["k"], upd["k_new"],
                                        (0, 0, 4, 0, 0))
    np.testing.assert_array_equal(np.asarray(got["k"]), np.asarray(want))


def test_commit_lowers_to_one_scatter_per_leaf():
    """The vector-length commit is a scatter, with no transpose of the
    pool, in the program as XLA receives it."""
    pool = _pool_shape("tinyllama-1.1b", "kv")
    kv, upd, length, mask = _case(pool, 1, np.zeros(pool[1]), True, 5)
    hlo = jax.jit(D._commit_kv).lower(kv, upd, length, mask).as_text()
    assert hlo.count('"stablehlo.scatter"(') == 2
    pool_ty = "tensor<" + "x".join(map(str, pool)) + "xf32>"
    transposes = [line for line in hlo.splitlines()
                  if "stablehlo.transpose" in line
                  and line.rstrip().endswith(pool_ty)]
    assert transposes == []
