"""Paged decode attention: block-table indirection inside the kernel.

Single-token decode attention against the paged KV pool
(``models/decode.paged_cache_init``): each query attends its slot's
pages through a block table instead of a contiguous stripe. Two
implementations share one contract (registered through
``kernels/registry.py`` as the ``paged_attention`` entry point):

``paged_attention_ref``
  Pure-jnp oracle — gathers the slot's pages into the contiguous view
  and runs exactly the concat-new-column softmax of
  ``models.attention.decode_attention``, so it is bit-compatible with
  the contiguous decode path. Conformance baseline.

``paged_attention_kernel``
  Pallas kernel, grid ``(batch, kv_head)``: each program walks its
  slot's block table with an online-softmax ``fori_loop`` — one page of
  K/V live at a time, never materializing the gathered
  ``(B, max_len, H_kv, D)`` view — then folds the new token's K/V in as
  a final column. ``interpret=True`` runs anywhere (the CI path);
  compiled mode is the TPU/GPU serving fast path.

Contract (all backends)::

  paged_attention(q, k_pool, v_pool, block_tables, lengths,
                  k_new, v_new) -> ctx

  q            (B, H, D)       this step's queries, RoPE applied
  k/v_pool     (NB, bs, Hk, D) ONE layer's page pool
  block_tables (B, MB) int32   page ids, sequence order (0 = trash page)
  lengths      (B,)    int32   per-slot token counts (past tokens only)
  k/v_new      (B, Hk, D)      this token's K/V (enters the softmax as
                               an explicit extra column, NOT yet in the
                               pool — the caller commits it after the
                               layer scan)
  ctx          (B, H, D)

Sliding-window attention is not part of the kernel contract — the
gather-based inline path in ``models/decode.decode_step_paged`` handles
windowed families.

    >>> import jax, jax.numpy as jnp
    >>> q = jnp.ones((2, 4, 8)); kn = jnp.ones((2, 2, 8))
    >>> pool = jnp.zeros((5, 4, 2, 8))
    >>> bt = jnp.zeros((2, 3), jnp.int32)
    >>> lengths = jnp.zeros((2,), jnp.int32)
    >>> paged_attention_ref(q, pool, pool, bt, lengths, kn, kn).shape
    (2, 4, 8)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def paged_attention_ref(q, k_pool, v_pool, block_tables, lengths,
                        k_new, v_new) -> jax.Array:
    """Gather-based oracle, decode-attention math (see module contract)."""
    b, h, d = q.shape
    nb, bs, hk, _ = k_pool.shape
    g = h // hk
    mb = block_tables.shape[1]
    kg = k_pool[block_tables].reshape(b, mb * bs, hk, d)
    vg = v_pool[block_tables].reshape(b, mb * bs, hk, d)
    qh = q.reshape(b, 1, hk, g, d)
    logits = jnp.einsum(
        "bskgd,btkd->bkgst", qh.astype(kg.dtype), kg,
        preferred_element_type=jnp.float32,
    )
    kpos = jnp.arange(mb * bs)
    valid = kpos[None, :] < lengths[:, None]
    logits = jnp.where(valid[:, None, None, None], logits, NEG_INF)
    logit_new = jnp.einsum(
        "bskgd,btkd->bkgst", qh.astype(k_new.dtype), k_new[:, None],
        preferred_element_type=jnp.float32,
    )
    scale = 1.0 / math.sqrt(d)
    full = jnp.concatenate([logits, logit_new], axis=-1) * scale
    probs = jax.nn.softmax(full.astype(jnp.float32), axis=-1)
    p_past, p_new = probs[..., :-1], probs[..., -1:]
    ctx = jnp.einsum(
        "bkgst,btkd->bskgd", p_past.astype(kg.dtype), vg,
        preferred_element_type=jnp.float32,
    ) + jnp.einsum(
        "bkgst,btkd->bskgd", p_new.astype(v_new.dtype), v_new[:, None],
        preferred_element_type=jnp.float32,
    )
    return ctx.astype(q.dtype).reshape(b, h, d)


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref,
                  o_ref, *, bs: int, scale: float):
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
    g, d = q.shape
    bt_row = bt_ref[0]                                   # (MB,)
    length = len_ref[0]
    n_iter = (length + bs - 1) // bs

    def body(i, carry):
        m, l, acc = carry
        blk = bt_row[i]
        # one page of this program's kv head, streamed through VMEM
        k = k_ref[blk, :, 0, :]                          # (bs, D)
        v = v_ref[blk, :, 0, :]
        logits = jax.lax.dot_general(
            q, k.astype(jnp.float32), (((1,), (1,)), ((), ()))
        )                                                # (G, bs)
        pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (g, bs), 1)
        logits = jnp.where(pos < length, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[:, None])
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot(
            p, v.astype(jnp.float32), preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((g,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((g,), jnp.float32)
    a0 = jnp.zeros((g, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_iter, body, (m0, l0, a0))

    # the new token's own K/V as the final online-softmax column
    kn = kn_ref[0, 0, 0].astype(jnp.float32)             # (D,)
    vn = vn_ref[0, 0, 0].astype(jnp.float32)
    col = q @ kn                                         # (G,)
    m2 = jnp.maximum(m, col)
    corr = jnp.exp(m - m2)
    p_new = jnp.exp(col - m2)
    l2 = l * corr + p_new
    acc2 = acc * corr[:, None] + p_new[:, None] * vn[None, :]
    o_ref[0, 0] = (acc2 / jnp.maximum(l2, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention_kernel(q, k_pool, v_pool, block_tables, lengths,
                           k_new, v_new, *, interpret: bool = True
                           ) -> jax.Array:
    """Pallas paged attention (see module contract)."""
    b, h, d = q.shape
    nb, bs, hk, _ = k_pool.shape
    g = h // hk
    mb = block_tables.shape[1]
    q4 = q.reshape(b, hk, g, d)
    kn4 = k_new.reshape(b, hk, 1, d)
    vn4 = v_new.reshape(b, hk, 1, d)
    out = pl.pallas_call(
        functools.partial(_paged_kernel, bs=bs, scale=1.0 / math.sqrt(d)),
        grid=(b, hk),
        in_specs=[
            pl.BlockSpec((1, mb), lambda i, j: (i, 0)),          # tables
            pl.BlockSpec((1,), lambda i, j: (i,)),               # lengths
            pl.BlockSpec((1, 1, g, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((nb, bs, 1, d), lambda i, j: (0, 0, j, 0)),
            pl.BlockSpec((nb, bs, 1, d), lambda i, j: (0, 0, j, 0)),
            pl.BlockSpec((1, 1, 1, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, d), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hk, g, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q4, k_pool, v_pool, kn4, vn4)
    return out.reshape(b, h, d)
