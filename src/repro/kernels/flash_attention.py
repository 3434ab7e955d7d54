"""Pallas flash-attention kernel (TPU): VMEM-resident online softmax.

§Perf finding (EXPERIMENTS.md, qwen3 hillclimb): XLA-level chunked
attention does NOT cut HBM traffic — the per-block accumulator spills to
HBM every loop iteration, so measured bytes went UP 28 %. The fix has to
be a fused kernel whose running (m, l, acc) statistics live in VMEM
across the whole KV sweep; then HBM sees exactly q+k+v+out. This module
is that kernel:

  * grid = (batch*kv_head*group, q_blocks); each program owns one q tile,
  * K/V stream through VMEM via BlockSpec; the online-softmax loop runs
    in-register/VMEM (jax.lax.fori_loop over KV tiles),
  * causal + sliding-window masks applied per tile; tiles fully in the
    causal future are skipped via the loop bound (halves the sweep).

Validated in interpret mode against the naive SDPA oracle.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, kv_len: int, kv_block: int,
                  causal: bool, window: int, q_block: int, scale: float):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # (Lq, D)
    lq, d = q.shape
    q_start = qi * q_block

    n_kv = kv_len // kv_block
    if causal:
        # last kv tile that can be visible to this q tile
        last = (q_start + lq - 1) // kv_block + 1
        n_iter = jnp.minimum(n_kv, last)
    else:
        n_iter = n_kv

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(i * kv_block, kv_block), :]
        v = v_ref[0, pl.ds(i * kv_block, kv_block), :]
        logits = jax.lax.dot_general(
            q, k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
        )                                              # (Lq, Lkv)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (lq, kv_block), 0)
        kpos = i * kv_block + jax.lax.broadcasted_iota(
            jnp.int32, (lq, kv_block), 1
        )
        mask = jnp.ones((lq, kv_block), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[:, None])
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((lq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((lq,), jnp.float32)
    a0 = jnp.zeros((lq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_iter, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_block", "kv_block", "interpret"),
)
def flash_attention(
    q: jax.Array,        # (BH, S, D)  batch*heads flattened
    k: jax.Array,        # (BH, S_kv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_block: int = 256,
    kv_block: int = 512,
    interpret: bool = True,
) -> jax.Array:
    bh, s, d = q.shape
    s_kv = k.shape[1]
    lq = min(q_block, s)
    lkv = min(kv_block, s_kv)
    assert s % lq == 0 and s_kv % lkv == 0, "pad seq to block multiples"
    grid = (bh, s // lq)
    return pl.pallas_call(
        functools.partial(
            _flash_kernel, kv_len=s_kv, kv_block=lkv, causal=causal,
            window=window, q_block=lq, scale=1.0 / math.sqrt(d),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, lq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, s_kv, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, s_kv, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, lq, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        interpret=interpret,
    )(q, k, v)


def flash_attention_gqa(q, k, v, causal=True, window=0, **kw):
    """(B,S,H,D) x (B,Skv,Hk,D) convenience wrapper (expands GQA groups)."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    g = h // hk
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(
        b * h, k.shape[1], d
    )
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(
        b * h, v.shape[1], d
    )
    o = flash_attention(qf, kf, vf, causal=causal, window=window, **kw)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3).reshape(b, s, h * d)
