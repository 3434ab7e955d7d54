"""GQA attention: full/sliding-window causal, cross, and cached decode.

All projections are PSQLinear (the HCiM technique applies to every QKVO
matmul). The decode path consumes a KV cache laid out (B, S, H_kv, D)
so the sequence dim can be sharded across the data axis for 500k-context
serving (the softmax reduction over a sharded axis lowers to
collective-assisted reductions under pjit).
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import QuantConfig
from repro.core.psq_linear import apply_linear, init_linear
from repro.models.layers import apply_norm, apply_rope, init_norm
from repro.parallel.sharding import constrain

Params = Dict
NEG_INF = -1e9


class AttnConfig(NamedTuple):
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qk_norm: bool = False
    sliding_window: int = 0        # 0 => full attention
    rope_theta: float = 10000.0
    use_bias: bool = False
    causal: bool = True
    impl: str = "naive"            # naive | flash (chunked online softmax)
    kv_block: int = 1024


def init_attention(key: jax.Array, cfg: AttnConfig, quant: QuantConfig) -> Params:
    ks = jax.random.split(key, 6)
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p: Params = {
        "wq": init_linear(ks[0], d, h * hd, quant, use_bias=cfg.use_bias),
        "wk": init_linear(ks[1], d, hk * hd, quant, use_bias=cfg.use_bias),
        "wv": init_linear(ks[2], d, hk * hd, quant, use_bias=cfg.use_bias),
        "wo": init_linear(ks[3], h * hd, d, quant, use_bias=cfg.use_bias),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_norm("rmsnorm", hd)
        p["k_norm"] = init_norm("rmsnorm", hd)
    return p


@jax.named_scope("qkv")
def _project_qkv(
    p: Params, x: jax.Array, cfg: AttnConfig, quant: QuantConfig,
    positions: jax.Array, xkv: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, Dict]:
    b, s, _ = x.shape
    src = x if xkv is None else xkv
    s_kv = src.shape[1]
    q, st1 = apply_linear(p["wq"], x, quant)
    k, st2 = apply_linear(p["wk"], src, quant)
    v, st3 = apply_linear(p["wv"], src, quant)
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s_kv, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s_kv, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = apply_norm("rmsnorm", p["q_norm"], q)
        k = apply_norm("rmsnorm", p["k_norm"], k)
    if cfg.rope_theta > 0 and xkv is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")
    v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    stats = {}
    for st in (st1, st2, st3):
        stats.update(st)
    return q, k, v, stats


def _sdpa(
    q: jax.Array,            # (B, S, H, D)
    k: jax.Array,            # (B, S_kv, Hk, D)
    v: jax.Array,
    causal: bool,
    sliding_window: int,
    q_offset: jax.Array | int = 0,
) -> jax.Array:
    b, s, h, d = q.shape
    s_kv = k.shape[1]
    groups = h // k.shape[2]
    qh = q.reshape(b, s, k.shape[2], groups, d)
    logits = jnp.einsum("bskgd,btkd->bkgst", qh, k) / math.sqrt(d)
    qpos = jnp.arange(s) + q_offset
    kpos = jnp.arange(s_kv)
    mask = jnp.ones((s, s_kv), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if sliding_window > 0:
        mask &= kpos[None, :] > qpos[:, None] - sliding_window
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, h * d)


def _sdpa_flash(
    q: jax.Array,            # (B, S, H, D)
    k: jax.Array,            # (B, S_kv, Hk, D)
    v: jax.Array,
    causal: bool,
    sliding_window: int,
    kv_block: int = 1024,
) -> jax.Array:
    """Chunked online-softmax attention (flash-style, lax.scan over KV).

    Never materializes the (S, S_kv) score matrix in HBM: per KV block
    only an (B, Hk, G, S, L) tile is live, with running (m, l, acc)
    statistics carried in f32 — the memory-roofline fix for the 32k
    cells (§Perf). Bit-compatible with _sdpa up to fp reassociation.
    """
    b, s, h, d = q.shape
    s_kv = k.shape[1]
    hk = k.shape[2]
    g = h // hk
    L = min(kv_block, s_kv)
    nb = math.ceil(s_kv / L)
    pad = nb * L - s_kv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qh = q.reshape(b, s, hk, g, d)
    kb = jnp.moveaxis(k.reshape(b, nb, L, hk, d), 1, 0)   # (NB,B,L,Hk,D)
    vb = jnp.moveaxis(v.reshape(b, nb, L, hk, d), 1, 0)
    qpos = jnp.arange(s)
    scale = 1.0 / math.sqrt(d)

    def step(carry, inp):
        m, l, acc = carry                                  # (B,Hk,G,S), ..., (B,Hk,G,S,D)
        kc, vc, blk = inp
        logits = jnp.einsum(
            "bskgd,blkd->bkgsl", qh, kc
        ).astype(jnp.float32) * scale                      # (B,Hk,G,S,L)
        kpos = blk * L + jnp.arange(L)
        mask = jnp.ones((s, L), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if sliding_window > 0:
            mask &= kpos[None, :] > qpos[:, None] - sliding_window
        mask &= (kpos < s_kv)[None, :]
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        corr = jnp.exp(m - m_new)
        p_blk = jnp.exp(logits - m_new[..., None])
        l_new = l * corr + jnp.sum(p_blk, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgsl,blkd->bkgsd", p_blk.astype(q.dtype), vc
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hk, g, s), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hk, g, s), jnp.float32)
    a0 = jnp.zeros((b, hk, g, s, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (kb, vb, jnp.arange(nb))
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1)                          # (B,S,Hk,G,D)
    return out.reshape(b, s, h * d).astype(q.dtype)


def apply_attention(
    p: Params, x: jax.Array, cfg: AttnConfig, quant: QuantConfig,
    positions: Optional[jax.Array] = None,
    xkv: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict]:
    """Full (training/prefill) attention; cross-attention when xkv given."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v, stats = _project_qkv(p, x, cfg, quant, positions, xkv)
    causal = cfg.causal and xkv is None
    window = cfg.sliding_window if xkv is None else 0
    with jax.named_scope("attention"):
        if cfg.impl == "flash":
            ctx = _sdpa_flash(q, k, v, causal, window,
                              kv_block=cfg.kv_block)
        else:
            ctx = _sdpa(q, k, v, causal, window)
        ctx = constrain(ctx, "batch", "seq", "qkv_features")
        y, st = apply_linear(p["wo"], ctx, quant)
    stats.update(st)
    return y, stats


# ---------------------------------------------------------------------------
# Cached decode
# ---------------------------------------------------------------------------

def init_kv_cache(
    batch: int, max_len: int, n_kv_heads: int, head_dim: int,
    dtype=jnp.bfloat16, long_context: bool = False,
) -> Dict:
    seq_axis = "long_kv_seq" if long_context else "kv_seq"
    k = constrain(
        jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        "batch", seq_axis, "kv_heads", "head_dim",
    )
    v = constrain(
        jnp.zeros((batch, max_len, n_kv_heads, head_dim), dtype),
        "batch", seq_axis, "kv_heads", "head_dim",
    )
    return {"k": k, "v": v, "length": jnp.zeros((), jnp.int32)}


def decode_attention(
    p: Params, x: jax.Array, cache: Dict, cfg: AttnConfig, quant: QuantConfig,
    defer_update: bool = False,
) -> Tuple[jax.Array, Dict, Dict]:
    """One-token decode step against a (possibly sequence-sharded) cache.

    x: (B, 1, d). Returns (y, new_cache, stats); with ``defer_update``
    returns (y, (k_new, v_new), stats) and NEVER writes the cache — the
    new token enters the softmax as an explicit extra column. Inside the
    layer scan this is essential: materializing an updated cache per
    layer compiles to a full stacked-cache copy every iteration
    (measured 40x the necessary decode traffic — EXPERIMENTS.md §Perf);
    the caller instead commits all layers' (k_new, v_new) with ONE tiny
    dynamic-update-slice after the scan.

    ``cache["length"]`` may be a scalar (uniform batch, the classic
    static path) or an ``(B,)`` vector of per-row lengths (the
    continuous-batching slot pool): masking, RoPE positions and cache
    writes are all per-row in the vector case.
    """
    b = x.shape[0]
    length = cache["length"]
    lv = jnp.broadcast_to(length, (b,)) if jnp.ndim(length) == 0 else length
    pos = lv[:, None]
    q, k_new, v_new, stats = _project_qkv(p, x, cfg, quant, pos)
    with jax.named_scope("attention"):
        k, v = cache["k"], cache["v"]
        s_kv = k.shape[1]
        groups = cfg.n_heads // cfg.n_kv_heads
        qh = q.reshape(b, 1, cfg.n_kv_heads, groups, cfg.head_dim)
        # compute in the cache dtype (bf16) with f32 accumulation: upcasting
        # `k` would convert (and loop-carry) the entire stacked cache in f32
        logits = jnp.einsum(
            "bskgd,btkd->bkgst", qh.astype(k.dtype), k,
            preferred_element_type=jnp.float32,
        )
        kpos = jnp.arange(s_kv)
        valid = kpos[None, :] < lv[:, None]              # past tokens only
        if cfg.sliding_window > 0:
            valid &= kpos[None, :] > lv[:, None] - cfg.sliding_window
        logits = jnp.where(valid[:, None, None, None], logits, NEG_INF)
        # the new token's own k as an explicit extra column
        logit_new = jnp.einsum(
            "bskgd,btkd->bkgst", qh.astype(k_new.dtype),
            k_new.astype(k_new.dtype), preferred_element_type=jnp.float32,
        )
        scale = 1.0 / math.sqrt(cfg.head_dim)
        full = jnp.concatenate([logits, logit_new], axis=-1) * scale
        probs = jax.nn.softmax(full.astype(jnp.float32), axis=-1)
        p_past, p_new = probs[..., :-1], probs[..., -1:]
        ctx = jnp.einsum(
            "bkgst,btkd->bskgd", p_past.astype(k.dtype), v,
            preferred_element_type=jnp.float32,
        ) + jnp.einsum(
            "bkgst,btkd->bskgd", p_new.astype(v_new.dtype), v_new,
            preferred_element_type=jnp.float32,
        )
        ctx = ctx.astype(q.dtype).reshape(b, 1, cfg.n_heads * cfg.head_dim)
        y, st = apply_linear(p["wo"], ctx, quant)
    stats.update(st)
    if defer_update:
        return y, (k_new, v_new), stats
    with jax.named_scope("kv_commit"):
        if jnp.ndim(length) == 0:
            k = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k_new.astype(cache["k"].dtype), length, axis=1
            )
            v = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v_new.astype(cache["v"].dtype), length, axis=1
            )
        else:
            write = jax.vmap(
                lambda c, u, l: jax.lax.dynamic_update_slice(c, u, (l, 0, 0))
            )
            k = write(cache["k"], k_new.astype(cache["k"].dtype), lv)
            v = write(cache["v"], v_new.astype(cache["v"].dtype), lv)
    new_cache = {"k": k, "v": v, "length": length + 1}
    return y, new_cache, stats


def cross_attention_cache(
    p: Params, enc_out: jax.Array, cfg: AttnConfig, quant: QuantConfig
) -> Dict:
    """Precompute encoder K/V for decode-time cross-attention."""
    b, s, _ = enc_out.shape
    k, _ = apply_linear(p["wk"], enc_out, quant)
    v, _ = apply_linear(p["wv"], enc_out, quant)
    return {
        "k": k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim),
        "v": v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim),
    }


def decode_cross_attention(
    p: Params, x: jax.Array, xcache: Dict, cfg: AttnConfig, quant: QuantConfig
) -> Tuple[jax.Array, Dict]:
    # x: (B, S, d) — S is 1 for classic decode, K+1 for spec verify.
    # Cross-attention has no causal mask and no positions, so any query
    # width attends the full encoder output identically.
    b, s, _ = x.shape
    q, stats = apply_linear(p["wq"], x, quant)
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = apply_norm("rmsnorm", p["q_norm"], q)
    k, v = xcache["k"], xcache["v"]
    groups = cfg.n_heads // cfg.n_kv_heads
    qh = q.reshape(b, s, cfg.n_kv_heads, groups, cfg.head_dim)
    logits = jnp.einsum("bskgd,btkd->bkgst", qh, k.astype(q.dtype))
    logits = logits / math.sqrt(cfg.head_dim)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    ctx = jnp.einsum("bkgst,btkd->bskgd", probs, v.astype(q.dtype))
    ctx = ctx.reshape(b, s, cfg.n_heads * cfg.head_dim)
    y, st = apply_linear(p["wo"], ctx, quant)
    stats.update(st)
    return y, stats
