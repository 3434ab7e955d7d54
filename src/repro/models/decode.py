"""Serving-side execution on the stacked-layer layout.

Caches are *stacked* pytrees (leading dim = #layers in the stack), so
``decode_step`` is a single ``lax.scan`` over (layer params, layer cache)
— one compiled block regardless of depth. Under the active rules table
(``parallel/sharding.py``) per-slot cache leaves follow the ``batch``
axis onto the ``data`` mesh axis; the KV sequence dim stays local except
for the 500k-context cells (``long_kv_seq`` -> ``data``).

``decode_step`` is exactly what launch/dryrun.py lowers for the
``decode_*`` / ``long_500k`` shape cells; ``prefill`` is the parallel
prompt pass that fills the same cache structure (no token-by-token scan:
attention K/V come from the parallel forward, SSM/xLSTM final states
from their chunked forms).

Two KV layouts share the same decode math:

* **contiguous** — one ``(B, max_len, H_kv, D)`` stripe per slot
  (``init_cache``/``cache_init``), the static path and the default
  continuous path. Recurrent families (ssm/xlstm/hybrid) ride this
  layout too: their per-slot state rows (SSM states, mLSTM/sLSTM
  triples, conv buffers — no sequence axis) scatter through the same
  ``cache_insert``, with ``prefill`` threading per-row true lengths so
  right-padded buckets stay bit-exact;
* **paged** — one ``(num_blocks, block_size, H_kv, D)`` page pool per
  layer plus per-slot block tables (``paged_cache_init`` /
  ``decode_step_paged`` / ``prefill_paged_suffix``), the
  continuous-engine layout that enables shared-prefix reuse
  (``serve/paged_kv.py``, docs/memory.md).

The serving engine does not call ``decode_step`` once per token: the
greedy hot loop runs through ``decode_multi_step`` /
``decode_multi_step_paged``, a device-side ``lax.while_loop`` that takes
up to ``decode_horizon`` steps per host round-trip (on-device argmax,
per-slot EOS/budget flags, retirement masks via ``step_mask``).

Named scopes mark the parts of every step in the compiled programs' op
metadata (``op_name``), so a device trace can sum time per part:
``qkv``, ``attention``, ``kv_commit``, ``ffn``, ``lm_head`` and
``sample``. An op under none of them (embedding, norms, loop
bookkeeping, copies XLA inserts) carries no scope.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.psq_linear import apply_linear
from repro.models import attention as attn_mod
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models import xlstm as xlstm_mod
from repro.models.transformer import (
    attn_config,
    encode,
    layer_scan,
    ssm_config,
    stack_plan,
    xlstm_config,
)
from repro.parallel.sharding import constrain

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------

def _kv_zeros(n: int, batch: int, max_len: int, cfg: ArchConfig,
              dtype, long_ctx: bool) -> Dict:
    seq_ax = "long_kv_seq" if long_ctx else "kv_seq"
    shape = (n, batch, max_len, cfg.n_kv_heads, cfg.resolved_head_dim)

    def z():
        # distinct buffers for k and v: a shared zeros array would be the
        # same buffer twice in a donated cache (serve engine donation)
        return constrain(jnp.zeros(shape, dtype),
                         None, "batch", seq_ax, "kv_heads", "head_dim")

    return {"k": z(), "v": z()}


def _constrain_state(tree):
    """Recurrent state pools follow the slot axis onto the mesh.

    Every stacked recurrent leaf — ``(n_layers, batch, ...)`` SSM
    states, mLSTM (C, n, m), sLSTM scalars, conv buffers — has the slot
    ("batch") axis at position 1; the ``recurrent_state -> data`` rule
    (``parallel/sharding.py``) shards it like the KV slot pool. No-op
    without active rules.
    """
    return jax.tree.map(
        lambda a: constrain(
            a, *([None, "recurrent_state"] + [None] * (a.ndim - 2))
        ),
        tree,
    )


def _stack_cache(init_one, n: int):
    if n == 0:
        return None
    return _constrain_state(jax.vmap(lambda _: init_one())(jnp.arange(n)))


def init_cache(
    params: Params, cfg: ArchConfig, batch: int, max_len: int,
    dtype=jnp.bfloat16, enc_out: Optional[jax.Array] = None,
) -> Dict:
    long_ctx = max_len >= 100_000
    plan = stack_plan(cfg)
    cache: Dict[str, Any] = {"length": jnp.zeros((), jnp.int32)}
    if cfg.family in ("dense", "vlm", "moe", "encdec"):
        cache["kv"] = _kv_zeros(cfg.n_layers, batch, max_len, cfg, dtype, long_ctx)
    elif cfg.family == "hybrid":
        g, pg, tail = plan["groups"], plan["per_group"], plan["tail"]
        scfg = ssm_config(cfg)
        cache["ssm_groups"] = _stack_cache(
            lambda: ssm_mod.init_mamba2_cache(batch, scfg), g * pg
        )
        cache["ssm_tail"] = _stack_cache(
            lambda: ssm_mod.init_mamba2_cache(batch, scfg), tail
        )
        cache["kv_shared"] = _kv_zeros(g, batch, max_len, cfg, dtype, long_ctx)
    elif cfg.family == "ssm":
        g, pg, tail = plan["groups"], plan["per_group"], plan["tail"]
        xcfg = xlstm_config(cfg)
        cache["mlstm_groups"] = _stack_cache(
            lambda: xlstm_mod.init_mlstm_cache(batch, xcfg), g * pg
        )
        cache["slstm"] = _stack_cache(
            lambda: xlstm_mod.init_slstm_cache(batch, xcfg), g
        )
        cache["mlstm_tail"] = _stack_cache(
            lambda: xlstm_mod.init_mlstm_cache(batch, xcfg), tail
        )
    if cfg.family == "encdec" and enc_out is not None:
        cross = jax.vmap(
            lambda lp: attn_mod.cross_attention_cache(
                lp["cross"], enc_out, attn_config(cfg), cfg.quant
            )
        )(params["blocks"])
        cache["cross"] = cross
    return cache


# ---------------------------------------------------------------------------
# slot-indexed batch caches (continuous batching)
# ---------------------------------------------------------------------------

def cache_init(
    params: Params, cfg: ArchConfig, n_slots: int, max_len: int,
    dtype=jnp.bfloat16, enc_len: int = 0,
) -> Dict:
    """A decode-slot pool: :func:`init_cache` with per-slot lengths.

    The returned cache is shaped exactly like the static one except
    ``cache["length"]`` is an ``(n_slots,)`` int32 vector, so each slot
    advances independently — :func:`decode_step` masks, positions and
    writes per slot. Fresh slots start at length 0; admit a request with
    :func:`cache_insert`. Under active sharding rules the length vector
    follows the slot ("batch") axis, like every other per-slot leaf.

    ``enc_len > 0`` (encdec only) adds a per-slot cross-attention KV
    pool — ``cache["cross"]["k"/"v"]`` of shape
    ``(n_layers, n_slots, enc_len, H_kv, D)`` — that admission scatters
    each request's encoder-output KV into, exactly like the self KV
    stripes. Free slots hold zeros: cross-attention over an all-zero
    K/V is a uniform softmax times zero values, a harmless constant that
    per-slot masking never lets a live request see.
    """
    cache = init_cache(params, cfg, n_slots, max_len, dtype=dtype)
    if cfg.family == "encdec" and enc_len > 0:
        shape = (cfg.n_layers, n_slots, enc_len,
                 cfg.n_kv_heads, cfg.resolved_head_dim)

        def z():
            return constrain(jnp.zeros(shape, dtype),
                             None, "batch", None, "kv_heads", "head_dim")

        cache["cross"] = {"k": z(), "v": z()}
    cache["length"] = constrain(jnp.zeros((n_slots,), jnp.int32), "batch")
    return cache


def cache_insert(dst: Dict, src: Dict, row, slot, length) -> Dict:
    """Scatter row ``row`` of a prefilled cache into ``slot`` of a live pool.

    ``src`` is the cache returned by :func:`prefill` over a (bucketed)
    prompt batch; ``dst`` is a :func:`cache_init` pool mid-decode. Every
    stacked cache leaf — KV stripes AND recurrent leaves (SSM
    ``state``/``conv``, mLSTM ``C``/``n``/``m``, sLSTM scalars) — has
    the batch axis at position 1, so one generic dynamic-update-slice
    per leaf moves the new request's state in; the
    prompt axis of ``src`` may be shorter than the pool's ``max_len``
    (only the prefilled prefix is copied). ``length`` is the request's
    TRUE prompt length — positions beyond it in ``src`` are right-pad
    junk that stays masked (and is progressively overwritten by decode
    writes, which land exactly at the slot's length).

    ``row``/``slot``/``length`` may be traced scalars: under ``jax.jit``
    this op is shape-stable across admissions (one compile per prefill
    bucket shape).

    A prefilled chunk may also be WIDER than the pool on trailing axes
    — VLM patch positions push the prefill KV width to
    ``patches + bucket``, which can exceed the pool's ``max_len``. Every
    TRUE position is below ``max_len`` (the engine's submit gate bounds
    ``patches + prompt + max_new``), so the overhang is right-pad junk
    and is sliced off before the scatter.
    """
    def ins(d, s_leaf):
        chunk = jax.lax.dynamic_slice_in_dim(s_leaf, row, 1, axis=1)
        if any(cs > ds for cs, ds in zip(chunk.shape[2:], d.shape[2:])):
            chunk = chunk[(slice(None), slice(None))
                          + tuple(slice(0, ds) for ds in d.shape[2:])]
        start = (0, slot) + (0,) * (d.ndim - 2)
        return jax.lax.dynamic_update_slice(d, chunk.astype(d.dtype), start)

    out = {
        k: jax.tree.map(ins, dst[k], src[k])
        for k in dst if k != "length"
    }
    out["length"] = dst["length"].at[slot].set(
        jnp.asarray(length, dst["length"].dtype))
    return out


def hoist_decode_params(params: Params, cfg: ArchConfig) -> Params:
    """Fold per-token-invariant decode constants into served params.

    Mamba2 layers gain ``A = -exp(A_log)`` (``ssm.decode_constants``) so
    :func:`decode_step` stops re-deriving it from weights on every token
    step; other families pass through unchanged. Outputs are
    bit-identical — the same elementwise expression, evaluated once at
    load instead of per step (the serve engine applies this at
    construction; verified by an HLO op-count test).
    """
    if cfg.family != "hybrid":
        return params
    out = dict(params)
    for key in ("mamba_groups", "mamba_tail"):
        blk = params.get(key)
        if blk is not None:
            out[key] = {**blk, "mamba": ssm_mod.decode_constants(blk["mamba"])}
    return out


# ---------------------------------------------------------------------------
# paged cache (fixed page pool + per-slot block tables)
# ---------------------------------------------------------------------------

# families whose decode state is a pure KV cache — the only ones the
# paged layout supports (recurrent state has no sequence axis to page;
# encdec cross-attention KV has no pages and serves through the
# contiguous continuous scheduler instead)
_PAGED_FAMILIES = ("dense", "moe", "vlm")


def _check_paged_family(cfg: ArchConfig) -> None:
    if cfg.family not in _PAGED_FAMILIES:
        raise ValueError(
            f"paged KV cache supports the pure KV-cache families "
            f"{_PAGED_FAMILIES}, got {cfg.family!r}"
        )


def paged_cache_init(
    params: Params, cfg: ArchConfig, n_slots: int, max_len: int,
    block_size: int, num_blocks: int, dtype=jnp.bfloat16,
) -> Dict:
    """A paged decode pool: page-granular KV storage + per-slot lengths.

    Instead of one contiguous ``(n_slots, max_len, ...)`` stripe per
    leaf (:func:`cache_init`), KV lives in ONE pool of ``num_blocks``
    pages of ``block_size`` tokens per layer stack —
    ``(n_layers, num_blocks, block_size, H_kv, D)`` — and a slot reaches
    its sequence through a block table (``serve/paged_kv.py``) passed to
    :func:`decode_step_paged` each step. Page 0 is the trash page free
    slots write into. Under active sharding rules the page axis follows
    the ``kv_blocks`` rule (``data`` mesh axis) and lengths follow
    ``batch``.
    """
    del params
    _check_paged_family(cfg)
    if max_len % block_size:
        raise ValueError(
            f"max_len ({max_len}) must be a multiple of "
            f"block_size ({block_size})"
        )
    shape = (cfg.n_layers, num_blocks, block_size,
             cfg.n_kv_heads, cfg.resolved_head_dim)

    def z():
        # distinct k/v buffers: donation-safe, like _kv_zeros
        return constrain(jnp.zeros(shape, dtype),
                         None, "kv_blocks", None, "kv_heads", "head_dim")

    return {
        "kv": {"k": z(), "v": z()},
        "length": constrain(jnp.zeros((n_slots,), jnp.int32), "batch"),
    }


def paged_cache_insert(dst: Dict, src_kv: Dict, row, slot, block_row,
                       start, total_len) -> Dict:
    """Scatter prefilled K/V rows into a slot's pages.

    ``src_kv`` is a ``{"k", "v"}`` pair of stacked ``(L, B, W, H_kv, D)``
    leaves (a :func:`prefill` cache's ``kv`` for cold admission, or
    :func:`prefill_paged_suffix` output for a prefix hit); token ``t`` of
    row ``row`` lands at sequence position ``start + t`` — page
    ``block_row[(start + t) // bs]``, offset ``(start + t) % bs``.
    Right-pad positions (``start + t >= total_len``) are routed to the
    trash page. ``row``/``slot``/``start``/``total_len`` may be traced:
    one compile per source width ``W``.
    """
    bs = dst["kv"]["k"].shape[2]
    mb = block_row.shape[-1]
    w = src_kv["k"].shape[2]
    t = jnp.arange(w)
    pos = start + t
    bi = jnp.minimum(pos // bs, mb - 1)
    blk = jnp.where(pos < total_len, block_row[bi], 0)
    off = pos % bs

    def ins(pool, s_leaf):
        chunk = jnp.take(s_leaf, row, axis=1).astype(pool.dtype)
        return pool.at[:, blk, off].set(chunk)

    return {
        "kv": {
            "k": ins(dst["kv"]["k"], src_kv["k"]),
            "v": ins(dst["kv"]["v"], src_kv["v"]),
        },
        "length": dst["length"].at[slot].set(
            jnp.asarray(total_len, dst["length"].dtype)),
    }


def _gather_pages(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """(NB, bs, H_kv, D) pool + (B, MB) tables -> (B, MB*bs, H_kv, D).

    Page order in the table is sequence order, so the gathered view is
    value-identical to the contiguous per-slot stripe — which is what
    makes paged decode bit-exact with the contiguous path.
    """
    b, mb = block_tables.shape
    g = pool[block_tables]                 # (B, MB, bs, Hkv, D)
    return g.reshape(b, mb * pool.shape[1], *pool.shape[2:])


@jax.named_scope("kv_commit")
def _commit_kv_paged(kv: Dict, upd: Dict, length: jax.Array,
                     block_tables: jax.Array, step_mask=None) -> Dict:
    """Write all layers' new-token K/V into each slot's current page.

    The paged analogue of :func:`_commit_kv`: position ``length[b]``
    maps through the block table; retired slots' tables point at the
    trash page (and their clamped page index lands there too), so the
    fixed-shape scatter never corrupts live pages. ``step_mask`` (B,)
    bool writes masked-out slots' OLD page contents back (a no-op), so
    a slot that finishes mid-horizon stops touching its pages.
    """
    bs = kv["k"].shape[2]
    b, mb = block_tables.shape
    bi = jnp.minimum(length // bs, mb - 1)
    blk = block_tables[jnp.arange(b), bi]
    off = length % bs

    def wr(pool, new):                      # new: (L, B, 1, Hkv, D)
        val = new[:, :, 0].astype(pool.dtype)
        if step_mask is not None:
            val = jnp.where(step_mask[None, :, None, None], val,
                            pool[:, blk, off])
        return pool.at[:, blk, off].set(val)

    return {"k": wr(kv["k"], upd["k_new"]), "v": wr(kv["v"], upd["v_new"])}


def decode_step_paged(
    params: Params, cfg: ArchConfig, token: jax.Array, cache: Dict,
    block_tables: jax.Array, attn_backend: Optional[str] = None,
    step_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict]:
    """One paged serving step: token (B,1) -> (logits (B,1,V), new cache).

    The inner attention gathers each slot's pages into the same
    ``(B, max_len, H_kv, D)`` view :func:`decode_step` reads, then runs
    the identical per-slot-length decode attention — greedy outputs are
    bit-exact with the contiguous path. ``attn_backend`` instead routes
    the attention core through a registered paged-attention kernel
    (``kernels/paged_attention.py``; ``reference`` / ``pallas-interpret``
    / ``pallas``) that never materializes the gathered view.
    ``step_mask`` (B,) bool makes masked-out slots full no-ops (page
    writes return old contents, lengths freeze) — the retirement mask
    :func:`decode_multi_step_paged` applies to slots that finish
    mid-horizon.
    """
    _check_paged_family(cfg)
    length = cache["length"]
    x = L.apply_embedding(params["embed"], token)

    paged_fn = None
    if attn_backend is not None:
        from repro.kernels import registry as _registry

        paged_fn = _registry.get_backend(attn_backend).paged_attention
        if paged_fn is None:
            raise ValueError(
                f"kernel backend {attn_backend!r} does not implement "
                f"paged_attention"
            )
        if cfg.sliding_window > 0:
            raise ValueError(
                "paged-attention kernels implement full causal attention; "
                "sliding-window families use the inline gather path "
                "(attn_backend=None)"
            )

    def body(x_, xs):
        lp, k_l, v_l = xs
        if paged_fn is None:
            kv = {"k": _gather_pages(k_l, block_tables),
                  "v": _gather_pages(v_l, block_tables)}
            return _attn_decode_one(lp, x_, kv, length, cfg, params=params)
        return _attn_decode_one_paged_kernel(
            lp, x_, k_l, v_l, block_tables, length, cfg, paged_fn
        )

    x, kv_upd = layer_scan(
        body, x, (params["blocks"], cache["kv"]["k"], cache["kv"]["v"]),
        unroll=not cfg.scan_layers,
    )
    new_cache = {
        "kv": _commit_kv_paged(cache["kv"], kv_upd, length, block_tables,
                               step_mask=step_mask),
        "length": (length + 1 if step_mask is None
                   else length + step_mask.astype(length.dtype)),
    }
    x = L.apply_norm(cfg.norm_type, params["final_norm"], x)
    logits = L.apply_lm_head(params["embed"], x, params.get("lm_head"))
    return logits, new_cache


def _attn_decode_one_paged_kernel(lp, x, k_pool, v_pool, block_tables,
                                  length, cfg: ArchConfig, paged_fn):
    """One block's decode step with the attention core dispatched to a
    registered paged-attention kernel (block-table indirection inside
    the kernel instead of a gathered KV view)."""
    q = cfg.quant
    acfg = attn_config(cfg)
    b = x.shape[0]
    lv = jnp.broadcast_to(length, (b,)) if jnp.ndim(length) == 0 else length
    xin = L.apply_norm(cfg.norm_type, lp["norm1"], x)
    qh, k_new, v_new, _ = attn_mod._project_qkv(
        lp["attn"], xin, acfg, q, lv[:, None]
    )
    with jax.named_scope("attention"):
        ctx = paged_fn(
            qh[:, 0], k_pool, v_pool, block_tables, lv,
            k_new[:, 0].astype(k_pool.dtype),
            v_new[:, 0].astype(v_pool.dtype),
        )
        ctx = ctx.astype(x.dtype).reshape(b, 1,
                                          acfg.n_heads * acfg.head_dim)
        h, _ = apply_linear(lp["attn"]["wo"], ctx, q)
    kv_out = {"k_new": k_new.astype(k_pool.dtype),
              "v_new": v_new.astype(v_pool.dtype)}
    return _ffn_block(lp, x + h, cfg, q), kv_out


def _prefix_sdpa(q, k_new, v_new, k_pref, v_pref, prefix_len, window: int):
    """Suffix-prefill attention: queries at ``prefix_len + i`` attend the
    cached prefix pages (masked to ``kpos < prefix_len``) plus the
    causal suffix — one softmax over both column groups, decode-style.
    """
    b, w, h, d = q.shape
    hk = k_new.shape[2]
    g = h // hk
    s = k_pref.shape[1]
    qh = q.reshape(b, w, hk, g, d)
    lp_past = jnp.einsum(
        "bskgd,btkd->bkgst", qh.astype(k_pref.dtype), k_pref,
        preferred_element_type=jnp.float32,
    )
    kpos = jnp.arange(s)
    qpos = prefix_len[:, None] + jnp.arange(w)[None, :]           # (b, w)
    valid = jnp.broadcast_to(
        kpos[None, None, :] < prefix_len[:, None, None], (b, w, s)
    )
    if window > 0:
        valid &= kpos[None, None, :] > qpos[:, :, None] - window
    lp_past = jnp.where(valid[:, None, None], lp_past, attn_mod.NEG_INF)
    lp_self = jnp.einsum(
        "bskgd,btkd->bkgst", qh.astype(k_new.dtype), k_new,
        preferred_element_type=jnp.float32,
    )
    i = jnp.arange(w)
    self_valid = i[None, :] <= i[:, None]                          # (wq, wk)
    if window > 0:
        self_valid &= i[None, :] > i[:, None] - window
    lp_self = jnp.where(self_valid[None, None, None], lp_self,
                        attn_mod.NEG_INF)
    scale = 1.0 / math.sqrt(d)
    full = jnp.concatenate([lp_past, lp_self], axis=-1) * scale
    probs = jax.nn.softmax(full.astype(jnp.float32), axis=-1)
    p_past, p_self = probs[..., :s], probs[..., s:]
    ctx = jnp.einsum(
        "bkgst,btkd->bskgd", p_past.astype(k_pref.dtype), v_pref,
        preferred_element_type=jnp.float32,
    ) + jnp.einsum(
        "bkgst,btkd->bskgd", p_self.astype(v_new.dtype), v_new,
        preferred_element_type=jnp.float32,
    )
    return ctx.astype(q.dtype).reshape(b, w, h * d)


def prefill_paged_suffix(
    params: Params, cfg: ArchConfig, tokens: jax.Array, cache: Dict,
    block_tables: jax.Array, prefix_len, per_token_ffn: bool = False,
) -> Tuple[jax.Array, Dict]:
    """Prefill ONLY a prompt's un-cached suffix against reused pages.

    ``tokens`` (B, W) are the suffix tokens (right-padded); the cached
    prefix K/V — ``prefix_len`` tokens already sitting in the slot's
    pages via the radix index — is read through ``block_tables``
    (B, MB). RoPE positions are offset by ``prefix_len`` and every
    suffix query attends [cached prefix, causal suffix] in one softmax,
    so the result matches a full-prompt prefill. Returns
    ``(suffix logits (B, W, V), {"k", "v"} stacked (L, B, W, Hkv, D))``
    ready for :func:`paged_cache_insert` at ``start=prefix_len``.

    ``per_token_ffn`` routes each position in its own MoE group (see
    :func:`_ffn_block`): the spec-decode verify reuses this function as
    a width-(K+1) decode step and must be bit-exact with sequential
    width-1 decoding, whereas prompt-suffix prefill keeps the default
    width-chunked routing that full prefill uses.
    """
    _check_paged_family(cfg)
    q = cfg.quant
    acfg = attn_config(cfg)
    b, w = tokens.shape
    lv = (jnp.broadcast_to(prefix_len, (b,))
          if jnp.ndim(prefix_len) == 0 else prefix_len)
    x = L.apply_embedding(params["embed"], tokens)
    positions = lv[:, None] + jnp.arange(w)[None, :]

    def body(x_, xs):
        lp, k_l, v_l = xs
        xin = L.apply_norm(cfg.norm_type, lp["norm1"], x_)
        qh, kh, vh, _ = attn_mod._project_qkv(lp["attn"], xin, acfg, q,
                                              positions)
        with jax.named_scope("attention"):
            ctx = _prefix_sdpa(
                qh, kh, vh,
                _gather_pages(k_l, block_tables),
                _gather_pages(v_l, block_tables),
                lv, cfg.sliding_window,
            )
            h, _ = apply_linear(lp["attn"]["wo"], ctx, q)
        x2 = _ffn_block(lp, x_ + h, cfg, q, per_token=per_token_ffn)
        return x2, (kh.astype(k_l.dtype), vh.astype(v_l.dtype))

    x, (ks, vs) = layer_scan(
        body, x, (params["blocks"], cache["kv"]["k"], cache["kv"]["v"]),
        unroll=not cfg.scan_layers,
    )
    x = L.apply_norm(cfg.norm_type, params["final_norm"], x)
    logits = L.apply_lm_head(params["embed"], x, params.get("lm_head"))
    return logits, {"k": ks, "v": vs}


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

@jax.named_scope("kv_commit")
def _commit_kv(kv, upd, length, step_mask=None):
    """Write all layers' new-token K/V with ONE tiny in-place update
    (never rewrite the stacked cache inside the layer scan).

    ``length`` scalar: one write position for the whole batch.
    ``length`` (B,) vector: per-slot positions (continuous batching) —
    one scatter per leaf writes row ``(layer, b, length[b] + w)`` of
    the ``(layers, slots, S, kv_heads, head_dim)`` pool for every
    layer, slot and width position ``w``, in the pool's own layout (a
    per-slot ``dynamic_update_slice`` under ``vmap`` would be lowered
    as a transpose of the whole pool). Each start is clamped to
    ``S - W`` exactly as ``dynamic_update_slice`` clamps it.
    ``step_mask`` (B,) bool (vector lengths only): slots with a False
    mask are pointed past the sequence axis and dropped — the commit is
    a true no-op for retired slots inside :func:`decode_multi_step`, so
    the donated cache never picks up junk from a slot that finished
    mid-horizon.
    """
    if jnp.ndim(length) == 0:
        return {
            "k": jax.lax.dynamic_update_slice(
                kv["k"], upd["k_new"], (0, 0, length, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                kv["v"], upd["v_new"], (0, 0, length, 0, 0)),
        }
    n_slots, seq, width = (kv["k"].shape[1], kv["k"].shape[2],
                           upd["k_new"].shape[2])
    start = jnp.clip(length, 0, seq - width)
    if step_mask is not None:
        start = jnp.where(step_mask, start, seq)
    # (B, W) positions against (B, 1) slots: slot-major, distinct pairs
    pos = start[:, None] + jnp.arange(width, dtype=start.dtype)
    slot = jnp.arange(n_slots, dtype=start.dtype)[:, None]

    def write(pool, new):
        return pool.at[:, slot, pos].set(
            new, mode="drop", indices_are_sorted=True, unique_indices=True)

    return {"k": write(kv["k"], upd["k_new"]),
            "v": write(kv["v"], upd["v_new"])}


def _select_slots(step_mask, new, old):
    """Per-slot select between a step's new state and the old one.

    Every stacked recurrent leaf has the slot ("batch") axis at
    position 1 (see :func:`cache_insert`), so one broadcasted ``where``
    per leaf freezes retired slots' state mid-horizon.
    """
    return jax.tree.map(
        lambda n_, o_: jnp.where(
            step_mask.reshape((1, -1) + (1,) * (n_.ndim - 2)), n_, o_
        ),
        new, old,
    )


@jax.named_scope("ffn")
def _ffn_block(lp, x, cfg: ArchConfig, q, per_token: bool = False):
    """Post-attention block tail (norm2 + MoE-or-MLP, dense residual)
    shared by the prefill, decode and paged-suffix paths.

    ``per_token=True`` folds the width axis into the batch so every
    token routes in its own MoE group of one — capacity-based dispatch
    is width-dependent (tokens in a chunk compete for expert capacity),
    and the spec-decode verify needs each position's output bit-exact
    with the width-1 decode path it replaces. MLP families are
    per-token already; the fold is a no-op reshape, so it is applied
    only where it matters.
    """
    if per_token and "moe" in lp and x.shape[1] > 1:
        b, s, d = x.shape
        y = _ffn_block(lp, x.reshape(b * s, 1, d), cfg, q)
        return y.reshape(b, s, d)
    z = L.apply_norm(cfg.norm_type, lp["norm2"], x)
    if "moe" in lp:
        h, _ = moe_mod.apply_moe(
            lp["moe"], z, cfg.n_experts, cfg.moe_top_k, q,
            act=cfg.act, chunk_size=cfg.moe_chunk, impl=cfg.moe_impl,
        )
        if cfg.dense_residual:
            h2, _ = L.apply_mlp(lp["mlp"], z, cfg.act, q)
            h = h + h2
    else:
        h, _ = L.apply_mlp(lp["mlp"], z, cfg.act, q)
    return x + h


def _attn_decode_one(lp, x, kv, length, cfg: ArchConfig, params=None,
                     shared: bool = False, cross_cache=None):
    q = cfg.quant
    ap = params["shared_attn"] if shared else lp["attn"]
    nrm = params["shared_norm"] if shared else lp["norm1"]
    h, (k_new, v_new), _ = attn_mod.decode_attention(
        ap, L.apply_norm(cfg.norm_type, nrm, x),
        {**kv, "length": length}, attn_config(cfg), q,
        defer_update=True,
    )
    kv_out = {"k_new": k_new.astype(kv["k"].dtype),
              "v_new": v_new.astype(kv["v"].dtype)}
    x = x + h
    if shared:
        h, _ = L.apply_mlp(
            params["shared_mlp"],
            L.apply_norm(cfg.norm_type, params["shared_mlp_norm"], x),
            cfg.act, q,
        )
        return x + h, kv_out
    if cross_cache is not None:
        h, _ = attn_mod.decode_cross_attention(
            lp["cross"], L.apply_norm(cfg.norm_type, lp["norm_cross"], x),
            cross_cache, attn_config(cfg), q,
        )
        x = x + h
    return _ffn_block(lp, x, cfg, q), kv_out


def decode_step(
    params: Params, cfg: ArchConfig, token: jax.Array, cache: Dict,
    step_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict]:
    """One serving step: token (B,1) -> (logits (B,1,V), updated cache).

    Works on both cache flavors: a scalar ``length`` advances the whole
    batch in lockstep (static batching), an ``(B,)`` vector advances each
    slot at its own position (continuous batching via :func:`cache_init`
    / :func:`cache_insert`) — masking, RoPE and K/V writes are per-slot.

    ``step_mask`` (B,) bool (vector lengths only) makes the step a full
    cache no-op for masked-out slots: their length freezes, the K/V
    commit writes their old value back, and recurrent state is held —
    the retirement mask :func:`decode_multi_step` applies to slots that
    finish mid-horizon, so a done slot's continued (batched) execution
    cannot perturb the donated cache.
    """
    q = cfg.quant
    length = cache["length"]
    x = L.apply_embedding(params["embed"], token)
    new_cache: Dict[str, Any] = {
        "length": (length + 1 if step_mask is None
                   else length + step_mask.astype(length.dtype))
    }
    plan = stack_plan(cfg)

    if cfg.family in ("dense", "vlm", "moe", "encdec"):
        has_cross = "cross" in cache

        def body(x_, xs):
            lp, kv, cc = xs
            x2, kv_out = _attn_decode_one(
                lp, x_, kv, length, cfg, params=params,
                cross_cache=cc if has_cross else None,
            )
            return x2, kv_out

        xs = (
            params["blocks"],
            {"k": cache["kv"]["k"], "v": cache["kv"]["v"]},
            cache.get("cross", jnp.zeros((cfg.n_layers,))),
        )
        x, kv_upd = layer_scan(body, x, xs, unroll=not cfg.scan_layers)
        new_cache["kv"] = _commit_kv(cache["kv"], kv_upd, length,
                                     step_mask=step_mask)
        if has_cross:
            new_cache["cross"] = cache["cross"]
    elif cfg.family == "hybrid":
        g, pg, tail = plan["groups"], plan["per_group"], plan["tail"]
        scfg = ssm_config(cfg)
        if g > 0:
            grouped_p = jax.tree.map(
                lambda a: a.reshape(g, pg, *a.shape[1:]), params["mamba_groups"]
            )
            grouped_c = jax.tree.map(
                lambda a: a.reshape(g, pg, *a.shape[1:]), cache["ssm_groups"]
            )

            def superstep(x_, xs):
                gp, gc, kv = xs

                def inner(xi, ys):
                    lp, lc = ys
                    h, st, _ = ssm_mod.decode_mamba2(
                        lp["mamba"],
                        L.apply_norm(cfg.norm_type, lp["norm1"], xi),
                        lc, scfg, q,
                    )
                    return xi + h, st

                x_, st_new = layer_scan(inner, x_, (gp, gc),
                                        unroll=not cfg.scan_layers)
                x_, kv_out = _attn_decode_one(
                    None, x_, kv, length, cfg, params=params, shared=True
                )
                return x_, (st_new, kv_out)

            x, (ssm_new, kv_upd) = layer_scan(
                superstep, x,
                (grouped_p, grouped_c,
                 {"k": cache["kv_shared"]["k"], "v": cache["kv_shared"]["v"]}),
                unroll=not cfg.scan_layers,
            )
            ssm_flat = jax.tree.map(
                lambda a: a.reshape(g * pg, *a.shape[2:]), ssm_new
            )
            if step_mask is not None:
                ssm_flat = _select_slots(step_mask, ssm_flat,
                                         cache["ssm_groups"])
            new_cache["ssm_groups"] = ssm_flat
            new_cache["kv_shared"] = _commit_kv(
                cache["kv_shared"], kv_upd, length, step_mask=step_mask)
        else:
            # g == 0 (pure-mamba stack): carry the empty group leaves so
            # the cache pytree is step-invariant (while_loop carry)
            new_cache["ssm_groups"] = cache.get("ssm_groups")
            new_cache["kv_shared"] = cache.get("kv_shared")
        if tail:
            def tail_body(x_, ys):
                lp, lc = ys
                h, st, _ = ssm_mod.decode_mamba2(
                    lp["mamba"], L.apply_norm(cfg.norm_type, lp["norm1"], x_),
                    lc, scfg, q,
                )
                return x_ + h, st

            x, tail_new = layer_scan(
                tail_body, x, (params["mamba_tail"], cache["ssm_tail"]),
                unroll=not cfg.scan_layers,
            )
            if step_mask is not None:
                tail_new = _select_slots(step_mask, tail_new,
                                         cache["ssm_tail"])
            new_cache["ssm_tail"] = tail_new
        else:
            new_cache["ssm_tail"] = cache.get("ssm_tail")
    elif cfg.family == "ssm":
        g, pg, tail = plan["groups"], plan["per_group"], plan["tail"]
        xcfg = xlstm_config(cfg)

        def ml_body(x_, ys):
            lp, lc = ys
            h, st, _ = xlstm_mod.decode_mlstm(
                lp["mlstm"], L.apply_norm(cfg.norm_type, lp["norm1"], x_),
                lc, xcfg, q,
            )
            return x_ + h, st

        if g > 0:
            grouped_p = jax.tree.map(
                lambda a: a.reshape(g, pg, *a.shape[1:]), params["mlstm_groups"]
            )
            grouped_c = jax.tree.map(
                lambda a: a.reshape(g, pg, *a.shape[1:]), cache["mlstm_groups"]
            )

            def superstep(x_, xs):
                gp, gc, sp, sc = xs
                x_, ml_new = layer_scan(ml_body, x_, (gp, gc),
                                        unroll=not cfg.scan_layers)
                h, s_new, _ = xlstm_mod.decode_slstm(
                    sp["slstm"],
                    L.apply_norm(cfg.norm_type, sp["norm1"], x_),
                    sc, xcfg, q,
                )
                return x_ + h, (ml_new, s_new)

            x, (ml_new, sl_new) = layer_scan(
                superstep, x,
                (grouped_p, grouped_c, params["slstm_blocks"], cache["slstm"]),
                unroll=not cfg.scan_layers,
            )
            ml_flat = jax.tree.map(
                lambda a: a.reshape(g * pg, *a.shape[2:]), ml_new
            )
            if step_mask is not None:
                ml_flat = _select_slots(step_mask, ml_flat,
                                        cache["mlstm_groups"])
                sl_new = _select_slots(step_mask, sl_new, cache["slstm"])
            new_cache["mlstm_groups"] = ml_flat
            new_cache["slstm"] = sl_new
        else:
            # g == 0: carry the empty group leaves so the cache pytree
            # is step-invariant (while_loop carry)
            new_cache["mlstm_groups"] = cache.get("mlstm_groups")
            new_cache["slstm"] = cache.get("slstm")
        if tail:
            x, tail_new = layer_scan(
                ml_body, x, (params["mlstm_tail"], cache["mlstm_tail"]),
                unroll=not cfg.scan_layers,
            )
            if step_mask is not None:
                tail_new = _select_slots(step_mask, tail_new,
                                         cache["mlstm_tail"])
            new_cache["mlstm_tail"] = tail_new
        else:
            new_cache["mlstm_tail"] = cache.get("mlstm_tail")

    x = L.apply_norm(cfg.norm_type, params["final_norm"], x)
    logits = L.apply_lm_head(params["embed"], x, params.get("lm_head"))
    return logits, new_cache


# ---------------------------------------------------------------------------
# parallel prefill
# ---------------------------------------------------------------------------

def prefill(
    params: Params, cfg: ArchConfig, batch: Dict[str, jax.Array],
    max_len: int, dtype=jnp.bfloat16,
) -> Tuple[jax.Array, Dict]:
    """Parallel prompt pass that returns (prompt logits, filled cache).

    ``batch["lengths"]`` (optional, (B,) int32) marks each row's TRUE
    prompt length in a RIGHT-padded batch. Attention K/V need no help
    (the causal mask keeps right-pad junk out of true positions; junk
    K/V rows stay masked by per-slot lengths at decode), but recurrent
    state folds every token it sees — with ``lengths`` the SSM/xLSTM
    scans make padded positions exact state no-ops and return each row's
    final state *at its true length* (see ``apply_mamba2`` /
    ``apply_mlstm`` / ``apply_slstm``), which is what lets bucketed
    continuous-batching prefill admit recurrent families bit-exactly.
    When given, ``cache["length"]`` is the per-row vector.
    """
    q = cfg.quant
    tokens = batch["tokens"]
    lengths = batch.get("lengths")
    b = tokens.shape[0]
    x = L.apply_embedding(params["embed"], tokens)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x], axis=1)
    s = x.shape[1]
    enc_out = None
    if cfg.family == "encdec":
        enc_out = encode(params, cfg, batch["enc_embeds"], {})
    # VLM patch positions can push the prefill width past max_len (the
    # overhang is right-pad junk; cache_insert slices it back off)
    cache = init_cache(params, cfg, b, max(max_len, s), dtype=dtype,
                       enc_out=enc_out)
    plan = stack_plan(cfg)

    def attn_prefill_one(lp, x_, shared=False, cross=None):
        ap = params["shared_attn"] if shared else lp["attn"]
        nrm = params["shared_norm"] if shared else lp["norm1"]
        xin = L.apply_norm(cfg.norm_type, nrm, x_)
        pos = jnp.broadcast_to(jnp.arange(s), (b, s))
        qh, kh, vh, _ = attn_mod._project_qkv(ap, xin, attn_config(cfg), q, pos)
        with jax.named_scope("attention"):
            ctx = attn_mod._sdpa(qh, kh, vh, True, cfg.sliding_window)
            h, _ = apply_linear(ap["wo"], ctx, q)
        x_ = x_ + h
        if shared:
            h, _ = L.apply_mlp(
                params["shared_mlp"],
                L.apply_norm(cfg.norm_type, params["shared_mlp_norm"], x_),
                cfg.act, q,
            )
            return x_ + h, (kh, vh)
        if cross is not None:
            h, _ = attn_mod.apply_attention(
                lp["cross"], L.apply_norm(cfg.norm_type, lp["norm_cross"], x_),
                attn_config(cfg), q, xkv=cross,
            )
            x_ = x_ + h
        return _ffn_block(lp, x_, cfg, q), (kh, vh)

    @jax.named_scope("kv_commit")
    def write_kv(kv_stacked, k_layers, v_layers):
        k = jax.lax.dynamic_update_slice_in_dim(
            kv_stacked["k"], k_layers.astype(kv_stacked["k"].dtype), 0, axis=2
        )
        v = jax.lax.dynamic_update_slice_in_dim(
            kv_stacked["v"], v_layers.astype(kv_stacked["v"].dtype), 0, axis=2
        )
        return {"k": k, "v": v}

    if cfg.family in ("dense", "vlm", "moe", "encdec"):
        def body(x_, xs):
            lp, cc = xs
            x2, kv = attn_prefill_one(
                lp, x_, cross=enc_out if cfg.family == "encdec" else None
            )
            return x2, kv

        x, (ks, vs) = layer_scan(
            body, x, (params["blocks"], jnp.zeros((cfg.n_layers,))),
            unroll=not cfg.scan_layers,
        )
        cache["kv"] = write_kv(cache["kv"], ks, vs)
    elif cfg.family == "hybrid":
        g, pg, tail = plan["groups"], plan["per_group"], plan["tail"]
        scfg = ssm_config(cfg)

        def mamba_one(x_, lp):
            h, _, st = ssm_mod.apply_mamba2(
                lp["mamba"], L.apply_norm(cfg.norm_type, lp["norm1"], x_),
                scfg, q, return_cache=True, lengths=lengths,
            )
            return x_ + h, st

        if g > 0:
            grouped_p = jax.tree.map(
                lambda a: a.reshape(g, pg, *a.shape[1:]), params["mamba_groups"]
            )

            def superstep(x_, gp):
                x_, st = layer_scan(mamba_one, x_, gp,
                                    unroll=not cfg.scan_layers)
                x_, kv = attn_prefill_one(None, x_, shared=True)
                return x_, (st, kv)

            x, (ssm_states, (ks, vs)) = layer_scan(
                superstep, x, grouped_p, unroll=not cfg.scan_layers)
            cache["ssm_groups"] = _constrain_state(jax.tree.map(
                lambda a: a.reshape(g * pg, *a.shape[2:]), ssm_states
            ))
            cache["kv_shared"] = write_kv(cache["kv_shared"], ks, vs)
        if tail:
            x, tail_states = layer_scan(mamba_one, x, params["mamba_tail"],
                                        unroll=not cfg.scan_layers)
            cache["ssm_tail"] = _constrain_state(tail_states)
    elif cfg.family == "ssm":
        g, pg, tail = plan["groups"], plan["per_group"], plan["tail"]
        xcfg = xlstm_config(cfg)

        def ml_one(x_, lp):
            h, _, st = xlstm_mod.apply_mlstm(
                lp["mlstm"], L.apply_norm(cfg.norm_type, lp["norm1"], x_),
                xcfg, q, return_cache=True, lengths=lengths,
            )
            return x_ + h, st

        if g > 0:
            grouped_p = jax.tree.map(
                lambda a: a.reshape(g, pg, *a.shape[1:]), params["mlstm_groups"]
            )

            def superstep(x_, xs):
                gp, sp = xs
                x_, ml_st = layer_scan(ml_one, x_, gp,
                                       unroll=not cfg.scan_layers)
                h, _, s_st = xlstm_mod.apply_slstm(
                    sp["slstm"], L.apply_norm(cfg.norm_type, sp["norm1"], x_),
                    xcfg, q, return_cache=True, lengths=lengths,
                )
                return x_ + h, (ml_st, s_st)

            x, (ml_states, s_states) = layer_scan(
                superstep, x, (grouped_p, params["slstm_blocks"]),
                unroll=not cfg.scan_layers,
            )
            cache["mlstm_groups"] = _constrain_state(jax.tree.map(
                lambda a: a.reshape(g * pg, *a.shape[2:]), ml_states
            ))
            cache["slstm"] = _constrain_state(s_states)
        if tail:
            x, tail_states = layer_scan(ml_one, x, params["mlstm_tail"],
                                        unroll=not cfg.scan_layers)
            cache["mlstm_tail"] = _constrain_state(tail_states)

    x = L.apply_norm(cfg.norm_type, params["final_norm"], x)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        x = x[:, batch["patch_embeds"].shape[1]:]
    logits = L.apply_lm_head(params["embed"], x, params.get("lm_head"))
    if lengths is not None:
        # patch positions sit below the prompt tokens in the KV cache,
        # so each row's true cache length is patches + prompt length
        off = (batch["patch_embeds"].shape[1]
               if cfg.family == "vlm" and "patch_embeds" in batch else 0)
        cache["length"] = jnp.asarray(lengths, jnp.int32) + off
    else:
        cache["length"] = jnp.asarray(s, jnp.int32)
    return logits, cache


# ---------------------------------------------------------------------------
# on-device multi-step decode
# ---------------------------------------------------------------------------
#
# The serving hot loop. Instead of one jit call (and one host sync) per
# token, the engine calls decode_multi_step once per *horizon*: a
# lax.while_loop runs up to H decode steps entirely on device — greedy
# argmax sampling, per-slot EOS / max-new-token flags, and retirement
# masks (a slot that finishes mid-horizon keeps executing in the batch,
# but its step is a full cache no-op via ``step_mask``, so cache
# donation stays valid). The loop exits early once every live slot is
# done, and the host syncs only at horizon boundaries — O(tokens/H)
# round-trips per request instead of O(tokens).
#
# Greedy only: argmax needs no RNG carry and is what makes the loop
# bit-exact-testable against the host loop. Temperature sampling stays
# on the host path in serve/engine.py.


def _multi_step_loop(step_fn, cache, last_tok, live, eos_ids, budget,
                     horizon: int):
    """Run ``step_fn`` up to ``horizon`` times under a device while-loop.

    ``step_fn(cache, token_B1, emit_mask) -> (logits, cache)`` is one
    masked decode step. Carry: (cache, last token, done mask, token
    buffer, per-slot emitted count, per-slot remaining budget, step).
    Returns ``(buf, emitted, done, last_tok, cache, steps)`` where
    ``buf`` is (B, H) int32 with -1 in never-written positions.
    """
    n = last_tok.shape[0]
    last_tok = constrain(last_tok.astype(jnp.int32), "batch")
    done0 = constrain(jnp.logical_not(live) | (budget <= 0), "batch")
    buf0 = constrain(jnp.full((n, horizon), -1, jnp.int32), "batch", None)
    emitted0 = constrain(jnp.zeros((n,), jnp.int32), "batch")
    budget0 = constrain(budget.astype(jnp.int32), "batch")
    eos_ids = constrain(eos_ids.astype(jnp.int32), "batch")

    def cond(carry):
        _, _, done, _, _, _, s = carry
        return (s < horizon) & jnp.any(~done)

    def body(carry):
        cache, last, done, buf, emitted, rem, s = carry
        emit = ~done
        logits, cache = step_fn(cache, last[:, None], emit)
        with jax.named_scope("sample"):
            tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            tok = constrain(jnp.where(emit, tok, -1), "batch")
        buf = jax.lax.dynamic_update_slice(buf, tok[:, None], (0, s))
        emitted = emitted + emit.astype(jnp.int32)
        rem = rem - emit.astype(jnp.int32)
        done = done | (emit & ((tok == eos_ids) | (rem <= 0)))
        last = jnp.where(emit, tok, last)
        return (cache, last, done, buf, emitted, rem, s + 1)

    carry = (cache, last_tok, done0, buf0, emitted0, budget0,
             jnp.asarray(0, jnp.int32))
    cache, last, done, buf, emitted, _, steps = jax.lax.while_loop(
        cond, body, carry
    )
    return buf, emitted, done, last, cache, steps


def decode_multi_step(
    params: Params, cfg: ArchConfig, cache: Dict, last_tok: jax.Array,
    live: jax.Array, eos_ids: jax.Array, budget: jax.Array, horizon: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, Dict, jax.Array]:
    """Up to ``horizon`` greedy decode steps on device (contiguous cache).

    Args: ``last_tok`` (B,) last token per slot, ``live`` (B,) bool slot
    occupancy, ``eos_ids`` (B,) per-request EOS (-1 = none), ``budget``
    (B,) remaining new-token allowance. ``horizon`` is static — one
    compile per horizon value. Returns ``(buf, emitted, done, last_tok,
    cache, steps)``: ``buf[i, :emitted[i]]`` are slot i's new tokens.
    Bit-exact with ``horizon`` host-driven :func:`decode_step` calls
    under greedy sampling.
    """
    def step_fn(c, tok, emit):
        return decode_step(params, cfg, tok, c, step_mask=emit)

    return _multi_step_loop(step_fn, cache, last_tok, live, eos_ids,
                            budget, horizon)


def decode_multi_step_paged(
    params: Params, cfg: ArchConfig, cache: Dict, block_tables: jax.Array,
    last_tok: jax.Array, live: jax.Array, eos_ids: jax.Array,
    budget: jax.Array, horizon: int, attn_backend: Optional[str] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, Dict, jax.Array]:
    """Paged analogue of :func:`decode_multi_step`.

    ``block_tables`` must already map every position the loop can write
    — the engine pre-reserves min(horizon, budget) pages per live slot
    via ``PagedKVManager.prepare_append`` before invoking this (and
    falls back to horizon=1 for a round where a copy-on-write valve
    would trigger mid-horizon; see ``PagedKVManager.mid_horizon_cow``).
    """
    def step_fn(c, tok, emit):
        return decode_step_paged(params, cfg, tok, c, block_tables,
                                 attn_backend=attn_backend, step_mask=emit)

    return _multi_step_loop(step_fn, cache, last_tok, live, eos_ids,
                            budget, horizon)


# ---------------------------------------------------------------------------
# speculative decoding (draft propose + batched verify)
# ---------------------------------------------------------------------------
#
# A small draft model (same family/vocab, its own ArchConfig + cache)
# proposes K greedy tokens per slot; the main model scores all K+1
# positions (pending token + proposals) in ONE masked forward —
# decode_verify below, the width-(K+1) generalization of decode_step
# built on the same _prefix_sdpa math as paged suffix prefill. The
# engine accepts the longest prefix where the draft's proposal equals
# the main model's argmax, emits one bonus token, and rolls both caches
# back with a per-slot length edit (plus PagedKVManager.truncate on the
# paged path). Greedy outputs are token-identical to vanilla decode by
# construction: every emitted token IS a main-model argmax at the same
# cache state.
#
# _SPEC_FAMILIES: pure-KV families only. Recurrent state (ssm/hybrid)
# folds every token into a fixed-size state — there is no length edit
# that un-folds a rejected token.
_SPEC_FAMILIES = ("dense", "moe", "vlm", "encdec")


def decode_verify(
    params: Params, cfg: ArchConfig, tokens: jax.Array, cache: Dict,
) -> Tuple[jax.Array, Dict]:
    """Score ``tokens`` (B, W) at positions ``length .. length+W-1``.

    The width-W analogue of :func:`decode_step` on a contiguous slot
    pool: queries attend the cached prefix (masked to
    ``kpos < length``) plus the causal in-flight suffix via
    :func:`_prefix_sdpa` — the same one-softmax construction the paged
    suffix prefill uses, so each position's logits are bit-exact with W
    sequential ``decode_step`` calls. K/V for all W positions are
    committed at ``length .. length+W-1``; ``cache["length"]`` is NOT
    advanced — the engine sets it to the accepted length afterwards
    (the rollback is exactly that length edit; rejected positions'
    K/V become junk above the length watermark, overwritten by the
    next round's writes and never attended).
    """
    if cfg.family not in _SPEC_FAMILIES:
        raise ValueError(
            f"decode_verify supports the pure KV-cache families "
            f"{_SPEC_FAMILIES}, got {cfg.family!r}"
        )
    q = cfg.quant
    acfg = attn_config(cfg)
    lengths = cache["length"]
    b, w = tokens.shape
    x = L.apply_embedding(params["embed"], tokens)
    positions = lengths[:, None] + jnp.arange(w)[None, :]
    has_cross = "cross" in cache

    def body(x_, xs):
        lp, k_l, v_l, cc = xs
        xin = L.apply_norm(cfg.norm_type, lp["norm1"], x_)
        qh, kh, vh, _ = attn_mod._project_qkv(lp["attn"], xin, acfg, q,
                                              positions)
        with jax.named_scope("attention"):
            ctx = _prefix_sdpa(qh, kh, vh, k_l, v_l, lengths,
                               cfg.sliding_window)
            h, _ = apply_linear(lp["attn"]["wo"], ctx, q)
        x_ = x_ + h
        if has_cross:
            h, _ = attn_mod.decode_cross_attention(
                lp["cross"],
                L.apply_norm(cfg.norm_type, lp["norm_cross"], x_),
                cc, acfg, q,
            )
            x_ = x_ + h
        return (_ffn_block(lp, x_, cfg, q, per_token=True),
                (kh.astype(k_l.dtype), vh.astype(v_l.dtype)))

    xs = (params["blocks"], cache["kv"]["k"], cache["kv"]["v"],
          cache.get("cross", jnp.zeros((cfg.n_layers,))))
    x, (ks, vs) = layer_scan(body, x, xs, unroll=not cfg.scan_layers)
    x = L.apply_norm(cfg.norm_type, params["final_norm"], x)
    logits = L.apply_lm_head(params["embed"], x, params.get("lm_head"))
    new_cache = dict(cache)
    new_cache["kv"] = _commit_kv(
        cache["kv"], {"k_new": ks, "v_new": vs}, lengths)
    return logits, new_cache


def decode_propose(
    params: Params, cfg: ArchConfig, cache: Dict, last_tok: jax.Array,
    live: jax.Array, k_steps: int,
) -> Tuple[jax.Array, Dict]:
    """Run ``k_steps`` greedy draft steps; returns ((B, k_steps), cache).

    A ``lax.scan`` over masked :func:`decode_step` calls. Proposal 0
    extends the shared pending token, so the engine verifies proposals
    ``0 .. k-2`` and the LAST step exists only to commit its
    predecessor's K/V — after ``k_steps = K+1`` steps the draft cache
    holds every position a full acceptance needs, and any rollback
    target is a pure length edit. Non-live slots carry their token
    unchanged and their step is a cache no-op (``step_mask``).
    """
    def step(carry, _):
        c, tok = carry
        logits, c = decode_step(params, cfg, tok[:, None], c,
                                step_mask=live)
        with jax.named_scope("sample"):
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            nxt = jnp.where(live, nxt, tok)
        return (c, nxt), nxt

    (cache, _), toks = jax.lax.scan(
        step, (cache, last_tok.astype(jnp.int32)), None, length=k_steps)
    return jnp.moveaxis(toks, 0, 1), cache


@jax.named_scope("kv_commit")
def paged_verify_commit(
    kv: Dict, upd: Dict, lengths: jax.Array, block_tables: jax.Array,
    live: jax.Array,
) -> Dict:
    """Write a width-W verify's K/V into each live slot's pages.

    The width-W analogue of :func:`_commit_kv_paged`: position
    ``lengths[b] + j`` maps through slot ``b``'s block table (the engine
    pre-reserves all W positions via ``PagedKVManager.prepare_append``
    before the verify forward). Non-live slots are routed to the trash
    page — their tables may hold stale entries that now alias reallocated
    live pages, so masking by table contents alone is not enough.
    ``upd`` is the ``{"k", "v"}`` stacked (L, B, W, Hkv, D) pair from
    :func:`prefill_paged_suffix`.
    """
    bs = kv["k"].shape[2]
    mb = block_tables.shape[1]
    w = upd["k"].shape[2]
    pos = lengths[:, None] + jnp.arange(w)[None, :]            # (B, W)
    bi = jnp.minimum(pos // bs, mb - 1)
    blk = jnp.take_along_axis(block_tables, bi, axis=1)
    blk = jnp.where(live[:, None], blk, 0)
    off = pos % bs

    def wr(pool, new):
        return pool.at[:, blk, off].set(new.astype(pool.dtype))

    return {"k": wr(kv["k"], upd["k"]), "v": wr(kv["v"], upd["v"])}
