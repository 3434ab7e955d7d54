"""Shared neural-net layers (norms, RoPE, MLPs, embeddings).

Every projection is a PSQLinear so the HCiM execution mode applies
uniformly across the zoo. Parameters are plain nested dicts.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.config import QuantConfig
from repro.core.psq_linear import apply_linear, init_linear
from repro.parallel.sharding import constrain

Params = Dict


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def apply_rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * p["scale"]).astype(x.dtype)


def init_layernorm(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def apply_layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def init_norm(kind: str, d: int) -> Params:
    return init_rmsnorm(d) if kind == "rmsnorm" else init_layernorm(d)


def apply_norm(kind: str, p: Params, x: jax.Array) -> jax.Array:
    return apply_rmsnorm(p, x) if kind == "rmsnorm" else apply_layernorm(p, x)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                     # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]                    # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(
    key: jax.Array, d: int, d_ff: int, act: str, quant: QuantConfig,
    use_bias: bool = False,
) -> Params:
    ks = jax.random.split(key, 3)
    if act == "swiglu":
        return {
            "gate": init_linear(ks[0], d, d_ff, quant, use_bias=use_bias),
            "up": init_linear(ks[1], d, d_ff, quant, use_bias=use_bias),
            "down": init_linear(ks[2], d_ff, d, quant, use_bias=use_bias),
        }
    return {
        "fc": init_linear(ks[0], d, d_ff, quant, use_bias=use_bias),
        "proj": init_linear(ks[1], d_ff, d, quant, use_bias=use_bias),
    }


def apply_mlp(
    p: Params, x: jax.Array, act: str, quant: QuantConfig
) -> Tuple[jax.Array, Dict]:
    stats = {}
    if act == "swiglu":
        g, s1 = apply_linear(p["gate"], x, quant)
        u, s2 = apply_linear(p["up"], x, quant)
        h = jax.nn.silu(g) * u
        h = constrain(h, "batch", "seq", "ffn")
        y, s3 = apply_linear(p["down"], h, quant)
        stats = _merge(s1, s2, s3)
    else:
        h, s1 = apply_linear(p["fc"], x, quant)
        h = jax.nn.gelu(h)
        h = constrain(h, "batch", "seq", "ffn")
        y, s2 = apply_linear(p["proj"], h, quant)
        stats = _merge(s1, s2)
    return y, stats


def _merge(*stats: Dict) -> Dict:
    out: Dict = {}
    vals = [s["p_zero_frac"] for s in stats if "p_zero_frac" in s]
    if vals:
        out["p_zero_frac"] = sum(vals) / len(vals)
    return out


# ---------------------------------------------------------------------------
# Embedding / LM head (kept full-precision, standard PSQ practice)
# ---------------------------------------------------------------------------

def init_embedding(key: jax.Array, vocab: int, d: int) -> Params:
    return {"table": jax.random.normal(key, (vocab, d)) * 0.02}


def apply_embedding(p: Params, ids: jax.Array) -> jax.Array:
    return constrain(jnp.take(p["table"], ids, axis=0), "batch", "seq", "embed")


@jax.named_scope("lm_head")
def apply_lm_head(
    p_emb: Params, x: jax.Array, head: Optional[Params] = None
) -> jax.Array:
    if head is not None:
        if "w_packed" in head:  # int4 deployment weights
            from repro.core.psq_linear import _unpack_int4_matmul

            return _unpack_int4_matmul(x, head["w_packed"], head["w_scale"])
        return x @ head["w"].astype(x.dtype)
    return x @ p_emb["table"].T.astype(x.dtype)


def init_lm_head(key: jax.Array, d: int, vocab: int) -> Params:
    return {"w": jax.random.normal(key, (d, vocab)) * (1.0 / math.sqrt(d))}
