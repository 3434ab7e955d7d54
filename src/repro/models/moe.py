"""Mixture-of-Experts with capacity-based top-k dispatch (chunked).

Designed for both 40-expert (granite, top-8) and 128-expert (arctic,
top-2 + dense residual) configurations:

* the router runs in fp32 (standard practice; it is *not* PSQ-quantized
  — mirroring the paper's convention of keeping tiny accuracy-critical
  layers at full precision),
* tokens are processed in fixed-size chunks so the (E, C, d) gather
  intermediate stays small at any sequence length — this is what keeps
  the 1M-token arctic dry-run compilable,
* expert weights live as (E, d, ff) stacked tensors: expert-parallel
  (E over the model axis) when E divides the axis, otherwise the expert
  FFN dim shards (granite's 40 experts on a 16-way axis),
* an auxiliary load-balance loss (Switch-style) is returned in stats.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.config import QuantConfig
from repro.core import psq
from repro.core.psq_linear import init_linear
from repro.parallel import sharding as shd
from repro.parallel.sharding import constrain

Params = Dict


def init_moe(
    key: jax.Array, d: int, d_ff: int, n_experts: int, top_k: int,
    quant: QuantConfig, act: str = "swiglu",
) -> Params:
    ks = jax.random.split(key, 4)
    std = 1.0 / math.sqrt(d)
    p: Params = {
        "router": jax.random.normal(ks[0], (d, n_experts)) * 0.02,
        "w_gate": jax.random.normal(ks[1], (n_experts, d, d_ff)) * std,
        "w_up": jax.random.normal(ks[2], (n_experts, d, d_ff)) * std,
        "w_down": jax.random.normal(ks[3], (n_experts, d_ff, d))
        * (1.0 / math.sqrt(d_ff)),
    }
    if quant.quantized:
        # one PSQ quantizer state per expert weight family (layer-level
        # scale factors per the paper; expert dim folds into the tile dim)
        for name, (kin, out) in {
            "w_gate": (d, d_ff), "w_up": (d, d_ff), "w_down": (d_ff, d)
        }.items():
            qp = psq.init_psq_params(key, kin, out, quant, w_std=std)
            p[f"{name}_q"] = qp
    return p


def _expert_ffn(
    p: Params, xs: jax.Array, quant: QuantConfig, act: str,
    constrained: bool = True,
) -> jax.Array:
    """xs: (E, C, d) gathered tokens -> (E, C, d) expert outputs.

    ``constrained=False`` drops the logical activation constraints —
    required inside the expert-parallel shard_map, where every mesh axis
    is manual and ``with_sharding_constraint`` would reject the spec.
    """
    if quant.quantized:
        # PSQ per expert: vmap the quantized matmul over the expert dim,
        # sharing the per-layer quantizer state (paper quantizes at layer
        # granularity; scale-factor tensors are per-layer here).
        def one(xe, wg, wu, wd):
            g, _ = psq.psq_matmul(xe, wg, p["w_gate_q"], quant)
            u, _ = psq.psq_matmul(xe, wu, p["w_up_q"], quant)
            h = jax.nn.silu(g) * u if act == "swiglu" else jax.nn.gelu(g)
            y, _ = psq.psq_matmul(h, wd, p["w_down_q"], quant)
            return y

        return jax.vmap(one)(xs, p["w_gate"], p["w_up"], p["w_down"])
    g = jnp.einsum("ecd,edf->ecf", xs, p["w_gate"])
    if act == "swiglu":
        u = jnp.einsum("ecd,edf->ecf", xs, p["w_up"])
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(g)
    if constrained:
        h = constrain(h, "experts", None, "expert_ffn")
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


def _route_chunk(
    router: jax.Array, x: jax.Array, n_experts: int, top_k: int,
    capacity: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Top-k routing for one chunk: x (T, d) -> (sel_gate, sel_idx, aux, me).

    Pure function of the (replicated) router weights, so the single
    device and every expert-parallel shard compute the identical
    ``(E, C)`` selection — the invariant that keeps the sharded combine
    bit-exact with the local scatter-add.
    """
    t = x.shape[0]
    logits = (x.astype(jnp.float32) @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)        # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )

    # Switch-style load balance aux loss
    me = jnp.mean(probs, axis=0)                             # (E,)
    ce = jnp.zeros((n_experts,)).at[gate_idx.reshape(-1)].add(
        jnp.ones((t * top_k,)) / (t * top_k)
    )
    aux = n_experts * jnp.sum(me * ce)

    # per-expert token selection: score matrix (E, T) of assigned gates
    assign = jnp.zeros((t, n_experts), jnp.float32)
    assign = assign.at[jnp.arange(t)[:, None], gate_idx].set(gate_vals)
    # pick up to `capacity` highest-gate tokens per expert
    sel_gate, sel_idx = jax.lax.top_k(assign.T, capacity)    # (E, C)
    return sel_gate, sel_idx, aux, me


def _combine_chunk(x: jax.Array, ys: jax.Array, sel_idx: jax.Array):
    """Scatter-add (E, C, d) gated expert outputs back to token order."""
    d = x.shape[-1]
    return jnp.zeros_like(x).at[sel_idx.reshape(-1)].add(
        ys.reshape(-1, d), mode="drop"
    )


def _moe_chunk(
    p: Params, x: jax.Array, n_experts: int, top_k: int,
    capacity: int, quant: QuantConfig, act: str,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Route one chunk of tokens. x: (T, d) -> (y, aux_loss, me_fraction)."""
    sel_gate, sel_idx, aux, me = _route_chunk(
        p["router"], x, n_experts, top_k, capacity
    )
    xs = jnp.take(x, sel_idx, axis=0)                        # (E, C, d)
    xs = xs * (sel_gate > 0.0)[..., None].astype(x.dtype)
    ys = _expert_ffn(p, xs, quant, act)                      # (E, C, d)
    ys = ys * sel_gate[..., None].astype(ys.dtype)
    y = _combine_chunk(x, ys, sel_idx)
    return y, aux, me


def _apply_moe_ep(
    p: Params, groups: jax.Array, n_experts: int, top_k: int,
    capacity: int, quant: QuantConfig, act: str, mesh, axis: str,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Expert-parallel dispatch over the ``axis`` mesh axis.

    Each device owns ``E / n`` expert FFN stacks (``w_gate``/``w_up``/
    ``w_down`` leading-dim sharded; router + PSQ quantizer state
    replicated) and computes only its local experts' gathered tokens.
    Routing is replicated — every shard derives the identical global
    ``(E, C)`` selection from the replicated router — so an
    ``all_gather`` of the local gated outputs reassembles the exact
    ``(E, C, d)`` tensor the single-device path feeds its scatter-add,
    and the combine is the identical op: bit-exact by construction for
    ANY top_k (a psum-of-partials combine would reassociate the
    per-token float sums across shards; the gather costs top_k x more
    bandwidth and buys determinism).

    ``groups`` (G, T, d) are the already-chunked token groups; the
    group dim follows the ``batch`` rule (dispatch never crosses the
    data axis), expert weights ride ``axis``.
    """
    n = mesh.shape[axis]
    e_local = n_experts // n

    pspecs = {
        k: (P(axis) if k in ("w_gate", "w_up", "w_down")
            else jax.tree.map(lambda _: P(), v))
        for k, v in p.items()
    }
    gspec = shd.data_pspec(groups.ndim, groups.shape, exclude=(axis,))
    g = groups.shape[0]
    aux_spec = shd.data_pspec(1, (g,), exclude=(axis,))
    me_spec = shd.data_pspec(2, (g, n_experts), exclude=(axis,))

    def local_fn(pl, gl):
        e_lo = jax.lax.axis_index(axis) * e_local

        def phase1(xc):
            sel_gate, sel_idx, aux, me = _route_chunk(
                pl["router"], xc, n_experts, top_k, capacity
            )
            sg = jax.lax.dynamic_slice_in_dim(sel_gate, e_lo, e_local, 0)
            si = jax.lax.dynamic_slice_in_dim(sel_idx, e_lo, e_local, 0)
            xs = jnp.take(xc, si, axis=0)                # (E/n, C, d)
            xs = xs * (sg > 0.0)[..., None].astype(xc.dtype)
            ys = _expert_ffn(pl, xs, quant, act, constrained=False)
            ys = ys * sg[..., None].astype(ys.dtype)
            return ys, sel_idx, aux, me

        ys_l, sel_idx, aux, me = jax.vmap(phase1)(gl)    # (G, E/n, C, d)
        ys = jax.lax.all_gather(ys_l, axis, axis=1, tiled=True)
        y = jax.vmap(_combine_chunk)(gl, ys, sel_idx)
        return y, aux, me

    fn = jax.shard_map(
        local_fn, mesh=mesh, in_specs=(pspecs, gspec),
        out_specs=(gspec, aux_spec, me_spec), check_vma=False,
    )
    return fn(p, groups)


def apply_moe_dense(
    p: Params, x: jax.Array, n_experts: int, top_k: int,
    quant: QuantConfig, act: str = "swiglu",
) -> Tuple[jax.Array, Dict]:
    """Weighted-dense mixture: every expert computed, gated by top-k probs.

    For many-small-expert configs (granite: 40 experts of d_ff=512) the
    dispatch machinery costs far more than it saves — E/top_k extra
    expert FLOPs buy the removal of ALL gather/scatter/capacity traffic
    and turn the expert matmuls into two large TP-sharded einsums
    (EXPERIMENTS.md §Perf, granite hillclimb).
    """
    b, s, d = x.shape
    logits = (x.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (B,S,E)
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
    )
    gates = jnp.zeros_like(probs).at[
        jnp.arange(b)[:, None, None],
        jnp.arange(s)[None, :, None],
        gate_idx,
    ].set(gate_vals)                                          # (B,S,E) sparse

    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean((gates > 0).astype(jnp.float32), axis=(0, 1)) * (
        n_experts / top_k
    )
    aux = jnp.sum(me * ce)

    h_g = jnp.einsum("bsd,edf->bsef", x, p["w_gate"].astype(x.dtype))
    if act == "swiglu":
        h_u = jnp.einsum("bsd,edf->bsef", x, p["w_up"].astype(x.dtype))
        h = jax.nn.silu(h_g) * h_u
    else:
        h = jax.nn.gelu(h_g)
    h = constrain(h, "batch", "seq", None, "expert_ffn")
    y = jnp.einsum(
        "bsef,efd,bse->bsd", h, p["w_down"].astype(h.dtype),
        gates.astype(h.dtype),
    )
    return constrain(y, "batch", "seq", "embed"), {
        "moe_aux_loss": aux, "router_me": me,
    }


def apply_moe(
    p: Params, x: jax.Array, n_experts: int, top_k: int,
    quant: QuantConfig, act: str = "swiglu",
    capacity_factor: float = 1.25, chunk_size: int = 4096,
    impl: str = "dispatch",
) -> Tuple[jax.Array, Dict]:
    """x: (B, S, d). Locality-aware top-k routing with capacity dropping.

    Routing groups are formed *within* each batch row (sequence chunks of
    ``chunk_size``), so under batch->data sharding the gather/scatter of
    the dispatch never crosses devices — the expert compute itself is
    either expert-parallel (E % axis == 0) or TP over the expert FFN.
    (The original token-major chunking resharded the whole activation
    per chunk; see EXPERIMENTS.md §Perf granite hillclimb.)

    Under active expert-parallel rules (``RULES_EXPERT`` + a mesh with
    an ``expert`` axis; see :func:`repro.parallel.sharding.expert_axes`)
    the dispatch runs as a shard_map with each device computing its
    local expert slab — bit-exact with the single-device path (see
    :func:`_apply_moe_ep`). Falls back to single-device dispatch when
    the expert count does not divide the axis. The ``dense`` impl stays
    on the TP (``expert_ffn -> model``) path.
    """
    if impl == "dense":
        return apply_moe_dense(p, x, n_experts, top_k, quant, act=act)
    b, s, d = x.shape
    chunk = max(1, min(chunk_size, s))
    n_chunks = math.ceil(s / chunk)
    pad = n_chunks * chunk - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    groups = x.reshape(b * n_chunks, chunk, d)
    capacity = min(chunk, max(1, int(capacity_factor * chunk * top_k / n_experts)))

    ep = shd.expert_axes()
    if ep is not None and n_experts % ep[0].shape[ep[1]] == 0:
        ys, aux, mes = _apply_moe_ep(
            p, groups, n_experts, top_k, capacity, quant, act, *ep
        )
    else:
        def route(xc):
            return _moe_chunk(p, xc, n_experts, top_k, capacity, quant, act)

        ys, aux, mes = jax.vmap(route)(groups)
    y = ys.reshape(b, n_chunks * chunk, d)[:, :s]
    y = constrain(y, "batch", "seq", "embed")
    stats = {
        "moe_aux_loss": jnp.mean(aux),
        "router_me": jnp.mean(mes, axis=0),
    }
    return y, stats
