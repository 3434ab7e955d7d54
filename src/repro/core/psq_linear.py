"""PSQLinear — a linear layer whose execution mode is the paper's knob.

Every projection in the model zoo routes through this module so the HCiM
technique (mode="psq"), the ADC baselines (mode="adc") and the fp path
(mode="none") are selectable per experiment from the config system.

Serving additionally routes through the tensor-parallel path when the
active sharding rules ask for it (``parallel.sharding.tp_axes``): a
packed layer's columns are split over the ``model`` mesh axis, each
device runs the full PSQ pipeline on its column slice via the registered
kernel backend (per-shard dispatch — the kernel sees local shapes), and
one ``psum`` performs the cross-device shift-add that recombines the
column blocks. Column splitting is bit-exact: every step of the HCiM
pipeline downstream of the weight codes (bit-plane partial sums,
comparator, DCiM scale-factor accumulate, digital offset correction) is
independent per output column.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.core import psq
from repro.core.config import QuantConfig
from repro.kernels import occupancy, registry
from repro.parallel import sharding as shd

Params = Dict[str, jax.Array]


def init_linear(
    key: jax.Array,
    k_in: int,
    n_out: int,
    cfg: QuantConfig,
    use_bias: bool = False,
    w_init_std: Optional[float] = None,
    dtype=jnp.float32,
) -> Params:
    """Create parameters for one (possibly quantized) linear layer."""
    wkey, _ = jax.random.split(key)
    std = w_init_std if w_init_std is not None else 1.0 / math.sqrt(k_in)
    p: Params = {"w": (jax.random.normal(wkey, (k_in, n_out)) * std).astype(dtype)}
    if use_bias:
        p["b"] = jnp.zeros((n_out,), dtype)
    if cfg.quantized:
        p.update(psq.init_psq_params(key, k_in, n_out, cfg, w_std=std))
        if cfg.per_channel_w:
            p["step_w"] = jnp.full((n_out,), float(p["step_w"]), jnp.float32)
    return p


def pack_weight_int4(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-out-channel int4 packing: (..., K, O) -> int8 (..., K/2, O).

    Deployment format for PSQ-trained weights (4-bit is the paper's CIFAR
    recipe): two two's-complement nibbles per byte along K, so the decode
    step streams 4x fewer weight bytes from HBM than bf16.
    """
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 7.0
    wi = jnp.clip(jnp.round(w / jnp.maximum(scale, 1e-9)), -8, 7)
    u = jnp.mod(wi.astype(jnp.int32), 16)
    lo, hi = u[..., 0::2, :], u[..., 1::2, :]
    return (lo | (hi << 4)).astype(jnp.int8), scale.astype(jnp.float32)


def _unpack_int4_matmul(x: jax.Array, packed: jax.Array, scale: jax.Array):
    w8 = packed.astype(jnp.int32)
    lo = w8 & 0xF
    hi = (w8 >> 4) & 0xF
    lo = lo - 16 * (lo >= 8).astype(jnp.int32)
    hi = hi - 16 * (hi >= 8).astype(jnp.int32)
    w_int = jnp.stack([lo, hi], axis=-2)
    w_int = w_int.reshape(*packed.shape[:-2], 2 * packed.shape[-2],
                          packed.shape[-1])
    w = w_int.astype(x.dtype) * scale.astype(x.dtype)
    return x @ w


def pack_tree_for_serving(node):
    """Replace every linear master weight in a param tree by its int4
    packed + per-channel-scale pair (embeddings/norms untouched)."""
    if isinstance(node, dict):
        out = {}
        for k, v in node.items():
            if (
                k == "w" and hasattr(v, "ndim") and v.ndim >= 2
                and v.shape[-2] % 2 == 0
            ):
                out["w_packed"], out["w_scale"] = pack_weight_int4(v)
            else:
                out[k] = pack_tree_for_serving(v)
        return out
    if isinstance(node, (list, tuple)):
        return type(node)(pack_tree_for_serving(v) for v in node)
    return node


def serve_linear_tp(
    layer, x: jax.Array, mesh: Mesh, axis: str
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Tensor-parallel packed-layer forward: columns over ``axis``.

    ``shard_map`` hands each device its column slice of the packed state
    (specs from :func:`repro.parallel.sharding.packed_layer_pspecs`);
    the kernel backend is dispatched per shard on the local ``(B, K) x
    (K, O/n)`` problem; each shard scatters its block into a zero
    ``(B, O)`` buffer and a single ``psum`` over ``axis`` recombines —
    the cross-device digital shift-add. Adding disjoint blocks of exact
    values keeps the result bit-identical to the single-device forward.

    Falls back to the unsharded forward when the column count does not
    divide the axis (the divisibility story of the rules table).

    Sparsity skipping survives the split: the replicated occupancy
    metadata describes the GLOBAL column space, so it is re-sliced to
    the local ``(K, O/n)`` problem before entering the mapped trace
    (:func:`repro.kernels.occupancy.shard_occupancy` — the conservative
    AND across shard slices, since ``shard_map`` traces once for every
    device). When the split is not representable (a shard boundary
    inside a metadata block) the re-slice returns ``None`` and the
    shape guard (``occupancy_for_kernel``) keeps the shards dense —
    correct either way, because skipped blocks are all-zero weights.
    """
    n = mesh.shape[axis]
    o = layer.w_codes.shape[-1]
    if o % n != 0:
        return layer.apply_serving(x)
    socc = occupancy.shard_occupancy(layer.occupancy, n)
    if socc is not layer.occupancy:
        # occupancy is pytree aux data: replacing it never touches the
        # array leaves or their shard specs
        layer = dataclasses.replace(layer, occupancy=socc)
    # fail fast on an unavailable backend before entering the mapped
    # trace, where the registry error would lose the sharding context
    registry.resolve_backend(layer.cfg)
    specs = shd.packed_layer_pspecs(layer, mesh=mesh)
    xspec = shd.data_pspec(x.ndim, x.shape, exclude=(axis,))

    def local_fn(lyr, xl):
        y, _ = lyr.apply_serving(xl)
        idx = jax.lax.axis_index(axis)
        full = jnp.zeros(y.shape[:-1] + (o,), y.dtype)
        full = jax.lax.dynamic_update_slice_in_dim(
            full, y, idx * (o // n), axis=y.ndim - 1
        )
        return jax.lax.psum(full, axis)

    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=(specs, xspec),
                       out_specs=xspec, check_vma=False)
    return fn(layer, x), {}


def apply_linear(
    params: Params,
    x: jax.Array,
    cfg: QuantConfig,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """y = quantized_matmul(x, w) + b. Returns (y, stats)."""
    if hasattr(params, "apply_serving"):
        # PackedLayer (repro.serve.cache): weight-stationary packed state,
        # quantized/packed once at model load — bias folded in there.
        tp = shd.tp_axes()
        if tp is not None:
            return serve_linear_tp(params, x, *tp)
        return params.apply_serving(x)
    if "w_packed" in params:  # int4 weight-stationary serving path
        y = _unpack_int4_matmul(x, params["w_packed"], params["w_scale"])
        stats: Dict[str, jax.Array] = {}
    elif not cfg.quantized:
        y = x @ params["w"].astype(x.dtype)
        stats = {}
    elif cfg.kernel_path:
        from repro.kernels import ops as kernel_ops

        y, stats = kernel_ops.psq_matmul(x, params["w"], params, cfg)
    else:
        y, stats = psq.psq_matmul(x, params["w"], params, cfg)
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y, stats


def linear_flops(k_in: int, n_out: int, tokens: int) -> int:
    return 2 * k_in * n_out * tokens
