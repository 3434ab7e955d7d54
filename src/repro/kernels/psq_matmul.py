"""Pallas TPU kernel for the HCiM crossbar datapath (paper §4.2).

TPU adaptation of the analog-crossbar + comparator + DCiM pipeline:

* the K (reduction) dimension is blocked by ``xbar_rows`` — one grid step
  along the last grid axis corresponds to one analog crossbar tile;
* input bit-streams / weight bit-slices are extracted in VREGs
  (floor/mod on integer-valued f32 — cheap VPU work);
* each (stream j, slice k) pair issues one MXU matmul on {0,1} bit
  matrices (bf16 operands, f32 accumulation — exact for sums ≤ 256);
* the comparator and the DCiM scale-factor accumulate
  ``acc += 0.5 * kappa_k * sigma_j * p * s_q`` are fused elementwise ops
  on the matmul result while it is still in VMEM/VREGs — this is the
  TPU-native analogue of performing the scale-factor math *in memory*
  next to the partial sums (no HBM round-trip for ps / p / s);
* crossbar tiles accumulate into the output block across the innermost
  grid axis (digital shift-add across crossbars).

The kernel computes values only (inference / deployment path). QAT
gradients are attached in :mod:`repro.kernels.ops` via a custom VJP whose
backward pass reuses the jnp reference semantics.

Optimized variant (``fuse_planes=True``, a beyond-paper optimization
recorded in EXPERIMENTS.md §Perf): all ``n_a × n_w`` bit-plane pairs are
evaluated by a single MXU call on an ``(n_a·BB, R) x (R, n_w·BO)``
operand pair, turning 16 skinny matmuls into one large one (better MXU
occupancy at identical FLOPs).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.occupancy import (
    ColumnOccupancy,
    kernel_block_flags,
    occupancy_for_kernel,
)


def _py_bit_weights(n: int):
    """Two's-complement plane significances as static python floats."""
    w = [float(2 ** k) for k in range(n)]
    w[-1] = -float(2 ** (n - 1))
    return w


def _extract_bit(u: jax.Array, k: int) -> jax.Array:
    return jnp.mod(jnp.floor(u / float(2 ** k)), 2.0)


def _bit_dot(a, b):
    # {0,1} bf16 operands sum exactly in one bf16 MXU pass. The precision
    # is pinned so that a caller's default_matmul_precision("highest")
    # cannot ask Mosaic for an fp32 contraction of bf16 operands, which it
    # refuses.
    return jax.lax.dot(a, b, precision=jax.lax.Precision.DEFAULT,
                       preferred_element_type=jnp.float32)


def _comparator(a, alpha, levels: str):
    if levels == "ternary":
        return jnp.where(a >= alpha, 1.0, jnp.where(a <= -alpha, -1.0, 0.0))
    return jnp.where(a >= 0.0, 1.0, -1.0)


def _psq_kernel(
    alpha_ref,
    z_ref,
    x_ref,
    w_ref,
    sf_ref,
    o_ref,
    *,
    n_a: int,
    n_w: int,
    levels: str,
    adc_bits: int,
    xbar_rows: int,
    fuse_planes: bool,
    sparsity_skip: bool,
):
    t = pl.program_id(2)
    x = x_ref[...].astype(jnp.float32)       # (BB, R) integer-valued
    alpha = alpha_ref[0, 0]                  # SMEM scalar
    sigma = _py_bit_weights(n_a)             # python floats: static constants
    kappa = _py_bit_weights(n_w)
    c_w = sum(kappa)

    bb, r = x.shape
    bo = o_ref.shape[1]
    u_x = jnp.mod(x, float(2 ** n_a))

    def _dense_acc():
        w = w_ref[...].astype(jnp.float32)   # (R, BO)
        u_w = jnp.mod(w, float(2 ** n_w))
        if levels == "adc":
            step = max(1.0, xbar_rows / float(2 ** adc_bits))
            qmax = float(2 ** adc_bits - 1)
            acc = jnp.zeros((bb, bo), jnp.float32)
            for j in range(n_a):
                xb = _extract_bit(u_x, j).astype(jnp.bfloat16)
                for k in range(n_w):
                    wb = _extract_bit(u_w, k).astype(jnp.bfloat16)
                    ps = _bit_dot(xb, wb)
                    code = jnp.clip(
                        jnp.sign(ps) * jnp.floor(jnp.abs(ps) / step + 0.5),
                        0.0, qmax,
                    )
                    acc += (float(sigma[j]) * float(kappa[k]) * step) * code
            return acc
        if fuse_planes:
            # one (n_a*BB, R) x (R, n_w*BO) MXU pass for all bit-plane pairs
            xb_all = jnp.concatenate(
                [_extract_bit(u_x, j) for j in range(n_a)], axis=0
            ).astype(jnp.bfloat16)                           # (n_a*BB, R)
            wb_all = jnp.concatenate(
                [_extract_bit(u_w, k) for k in range(n_w)], axis=1
            ).astype(jnp.bfloat16)                           # (R, n_w*BO)
            ps_all = _bit_dot(xb_all, wb_all)
            rows_all = jnp.sum(xb_all.astype(jnp.float32), axis=1,
                               keepdims=True)
            acc = jnp.zeros((bb, bo), jnp.float32)
            for j in range(n_a):
                ps_j = ps_all[j * bb:(j + 1) * bb]
                rs_j = rows_all[j * bb:(j + 1) * bb]
                for k in range(n_w):
                    a = 2.0 * ps_j[:, k * bo:(k + 1) * bo] - rs_j
                    p = _comparator(a, alpha, levels)
                    sf = sf_ref[0, j, k, :].astype(jnp.float32)
                    acc += (0.5 * float(sigma[j]) * float(kappa[k])) * p * sf[None, :]
            acc += 0.5 * c_w * jnp.sum(x, axis=1, keepdims=True)
            return acc
        acc = jnp.zeros((bb, bo), jnp.float32)
        for j in range(n_a):
            xb = _extract_bit(u_x, j)
            rowsum = jnp.sum(xb, axis=1, keepdims=True)
            xb16 = xb.astype(jnp.bfloat16)
            for k in range(n_w):
                wb = _extract_bit(u_w, k).astype(jnp.bfloat16)
                ps = _bit_dot(xb16, wb)
                a = 2.0 * ps - rowsum
                p = _comparator(a, alpha, levels)
                sf = sf_ref[0, j, k, :].astype(jnp.float32)
                acc += (0.5 * float(sigma[j]) * float(kappa[k])) * p * sf[None, :]
        # unipolar->bipolar digital correction, this tile's rows only
        acc += 0.5 * c_w * jnp.sum(x, axis=1, keepdims=True)
        return acc

    def _skip_acc():
        # All-zero weight block (pack-time occupancy metadata): every
        # partial sum is exactly 0, so the comparator input collapses to
        # ``-rowsum`` — no MXU work. Each op below mirrors the dense
        # branch on ``ps = 0`` verbatim (same values, same accumulation
        # order), so the result is bit-identical to dense execution.
        acc = jnp.zeros((bb, bo), jnp.float32)
        for j in range(n_a):
            xb = _extract_bit(u_x, j)
            rowsum = jnp.sum(xb, axis=1, keepdims=True)
            a0 = 0.0 - rowsum                  # == 2.0 * ps - rowsum, ps = 0
            p0 = _comparator(a0, alpha, levels)
            for k in range(n_w):
                sf = sf_ref[0, j, k, :].astype(jnp.float32)
                acc += (0.5 * float(sigma[j]) * float(kappa[k])) * p0 * sf[None, :]
        acc += 0.5 * c_w * jnp.sum(x, axis=1, keepdims=True)
        return acc

    @pl.when(t == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if not sparsity_skip:
        o_ref[...] += _dense_acc()
    elif levels == "adc":
        # a zero block contributes an exact 0 under ADC quantization:
        # skipping is simply not accumulating
        @pl.when(z_ref[t, pl.program_id(1)] == 0)
        def _adc_dense():
            o_ref[...] += _dense_acc()
    else:
        flag = z_ref[t, pl.program_id(1)]    # this (tile, column block)

        @pl.when(flag == 0)
        def _dense():
            o_ref[...] += _dense_acc()

        @pl.when(flag != 0)
        def _skip():
            o_ref[...] += _skip_acc()


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_a", "n_w", "levels", "adc_bits", "xbar_rows",
        "block_b", "block_o", "fuse_planes", "occupancy", "interpret",
    ),
)
def psq_matmul_kernel(
    x_int: jax.Array,        # (B, K) integer-valued f32
    w_int: jax.Array,        # (K, O) integer-valued f32
    sf_q: jax.Array,         # (T, n_a, n_w, O) dequantized fixed-point SFs
    alpha: jax.Array,        # () ternary threshold
    *,
    n_a: int,
    n_w: int,
    levels: str,             # ternary | binary | adc
    adc_bits: int = 7,
    xbar_rows: int = 128,
    block_b: int = 128,
    block_o: int = 128,
    fuse_planes: bool = False,
    occupancy: Optional[ColumnOccupancy] = None,
    interpret: bool = True,
) -> jax.Array:
    """Quantized integer output ``y_int_q`` (B, O) of the HCiM pipeline.

    ``occupancy`` (hashable pack-time metadata, hence a jit static arg)
    enables the sparsity-skipping path: each ``(tile, column-block)``
    grid step whose weight slab is all-zero takes the cheap comparator
    branch instead of the ``n_a x n_w`` MXU pass — bit-identical output
    by construction (see :mod:`repro.kernels.occupancy`).
    """
    b, k = x_int.shape
    o = w_int.shape[1]
    r = xbar_rows
    t = math.ceil(k / r)

    bb = min(block_b, _ceil_to(b, 8))
    bo = min(block_o, _ceil_to(o, 128))
    b_pad = _ceil_to(b, bb)
    o_pad = _ceil_to(o, bo)
    k_pad = t * r

    occ = occupancy_for_kernel(occupancy, o, k, xbar_rows)
    sparsity_skip = occ is not None
    if sparsity_skip:
        flags_np = kernel_block_flags(occ, bo, o_pad)      # (T, O_pad/BO)
    else:
        flags_np = np.zeros((t, o_pad // bo), np.int32)

    x_p = jnp.pad(x_int, ((0, b_pad - b), (0, k_pad - k)))
    w_p = jnp.pad(w_int, ((0, k_pad - k), (0, o_pad - o)))
    # reduced scale-factor granularities broadcast up to full column shape
    sf_full = jnp.broadcast_to(sf_q, (t, n_a, n_w, o))
    sf_p = jnp.pad(sf_full, ((0, 0), (0, 0), (0, 0), (0, o_pad - o)))
    alpha_arr = jnp.reshape(alpha, (1, 1)).astype(jnp.float32)
    z_arr = jnp.asarray(flags_np)

    grid = (b_pad // bb, o_pad // bo, t)
    out = pl.pallas_call(
        functools.partial(
            _psq_kernel,
            n_a=n_a,
            n_w=n_w,
            levels=levels,
            adc_bits=adc_bits,
            xbar_rows=r,
            fuse_planes=fuse_planes,
            sparsity_skip=sparsity_skip,
        ),
        grid=grid,
        in_specs=[
            # scalars and the (T, O_pad/BO) skip flags live whole in SMEM:
            # Mosaic refuses VMEM blocks below the (8, 128) tile
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bb, r), lambda bi, oi, ti: (bi, ti)),
            pl.BlockSpec((r, bo), lambda bi, oi, ti: (ti, oi)),
            pl.BlockSpec((1, n_a, n_w, bo), lambda bi, oi, ti: (ti, 0, 0, oi)),
        ],
        out_specs=pl.BlockSpec((bb, bo), lambda bi, oi, ti: (bi, oi)),
        out_shape=jax.ShapeDtypeStruct((b_pad, o_pad), jnp.float32),
        interpret=interpret,
    )(alpha_arr, z_arr, x_p, w_p, sf_p)
    return out[:b, :o]
