"""Pack-time ternary occupancy metadata for sparsity-skipping kernels.

HCiM's digital CiM array clock-gates columns whose ternary comparator
output is zero (paper §4.2.2, Fig. 5a). The *statically known* slice of
that sparsity is visible at pack time: a weight column whose codes are
all zero inside one crossbar tile produces ``ps = 0`` for every input,
so its comparator input collapses to ``-rowsum`` — no matmul needed.
:func:`column_occupancy` records, per (crossbar tile, column block):

* whether the **entire** ``(xbar_rows, block)`` weight slab is zero
  (``zero_blocks`` — the unit the kernels actually skip),
* the fraction of all-zero columns in the block (``zero_col_frac`` —
  feeds the :func:`repro.hwmodel.system.serve_energy` accounting),
* the same fraction per weight bit-slice plane (``plane_zero_frac``).

The metadata is plain hashable python data (nested tuples), so it rides
along as pytree *aux data* on :class:`repro.serve.cache.PackedLayer` and
as a static argument of the jitted Pallas kernel — it never enters a
trace and survives mesh re-placement untouched.

    >>> import numpy as np
    >>> w = np.zeros((4, 4)); w[:, 0] = 3          # column 0 dense
    >>> occ = column_occupancy(w, xbar_rows=2, n_w=4, block=2)
    >>> occ.n_tiles, occ.n_cols
    (2, 4)
    >>> occ.zero_blocks      # block 0 holds the dense column
    ((False, True), (False, True))
    >>> occ.zero_col_frac
    ((0.5, 1.0), (0.5, 1.0))
    >>> round(occ.mean_zero_fraction, 3)
    0.75
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import numpy as np

# metadata column-block width: matches the Pallas kernel's default
# block_o (and the TPU lane count), so one metadata block maps onto one
# kernel grid block in the common case
META_BLOCK = 128


@dataclasses.dataclass(frozen=True)
class ColumnOccupancy:
    """Static per-(tile, column-block) zero-weight occupancy of one layer.

    Frozen + tuple-valued so instances are hashable (jit static args,
    pytree aux data) and comparable (pytree structure equality across
    mesh re-placement).
    """

    n_cols: int                                   # O of the packed layer
    n_tiles: int                                  # T = ceil(K / xbar_rows)
    n_w: int                                      # weight bit planes
    block: int                                    # metadata block width
    zero_blocks: Tuple[Tuple[bool, ...], ...]     # (T, NB)
    zero_col_frac: Tuple[Tuple[float, ...], ...]  # (T, NB)
    plane_zero_frac: Tuple[Tuple[Tuple[float, ...], ...], ...]  # (T,n_w,NB)

    @property
    def n_blocks(self) -> int:
        return math.ceil(self.n_cols / self.block)

    @property
    def mean_zero_fraction(self) -> float:
        """Fraction of (tile, column) pairs that are all-zero — the
        statically-skippable share of DCiM column events, fed to the
        energy model as its serve-time occupancy.

        Weighted by real columns per block (the last block may be
        ragged), so the figure is exact for any O.
        """
        total = zero = 0.0
        for t in range(self.n_tiles):
            for b in range(self.n_blocks):
                cols = min(self.block, self.n_cols - b * self.block)
                total += cols
                zero += self.zero_col_frac[t][b] * cols
        return zero / total if total else 0.0

    @property
    def skippable_block_fraction(self) -> float:
        """Fraction of (tile, block) kernel grid steps that skip the MXU."""
        flat = [f for row in self.zero_blocks for f in row]
        return sum(flat) / len(flat) if flat else 0.0

    def zero_blocks_np(self) -> np.ndarray:
        return np.asarray(self.zero_blocks, dtype=bool)

    def matches(self, n_cols: int, xbar_rows: int, k: int) -> bool:
        """True when this metadata describes a ``(k, n_cols)`` weight at
        the given tiling — the guard that keeps a tensor-parallel shard
        (local columns, global metadata) on the dense path."""
        return (self.n_cols == n_cols
                and self.n_tiles == math.ceil(k / xbar_rows))


def column_occupancy(
    w_int, *, xbar_rows: int, n_w: int, block: int = META_BLOCK
) -> ColumnOccupancy:
    """Derive :class:`ColumnOccupancy` from integer weight codes.

    ``w_int`` is the ``(K, O)`` two's-complement LSQ code matrix (any
    integer-valued array-like; concrete, not traced). A column is
    *zero in tile t* iff every one of its ``xbar_rows`` codes in that
    tile is 0 — equivalently every bit-slice plane is zero, which is why
    the whole-block flag licenses skipping every (stream, plane) matmul.

    >>> import numpy as np
    >>> occ = column_occupancy(np.zeros((8, 3)), xbar_rows=8, n_w=4)
    >>> occ.zero_blocks, occ.n_blocks
    (((True,),), 1)
    >>> occ.mean_zero_fraction, occ.skippable_block_fraction
    (1.0, 1.0)
    """
    w = np.asarray(w_int)
    if w.ndim != 2:
        raise ValueError(f"column_occupancy needs a 2-D (K, O) weight, "
                         f"got shape {w.shape}")
    k, o = w.shape
    t = math.ceil(k / xbar_rows)
    kp = t * xbar_rows
    w = np.pad(w.astype(np.int32), ((0, kp - k), (0, 0)))
    # OR over each tile's rows: zero iff every code is zero; bit j of the
    # two's-complement codes' OR is zero iff plane j is zero in that tile
    bits = np.bitwise_or.reduce(w.reshape(t, xbar_rows, o), axis=1)  # (T, O)
    zero_cols = bits == 0                                      # (T, O)
    plane_zero = np.stack([((bits >> j) & 1) == 0 for j in range(n_w)],
                          axis=1)                              # (T, n_w, O)

    nb = math.ceil(o / block)
    starts = np.arange(nb) * block
    cols = np.minimum(starts + block, o) - starts              # (NB,)
    zero_n = np.add.reduceat(zero_cols.astype(np.int32), starts, axis=-1)
    plane_n = np.add.reduceat(plane_zero.astype(np.int32), starts, axis=-1)
    zb = tuple(tuple(row) for row in (zero_n == cols).tolist())
    zf = tuple(tuple(row) for row in (zero_n / cols).tolist())
    pf = tuple(tuple(tuple(r) for r in tile)
               for tile in (plane_n / cols).tolist())
    return ColumnOccupancy(
        n_cols=o, n_tiles=t, n_w=n_w, block=block,
        zero_blocks=tuple(zb), zero_col_frac=tuple(zf),
        plane_zero_frac=tuple(pf),
    )


def merge_occupancies(occs) -> Optional[ColumnOccupancy]:
    """Conservative intersection across scan-stacked layers.

    ``lax.scan`` slices a stacked :class:`~repro.serve.cache.PackedLayer`
    into per-layer views that all share ONE static metadata object, so
    the merged metadata must be safe for every layer: a block is
    skippable only when it is zero in **all** layers (logical AND), and
    the occupancy fractions are the per-layer minimum. Returns ``None``
    for an empty list, any ``None`` entry, or mismatched tilings.

    >>> import numpy as np
    >>> a = column_occupancy(np.zeros((4, 4)), xbar_rows=4, n_w=2, block=2)
    >>> b = np.zeros((4, 4)); b[:, 0] = 1
    >>> m = merge_occupancies([a, column_occupancy(b, xbar_rows=4, n_w=2,
    ...                                            block=2)])
    >>> m.zero_blocks                      # block 0 dense in layer b
    ((False, True),)
    >>> merge_occupancies([]) is None
    True
    """
    occs = list(occs)
    if not occs or any(o is None for o in occs):
        return None
    first = occs[0]
    key = (first.n_cols, first.n_tiles, first.n_w, first.block)
    if any((o.n_cols, o.n_tiles, o.n_w, o.block) != key for o in occs[1:]):
        return None
    zb = np.logical_and.reduce([o.zero_blocks_np() for o in occs])
    zf = np.minimum.reduce([np.asarray(o.zero_col_frac) for o in occs])
    pf = np.minimum.reduce([np.asarray(o.plane_zero_frac) for o in occs])
    return ColumnOccupancy(
        n_cols=first.n_cols, n_tiles=first.n_tiles, n_w=first.n_w,
        block=first.block,
        zero_blocks=tuple(tuple(bool(v) for v in row) for row in zb),
        zero_col_frac=tuple(tuple(float(v) for v in row) for row in zf),
        plane_zero_frac=tuple(
            tuple(tuple(float(v) for v in row) for row in plane)
            for plane in pf
        ),
    )


def shard_occupancy(
    occ: Optional[ColumnOccupancy], n_shards: int
) -> Optional[ColumnOccupancy]:
    """Re-slice global column metadata for an ``n_shards``-way column
    split, merged conservatively across shards.

    ``shard_map`` traces the tensor-parallel forward ONCE for every
    device (SPMD), so the per-shard static metadata must be a single
    object that is *safe for every shard*: shard ``s`` sees global
    columns ``[s*O/n, (s+1)*O/n)``, and the returned metadata marks a
    local block skippable only when the corresponding block is zero in
    ALL shards (logical AND; fractions are the per-shard minimum —
    exactly :func:`merge_occupancies` over the shard slices).

    Returns ``occ`` unchanged for ``n_shards <= 1`` and ``None`` (the
    dense path) when the split is not representable: columns that do
    not divide evenly, or a shard boundary that would cut through a
    metadata block.

    >>> import numpy as np
    >>> w = np.zeros((4, 8)); w[:, 0] = 1            # block 0 dense
    >>> occ = column_occupancy(w, xbar_rows=4, n_w=2, block=2)
    >>> s = shard_occupancy(occ, 2)                  # local O = 4
    >>> s.n_cols, s.zero_blocks
    (4, ((False, True),))
    >>> shard_occupancy(occ, 3) is None              # 8 % 3 != 0
    True
    """
    if occ is None or n_shards <= 1:
        return occ
    if occ.n_cols % n_shards:
        return None
    o_local = occ.n_cols // n_shards
    if o_local % occ.block:
        return None           # a shard boundary would split a block
    nbl = o_local // occ.block
    shards = []
    for s in range(n_shards):
        sl = slice(s * nbl, (s + 1) * nbl)
        shards.append(ColumnOccupancy(
            n_cols=o_local, n_tiles=occ.n_tiles, n_w=occ.n_w,
            block=occ.block,
            zero_blocks=tuple(row[sl] for row in occ.zero_blocks),
            zero_col_frac=tuple(row[sl] for row in occ.zero_col_frac),
            plane_zero_frac=tuple(
                tuple(p[sl] for p in plane)
                for plane in occ.plane_zero_frac
            ),
        ))
    return merge_occupancies(shards)


def kernel_block_flags(
    occ: ColumnOccupancy, block_o: int, o_pad: int
) -> np.ndarray:
    """Align metadata blocks to a kernel's column grid: int32 (T, O_pad/BO).

    A kernel grid block is skippable iff **every** metadata block it
    overlaps is all-zero (conservative when widths disagree); blocks
    past the real column count are pure padding and always skippable.

    >>> import numpy as np
    >>> occ = column_occupancy(np.zeros((4, 100)), xbar_rows=4, n_w=4)
    >>> kernel_block_flags(occ, 128, 128)
    array([[1]], dtype=int32)
    """
    zb = occ.zero_blocks_np()                    # (T, NB) at width occ.block
    n_ob = o_pad // block_o
    flags = np.zeros((occ.n_tiles, n_ob), np.int32)
    for oi in range(n_ob):
        lo = oi * block_o
        hi = min(lo + block_o, occ.n_cols)
        if lo >= occ.n_cols:
            flags[:, oi] = 1                     # padding-only block
            continue
        b0 = lo // occ.block
        b1 = math.ceil(hi / occ.block)
        flags[:, oi] = np.all(zb[:, b0:b1], axis=1)
    return flags


def occupancy_for_kernel(
    occ: Optional[ColumnOccupancy], n_cols: int, k: int, xbar_rows: int
) -> Optional[ColumnOccupancy]:
    """Validate metadata against the actual kernel operands.

    Returns ``occ`` when it describes this ``(k, n_cols)`` problem and
    has at least one skippable block; ``None`` otherwise (dense path) —
    notably under tensor parallelism, where each shard sees local
    columns but the replicated metadata still describes the global O.
    """
    if occ is None or not occ.matches(n_cols, xbar_rows, k):
        return None
    if not any(any(row) for row in occ.zero_blocks):
        return None
    return occ
