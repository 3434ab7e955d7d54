"""Quantized-execution configuration (the paper's technique as a config)."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from repro.core.quant import QuantSpec, CIFAR_SPEC


# How many scale factors a layer carries (Fig. 2(d) granularity study).
#   column     : one per (K-tile, input-bit-stream, weight-bit, out-column)
#                — the paper's operating point (Eq. 2: n_a * #columns per
#                crossbar).
#   per_stream : one per (K-tile, input-bit-stream)      (shared columns)
#   per_tile   : one per K-tile                          (shared streams)
#   per_layer  : a single scale factor                   (Fig 2d far left)
SF_GRANULARITIES = ("column", "per_stream", "per_tile", "per_layer")


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Execution mode of every PSQLinear in the model.

    mode:
      "none" — plain dense matmul (fp baseline).
      "psq"  — HCiM path: bit-sliced crossbar MVM, binary/ternary
               comparator partial sums, learned fixed-point scale factors
               accumulated DCiM-style (paper §4).
      "adc"  — analog-CiM baseline: bit-sliced crossbar MVM with a b-bit
               ADC per column (paper §5 baselines, b ∈ {4, 6, 7}).
    """

    mode: str = "none"                      # none | psq | adc
    psq_levels: str = "ternary"             # ternary | binary (Eq. 1)
    spec: QuantSpec = CIFAR_SPEC            # bit widths (a/w/sf)
    xbar_rows: int = 128                    # crossbar size R (config A=128, B=64)
    adc_bits: int = 7                       # for mode == "adc"
    sf_granularity: str = "column"
    per_channel_w: bool = False             # paper quantizes per layer
    collect_stats: bool = False             # export ternary sparsity etc.
    use_kernel: bool = False                # kernel path vs jnp QAT reference
    # named implementation from repro.kernels.registry; None -> process
    # default (compiled "pallas" on a TPU, "pallas-interpret" elsewhere,
    # unless overridden). Setting a backend implies the kernel path (see
    # ``kernel_path``).
    kernel_backend: Optional[str] = None
    fuse_planes: bool = False               # single-MXU-pass bit-plane fusion
    # skip all-zero ternary column blocks using pack-time occupancy
    # metadata (bit-exact; serving path only — QAT re-derives weights per
    # call and has no static metadata to skip with)
    sparsity_skip: bool = True

    def __post_init__(self):
        assert self.mode in ("none", "psq", "adc"), self.mode
        assert self.psq_levels in ("ternary", "binary"), self.psq_levels
        assert self.sf_granularity in SF_GRANULARITIES, self.sf_granularity
        assert self.xbar_rows in (32, 64, 128, 256), self.xbar_rows

    @property
    def quantized(self) -> bool:
        return self.mode != "none"

    @property
    def kernel_path(self) -> bool:
        """Route through the kernel registry rather than the jnp QAT ref."""
        return self.use_kernel or self.kernel_backend is not None

    def sf_shape(self, n_tiles: int, n_out: int) -> Tuple[int, int, int, int]:
        n_a, n_w = self.spec.n_bits_a, self.spec.n_bits_w
        if self.sf_granularity == "column":
            return (n_tiles, n_a, n_w, n_out)
        if self.sf_granularity == "per_stream":
            return (n_tiles, n_a, 1, 1)
        if self.sf_granularity == "per_tile":
            return (n_tiles, 1, 1, 1)
        return (1, 1, 1, 1)

    def num_scale_factors(self, k_in: int, n_out: int) -> int:
        import math

        t = math.ceil(k_in / self.xbar_rows)
        shape = self.sf_shape(t, n_out)
        n = 1
        for d in shape:
            n *= d
        return n


DENSE = QuantConfig(mode="none")
PSQ_TERNARY = QuantConfig(mode="psq", psq_levels="ternary")
PSQ_BINARY = QuantConfig(mode="psq", psq_levels="binary")


def adc_baseline(bits: int, xbar_rows: int = 128) -> QuantConfig:
    return QuantConfig(mode="adc", adc_bits=bits, xbar_rows=xbar_rows)
