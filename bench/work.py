"""Work counted from logical shapes, and the chip peaks it is held against.

Nothing here reads what a kernel happens to move: the operations and
bytes of a PSQ layer are those the HCiM datapath defines for the call,
so every exact implementation of it reads the same work.
"""
from __future__ import annotations

import json
import math
import pathlib
from typing import Dict, List, Tuple

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def psq_call(rows: float, k: int, o: int, psq: Dict) -> Tuple[float, float]:
    """(operations, bytes) of one PSQ matmul of ``rows`` x (K, O).

    Operations: every (input bit-stream, weight bit-slice) pair is one
    binary crossbar product over K, 2*rows*K*O operations each, run at
    the int8 peak (0/1 operands). Bytes: weight codes and fixed-point
    scale factors at their bits at rest, activation codes at n_a bits
    in, and float32 results out.
    """
    n_a, n_w, n_sf, r = psq["n_bits_a"], psq["n_bits_w"], psq["n_bits_sf"], \
        psq["xbar_rows"]
    t = math.ceil(k / r)
    ops = 2.0 * rows * k * o * n_a * n_w
    nbytes = (k * o * n_w / 8 + t * n_a * n_w * o * n_sf / 8
              + rows * k * n_a / 8 + rows * o * 4)
    return ops, nbytes


def psq_min_seconds(calls: float, rows_per_call: float, layers: List,
                    psq: Dict, peak: Dict) -> float:
    """Least time the chip could take for ``calls`` passes through every
    PSQ projection ``(name, K, O)`` in ``layers``, ``rows_per_call`` rows
    each: the larger of operations over the int8 peak and bytes over the
    HBM bandwidth, call by call."""
    total = 0.0
    for _, k, o in layers:
        ops, nbytes = psq_call(rows_per_call, k, o, psq)
        total += max(ops / peak["int8_ops_per_s"],
                     nbytes / peak["hbm_bytes_per_s"])
    return calls * total


def params_per_token(cfg: Dict, layers: List) -> int:
    """Parameters one token passes through: every projection of every
    layer, and the LM head (the embedding is a lookup)."""
    per_layer = sum(k * o for _, k, o in layers)
    return (cfg["num_hidden_layers"] * per_layer
            + cfg["hidden_size"] * cfg["vocab_size"])


def attention_flops(cfg: Dict, context: float) -> float:
    """QK^T and PV of one query token over ``context`` keys, all layers."""
    return (4.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * cfg["head_dim"] * context)


def model_flops(cfg: Dict, layers: List, tokens: float,
                context_sum: float) -> float:
    """Dense-equivalent FLOPs of ``tokens`` tokens whose contexts sum to
    ``context_sum`` keys: 2 x parameters x tokens, attention added."""
    return (2.0 * params_per_token(cfg, layers) * tokens
            + attention_flops(cfg, context_sum))
