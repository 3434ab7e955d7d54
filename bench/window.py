"""Window arithmetic: from per-request token timestamps to metrics.

A request record holds ``due`` (when it was due to be sent) and
``times``, the timestamp of each of its tokens. Where the engine hands
over several tokens at one host sync, the tokens between two real
timestamps are spread evenly over that interval (``stamp``), so every
gap comes from real timestamps.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolation percentile (numpy's default), ``q`` in [0, 100]."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def stamp(times: List[float], n_new: int, t: float) -> None:
    """Append ``n_new`` tokens delivered at sync time ``t``: the last one
    at ``t``, the others evenly between the previous token and ``t``."""
    if n_new <= 0:
        return
    t_prev = times[-1] if times else t
    for i in range(1, n_new + 1):
        times.append(t_prev + (t - t_prev) * i / n_new)


def gaps_in(records: Iterable[Dict], w0: float, w1: float) -> List[float]:
    """Every gap between consecutive tokens of a request whose two ends
    lie inside the window [w0, w1]."""
    out = []
    for r in records:
        ts = r["times"]
        for a, b in zip(ts, ts[1:]):
            if w0 <= a and b <= w1:
                out.append(b - a)
    return out


def tokens_in(records: Iterable[Dict], w0: float, w1: float) -> int:
    return sum(1 for r in records for t in r["times"] if w0 <= t < w1)


def arrived_in(records: Iterable[Dict], w0: float, w1: float) -> List[Dict]:
    return [r for r in records if w0 <= r["due"] < w1]


def ttfts(records: Iterable[Dict]) -> List[float]:
    return [r["times"][0] - r["due"] for r in records if r["times"]]


def window_metrics(records: List[Dict], w0: float, w1: float) -> Dict:
    """Every end-to-end number the window yields. ``tpot_ms`` is the
    window's aggregate: the sum of all its inter-token gaps over their
    number, so each gap weighs the same and a short request counts by
    its gaps, not as one of N requests."""
    arrived = arrived_in(records, w0, w1)
    gaps = gaps_in(records, w0, w1)
    tt = ttfts(arrived)
    return {
        "arrived": len(arrived),
        "without_first_token": sum(1 for r in arrived if not r["times"]),
        "ttft_p50_s": percentile(tt, 50) if tt else None,
        "ttft_p90_s": percentile(tt, 90) if tt else None,
        "tpot_ms": 1e3 * sum(gaps) / len(gaps) if gaps else None,
        "itl_p50_ms": 1e3 * percentile(gaps, 50) if gaps else None,
        "itl_p99_ms": 1e3 * percentile(gaps, 99) if gaps else None,
        "gaps": len(gaps),
        "output_tokens_per_s": tokens_in(records, w0, w1) / (w1 - w0),
    }
