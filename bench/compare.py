"""The check that decides ``correct``: served tokens against the plain
reference.

Once the window has closed and the program is freed, a sample of the
finished requests, drawn from the seed and holding the longest, is run
through the reference: one forward pass over each prompt with its served
tokens. At every served position the gap is how far the served token's
reference logit lies below the reference's best. The numbers compared
are those that ``bench/checks/<cell>.json`` gives a limit: summaries of
the gaps over all compared tokens (``summarize``). The control reads the
same numbers for the token that the reference computed one precision
below the configuration's (``bf16``: one bf16 pass) puts first, and is
judged by the same ``verdict``.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

CHECKS_DIR = pathlib.Path(__file__).resolve().parent / "checks"
TARGET_TOKENS = 1200      # served tokens compared per run, at least
MAX_REQUESTS = 6
LENGTHS = (512, 1024, 2048)  # reference sequences are padded to one of these
# the control's precision: the next one below the configuration's
CONTROL = {"high": "bf16"}


def limits(cell: str) -> Dict[str, float]:
    """The cell's limits, set from readings (bench/checks/<cell>.json)."""
    return json.loads((CHECKS_DIR / f"{cell}.json").read_text())["limits"]


def sample(finished: List[Dict], seed: int) -> List[Dict]:
    """The longest finished request, then others drawn from ``seed``
    until TARGET_TOKENS served tokens or MAX_REQUESTS requests."""
    pool = [r for r in finished if r["output"]]
    if not pool:
        return []
    pool.sort(key=lambda r: (-len(r["output"]), r["index"]))
    chosen, rest = [pool[0]], pool[1:]
    order = np.random.default_rng(seed).permutation(len(rest))
    for i in order:
        if (sum(len(r["output"]) for r in chosen) >= TARGET_TOKENS
                or len(chosen) >= MAX_REQUESTS):
            break
        chosen.append(rest[int(i)])
    return chosen


@jax.jit
def _gaps(logits, idx, served, other):
    """At rows ``idx``: reference best minus the reference logit of the
    served token, and of the token ``other`` logits put first."""
    rows = logits[idx]
    best = jnp.max(rows, axis=-1)
    g_served = best - jnp.take_along_axis(rows, served[:, None], -1)[:, 0]
    pick = jnp.argmax(other[idx], axis=-1)
    g_other = best - jnp.take_along_axis(rows, pick[:, None], -1)[:, 0]
    return g_served, g_other


def readings(model, cfg: Dict, weights: Dict, reqs: List[Dict],
             control: bool = False) -> Dict[str, Dict]:
    """Gaps of the served tokens (``program``) against the reference at
    the configuration's precision and, with ``control``, of the tokens
    the reference one precision lower puts first (``control_<mode>``)."""
    mode = cfg["matmul_precision"]
    low = CONTROL[mode]
    gaps = {"program": [], f"control_{low}": []}
    for r in reqs:
        seq = np.concatenate([r["prompt"], np.asarray(r["output"], np.int32)])
        s = next((n for n in LENGTHS if n >= len(seq)), len(seq))
        toks = jnp.asarray(np.pad(seq, (0, s - len(seq))))
        p = len(r["prompt"])
        idx = jnp.arange(p - 1, p - 1 + len(r["output"]))
        served = jnp.asarray(r["output"], jnp.int32)
        ref = model.forward(cfg, weights, toks, mode)
        other = model.forward(cfg, weights, toks, low) if control \
            else ref
        g, c = _gaps(ref, idx, served, other)
        gaps["program"].append(np.asarray(g))
        if control:
            gaps[f"control_{low}"].append(np.asarray(c))
        del ref, other
    return {name: summarize(np.concatenate(v)) for name, v in gaps.items()
            if v}


def summarize(g: np.ndarray) -> Dict[str, float]:
    return {"mean_logit_gap": float(np.mean(g)),
            "widest_logit_gap": float(np.max(g)),
            "mismatch_share": float(np.mean(g > 0)),
            "mismatches": int(np.sum(g > 0)), "tokens": int(g.size)}


def verdict(read: Dict[str, float], without_first_token: int,
            limit: Dict[str, float]) -> Dict[str, Dict]:
    """Every number compared, with its limit: each number the cell's
    checks file limits, and the requests due in the window that never
    got a first token."""
    checks = {k: {"value": read[k], "limit": v} for k, v in limit.items()}
    checks["requests_without_first_token"] = {"value": without_first_token,
                                              "limit": 0}
    return checks


def passed(checks: Dict[str, Dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
