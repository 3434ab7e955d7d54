"""The one traffic generator: every mix is a data file it reads.

The schedule (arrival offsets, prompt and output lengths) comes from the
generator seed the traffic file fixes, so every run of a cell offers the
same work; ``--seed`` chooses only the prompt token ids (and the
weights). A traffic file holds:

``loop``            ``"open"`` (arrivals on a clock) or ``"closed"``
``rate_per_s``      open: Poisson arrival rate
``clients_per_slot`` closed: clients per engine decode slot; each sends
                    its next request when its last one finishes
``requests``        length of the fixed request stream
``generator_seed``  seed of the schedule
``preroll_s``       seconds of the same stream served before the window
``prompt_tokens`` / ``output_tokens``
                    ``{"median", "sigma", "min", "max"}``: lognormal
                    lengths (heavy-tailed), clipped to [min, max]
``context_limit``   prompt + output never exceeds it
``sources``         where each parameter comes from

The engine that serves the mix (slots, prefill batch, decode horizon)
is the deployment's, and sits in the configuration's file.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def _lengths(rng: np.random.Generator, spec: Dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def schedule(traffic: Dict) -> List[Dict]:
    """The cell's fixed request stream: ``offset`` (open loop: seconds
    after the stream starts), ``prompt_len`` and ``output_len``."""
    rng = np.random.default_rng(traffic["generator_seed"])
    n = traffic["requests"]
    prompt = _lengths(rng, traffic["prompt_tokens"], n)
    output = _lengths(rng, traffic["output_tokens"], n)
    output = np.minimum(output, traffic["context_limit"] - prompt)
    if traffic["loop"] == "open":
        offsets = np.cumsum(rng.exponential(1.0 / traffic["rate_per_s"],
                                            size=n))
    else:
        offsets = np.zeros(n)
    if np.any(output < 1):
        raise ValueError("a prompt leaves no room for output under the "
                         "context limit; lower prompt_tokens.max")
    return [{"offset": float(o), "prompt_len": int(p), "output_len": int(q)}
            for o, p, q in zip(offsets, prompt, output)]


def prompts(stream: List[Dict], vocab: int, seed: int) -> List[np.ndarray]:
    """Prompt token ids of every request, drawn from ``seed`` alone."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, size=sum(r["prompt_len"] for r in stream),
                       dtype=np.int32)
    cuts = np.cumsum([r["prompt_len"] for r in stream])[:-1]
    return np.split(ids, cuts)


def clients(traffic: Dict, slots: int) -> int:
    """Closed loop: number of clients of an engine with ``slots``."""
    return int(round(traffic["clients_per_slot"] * slots))
