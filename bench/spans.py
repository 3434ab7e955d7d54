"""The program's own spans and scopes in a profiler trace.

The serving engine annotates every scheduling round with ``serve.*``
host spans (``repro/serve``), and the compiled decode and prefill
programs carry named scopes in their ops' ``op_name`` metadata
(``repro/models/decode.py``). ``trace.load`` keeps only the harness's
own spans; this module reads the program's beside them and reduces
them to what says why the device idles and which part of a step owns
its time:

* ``host_ms_per_step``: host time of one ``serve.step`` outside its
  two ``*.wait`` children, averaged over the steps in the window;
* ``idle_engine_s``: device-idle time in the window while the host is
  inside a ``serve.*`` span;
* ``label_gaps``: each idle gap named by the innermost span that covers
  its midpoint, program spans before the harness's own;
* ``scope_times``: device time per top-level scope per phase.

A TPU trace names each op by its HLO instruction and carries no
``op_name`` (no stat of an op event holds it), so the scopes come from
the compiled program's text: ``op_names`` maps each instruction of a
module to its ``op_name``. An op under none of the scopes counts as
``unscoped``; an op of a module whose text was not given, ``unmapped``.

Events are ``(name, start s, end s)`` on the profiler's clock, as in
``trace.py``.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from bench import trace as TR

Event = Tuple[str, float, float]

SPANS = ("serve.step", "serve.admit", "serve.prefill.wait",
         "serve.decode.dispatch", "serve.decode.wait", "serve.decode.drain")
WAITS = ("serve.prefill.wait", "serve.decode.wait")
SCOPES = ("qkv", "attention", "kv_commit", "ffn", "lm_head", "sample")
UNSCOPED = "unscoped"
UNMAPPED = "unmapped"


def load(trace_dir: str) -> List[Event]:
    """The ``serve.*`` host spans of the newest trace under
    ``trace_dir``, in time order."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    spans: List[Event] = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                             for e in line.events if e.name in SPANS)
    return sorted(spans, key=lambda s: s[1])


def op_names(hlo_text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` of a compiled program's ``as_text()``
    (an empty ``op_name`` where the instruction has no metadata)."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.match(r'\s*(?:ROOT )?%([\w.\-]+) = ', line)
        if m:
            n = re.search(r'op_name="([^"]*)"', line)
            out[m.group(1)] = n.group(1) if n else ""
    return out


def decode_text(eng) -> str:
    """The compiled text of ``eng``'s decode program, as it serves
    (from the compile cache when warm)."""
    import jax.numpy as jnp

    n = eng.ecfg.max_batch
    return eng._decode_multi.lower(
        eng.params, eng._cache, jnp.asarray(eng.state.last_tok),
        jnp.asarray(eng.state.live_flags()), jnp.full((n,), -1, jnp.int32),
        jnp.ones((n,), jnp.int32), eng.ecfg.decode_horizon,
    ).compile().as_text()


def scope_of(op_name: str) -> str:
    """The outermost of the program's scopes in an op's ``op_name``
    path (``jit(f)/while/body/kv_commit/...`` -> ``kv_commit``)."""
    return next((p for p in op_name.split("/") if p in SCOPES), UNSCOPED)


def module_of(name: str) -> str:
    """``jit__decode_multi(5094939531756481140)`` -> ``jit__decode_multi``"""
    return name.split("(", 1)[0]


def _within(inner: Event, outer: Event) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def host_ms_per_step(spans: List[Event], w0: float,
                     w1: float) -> Optional[float]:
    """Mean over the ``serve.step`` spans that start in [w0, w1) of the
    step's length less its ``*.wait`` children, in ms."""
    steps = [s for s in spans if s[0] == "serve.step" and w0 <= s[1] < w1]
    if not steps:
        return None
    waits = [s for s in spans if s[0] in WAITS]
    host = sum(s[2] - s[1] for s in steps)
    host -= sum(w[2] - w[1] for w in waits
                if any(_within(w, s) for s in steps))
    return 1e3 * host / len(steps)


def _intersect(a: List[Tuple[float, float]],
               b: List[Tuple[float, float]]) -> float:
    """Length of the intersection of two unions of intervals."""
    a, b = TR._union(a), TR._union(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_engine_s(gaps: List[Tuple[float, float]],
                  spans: List[Event]) -> float:
    """Device-idle time (``gaps`` from ``trace.reduce_device``) while the
    host is inside any ``serve.*`` span."""
    return _intersect(gaps, [(a, b) for _, a, b in spans])


def _innermost(t: float, events: List[Event]) -> Optional[str]:
    cover = [e for e in events if e[1] <= t < e[2]]
    return min(cover, key=lambda e: e[2] - e[1])[0] if cover else None


def label_gaps(gaps: List[Tuple[float, float]], spans: List[Event],
               host: List[Event],
               top: Optional[int] = 10) -> List[list]:
    """The ``top`` longest gaps (all with ``None``), each ``[label,
    seconds]``: the innermost program span that covers its midpoint,
    else the innermost harness span (``host``), else ``untracked``."""
    out = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        out.append([_innermost(mid, spans) or _innermost(mid, host)
                    or "untracked", b - a])
    return sorted(out, key=lambda g: -g[1])[:top]


def scope_times(dev: Dict, names: Dict[str, Dict[str, str]], w0: float,
                w1: float) -> Dict[str, Dict[str, float]]:
    """Device seconds per scope within each phase, clipped to the window:
    ``{"decode": {"ffn": s, ...}, "prefill": {...}, "other": {...}}``.
    ``names`` maps a module (``module_of``) to its ``op_names``."""
    mods = sorted((a, b, n) for n, a, b in TR._clip(dev["modules"], w0, w1))
    starts = [m[0] for m in mods]
    out: Dict[str, Dict[str, float]] = {}
    for n, a, b in TR._clip(dev["ops"], w0, w1):
        op = TR.op_name(n)
        if op.startswith(TR.CONTAINERS):
            continue
        i = bisect.bisect_right(starts, a) - 1
        mod = mods[i][2] if i >= 0 and a < mods[i][1] else ""
        table = names.get(module_of(mod))
        scope = UNMAPPED if table is None else scope_of(table.get(op, ""))
        d = out.setdefault(TR.phase_of(mod), {})
        d[scope] = d.get(scope, 0.0) + (b - a)
    return out


def reduce(events: Dict, spans: List[Event],
           names: Optional[Dict[str, Dict[str, str]]] = None) -> Dict:
    """What the program's spans and scopes add to ``trace.reduce``:
    ``events`` is ``trace.load``'s, ``spans`` this module's ``load``, of
    the same trace; ``names`` maps modules to their ``op_names``.
    Device numbers are those of the first chip."""
    w0, w1 = events["window"]
    dev = events["devices"][0]
    gaps = TR.reduce_device(dev, w0, w1)["gaps"]
    inside = TR._clip(spans, w0, w1)
    return {
        "host_ms_per_step": host_ms_per_step(spans, w0, w1),
        "idle_engine_s": idle_engine_s(gaps, inside),
        "idle_gaps": label_gaps(gaps, inside, events["host"]),
        "scope_s": scope_times(dev, names or {}, w0, w1),
    }
