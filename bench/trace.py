"""From the profiler's trace to device times.

``load`` turns the ``.xplane.pb`` the JAX profiler wrote into plain
event lists (seconds on the host clock): the device's XLA modules and
ops, and the host spans the serving loop (``drive.py``) annotated.
``reduce`` derives from those lists everything the per-layer metrics
read; it is what the tests check on a small recorded trace
(``tests/data/trace_small.json``).
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]          # (name, start s, end s)

DECODE_MODULE = "jit__decode_multi"        # the program's jitted step names
PREFILL_MODULE = "jit__prefill_bucket"
PSQ_KERNEL = "psq_matmul_kernel"
CONTAINERS = ("while", "call", "conditional")   # ops that hold other ops
HOST_SPANS = ("step", "wait_arrival", "window")


def load(trace_dir: str) -> Dict:
    """Events of the newest trace under ``trace_dir``: ``window`` (the
    host's window span), ``modules`` and ``ops`` per device, ``host``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = devices.setdefault(plane.name, {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    dev[key].extend((e.name, e.start_ns * 1e-9,
                                     (e.start_ns + e.duration_ns) * 1e-9)
                                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events if e.name in HOST_SPANS)
    windows = [e for e in host if e[0] == "window"]
    if not devices or not windows:
        raise ValueError(f"trace has {len(devices)} TPU planes and "
                         f"{len(windows)} window spans")
    return {"window": windows[0][1:], "devices": list(devices.values()),
            "host": [e for e in host if e[0] != "window"]}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(events: List[Event], w0: float, w1: float) -> List[Event]:
    return [(n, max(a, w0), min(b, w1)) for n, a, b in events
            if b > w0 and a < w1]


def op_name(name: str) -> str:
    """``%psq_matmul_kernel.53 = f32[...] ...`` -> ``psq_matmul_kernel.53``"""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def phase_of(name: str) -> str:
    if name.startswith(DECODE_MODULE):
        return "decode"
    if name.startswith(PREFILL_MODULE):
        return "prefill"
    return "other"


def reduce_device(dev: Dict, w0: float, w1: float) -> Dict:
    """Busy time, time per phase, PSQ kernel time per phase, op totals
    and idle gaps of one device inside the window [w0, w1]."""
    modules = _clip(dev["modules"], w0, w1)
    ops = _clip(dev["ops"], w0, w1)
    busy = _union([(a, b) for _, a, b in ops])
    phase_s = {"decode": 0.0, "prefill": 0.0, "other": 0.0}
    phase_calls = {"decode": 0, "prefill": 0, "other": 0}
    spans = []
    for n, a, b in modules:
        ph = phase_of(n)
        phase_s[ph] += b - a
        phase_calls[ph] += 1
        spans.append((a, b, ph))
    spans.sort()
    psq_s = {"decode": 0.0, "prefill": 0.0, "other": 0.0}
    psq_calls = {"decode": 0, "prefill": 0, "other": 0}
    totals: Dict[str, float] = {}
    for n, a, b in ops:
        name = op_name(n)
        if name.startswith(CONTAINERS):
            continue
        totals[name] = totals.get(name, 0.0) + (b - a)
        if name.startswith(PSQ_KERNEL):
            ph = next((p for s0, s1, p in spans if s0 <= a < s1), "other")
            psq_s[ph] += b - a
            psq_calls[ph] += 1
    gaps = [(b0, a1) for (_, b0), (a1, _) in zip(busy, busy[1:])]
    if busy:
        gaps = [(w0, busy[0][0])] + gaps + [(busy[-1][1], w1)]
    return {"busy_s": sum(b - a for a, b in busy), "phase_s": phase_s,
            "phase_calls": phase_calls, "psq_s": psq_s,
            "psq_calls": psq_calls, "op_s": totals,
            "gaps": [g for g in gaps if g[1] > g[0]]}


def reduce(events: Dict) -> Dict:
    """Device numbers averaged over the chips used, the ten ops that took
    most time and the ten longest idle gaps by what the host was doing."""
    w0, w1 = events["window"]
    per = [reduce_device(d, w0, w1) for d in events["devices"]]
    n = len(per)
    mean = lambda f: sum(f(p) for p in per) / n
    out = {
        "window_s": w1 - w0,
        "busy_s": mean(lambda p: p["busy_s"]),
        "phase_s": {k: mean(lambda p: p["phase_s"][k])
                    for k in per[0]["phase_s"]},
        "phase_calls": per[0]["phase_calls"],
        "psq_s": {k: mean(lambda p: p["psq_s"][k]) for k in per[0]["psq_s"]},
        "psq_calls": per[0]["psq_calls"],
    }
    ops: Dict[str, float] = {}
    for p in per:
        for k, v in p["op_s"].items():
            ops[k] = ops.get(k, 0.0) + v / n
    out["device_ops"] = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    host = sorted(events["host"], key=lambda e: e[1])
    labelled = []
    for a, b in per[0]["gaps"]:
        mid = 0.5 * (a + b)
        label = next((nm for nm, s0, s1 in host if s0 <= mid < s1),
                     "untracked")
        labelled.append([label, b - a])
    out["idle_gaps"] = sorted(labelled, key=lambda g: -g[1])[:10]
    return out
