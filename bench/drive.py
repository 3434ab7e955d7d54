"""Drive ``ServeEngine`` with a cell's traffic and record every token.

One thread, one process: the loop submits requests when they are due
(open loop) or when a client's last request finishes (closed loop),
calls ``engine.step()`` while anything is queued or live, and stamps
each token it hands back. A request is timed from when it was due.
"""
from __future__ import annotations

import contextlib
import gc
import time
from typing import Callable, Dict, List, Optional

import jax

from bench import traffic as T
from bench.window import stamp

DRAIN_S = 60.0   # how long past the window a first token is waited for


class JitEvents:
    """Traces, backend compiles and persistent-cache loads in this
    process, as JAX reports them."""

    DURATIONS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
                 "/jax/core/compile/backend_compile_duration": "compiles"}

    def __init__(self):
        self.counts = {"traces": 0, "compiles": 0, "cache_loads": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, name, secs, **_):
        if name in self.DURATIONS:
            self.counts[self.DURATIONS[name]] += 1

    def _on_event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.counts["cache_loads"] += 1


class GcPauses:
    """Collections of Python's garbage collector: (start, seconds)."""

    def __init__(self):
        self.pauses: List = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.time()
        else:
            self.pauses.append((self._t, time.time() - self._t))

    def close(self) -> None:
        gc.callbacks.remove(self._on)


def make_engine(arch, params, cfg: Dict, traffic: Dict):
    """The program's serving engine as the configuration's deployment
    sets it up, its context as long as the traffic's limit."""
    from repro.serve import EngineConfig, ServeEngine

    e = cfg["engine"]
    return ServeEngine(params, arch, EngineConfig(
        max_batch=e["slots"], max_len=traffic["context_limit"],
        mode="continuous", prefill_batch=e["prefill_batch"],
        decode_horizon=e["decode_horizon"]))


def warm_up(eng, stream: List[Dict], prompts: List, cfg: Dict) -> int:
    """Serve, once, every prefill shape the stream can ask for: each
    prompt-length bucket the engine will use, at every prefill batch
    size up to the engine's, and the decode step. Returns the number of
    shapes."""
    batch = cfg["engine"]["prefill_batch"]
    longest = {}
    for r, p in zip(stream, prompts):
        b = eng._bucket(r["prompt_len"])
        if r["prompt_len"] > len(longest.get(b, ())):
            longest[b] = p
    sizes = [1 << i for i in range(batch.bit_length()) if 1 << i <= batch]
    for p in longest.values():
        for m in sizes:
            for _ in range(m):
                eng.submit(p, max_new_tokens=2)
            eng.run()
    eng.reset_stats()
    # what warm-up leaves behind lives as long as the process: frozen, a
    # full collection inside the window no longer walks it
    gc.collect()
    gc.freeze()
    return len(longest) * len(sizes)


class Recorder:
    """Per-request records: due time, token timestamps, served tokens."""

    def __init__(self, eng, stream, prompts):
        self.eng, self.stream, self.prompts = eng, stream, prompts
        self.records: List[Dict] = []
        # (start, seconds, prompt tokens prefilled) of every step
        self.steps: List = []
        self._by_uid: Dict[int, Dict] = {}

    def submit(self, i: int, due: float) -> None:
        uid = self.eng.submit(self.prompts[i],
                              max_new_tokens=self.stream[i]["output_len"])
        rec = {"index": i, "uid": uid, "due": due, "sent": time.time(),
               "times": [], "output": [], "prompt": self.prompts[i],
               "request": self.eng.queue[-1]}
        self.records.append(rec)
        self._by_uid[uid] = rec

    def step(self) -> List[Dict]:
        """One engine round; returns the records that finished in it."""
        t0, p0 = time.time(), self.eng.prefill_tokens
        deltas = self.eng.step()
        t = time.time()
        self.steps.append((t0, t - t0, self.eng.prefill_tokens - p0))
        done = []
        for uid, toks in deltas.items():
            rec = self._by_uid[uid]
            if not rec["times"]:
                # a client holds its first tokens when step() hands them
                # over: all at t, however early the engine made them
                rec["times"].extend([t] * len(toks))
            else:
                stamp(rec["times"], len(toks), t)
            rec["output"].extend(int(x) for x in toks)
            if rec["request"].done:
                done.append(rec)
        return done


def serve(eng, stream: List[Dict], prompts: List, traffic: Dict,
          seconds: float, on_open: Optional[Callable] = None,
          on_close: Optional[Callable] = None, annotate: bool = False):
    """Pre-roll, then the measured window of ``seconds``, then the drain.

    Returns ``(recorder, w0, w1)``: the window is [w0, w1] on the
    ``time.time()`` clock. ``on_open``/``on_close`` run at the window's
    edges (counter snapshots, the profiler).
    """
    span = (jax.profiler.TraceAnnotation if annotate
            else lambda name: contextlib.nullcontext())
    rec = Recorder(eng, stream, prompts)
    closed = traffic["loop"] == "closed"
    t0 = time.time()
    w0 = t0 + traffic["preroll_s"]
    w1 = w0 + seconds
    nxt = 0
    if closed:
        for nxt in range(T.clients(traffic, eng.ecfg.max_batch)):
            rec.submit(nxt, t0)
        nxt += 1
    opened = False
    while True:
        now = time.time()
        if not opened and now >= w0:
            opened = True
            if on_open:
                on_open()
        if now >= w1:
            break
        if not closed:
            while nxt < len(stream) and t0 + stream[nxt]["offset"] <= now:
                rec.submit(nxt, t0 + stream[nxt]["offset"])
                nxt += 1
        if eng.drained:
            due = (t0 + stream[nxt]["offset"] if not closed
                   and nxt < len(stream) else w1)
            wake = min(due, w1 if opened else w0)
            with span("wait_arrival"):
                time.sleep(max(0.0, wake - time.time()))
            continue
        with span("step"):
            finished = rec.step()
        if closed:
            for _ in finished:
                if nxt < len(stream):
                    rec.submit(nxt, time.time())
                    nxt += 1
    if on_close:
        on_close()
    # late first tokens are waited for; no new request is sent
    pending = [r for r in rec.records if w0 <= r["due"] < w1]
    while (any(not r["times"] for r in pending) and not eng.drained
           and time.time() < w1 + DRAIN_S):
        rec.step()
    return rec, w0, w1
