"""Run one cell of the benchmark once.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Draws the weights and prompt ids from ``--seed``, builds the program's
``ServeEngine`` over PSQ-packed weights, warms every shape the cell's
traffic uses, serves a pre-roll of the traffic, then measures for
``--seconds``. With ``--trace 0`` the last line of stdout carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
read from a profiler trace of the window. Either way the served tokens
are then checked against the plain reference, and each number compared
is printed beside its limit as the last lines of stderr.

Needs a TPU with as many chips as the cell asks for; otherwise it exits
with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Dict, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
COMPILE_CACHE = ROOT / ".jax_cache"   # fixed, so a later run of the
                                      # checkout finds what this one built


def fail(msg: str, code: int = 2) -> None:
    print(f"bench: {msg}", file=sys.stderr)
    sys.exit(code)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def configure_jax(precision: str) -> None:
    """The compile cache, and the float32 matrix-product precision that
    the configuration states (``matmul_precision``): on a TPU a float32
    product runs in one bf16 pass unless told otherwise."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_default_matmul_precision", precision)


def device_info(n_used: int) -> Dict:
    import jax

    devs = jax.devices()[:n_used]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def window_counts(records, t0: float, t1: float) -> Dict:
    """Tokens decoded and prefilled in [t0, t1], with their contexts."""
    dec = dec_ctx = pre = pre_ctx = 0
    for r in records:
        p = len(r["prompt"])
        for i, t in enumerate(r["times"]):
            if not t0 <= t < t1:
                continue
            if i == 0:
                pre += p
                pre_ctx += p * (p + 1) // 2
            else:
                dec += 1
                dec_ctx += p + i
    return {"decode_tokens": dec, "decode_context": dec_ctx,
            "prefill_tokens": pre, "prefill_context": pre_ctx}


def run_cell(cell: Dict, name: str, seed: int, seconds: float, trace: bool,
             limits: Dict[str, float], backend: Optional[str] = None,
             n_chips: int = 1) -> Dict:
    """One run of one cell; returns the result line as a dict."""
    import jax

    from bench import compare, drive
    from bench import traffic as T
    from bench import window as W
    from bench import work
    from bench.cells import metric_reader

    cfg, traffic, model = cell["config"], cell["traffic"], cell["model"]
    # start: interpreter, imports and the TPU runtime coming up
    setup = {"start": time.time() - T_START}
    jit = drive.JitEvents()

    t = time.time()
    weights = model.draw(cfg, seed)
    jax.block_until_ready(weights)
    setup["weights"] = time.time() - t

    t = time.time()
    stream = T.schedule(traffic)
    prompts = T.prompts(stream, cfg["vocab_size"], seed)
    arch, params = model.program(cfg, weights, backend)
    eng = drive.make_engine(arch, params, cfg, traffic)
    setup["engine"] = time.time() - t

    t, c0 = time.time(), jit.counts["compiles"]
    shapes = drive.warm_up(eng, stream, prompts, cfg)
    setup["warm_up"] = time.time() - t
    warm_compiles = jit.counts["compiles"] - c0
    gc_pauses = drive.GcPauses()

    snap = {}
    tracer = {}

    def counters():
        return {"time": time.time(), "jit": dict(jit.counts),
                "decode_steps": eng.decode_steps,
                "prefill_calls": eng.prefill_calls}

    def on_open():
        if trace:
            tracer["dir"] = tempfile.mkdtemp(prefix="bench-trace-")
            jax.profiler.start_trace(tracer["dir"])
            tracer["span"] = jax.profiler.TraceAnnotation("window")
            tracer["span"].__enter__()
        snap["open"] = counters()

    def on_close():
        snap["close"] = counters()
        if trace:
            tracer["span"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    rec, w0, w1 = drive.serve(eng, stream, prompts, traffic, seconds,
                              on_open, on_close, annotate=trace)
    records = rec.records
    gc_pauses.close()
    gc.unfreeze()
    setup["pre_roll"] = w0 - T_START - sum(setup.values())
    setup_s = w0 - T_START
    device = device_info(n_chips)
    m = W.window_metrics(records, w0, w1)
    late = [r["sent"] - r["due"] for r in records if w0 <= r["due"] < w1]
    in_window = {k: snap["close"]["jit"][k] - snap["open"]["jit"][k]
                 for k in jit.counts}
    gcs = [d for t0, d in gc_pauses.pauses if w0 <= t0 < w1]
    # a step that prefilled nothing and still ran long is the host's
    slow = sorted((s for s in rec.steps if w0 <= s[0] < w1),
                  key=lambda s: -s[1])[:3]
    longest = ", ".join(f"{1e3 * d:.1f} ms ({n} prompt tokens)"
                        for _, d, n in slow)
    log(f"{name} seed {seed}: setup {setup_s:.3f} s (" + ", ".join(
        f"{k} {v:.3f}" for k, v in setup.items()) + f"); warm-up {shapes} "
        f"prefill shapes, {warm_compiles} compiles; in the window: "
        f"{json.dumps(in_window)}, longest steps {longest}, "
        f"{len(gcs)} GC passes, longest {1e3 * max(gcs, default=0.0):.1f} "
        f"ms; window {seconds} s, {m['arrived']} requests arrived, "
        f"{m['without_first_token']} without a first token; generator "
        f"late by at most {1e3 * max(late, default=0.0):.1f} ms")
    log("window " + json.dumps(m))
    log(f"device {json.dumps(device)}")

    result = {"correct": False, "attempted": m["arrived"],
              "failed": m["without_first_token"], "metrics": {},
              "device": device}
    if trace:
        from bench import trace as TR

        t = time.time()
        red = TR.reduce(TR.load(tracer["dir"]))
        shutil.rmtree(tracer["dir"], ignore_errors=True)
        o, c = snap["open"], snap["close"]
        counts = window_counts(records, o["time"], c["time"])
        counts.update(decode_steps=c["decode_steps"] - o["decode_steps"],
                      prefill_calls=c["prefill_calls"] - o["prefill_calls"])
        ctx = SimpleNamespace(trace=red, counts=counts, cfg=cfg,
                              layers=model.psq_layers(cfg), psq=cfg["psq"],
                              peak=work.peaks(device["kind"]))
        for entry in cell["per_layer"]:
            v = metric_reader(entry["name"]).read(ctx)
            if v is not None:
                result["metrics"][entry["name"]] = {"value": v,
                                                    "unit": entry["unit"]}
        result["device"].update(busy_s=red["busy_s"],
                                window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        log(f"trace read in {time.time() - t:.3f} s; counts "
            f"{json.dumps(counts)}; phases {json.dumps(red['phase_s'])}; "
            f"psq {json.dumps(red['psq_s'])}")
    else:
        values = dict(m, setup_s=setup_s)
        for entry in cell["end_to_end"]:
            v = values.get(entry["name"])
            if v is None:
                raise RuntimeError(f"the window yields no {entry['name']}")
            result["metrics"][entry["name"]] = {"value": v,
                                                "unit": entry["unit"]}

    finished = [r for r in records if r["request"].done]
    del eng, params, rec, records
    gc.collect()
    t = time.time()
    reqs = compare.sample(finished, seed)
    if not reqs:
        raise RuntimeError("no request finished: nothing to compare")
    read = compare.readings(model, cfg, weights, reqs)["program"]
    log(f"reference check {time.time() - t:.3f} s over {read['tokens']} "
        f"served tokens of {len(reqs)} requests: {json.dumps(read)}")
    checks = compare.verdict(read, m["without_first_token"], limits)
    result["correct"] = compare.passed(checks)
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        fail("--seed must be a whole number >= 0")
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the program (src/repro) is not in the checkout at {ROOT}", 1)
    sys.path.insert(0, str(ROOT / "src"))

    from bench import cells, compare

    bench = cells.load_benchmark()
    cell = cells.find(bench, args.workload)
    chips = cell["workload"]["chips"]

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        fail(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
             f"{devs[0].platform!r} device(s)")
    configure_jax(cell["config"]["matmul_precision"])
    result = run_cell(cell, args.workload, args.seed, args.seconds,
                      bool(args.trace), compare.limits(args.workload),
                      n_chips=chips)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
