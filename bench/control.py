"""Readings that set a cell's limits: the program over many seeds, and
the control.

    python3 -m bench.control --workload m7b-chat --seeds 11 12 13 --seconds 20

For each seed, in one process: draw the weights, serve the cell's
traffic (pre-roll, then ``--seconds`` at the cell's own load), and read
the numbers the check compares for the served tokens (``program``) and
for the tokens that the reference computed one precision below the
configuration's puts first at the same positions (``control_bf16``; the
control needs no decoding). Where the cell has limits, each reading is
judged by the check's own ``verdict``, as a run would judge it. One engine serves every seed: its compiled programs take the
weights as an argument, so only the first seed compiles and warms up.
Each seed prints one JSON line; ``bench/checks/<cell>.json`` keeps the
limit set from them. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from bench.run import ROOT, configure_jax, fail, log


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from bench import cells, compare, drive
    from bench import traffic as T

    cell = cells.find(cells.load_benchmark(), args.workload)
    if jax.devices()[0].platform != "tpu":
        fail("needs a TPU")
    configure_jax(cell["config"]["matmul_precision"])
    cfg, traffic, model = cell["config"], cell["traffic"], cell["model"]
    stream = T.schedule(traffic)
    eng = None
    for seed in args.seeds:
        t = time.time()
        weights = model.draw(cfg, seed)
        arch, params = model.program(cfg, weights)
        prompts = T.prompts(stream, cfg["vocab_size"], seed)
        if eng is None:
            eng = drive.make_engine(arch, params, cfg, traffic)
            drive.warm_up(eng, stream, prompts, cfg)
        eng.params = params
        eng.reset_stats()
        rec, _, _ = drive.serve(eng, stream, prompts, traffic, args.seconds)
        # nothing of this seed stays queued or live for the next one;
        # only finished requests are compared
        eng.queue.clear()
        for slot, r in enumerate(eng.state.slots):
            if r is not None:
                eng.state.retire(slot)
        reqs = compare.sample([r for r in rec.records if r["request"].done],
                              seed)
        del rec, params
        gc.collect()
        read = compare.readings(model, cfg, weights, reqs, control=True)
        correct = {}
        if (compare.CHECKS_DIR / f"{args.workload}.json").exists():
            limits = compare.limits(args.workload)
            correct = {k: compare.passed(compare.verdict(v, 0, limits))
                       for k, v in read.items()}
        out = {"cell": args.workload, "seed": seed,
               "requests": len(reqs), **read, "correct": correct,
               "seconds": round(time.time() - t, 3)}
        log(json.dumps(out))
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
