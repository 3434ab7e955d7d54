"""Find an open-loop cell's knee: the highest arrival rate it sustains.

    python3 -m bench.sweep --workload m7b-chat --rates 1 1.25 1.5 --seconds 30

For each rate, in one process and one engine, the cell's traffic is
served at that rate (pre-roll, then ``--seconds``), and the backlog
of requests due but not yet started is taken at every second of the
window. A rate is sustained when that backlog does not grow: its last
quarter's mean is no more than one request above its first quarter's.
The backlog at a second is read back from the records: requests due by
then without a first token.
A cell is then defined at about four fifths of the knee; the rate goes
into its traffic file as a number. The benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

from bench.run import ROOT, configure_jax, fail


def backlog_at(records, t: float) -> int:
    """Requests due by ``t`` that had no first token yet at ``t``."""
    return sum(1 for r in records
               if r["due"] <= t and (not r["times"] or r["times"][0] > t))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2147483600)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from bench import cells, drive
    from bench import traffic as T
    from bench import window as W

    cell = cells.find(cells.load_benchmark(), args.workload)
    if jax.devices()[0].platform != "tpu":
        fail("needs a TPU")
    if cell["traffic"]["loop"] != "open":
        fail("a closed loop has no arrival rate to sweep")
    configure_jax(cell["config"]["matmul_precision"])
    cfg, model = cell["config"], cell["model"]
    weights = model.draw(cfg, args.seed)
    arch, params = model.program(cfg, weights)
    eng = None
    knee = None
    for rate in args.rates:
        traffic = dict(cell["traffic"], rate_per_s=rate)
        stream = T.schedule(traffic)
        prompts = T.prompts(stream, cfg["vocab_size"], args.seed)
        if eng is None:
            eng = drive.make_engine(arch, params, cfg, traffic)
            drive.warm_up(eng, stream, prompts, cfg)
        rec, w0, w1 = drive.serve(eng, stream, prompts, traffic,
                                  args.seconds)
        backlog = [backlog_at(rec.records, t)
                   for t in np.arange(w0, w1, 1.0)]
        m = W.window_metrics(rec.records, w0, w1)
        q = max(1, len(backlog) // 4)
        grows = (np.mean(backlog[-q:]) > np.mean(backlog[:q]) + 1.0
                 if backlog else True)
        out = {"rate_per_s": rate, "backlog": backlog, "grows": bool(grows),
               **{k: m[k] for k in ("arrived", "ttft_p90_s", "tpot_ms",
                                    "itl_p99_ms", "output_tokens_per_s")}}
        print(json.dumps(out), flush=True)
        eng.queue.clear()
        for slot, r in enumerate(eng.state.slots):
            if r is not None:
                eng.state.retire(slot)
        if not grows:
            knee = rate
    print(json.dumps({"knee_per_s": knee}), flush=True)


if __name__ == "__main__":
    main()
