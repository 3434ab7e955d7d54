"""Serving bench: continuous vs static batching under a mixed-length trace.

Generates a synthetic trace of requests with mixed prompt lengths and
mixed decode budgets — the regime where static batching collapses
(finished sequences hold their slot until the whole batch retires) and
continuous batching keeps the slot pool full. Both schedulers run the
SAME model, trace and slot count; each engine is warmed first so the
comparison measures steady-state scheduling, not compilation.

Reports tokens/s, mean TTFT and mean slot occupancy per mode plus the
continuous/static speedup, and writes the result as JSON
(``BENCH_serve.json``) so CI can archive the perf trajectory.

The ``recurrent_continuous`` section runs the recurrent-state families
(zamba2 hybrid, xlstm) through the same continuous-vs-static comparison
on their own mixed-length traces: masked-length prefill makes the slot
pool exact for recurrent state, so the delta is pure scheduling.
``--recurrent`` runs only this section.

The ``device_loop`` section sweeps the on-device multi-step decode loop
(``EngineConfig.decode_horizon`` 1 / 8 / 32) over the same mixed-length
trace: one ``lax.while_loop`` jit call per horizon instead of one jit
call per token, so ``host_syncs`` drops ~H-fold with bit-identical
greedy outputs. ``--device-loop`` runs only this section.

The ``paged_prefix`` section drives the PAGED engine with a
shared-system-prompt trace (every request = one long shared prefix + a
short unique tail — the chat-serving regime) with prefix reuse off vs
on: the radix index serves the shared pages from the pool, so steady
state prefills only the unique tails. Records true prefill tokens,
cached prefix tokens, the prefill-token reduction and the tokens/s
speedup (docs/memory.md). ``--paged`` runs only this section.

The ``moe`` section serves the reduced granite MoE config through the
continuous slot pool single-device and — when ``--devices`` forges
enough virtual devices — on a ``(1, 1, E)`` ("data", "model",
"expert") mesh with the expert FFN stacks sharded over the ``expert``
axis (router replicated, bit-exact dispatch; docs/parallelism.md),
asserting token-identical greedy outputs. ``--moe`` runs only this
section (``BENCH_serve_moe.json``).

The ``spec`` section (``--spec`` → ``BENCH_serve_spec.json``) benches
speculative decoding against vanilla continuous serving at ``spec_k``
in {2, 4} with two drafts — an untrained 1-layer copy (accept-rate
floor) and the served model itself (accept-rate ceiling) — plus the
side-input families (whisper encdec, llava VLM patches) continuous vs
static. Every entry records accept rate, host syncs and the tokens/s
ratio, and asserts greedy outputs token-identical across all paths.

The ``streaming`` section replays a seeded Poisson arrival schedule
through the incremental submit/poll front-end
(``repro.launch.serve.StreamingFrontend`` over ``ServeEngine.step()``):
requests arrive mid-flight at exponential inter-arrival gaps (measured
in scheduler rounds, so the schedule is exactly replayable), and the
entry records TTFT/TPOT/tokens-per-s UNDER LIVE ARRIVALS — admission
wait included — rather than the drain-the-queue figures above.
``--streaming`` runs only this section (plus ``admission``).

The ``admission`` section serves the same trace under both admission
policies (docs/scheduling.md): ``fcfs`` pow2-bucket waves vs
``cost-aware``, which prices every request through the engine's
hwmodel (``EnergyModel.request_cost_pj``) and defers admissions that
would push the modeled in-flight energy past a pJ cap (set here to
two worst-case requests, so deferrals are exercised). Greedy outputs
are asserted token-identical across policies — admission order never
changes what a request decodes, only when.

Every per-mode entry reports the engine's modeled hwmodel energy
attribution (``energy_pj``, ``energy_pj_per_request``, ``edap``,
``mean_occupancy`` — docs/energy.md). The ``--energy`` section serves
one psq-packed trace and sweeps ``energy_report`` across accounting
styles (adc / quarry / hcim) x an occupancy grid without re-serving,
recording the modeled hcim-vs-adc reduction; CI archives it as
``BENCH_serve_energy.json``.

``--devices N`` additionally sweeps tensor-parallel mesh sizes: N CPU
virtual devices are forged (``--xla_force_host_platform_device_count``,
so the flag must come before any other JAX use in the process) and the
psq-packed continuous engine runs once per ``model``-axis size in
{1, 2, ..., N} (powers of two), recording a per-mesh-size tokens/s
entry under ``"sharded"``. On CPU this measures dispatch overhead, not
speedup — the point is that CI exercises the 1/2/4-way sharded
datapath end to end (docs/parallelism.md).

    PYTHONPATH=src python benchmarks/serve_bench.py [--smoke] \
        [--requests 32] [--slots 8] [--psq-packed] [--devices 4] \
        [--out BENCH_serve.json]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Dict, List, Tuple

import numpy as np
import jax

from repro.configs import get_config
from repro.core.config import PSQ_TERNARY
from repro.kernels import registry
from repro.launch.mesh import make_mesh
from repro.models import init_model
from repro.serve import (
    EngineConfig, PackedModelCache, ServeEngine, pack_tree_psq,
    throughput_stats,
)


def make_trace(n: int, prompt_rng: Tuple[int, int], new_rng: Tuple[int, int],
               vocab: int, seed: int = 0) -> List[Tuple[np.ndarray, int]]:
    """Mixed-length synthetic trace: (prompt, max_new_tokens) pairs."""
    rng = np.random.RandomState(seed)
    trace = []
    for _ in range(n):
        plen = int(rng.randint(prompt_rng[0], prompt_rng[1] + 1))
        mnew = int(rng.randint(new_rng[0], new_rng[1] + 1))
        trace.append((rng.randint(0, vocab, size=plen), mnew))
    return trace


def make_shared_prefix_trace(
    n: int, prefix_len: int, tail_rng: Tuple[int, int],
    new_rng: Tuple[int, int], vocab: int, seed: int = 0,
) -> List[Tuple[np.ndarray, int]]:
    """Chat-style trace: one shared system prompt + short unique tails."""
    rng = np.random.RandomState(seed)
    sys_prompt = rng.randint(0, vocab, size=prefix_len)
    trace = []
    for _ in range(n):
        tail = rng.randint(0, vocab,
                           size=int(rng.randint(tail_rng[0],
                                                tail_rng[1] + 1)))
        mnew = int(rng.randint(new_rng[0], new_rng[1] + 1))
        trace.append((np.concatenate([sys_prompt, tail]), mnew))
    return trace


def make_arrivals(n: int, mean_gap_rounds: float, seed: int = 0) -> List[int]:
    """Seeded, replayable Poisson arrival schedule.

    Returns the scheduler round at which each of ``n`` requests
    arrives: exponential inter-arrival gaps with the given mean,
    cumulated and floored to round indices, shifted so the first
    request arrives at round 0. Measuring arrivals in scheduler rounds
    (not wall time) makes the schedule exactly replayable — the same
    seed produces the same admission pattern on any machine.
    """
    rng = np.random.RandomState(seed)
    t = np.floor(np.cumsum(rng.exponential(mean_gap_rounds, size=n)))
    return [int(v - t[0]) for v in t]


def bench_mode(mode: str, params, cfg, trace, slots: int,
               max_len: int, mesh=None, repeats: int = 1,
               extra_inputs=None, draft_params=None,
               **ecfg_kw) -> Dict[str, float]:
    eng = ServeEngine(params, cfg,
                      EngineConfig(max_batch=slots, max_len=max_len,
                                   mode=mode, **ecfg_kw),
                      extra_inputs=extra_inputs, mesh=mesh,
                      draft_params=draft_params)
    # side-input rows are positional by uid, which drifts across the
    # warm-up + repeat runs below — pin each request to its trace row
    def submit_all():
        for i, (prompt, mnew) in enumerate(trace):
            eng.submit(prompt, max_new_tokens=mnew,
                       extra_idx=i if extra_inputs else None)

    # warm-up pass: compile every (bucket, batch) shape the trace needs
    # (and, for a paged engine, populate the prefix index — the measured
    # passes below are the steady state)
    submit_all()
    eng.run()

    # best-of-N: sub-second CPU runs are wall-clock noisy
    wall, done, sched = float("inf"), None, None
    for _ in range(max(repeats, 1)):
        eng.reset_stats()
        t0 = time.time()
        submit_all()
        reqs = eng.run()
        w = time.time() - t0
        if w < wall:
            wall, done, sched = w, reqs, eng.stats()
    stats = throughput_stats(done)
    out = {
        "mode": eng.mode,
        "wall_s": wall,
        "tokens_per_s": stats["tokens_per_s"],
        "total_tokens": stats["total_tokens"],
        "mean_ttft_s": stats["mean_ttft_s"],
        "mean_tpot_s": stats["mean_tpot_s"],
        "decode_steps": sched["decode_steps"],
        "host_syncs": sched["host_syncs"],
        "prefill_calls": sched["prefill_calls"],
        "prefill_tokens": sched["prefill_tokens"],
        "cached_prefix_tokens": sched["cached_prefix_tokens"],
        "mean_slot_occupancy": sched["mean_slot_occupancy"],
        # modeled hwmodel energy attribution (docs/energy.md): every
        # entry carries its style, total/per-request pJ, EDAP and the
        # measured ternary column occupancy of the served weights
        "energy_style": sched["energy_style"],
        "energy_pj": sched["energy_pj_total"],
        "energy_pj_per_request": sched["energy_pj_per_request"],
        "edap": sched["edap_total"],
        "mean_occupancy": sched["mean_occupancy"],
        "admission_policy": sched["admission_policy"],
        "admission_deferrals": sched["admission_deferrals"],
    }
    if "paged" in sched:
        out["paged"] = sched["paged"]
    if "spec_k" in sched:
        for k in ("spec_k", "spec_rounds", "spec_proposed",
                  "spec_accepted", "spec_accept_rate"):
            out[k] = sched[k]
    return out


def bench_streaming(params, cfg, trace, slots: int, max_len: int,
                    mean_gap_rounds: float, seed: int = 0) -> Dict:
    """Live-arrival serving through the incremental submit/poll API.

    Replays the seeded Poisson schedule from :func:`make_arrivals`
    through ``StreamingFrontend``: each scheduler round first submits
    every request whose arrival round has come, then advances the
    engine one ``step()`` and polls the per-request token deltas. TTFT
    here includes the admission wait a late arrival experiences behind
    a busy pool — the figure the drain-the-queue sections cannot show.
    The engine is warmed on the full trace first so the measured pass
    is steady-state scheduling, not compilation.
    """
    from repro.launch.serve import StreamingFrontend

    eng = ServeEngine(params, cfg,
                      EngineConfig(max_batch=slots, max_len=max_len,
                                   mode="continuous"))
    for prompt, mnew in trace:
        eng.submit(prompt, max_new_tokens=mnew)
    eng.run()
    eng.reset_stats()

    arrivals = make_arrivals(len(trace), mean_gap_rounds, seed)
    fe = StreamingFrontend(eng)
    uids: List[int] = []
    first_round: Dict[int, int] = {}
    rounds, nxt = 0, 0
    t0 = time.time()
    while nxt < len(trace) or not fe.drained:
        while nxt < len(trace) and arrivals[nxt] <= rounds:
            prompt, mnew = trace[nxt]
            uids.append(fe.submit(prompt, max_new_tokens=mnew))
            nxt += 1
        fe.step()          # no-op on idle rounds before the next arrival
        rounds += 1
        for uid in uids:
            toks, _ = fe.poll(uid)
            if toks and uid not in first_round:
                first_round[uid] = rounds
    wall = time.time() - t0
    stats = throughput_stats(eng.finished)
    sched = eng.stats()
    out = {
        "arrival_seed": seed,
        "arrival_mean_gap_rounds": mean_gap_rounds,
        "arrival_rounds": arrivals,
        "rounds": rounds,
        "mean_first_token_round": (
            float(np.mean([first_round[u] - arrivals[i]
                           for i, u in enumerate(uids)]))
            if first_round else 0.0
        ),
        "wall_s": wall,
        "tokens_per_s": stats["tokens_per_s"],
        "total_tokens": stats["total_tokens"],
        "mean_ttft_s": stats["mean_ttft_s"],
        "mean_tpot_s": stats["mean_tpot_s"],
        "decode_steps": sched["decode_steps"],
        "prefill_calls": sched["prefill_calls"],
        "mean_slot_occupancy": sched["mean_slot_occupancy"],
    }
    print(f"[serve_bench] streaming (Poisson gap {mean_gap_rounds:.1f} "
          f"rounds): {out['tokens_per_s']:8.1f} tok/s  "
          f"ttft {out['mean_ttft_s'] * 1e3:7.1f} ms  "
          f"rounds {rounds}  "
          f"first-token wait {out['mean_first_token_round']:.1f} rounds")
    return out


def bench_admission(params, cfg, trace, slots: int, max_len: int) -> Dict:
    """FCFS vs cost-aware admission under a pJ cap, same trace.

    The cap is set to two worst-case requests (priced through the same
    ``EnergyModel.request_cost_pj`` the policy consults at admission
    time), so the cost-aware run must defer admissions while slots are
    free — the budgeted regime. Greedy outputs are asserted identical:
    admission order changes WHEN a request decodes, never WHAT.
    """
    def serve(policy: str, budget: float):
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=slots, max_len=max_len,
                                       mode="continuous",
                                       admission_policy=policy,
                                       energy_budget_pj=budget))
        for prompt, mnew in trace:
            eng.submit(prompt, max_new_tokens=mnew)
        done = eng.run()
        return eng, {r.uid: list(r.output) for r in done}

    # price the trace through the engine's own hwmodel (no serving:
    # submit only populates the queue)
    pricer = ServeEngine(params, cfg,
                         EngineConfig(max_batch=slots, max_len=max_len,
                                      mode="continuous"))
    for prompt, mnew in trace:
        pricer.submit(prompt, max_new_tokens=mnew)
    costs = [pricer.energy.request_cost_pj(r) for r in pricer.queue]
    budget = 2.0 * max(costs) if costs else 0.0
    if budget <= 0.0:
        return {"skipped": "model prices every request at 0 pJ"}

    eng_f, toks_f = serve("fcfs", 0.0)
    eng_c, toks_c = serve("cost-aware", budget)
    match = toks_f == toks_c
    out = {
        "energy_budget_pj": budget,
        "request_cost_pj": {
            "min": min(costs), "max": max(costs),
            "mean": float(np.mean(costs)),
        },
        "tokens_match": match,
    }
    for name, eng in (("fcfs", eng_f), ("cost_aware", eng_c)):
        sched = eng.stats()
        stats = throughput_stats(eng.finished)
        out[name] = {
            "policy": sched["admission_policy"],
            "deferrals": sched["admission_deferrals"],
            "admissions": sched["admissions"],
            "tokens_per_s": stats["tokens_per_s"],
            "mean_ttft_s": stats["mean_ttft_s"],
            "energy_pj": sched["energy_pj_total"],
        }
        print(f"[serve_bench] admission {name:10s}: "
              f"{out[name]['tokens_per_s']:8.1f} tok/s  "
              f"deferrals {out[name]['deferrals']:3d}  "
              f"ttft {out[name]['mean_ttft_s'] * 1e3:7.1f} ms")
    print(f"[serve_bench] cost-aware cap {budget:.1f} pJ "
          f"(2x worst request): tokens_match={match}")
    if not match:
        raise SystemExit("[serve_bench] admission: cost-aware greedy "
                         "outputs diverged from fcfs")
    return out


def bench_paged_prefix(params, cfg, trace, slots: int, max_len: int,
                       block_size: int) -> Dict:
    """Paged engine, prefix reuse off vs on, same shared-prefix trace.

    Both engines are warmed on the full trace first (compiles every
    shape; for reuse=on it also populates the radix index), so the
    measured runs compare steady states: full re-prefill of every
    prompt vs prefilling only each request's unique tail.
    """
    out: Dict = {"block_size": block_size}
    for key, reuse in (("reuse_off", False), ("reuse_on", True)):
        out[key] = bench_mode("continuous", params, cfg, trace, slots,
                              max_len, repeats=5, paged=True,
                              block_size=block_size, prefix_reuse=reuse)
        r = out[key]
        print(f"[serve_bench] paged {key:9s}: "
              f"{r['tokens_per_s']:8.1f} tok/s  "
              f"prefill tokens {r['prefill_tokens']:5d}  "
              f"cached {r['cached_prefix_tokens']:5d}")
    off, on = out["reuse_off"], out["reuse_on"]
    out["prefill_token_reduction"] = (
        1.0 - on["prefill_tokens"] / max(off["prefill_tokens"], 1)
    )
    out["speedup_tokens_per_s"] = (
        on["tokens_per_s"] / max(off["tokens_per_s"], 1e-9)
    )
    print(f"[serve_bench] shared-prefix reuse: "
          f"{out['prefill_token_reduction'] * 100:.1f}% fewer prefill "
          f"tokens, {out['speedup_tokens_per_s']:.2f}x tokens/s")
    return out


def bench_recurrent(args) -> Dict:
    """Recurrent-state families on the continuous scheduler vs static.

    zamba2 (hybrid: Mamba2 groups + one shared attention block) and
    xlstm (mLSTM/sLSTM) run the same mixed-length trace through both
    schedulers. Masked-length prefill makes the continuous slot pool
    exact for recurrent state (models/decode.prefill), so the comparison
    is pure scheduling: static lockstep wastes steps on retired-but-held
    slots, the slot pool backfills them per step. Greedy outputs are
    bit-identical between the two modes (pinned by
    tests/test_recurrent_serving.py).
    """
    if args.smoke:
        n_req, prompt_rng, new_rng = 8, (4, 16), (2, 8)
        slots, max_len = 4, 48
    else:
        # decode-weighted budgets: recurrent decode steps are cheap
        # (no KV growth), so the trace keeps slots busy long enough for
        # scheduling — not prefill dispatch — to dominate the delta
        n_req, prompt_rng, new_rng = args.requests, (8, 64), (16, 64)
        slots, max_len = args.slots, 160
    out: Dict = {
        "requests": n_req, "prompt_len": list(prompt_rng),
        "max_new_tokens": list(new_rng), "slots": slots, "max_len": max_len,
    }
    for arch in ("zamba2-7b", "xlstm-350m"):
        cfg = get_config(arch).reduced()
        params = init_model(jax.random.PRNGKey(0), cfg)
        trace = make_trace(n_req, prompt_rng, new_rng, cfg.vocab_size)
        entry: Dict = {"family": cfg.family}
        for mode in ("static", "continuous"):
            entry[mode] = bench_mode(mode, params, cfg, trace, slots,
                                     max_len, repeats=5)
            r = entry[mode]
            print(f"[serve_bench] recurrent {arch} {mode:10s}: "
                  f"{r['tokens_per_s']:8.1f} tok/s  "
                  f"occupancy {r['mean_slot_occupancy']:.2f}  "
                  f"steps {r['decode_steps']}")
        entry["speedup_tokens_per_s"] = (
            entry["continuous"]["tokens_per_s"]
            / max(entry["static"]["tokens_per_s"], 1e-9)
        )
        entry["occupancy_gain"] = (
            entry["continuous"]["mean_slot_occupancy"]
            - entry["static"]["mean_slot_occupancy"]
        )
        print(f"[serve_bench] recurrent {arch}: "
              f"{entry['speedup_tokens_per_s']:.2f}x tokens/s, "
              f"occupancy +{entry['occupancy_gain']:.2f}")
        out[arch] = entry
    return out


def bench_device_loop(params, cfg, trace, slots: int, max_len: int) -> Dict:
    """Horizon sweep for the on-device multi-step decode loop.

    The same mixed-length trace runs the continuous greedy engine at
    ``decode_horizon`` 1 / 8 / 32: one jit call per horizon instead of
    per token, so ``host_syncs`` drops ~H-fold while ``decode_steps``
    (and greedy outputs — pinned by tests/test_device_loop.py) stay
    identical. Best-of-5 per horizon; the h=1 entry is the baseline the
    speedups compare against.
    """
    out: Dict = {"horizons": {}}
    base = None
    for h in (1, 8, 32):
        r = bench_mode("continuous", params, cfg, trace, slots, max_len,
                       repeats=5, decode_horizon=h)
        out["horizons"][str(h)] = r
        if base is None:
            base = r
        print(f"[serve_bench] device_loop h={h:2d}: "
              f"{r['tokens_per_s']:8.1f} tok/s  "
              f"syncs {r['host_syncs']:4d}  steps {r['decode_steps']:4d}  "
              f"tpot {r['mean_tpot_s'] * 1e3:6.2f} ms")
    h32 = out["horizons"]["32"]
    out["sync_reduction"] = 1.0 - h32["host_syncs"] / max(base["host_syncs"], 1)
    out["speedup_tokens_per_s"] = (
        h32["tokens_per_s"] / max(base["tokens_per_s"], 1e-9)
    )
    print(f"[serve_bench] device loop h=32 vs h=1: "
          f"{out['sync_reduction'] * 100:.1f}% fewer host syncs, "
          f"{out['speedup_tokens_per_s']:.2f}x tokens/s")
    return out


def bench_energy(args) -> Dict:
    """Modeled energy/EDAP section (``BENCH_serve_energy.json``).

    Serves one mixed-length trace from the psq-packed engine, then —
    without re-serving — sweeps ``eng.energy_report`` across accounting
    styles (adc / quarry / hcim) and an occupancy grid. The measured
    entry uses the pack-time ternary column occupancy of the served
    weights; the sweep entries override occupancy to show how the
    modeled hcim-vs-adc reduction scales with sparsity (docs/energy.md).
    """
    cfg = get_config(args.arch).reduced()
    qcfg = dataclasses.replace(PSQ_TERNARY, kernel_backend="reference",
                               xbar_rows=64)
    cfg = cfg.with_quant(qcfg)
    params = init_model(jax.random.PRNGKey(0), cfg)
    cache = PackedModelCache()
    params = pack_tree_psq(params, qcfg, cache)

    if args.smoke:
        n_req, prompt_rng, new_rng, slots, max_len = 6, (4, 12), (2, 4), 3, 32
    else:
        n_req, prompt_rng, new_rng = args.requests, (8, 64), (4, 32)
        slots, max_len = args.slots, 128
    trace = make_trace(n_req, prompt_rng, new_rng, cfg.vocab_size)

    eng = ServeEngine(params, cfg,
                      EngineConfig(max_batch=slots, max_len=max_len,
                                   mode="continuous"))
    for prompt, mnew in trace:
        eng.submit(prompt, max_new_tokens=mnew)
    eng.run()
    sched = eng.stats()

    out: Dict = {
        "requests": n_req, "slots": slots, "max_len": max_len,
        "energy_tokens": sched["energy_tokens"],
        "measured_occupancy": sched["mean_occupancy"],
        "measured": eng.energy_report(),
        "sweep": {},
    }
    for sp in (0.0, 0.25, 0.5, 0.75, 0.9):
        rep = eng.energy_report(occupancy=sp)
        rep["hcim_vs_adc_reduction"] = 1.0 - (
            rep["hcim"]["energy_pj_total"]
            / max(rep["adc"]["energy_pj_total"], 1e-12)
        )
        out["sweep"][f"{sp:.2f}"] = rep
        print(f"[serve_bench] energy occ={sp:.2f}: "
              + "  ".join(f"{s} {rep[s]['energy_pj_total']:12.1f} pJ"
                          for s in ("adc", "quarry", "hcim"))
              + f"  hcim/adc -{rep['hcim_vs_adc_reduction'] * 100:.1f}%")
    out["hcim_vs_adc_reduction_at_0.5"] = (
        out["sweep"]["0.50"]["hcim_vs_adc_reduction"]
    )
    print(f"[serve_bench] modeled hcim vs adc at occupancy 0.5: "
          f"{out['hcim_vs_adc_reduction_at_0.5'] * 100:.1f}% less energy "
          f"over {out['energy_tokens']} served tokens")
    return out


def bench_moe(args) -> Dict:
    """Expert-parallel MoE serving section (``BENCH_serve_moe.json``).

    Serves the reduced granite MoE config through the continuous slot
    pool twice over the same mixed-length trace: single-device, then on
    a ``(1, 1, E)`` ("data", "model", "expert") mesh with the expert
    FFN stacks sharded over the ``expert`` axis (router replicated —
    docs/parallelism.md). The dispatch reassembles the exact capacity
    tensor the single-device scatter consumes, so greedy outputs are
    bit-identical; the section records both throughputs and the
    token-level match. With one device (or a non-divisible expert
    count) only the single-device entry is emitted.
    """
    arch = "granite-moe-3b-a800m"
    cfg = get_config(arch).reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    if args.smoke:
        n_req, prompt_rng, new_rng, slots, max_len = 6, (4, 12), (2, 6), 3, 32
    else:
        n_req, prompt_rng, new_rng = args.requests, (8, 64), (4, 32)
        slots, max_len = args.slots, 128
    trace = make_trace(n_req, prompt_rng, new_rng, cfg.vocab_size)

    def serve_tokens(mesh):
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=slots, max_len=max_len,
                                       mode="continuous"),
                          mesh=mesh)
        for prompt, mnew in trace:
            eng.submit(prompt, max_new_tokens=mnew)
        done = eng.run()
        return {r.uid: list(r.output) for r in done}

    out: Dict = {
        "arch": arch, "family": cfg.family, "n_experts": cfg.n_experts,
        "moe_top_k": cfg.moe_top_k, "requests": n_req, "slots": slots,
        "max_len": max_len,
    }
    out["single"] = bench_mode("continuous", params, cfg, trace, slots,
                               max_len, repeats=3)
    print(f"[serve_bench] moe single-device: "
          f"{out['single']['tokens_per_s']:8.1f} tok/s  "
          f"steps {out['single']['decode_steps']}")

    e = 1
    while e * 2 <= len(jax.devices()) and cfg.n_experts % (e * 2) == 0:
        e *= 2
    if e > 1:
        mesh = make_mesh((1, 1, e), ("data", "model", "expert"))
        out["expert_parallel"] = dict(
            mesh=f"data=1,model=1,expert={e}",
            **bench_mode("continuous", params, cfg, trace, slots, max_len,
                         mesh=mesh, repeats=3),
        )
        out["tokens_match"] = serve_tokens(None) == serve_tokens(mesh)
        out["ep_vs_single_tokens_per_s"] = (
            out["expert_parallel"]["tokens_per_s"]
            / max(out["single"]["tokens_per_s"], 1e-9)
        )
        print(f"[serve_bench] moe expert={e}: "
              f"{out['expert_parallel']['tokens_per_s']:8.1f} tok/s  "
              f"tokens_match={out['tokens_match']}  "
              f"({out['ep_vs_single_tokens_per_s']:.2f}x vs single; CPU "
              f"measures dispatch overhead, not speedup)")
        if not out["tokens_match"]:
            raise SystemExit("[serve_bench] moe: expert-parallel greedy "
                             "outputs diverged from single-device")
    return out


def bench_spec(args) -> Dict:
    """Speculative decoding + side-input section (``BENCH_serve_spec.json``).

    Three comparisons on one mixed-length trace, all token-identical by
    construction (and asserted):

    * ``vanilla`` vs ``spec`` at ``spec_k`` in {2, 4} with a 1-layer
      random draft — the accept-rate floor (an untrained draft rarely
      matches the main argmax), so the entry measures pure verify-round
      overhead;
    * the same ``spec_k`` values with the served model as its own draft
      — the accept-rate ceiling (every proposal matches), showing the
      host-sync reduction speculative rounds buy when the draft is good;
    * ``side_input_continuous``: the encdec (whisper) and
      VLM-with-patches (llava) reduced configs served through the
      continuous slot pool vs the static oracle loop, tokens matched.
    """
    cfg = get_config(args.arch).reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    if args.smoke:
        n_req, prompt_rng, new_rng, slots, max_len = 8, (4, 16), (4, 10), 4, 48
    else:
        n_req, prompt_rng, new_rng = args.requests, (8, 32), (8, 32)
        slots, max_len = args.slots, 128
    trace = make_trace(n_req, prompt_rng, new_rng, cfg.vocab_size)

    def outputs(**kw):
        dp = kw.pop("draft_params", None)
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=slots, max_len=max_len,
                                       mode="continuous", **kw),
                          draft_params=dp)
        for prompt, mnew in trace:
            eng.submit(prompt, max_new_tokens=mnew)
        return {r.uid: list(r.output) for r in eng.run()}

    out: Dict = {"arch": args.arch, "requests": n_req, "slots": slots,
                 "max_len": max_len}
    base = bench_mode("continuous", params, cfg, trace, slots, max_len,
                      repeats=3)
    out["vanilla"] = base
    base_toks = outputs()
    print(f"[serve_bench] spec vanilla: {base['tokens_per_s']:8.1f} tok/s  "
          f"syncs {base['host_syncs']}")

    dcfg1 = dataclasses.replace(cfg, n_layers=1)
    drafts = {
        "draft_1layer": (dcfg1, init_model(jax.random.PRNGKey(1), dcfg1)),
        "draft_self": (cfg, params),
    }
    for name, (dcfg, dparams) in drafts.items():
        sec: Dict = {"draft_layers": dcfg.n_layers}
        for k in (2, 4):
            r = bench_mode("continuous", params, cfg, trace, slots,
                           max_len, repeats=3, spec_k=k, draft_config=dcfg,
                           draft_params=dparams)
            r["tokens_match"] = outputs(
                spec_k=k, draft_config=dcfg, draft_params=dparams
            ) == base_toks
            r["speedup_tokens_per_s"] = (
                r["tokens_per_s"] / max(base["tokens_per_s"], 1e-9)
            )
            sec[f"k{k}"] = r
            print(f"[serve_bench] spec {name} k={k}: "
                  f"{r['tokens_per_s']:8.1f} tok/s  "
                  f"accept {r['spec_accept_rate']:.3f}  "
                  f"syncs {r['host_syncs']}  "
                  f"({r['speedup_tokens_per_s']:.2f}x vs vanilla)  "
                  f"tokens_match={r['tokens_match']}")
            if not r["tokens_match"]:
                raise SystemExit(f"[serve_bench] spec {name} k={k}: greedy "
                                 f"outputs diverged from vanilla decode")
        out[name] = sec

    side: Dict = {}
    rng = np.random.RandomState(0)
    for arch in ("whisper-large-v3", "llava-next-mistral-7b"):
        scfg = get_config(arch).reduced()
        sparams = init_model(jax.random.PRNGKey(0), scfg)
        strace = make_trace(n_req, (4, 10), new_rng, scfg.vocab_size)
        extra = {}
        key = "enc_embeds" if scfg.family == "encdec" else "patch_embeds"
        extra[key] = (rng.randn(n_req, 8, scfg.d_model) * 0.1
                      ).astype(np.float32)
        entry: Dict = {"family": scfg.family, "side_input": key}
        for mode in ("static", "continuous"):
            entry[mode] = bench_mode(mode, sparams, scfg, strace, slots,
                                     max_len, repeats=3,
                                     extra_inputs=extra)
        entry["tokens_match"] = True
        for mode in ("static", "continuous"):
            eng = ServeEngine(sparams, scfg,
                              EngineConfig(max_batch=slots, max_len=max_len,
                                           mode=mode),
                              extra_inputs=extra)
            for i, (prompt, mnew) in enumerate(strace):
                eng.submit(prompt, max_new_tokens=mnew, extra_idx=i)
            toks = {r.uid: list(r.output) for r in eng.run()}
            if mode == "static":
                ref = toks
            else:
                entry["tokens_match"] = toks == ref
        entry["speedup_tokens_per_s"] = (
            entry["continuous"]["tokens_per_s"]
            / max(entry["static"]["tokens_per_s"], 1e-9)
        )
        print(f"[serve_bench] side-input {arch} ({scfg.family}): "
              f"continuous {entry['continuous']['tokens_per_s']:8.1f} tok/s "
              f"({entry['speedup_tokens_per_s']:.2f}x vs static)  "
              f"tokens_match={entry['tokens_match']}")
        if not entry["tokens_match"]:
            raise SystemExit(f"[serve_bench] side-input {arch}: continuous "
                             f"outputs diverged from static")
        side[arch] = entry
    out["side_input_continuous"] = side
    return out


def run(args) -> Dict:
    if args.spec:
        return {
            "bench": "serve_spec",
            "platform": jax.default_backend(),
            "spec": bench_spec(args),
        }
    if args.energy:
        return {
            "bench": "serve_energy",
            "arch": args.arch,
            "platform": jax.default_backend(),
            "energy": bench_energy(args),
        }
    if args.moe:
        return {
            "bench": "serve_moe",
            "platform": jax.default_backend(),
            "devices": len(jax.devices()),
            "moe": bench_moe(args),
        }
    cfg = get_config(args.arch).reduced()
    if not args.recurrent:
        # the recurrent section builds its own zamba2/xlstm models —
        # don't init (or pack) an args.arch model it never serves
        if args.psq_packed:
            qcfg = dataclasses.replace(PSQ_TERNARY,
                                       kernel_backend="reference",
                                       xbar_rows=64)
            cfg = cfg.with_quant(qcfg)
            params = init_model(jax.random.PRNGKey(0), cfg)
            cache = PackedModelCache()
            params = pack_tree_psq(params, qcfg, cache)
            print(f"[serve_bench] packed once at load: {cache.stats()}")
        else:
            params = init_model(jax.random.PRNGKey(0), cfg)

    if args.smoke:
        n_req, prompt_rng, new_rng = 8, (4, 16), (2, 8)
        slots, max_len = 4, 32
    else:
        n_req, prompt_rng, new_rng = args.requests, (8, 64), (4, 64)
        slots, max_len = args.slots, 160
    trace = make_trace(n_req, prompt_rng, new_rng, cfg.vocab_size)

    result: Dict = {
        "bench": "serve",
        "arch": args.arch,
        "weights": "psq-packed" if args.psq_packed else "fp32",
        "requests": n_req,
        "prompt_len": list(prompt_rng),
        "max_new_tokens": list(new_rng),
        "slots": slots,
        "max_len": max_len,
        "platform": jax.default_backend(),
        "devices": len(jax.devices()),
    }
    only_section = (args.paged or args.recurrent or args.device_loop
                    or args.streaming)
    if not only_section:
        for mode in ("static", "continuous"):
            result[mode] = bench_mode(mode, params, cfg, trace, slots,
                                      max_len)
            r = result[mode]
            print(f"[serve_bench] {mode:10s}: "
                  f"{r['tokens_per_s']:8.1f} tok/s  "
                  f"ttft {r['mean_ttft_s'] * 1e3:7.1f} ms  "
                  f"occupancy {r['mean_slot_occupancy']:.2f}  "
                  f"steps {r['decode_steps']}")
        result["speedup_tokens_per_s"] = (
            result["continuous"]["tokens_per_s"]
            / max(result["static"]["tokens_per_s"], 1e-9)
        )
        print(f"[serve_bench] continuous/static speedup: "
              f"{result['speedup_tokens_per_s']:.2f}x")

    # live-arrival streaming + admission-policy comparison on the same
    # trace: TTFT/TPOT under a replayable Poisson schedule through the
    # submit/poll front-end, and fcfs vs cost-aware under a pJ cap
    if args.streaming or not only_section:
        mean_gap = 1.0 if args.smoke else 2.0
        result["streaming"] = dict(
            requests=n_req, slots=slots, max_len=max_len,
            **bench_streaming(params, cfg, trace, slots, max_len, mean_gap),
        )
        result["admission"] = bench_admission(params, cfg, trace, slots,
                                              max_len)

    # horizon sweep for the on-device decode loop: same trace, same
    # greedy outputs, host syncs cut ~H-fold (docs/serving.md)
    if not args.paged and not args.recurrent and not args.streaming:
        result["device_loop"] = dict(
            requests=n_req, slots=slots, max_len=max_len,
            **bench_device_loop(params, cfg, trace, slots, max_len),
        )

    # shared-system-prompt trace on the paged engine: a prefill-heavy
    # regime (long shared prefix, short tails and decode budgets) where
    # radix prefix reuse pays directly in admission latency
    if not args.recurrent and not args.device_loop and not args.streaming:
        if args.smoke:
            pn, pfx, tails, pnew = 8, 24, (2, 6), (2, 4)
            pslots, pmax, pbs = 4, 64, 8
        else:
            pn, pfx, tails, pnew = 48, 64, (4, 12), (4, 8)
            pslots, pmax, pbs = args.slots, 128, 16
        ptrace = make_shared_prefix_trace(pn, pfx, tails, pnew,
                                          cfg.vocab_size)
        result["paged_prefix"] = dict(
            requests=pn, shared_prefix_len=pfx, tail_len=list(tails),
            max_new_tokens=list(pnew), slots=pslots, max_len=pmax,
            **bench_paged_prefix(params, cfg, ptrace, pslots, pmax, pbs),
        )

    # recurrent-state families (hybrid zamba2, xlstm) through the
    # continuous slot pool vs the static fallback — same mixed-length
    # trace per arch, bit-identical outputs, scheduling-only delta
    if not args.paged and not args.device_loop and not args.streaming:
        result["recurrent_continuous"] = bench_recurrent(args)

    # tiny MoE entry in the default section: single-device continuous
    # serve of the reduced granite MoE (the full expert-parallel
    # comparison is the --moe section / BENCH_serve_moe.json)
    if not only_section:
        result["moe"] = bench_moe(args)

    if not only_section and args.devices > 1:
        result["sharded"] = run_sharded_sweep(args)
    return result


def run_sharded_sweep(args) -> List[Dict]:
    """Per-mesh-size tokens/s for the tensor-parallel PSQ datapath.

    The same mixed-length trace drives the psq-packed continuous engine
    under a ``(1, m)`` ("data", "model") mesh for every power-of-two
    ``m`` up to ``--devices``. ``m=1`` is the single-device baseline
    the sharded entries compare against.
    """
    n_dev = len(jax.devices())
    if n_dev < args.devices:
        raise SystemExit(
            f"--devices {args.devices} but only {n_dev} JAX devices exist "
            f"— the flag must be the first JAX use in the process"
        )
    cfg = get_config(args.arch).reduced()
    qcfg = dataclasses.replace(PSQ_TERNARY, kernel_backend="reference",
                               xbar_rows=64)
    cfg = cfg.with_quant(qcfg)
    params = init_model(jax.random.PRNGKey(0), cfg)
    cache = PackedModelCache()

    if args.smoke:
        n_req, prompt_rng, new_rng, slots, max_len = 4, (4, 12), (2, 4), 2, 32
    else:
        n_req, prompt_rng, new_rng = args.requests, (8, 64), (4, 32)
        slots, max_len = args.slots, 128
    trace = make_trace(n_req, prompt_rng, new_rng, cfg.vocab_size)

    sizes = []
    m = 1
    while m <= args.devices:
        sizes.append(m)
        m *= 2
    entries: List[Dict] = []
    for m in sizes:
        mesh = make_mesh((1, m), ("data", "model"))
        packed = pack_tree_psq(params, qcfg, cache, mesh=mesh)
        r = bench_mode("continuous", packed, cfg, trace, slots, max_len,
                       mesh=mesh)
        entry = {"devices": m, "mesh": f"data=1,model={m}",
                 "pack_stats": cache.stats(), **r}
        entries.append(entry)
        print(f"[serve_bench] sharded model={m}: "
              f"{r['tokens_per_s']:8.1f} tok/s  "
              f"occupancy {r['mean_slot_occupancy']:.2f}")
    return entries


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--psq-packed", action="store_true",
                    help="serve from the weight-stationary PackedLayer cache")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace + model (CI mode)")
    ap.add_argument("--paged", action="store_true",
                    help="run only the paged shared-prefix section")
    ap.add_argument("--recurrent", action="store_true",
                    help="run only the recurrent-family (zamba2/xlstm) "
                         "continuous-vs-static section")
    ap.add_argument("--device-loop", action="store_true",
                    help="run only the device-loop horizon sweep "
                         "(decode_horizon 1/8/32)")
    ap.add_argument("--streaming", action="store_true",
                    help="run only the live-arrival streaming section "
                         "(seeded Poisson schedule through the "
                         "submit/poll front-end) plus the fcfs vs "
                         "cost-aware admission comparison")
    ap.add_argument("--moe", action="store_true",
                    help="run only the MoE serving section: continuous "
                         "granite-moe single-device vs expert-parallel "
                         "(with --devices N) with a bit-exact token "
                         "check (BENCH_serve_moe.json)")
    ap.add_argument("--spec", action="store_true",
                    help="run only the speculative-decoding + side-input "
                         "section: vanilla vs spec_k in {2,4} with floor/"
                         "ceiling drafts plus whisper/llava continuous-vs-"
                         "static, all token-matched "
                         "(BENCH_serve_spec.json)")
    ap.add_argument("--energy", action="store_true",
                    help="run only the modeled energy/EDAP section: "
                         "styles x occupancy-grid sweep on one "
                         "psq-packed engine run (BENCH_serve_energy.json)")
    ap.add_argument("--devices", type=int, default=0,
                    help="CPU virtual devices for the tensor-parallel mesh "
                         "sweep (must be the first JAX use in the process)")
    ap.add_argument("--out", default="BENCH_serve.json",
                    help="JSON output path")
    args = ap.parse_args()
    if args.devices:
        # safe despite the module-level jax import: the flag is read at
        # backend INIT, and nothing above touches devices before run()
        from repro.launch.mesh import force_host_device_count

        force_host_device_count(args.devices)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    result = run(args)
    result["kernel_backends"] = registry.describe()
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"[serve_bench] wrote {args.out}")


if __name__ == "__main__":
    main()
