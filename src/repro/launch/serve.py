"""Serving launcher: continuous-batching prefill/decode on the devices.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --requests 8 [--int4 | --psq-packed] [--backend reference] \
        [--slots 4] [--mode auto|continuous|static] [--reduced] \
        [--decode-horizon H] [--mesh DATA,MODEL] [--devices N]

The published configuration is served by default; ``--reduced`` swaps
in the small same-family config (``ArchConfig.reduced``) that a CPU
can initialise and serve in seconds — use it for CPU demos.

Every family — KV-cache, recurrent-state (SSM/xLSTM/hybrid) AND the
side-input families (encdec cross-KV, VLM patch embeds) — serves
through the continuous-batching slot pool (per-step retirement +
mid-flight admission, per-slot side-input pools; see docs/serving.md).
``--mode static`` keeps the drain-the-queue oracle loop around for
comparison. ``--paged`` switches the slot pool to the paged KV cache —
fixed-size pages, block tables and shared-prefix radix reuse;
attention-KV families only (docs/memory.md). ``--decode-horizon H``
batches up to H greedy decode steps into one on-device
``lax.while_loop`` per host round-trip (bit-exact with H=1; greedy
only). ``--spec-k K`` turns on speculative decoding: a small draft
model (``--draft`` arch, default a 1-layer copy of the served config)
proposes K greedy tokens per slot and the main model verifies them in
one masked forward — token-identical to vanilla greedy decode, see
docs/serving.md for the lifecycle and rollback rule.

Admission is policy-driven (docs/scheduling.md): ``--admission fcfs``
(default) is the pow2-bucket FIFO wave; ``--admission cost-aware
--energy-budget PJ`` budgets in-flight requests against their modeled
worst-case serve energy (``hwmodel.serve_energy`` — HCiM's pack-time
occupancy metadata makes the price static), deferring admissions that
would push the in-flight total past the cap.

``--streaming`` serves the same workload through the incremental
:class:`StreamingFrontend` (submit/poll over ``ServeEngine.step()``)
instead of one blocking ``run()``, printing tokens as rounds complete —
the API the replayable-arrival benchmark drives
(``benchmarks/serve_bench.py --streaming``).

Multi-device: ``--mesh 1,4`` runs the PSQ datapath tensor-parallel over
a 4-way ``model`` axis (packed layers column-sharded, one psum per
matmul) and ``--mesh 4,1`` shards the decode slot pool over ``data``.
A third component adds an ``expert`` axis — ``--mesh 1,1,4`` serves MoE
configs expert-parallel (expert FFN stacks sharded over experts, router
replicated, bit-exact dispatch; see docs/parallelism.md). On CPU,
``--devices N`` forges N virtual devices (sets
``--xla_force_host_platform_device_count`` — must run before any other
JAX use in the process).
"""
from __future__ import annotations

import argparse
import dataclasses
import math
from typing import Dict, List, Optional, Tuple


class StreamingFrontend:
    """Incremental submit/poll API over :meth:`ServeEngine.step`.

    The engine's blocking ``run()`` drains everything before returning;
    this front-end instead advances ONE scheduling round per
    :meth:`step` call and buffers each request's newly-emitted tokens
    until the caller :meth:`poll`\\ s them — the shape a network serving
    layer needs (arrivals between rounds, partial responses out as soon
    as a round completes). Purely host-side bookkeeping: scheduling,
    placement and execution stay in the engine layers, so streamed
    tokens are bit-identical to a drain-the-queue ``run()``.
    """

    def __init__(self, engine):
        self.engine = engine
        self._pending: Dict[int, List[int]] = {}   # undelivered tokens
        self._finished: set = set()

    def submit(self, prompt, max_new_tokens: int = 16,
               eos_id: Optional[int] = None,
               extra_idx: Optional[int] = None) -> int:
        """Enqueue a prompt mid-flight; returns its uid."""
        uid = self.engine.submit(prompt, max_new_tokens=max_new_tokens,
                                 eos_id=eos_id, extra_idx=extra_idx)
        self._pending[uid] = []
        return uid

    def step(self) -> None:
        """Advance one scheduling round (admission + one executor
        round) and buffer every request's new tokens."""
        for uid, toks in self.engine.step().items():
            self._pending.setdefault(uid, []).extend(toks)
        self._finished.update(r.uid for r in self.engine.finished)

    def poll(self, uid: int) -> Tuple[List[int], bool]:
        """Drain ``uid``'s tokens emitted since the last poll, plus a
        finished flag. ``([], True)`` after the final drain."""
        out = self._pending.get(uid, [])
        self._pending[uid] = []
        return out, uid in self._finished

    @property
    def drained(self) -> bool:
        return self.engine.drained


def _parse_args(argv=None):
    # configs/argparse only — jax is imported after --devices is applied
    from repro.configs import list_archs
    from repro.kernels import registry

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="serve the small same-family config (d_model "
                         "128, <=3 layers, vocab 256) instead of the "
                         "published widths; for CPU demos")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--int4", action="store_true",
                    help="serve int4-packed PSQ deployment weights")
    ap.add_argument("--psq-packed", action="store_true",
                    help="serve the full HCiM pipeline from the "
                         "weight-stationary PackedLayer cache")
    ap.add_argument("--backend", default=None,
                    choices=registry.registered_backends(),
                    help="kernel backend for --psq-packed "
                         "(default: 'reference' on CPU, else the "
                         "platform's default, compiled 'pallas' on TPU)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slot-pool size (static: batch size)")
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "continuous", "static"],
                    help="scheduler: continuous batching (KV families) "
                         "or the static drain-the-queue loop")
    ap.add_argument("--decode-horizon", type=int, default=1,
                    help="greedy decode steps per on-device while-loop "
                         "round-trip (continuous scheduler; 1 = one "
                         "host sync per token)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: page pool + block tables + "
                         "shared-prefix radix reuse (continuous only; "
                         "see docs/memory.md)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV page for --paged "
                         "(must divide --max-len)")
    ap.add_argument("--no-prefix-reuse", action="store_true",
                    help="keep the paged layout but disable the "
                         "shared-prefix radix index")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft-proposed tokens "
                         "per verify round (0 = off; continuous greedy "
                         "KV families only)")
    ap.add_argument("--draft", default=None, choices=list_archs(),
                    help="draft arch for --spec-k (same family; "
                         "default: 1-layer copy of --arch)")
    ap.add_argument("--energy-style", default="hcim",
                    choices=["adc", "quarry", "hcim"],
                    help="hwmodel accounting style for the per-request "
                         "energy/EDAP attribution in stats() "
                         "(docs/energy.md)")
    ap.add_argument("--admission", default="fcfs",
                    choices=["fcfs", "cost-aware"],
                    help="admission policy: pow2-bucket FIFO waves, or "
                         "energy-budgeted admission against the modeled "
                         "per-request serve energy (docs/scheduling.md)")
    ap.add_argument("--energy-budget", type=float, default=0.0,
                    metavar="PJ",
                    help="in-flight modeled-energy cap in pJ for "
                         "--admission cost-aware")
    ap.add_argument("--streaming", action="store_true",
                    help="serve through the incremental submit/poll "
                         "front-end (arrivals mid-flight, tokens out "
                         "per round) instead of one blocking run()")
    ap.add_argument("--mesh", default=None, metavar="DATA,MODEL[,EXPERT]",
                    help="mesh axis sizes, e.g. 1,4 (model-parallel PSQ "
                         "columns), 2,2, or 1,1,4 (expert-parallel MoE "
                         "serving); needs DATA*MODEL*EXPERT devices "
                         "(default: all devices data-parallel)")
    ap.add_argument("--devices", type=int, default=0,
                    help="CPU only: forge N virtual devices via XLA_FLAGS "
                         "(must be the first JAX use in the process)")
    return ap.parse_args(argv)


def main(argv=None):
    """Serve one seeded workload; returns the finished requests."""
    args = _parse_args(argv)
    if args.devices:
        from repro.launch.mesh import force_host_device_count

        force_host_device_count(args.devices)

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core.config import PSQ_TERNARY
    from repro.core.psq_linear import pack_tree_for_serving
    from repro.kernels import registry
    from repro.launch.mesh import make_host_mesh, make_mesh
    from repro.models import init_model
    from repro.serve import (
        EngineConfig, PackedModelCache, ServeEngine, pack_tree_psq,
        throughput_stats,
    )

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.mesh:
        sizes = tuple(int(v) for v in args.mesh.split(","))
        if len(sizes) not in (2, 3):
            raise SystemExit(
                f"--mesh takes DATA,MODEL or DATA,MODEL,EXPERT sizes, "
                f"got {args.mesh!r}"
            )
        axes = ("data", "model", "expert")[: len(sizes)]
        n = math.prod(sizes)
        if n > len(jax.devices()):
            raise SystemExit(
                f"--mesh {args.mesh} needs {n} devices, have "
                f"{len(jax.devices())} (on CPU add --devices {n})"
            )
        if len(sizes) == 3 and sizes[2] > 1 and cfg.family != "moe":
            raise SystemExit(
                f"--mesh {args.mesh}: an expert axis > 1 only applies to "
                f"MoE archs; {args.arch} has no experts"
            )
        mesh = make_mesh(sizes, axes)
    else:
        mesh = make_host_mesh()
    print(f"[serve] mesh: "
          f"{'x'.join(f'{k}={v}' for k, v in mesh.shape.items())}  "
          f"backends: {registry.describe()}")

    if args.psq_packed:
        backend = args.backend or (
            "reference" if jax.default_backend() == "cpu"
            else registry.default_backend()
        )
        qcfg = dataclasses.replace(PSQ_TERNARY, kernel_backend=backend)
        cfg = cfg.with_quant(qcfg)
        params = init_model(jax.random.PRNGKey(0), cfg)
        cache = PackedModelCache()
        params = pack_tree_psq(params, qcfg, cache, mesh=mesh)
        print(f"[serve] packed {cache.stats()['layers']} layers once "
              f"(backend={backend}, column-sharded over the model axis)")
    else:
        params = init_model(jax.random.PRNGKey(0), cfg)
    if args.int4:
        params = pack_tree_for_serving(params)

    draft_cfg, draft_params = None, None
    if args.spec_k:
        draft_cfg = (get_config(args.draft) if args.draft
                     else dataclasses.replace(cfg, n_layers=1))
        if args.draft and args.reduced:
            draft_cfg = draft_cfg.reduced()
        draft_params = init_model(jax.random.PRNGKey(1), draft_cfg)
        print(f"[serve] spec decode: k={args.spec_k}, draft "
              f"{args.draft or '1-layer copy'} "
              f"({draft_cfg.n_layers} layers)")

    extra = {}
    rng = np.random.RandomState(0)
    if cfg.family == "encdec":
        extra["enc_embeds"] = rng.randn(
            args.requests, args.max_len, cfg.d_model
        ).astype(np.float32) * 0.1
    eng = ServeEngine(
        params, cfg,
        EngineConfig(max_batch=args.slots, max_len=args.max_len,
                     temperature=args.temperature, mode=args.mode,
                     decode_horizon=args.decode_horizon,
                     paged=args.paged, block_size=args.block_size,
                     prefix_reuse=not args.no_prefix_reuse,
                     energy_style=args.energy_style,
                     spec_k=args.spec_k, draft_config=draft_cfg,
                     admission_policy=args.admission,
                     energy_budget_pj=args.energy_budget),
        extra_inputs=extra,
        mesh=mesh,
        draft_params=draft_params,
    )
    prompts = [rng.randint(0, cfg.vocab_size, size=rng.randint(4, 16))
               for _ in range(args.requests)]
    if args.streaming:
        fe = StreamingFrontend(eng)
        uids: list = []
        rounds = 0
        pending = list(prompts)
        while pending or not fe.drained:
            # stagger arrivals: two submits per round exercises
            # mid-flight admission instead of one up-front wave
            for p in pending[:2]:
                uids.append(fe.submit(p, max_new_tokens=args.max_new_tokens))
            del pending[:2]
            fe.step()
            rounds += 1
            for uid in uids:
                toks, done_flag = fe.poll(uid)
                if toks:
                    print(f"[stream] round {rounds:3d} uid {uid}: "
                          f"+{len(toks)} tok"
                          f"{' (done)' if done_flag else ''}")
        done = eng.finished
        print(f"[stream] drained after {rounds} rounds")
    else:
        for p in prompts:
            eng.submit(p, max_new_tokens=args.max_new_tokens)
        done = eng.run()
    stats = throughput_stats(done)
    sched = eng.stats()
    fmt = "psq-packed" if args.psq_packed else ("int4" if args.int4 else "fp")
    print(f"[serve] {args.arch} weights={fmt} scheduler={sched}")
    print(f"[serve] {args.arch} weights={fmt}: {stats}")
    if args.spec_k:
        print(f"[serve] {args.arch} spec: rounds={sched['spec_rounds']}, "
              f"accept_rate={sched['spec_accept_rate']:.3f}")
    if args.admission == "cost-aware":
        print(f"[serve] {args.arch} admission=cost-aware "
              f"budget={args.energy_budget:.0f} pJ "
              f"deferrals={sched['admission_deferrals']}")
    print(f"[serve] {args.arch} energy[{sched['energy_style']}]: "
          f"{sched['energy_pj_total']:.1f} pJ total, "
          f"{sched['energy_pj_per_request']:.1f} pJ/request, "
          f"edap {sched['edap_total']:.3g}, "
          f"mean occupancy {sched['mean_occupancy']:.3f}")
    return done


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
