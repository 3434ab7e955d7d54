"""Continuous-batching serving engine — the thin facade over three layers.

The stack is layered (docs/architecture.md, docs/scheduling.md):
``serve/scheduler.py`` owns decisions (Request/EngineConfig + the one
``validate()`` home, the AdmissionPolicy protocol with its FCFS and
cost-aware energy-budget policies, EnergyModel pricing, admitters);
``serve/state.py`` owns placement (SlotState over the contiguous
stripe, paged block pool and recurrent leaves); ``serve/executor.py``
owns execution (``build_compiled`` makes every jitted closure, and the
host-loop / device-horizon / spec-round / static executors advance the
pool behind one ``run_round()``). This module wires them together and
preserves the public API: ``submit()`` then ``run()`` (drain) or
``step()`` (one round — the streaming front-end in ``launch/serve.py``
polls incremental tokens between steps), plus ``stats()`` /
``energy_report()`` / ``throughput_stats``.

Both scheduling modes (continuous slot pool; static drain-the-queue
oracle) are bit-exact with sequential decoding — right-padded pow2
prefill buckets + per-row true lengths — and all shapes are fixed
after warm-up so nothing recompiles (asserted by the tier-1 suite).
Side-input families, speculative decoding, the paged KV layout and
multi-device meshes all serve through the same facade; see
docs/serving.md for the matrix.
"""
from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh

from repro.configs.base import ArchConfig
from repro.models import decode as D
from repro.parallel.sharding import (
    axis_rules,
    rules_for_mesh,
    shard_expert_params,
)
from repro.serve.executor import (
    DeviceHorizonExecutor,
    HostLoopExecutor,
    SpecRoundExecutor,
    StaticBatchExecutor,
    build_compiled,
)
from repro.serve.paged_kv import PagedKVManager, PoolExhausted
from repro.serve.scheduler import (
    ContiguousAdmitter,
    EngineConfig,
    EngineTelemetry,
    EnergyModel,
    PagedAdmitter,
    Request,
    next_pow2,
    resolve_admission_policy,
    right_pad,
)
from repro.serve.state import ContiguousSlotState, PagedSlotState

PyTree = Any

# families the continuous scheduler admits mid-flight — all of them
_CONTINUOUS_FAMILIES = ("dense", "moe", "vlm", "hybrid", "ssm", "encdec")

# encoder width for encdec engines built WITHOUT enc_embeds (zero rows
# at a fixed width, so both schedulers agree on the cross-KV pool shape)
_DEFAULT_ENC_LEN = 8

# recurrent-state families: admission scatters state rows, not KV stripes
_RECURRENT_FAMILIES = ("hybrid", "ssm")


class ServeEngine(EngineTelemetry):
    """Submit prompts, then :meth:`run` to completion (or :meth:`step`
    one scheduling round at a time for streaming callers);
    ``stats()`` exposes scheduler counters on top of throughput."""

    def __init__(self, params: PyTree, cfg: ArchConfig, ecfg: EngineConfig,
                 extra_inputs: Optional[Dict[str, np.ndarray]] = None,
                 mesh: Optional[Mesh] = None, rules=None,
                 draft_params: Optional[PyTree] = None):
        if params is not None:
            # per-token-invariant decode constants (e.g. Mamba2's
            # A = -exp(A_log)) fold into the served tree once at load
            params = D.hoist_decode_params(params, cfg)
        self.params = params
        self.cfg = cfg
        self.ecfg = ecfg
        self.extra = extra_inputs or {}
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self._uid = 0
        self._key = jax.random.PRNGKey(ecfg.seed)
        # ONE validation pass raises on every invalid knob combination
        self.mode = ecfg.validate(cfg, has_draft_params=draft_params
                                  is not None, extra=self.extra)

        # side-input geometry is fixed per engine so the pools compile once
        enc = self.extra.get("enc_embeds")
        self._enc_len = (int(np.asarray(enc).shape[1])
                         if enc is not None and np.asarray(enc).size
                         else _DEFAULT_ENC_LEN)
        pe = self.extra.get("patch_embeds")
        self._patch_len = (int(np.asarray(pe).shape[1])
                           if cfg.family == "vlm" and pe is not None
                           and np.asarray(pe).size else 0)

        # the device loop is greedy-only (on-device argmax, no RNG carry);
        # sampling stays host-side and spec decode has its own round loop
        self._use_device_loop = (
            self.mode == "continuous"
            and ecfg.device_loop
            and ecfg.temperature <= 0.0
            and not ecfg.spec_k
        )
        self._spec_k = int(ecfg.spec_k)
        self.draft_params = (D.hoist_decode_params(draft_params,
                                                   ecfg.draft_config)
                             if self._spec_k else None)

        # multi-device serving: rules activate around every traced
        # function (cache slots shard over "data", packed PSQ layers go
        # tensor-parallel over "model"; mesh=None = no-op annotations).
        # An "expert" axis places MoE expert stacks at load.
        self.mesh = mesh
        self._rules = rules if rules is not None else rules_for_mesh(mesh)
        if (mesh is not None and params is not None
                and "expert" in getattr(mesh, "axis_names", ())):
            self.params = params = shard_expert_params(
                params, mesh, self._rules
            )

        # scheduler telemetry (continuous mode): plain scalars, so a
        # long-running server's counters never grow
        self._zero_counters()

        # hwmodel energy pricing: admission/executors account through
        # this ONE hook; the cost-aware policy prices via the same model
        self.energy = EnergyModel(self.params, ecfg.energy_style)
        self.policy = resolve_admission_policy(ecfg, self.energy)

        # slot-state layer: contiguous stripes or the paged block pool
        # (PERSISTENT — prefix pages indexed in one run() feed the next)
        self._mgr = None
        self._cache = None
        self._draft_cache = None
        if ecfg.paged:
            mb = ecfg.max_len // ecfg.block_size
            nb = ecfg.num_blocks or (1 + 2 * ecfg.max_batch * mb)
            if mesh is not None:
                dsz = mesh.shape.get("data", 1)    # divisibility for the
                nb = -(-nb // dsz) * dsz           # kv_blocks->data rule
            self._mgr = PagedKVManager(
                ecfg.max_batch, ecfg.block_size, nb, mb,
                prefix_reuse=ecfg.prefix_reuse,
            )
            self.state = PagedSlotState(self, self._mgr)
            self._cache = self.state.init_pool()
            self.admitter = PagedAdmitter(self)
        else:
            self.state = ContiguousSlotState(self)
            self.admitter = ContiguousAdmitter(self)

        # executor layer: every jitted closure in one builder, assigned
        # to the attribute names the compile-count suite introspects
        fns = build_compiled(self)
        self._prefill_full = fns.prefill_full
        self._prefill_bucket = fns.prefill_bucket
        self._decode = fns.decode
        self._insert = fns.insert
        self._decode_multi = fns.decode_multi
        if ecfg.paged:
            self._decode_paged = fns.decode_paged
            self._insert_paged = fns.insert_paged
            self._prefill_suffix = fns.prefill_suffix
            self._copy_page = fns.copy_page
            self._decode_multi_paged = fns.decode_multi_paged
        if self._spec_k:
            self._draft_prefill = fns.draft_prefill
            self._draft_insert = fns.draft_insert
            self._draft_propose = fns.draft_propose
            self._verify = fns.verify
            self._set_len = fns.set_len
            if ecfg.paged:
                self._verify_paged = fns.verify_paged

        if self.mode == "static":
            self.executor = StaticBatchExecutor(self)
        elif self._spec_k:
            self.executor = SpecRoundExecutor(self)
        elif self._use_device_loop:
            self.executor = DeviceHorizonExecutor(self)
        else:
            self.executor = HostLoopExecutor(self)

    def _ctx(self):
        """Rules-activation context entered at trace time (and for the
        eager slot-pool construction)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return axis_rules(self._rules, self.mesh)

    # -- API ---------------------------------------------------------------
    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               eos_id: Optional[int] = None,
               extra_idx: Optional[int] = None) -> int:
        """Enqueue a prompt; returns its uid. ``eos_id=None`` resolves
        to ``EngineConfig.eos_id``; ``extra_idx`` picks the request's
        side-input row (default: positional by submission order)."""
        if eos_id is None:
            eos_id = self.ecfg.eos_id
        prompt = np.asarray(prompt, np.int32)
        # patch rows sit below the prompt and a verify can write spec_k
        # junk positions — both must fit so no KV write is ever clamped
        overhead = self._patch_len + self._spec_k
        if overhead + len(prompt) + max_new_tokens > self.ecfg.max_len:
            extra = (f" + side/spec overhead ({overhead})"
                     if overhead else "")
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens})"
                f"{extra} exceeds max_len ({self.ecfg.max_len})"
            )
        self._uid += 1
        r = Request(self._uid, prompt, max_new_tokens, eos_id,
                    t_enqueue=time.time(),
                    extra_idx=-1 if extra_idx is None else int(extra_idx))
        self.queue.append(r)
        return r.uid

    @property
    def drained(self) -> bool:
        """True when nothing is queued or in flight — the streaming
        front-end's idle signal."""
        return not self.queue and not self.state.any_live

    def step(self) -> Dict[int, List[int]]:
        """One continuous scheduling round: admit at the boundary, run
        one executor round, return the tokens each touched request
        gained (``{uid: [new tokens...]}``) for streaming pollers.
        A no-op (empty dict) when nothing is queued or live."""
        if self.mode != "continuous":
            raise ValueError("step() requires the continuous scheduler; "
                             "static mode only drains through run()")
        if self.drained:
            return {}
        with TraceAnnotation("serve.step"):
            return self._round()

    def _round(self) -> Dict[int, List[int]]:
        self._start()                    # idempotent pool allocation
        before = {r.uid: (r, len(r.output)) for r in self.queue}
        for r in self.state.slots:
            if r is not None:
                before[r.uid] = (r, len(r.output))
        # admission at the round boundary. `stalled` breaks when the
        # pool/budget can't take the queue head — retirement frees both,
        # so fall through to the executor rather than spin here.
        stalled = False
        if self.queue:
            with TraceAnnotation("serve.admit"):
                while self.queue and not stalled:
                    free = self.state.free()
                    if not free:
                        break
                    stalled = not self.admitter.admit(free)
        if self.state.any_live:
            self.executor.run_round()
        elif stalled:
            # nothing live to retire: the pool can never hold the
            # queue head — surface it instead of spinning forever
            raise PoolExhausted(
                f"page pool ({self._mgr.pool.num_blocks} "
                f"blocks) cannot hold the queue head's "
                f"prompt plus its decode budget with no "
                f"live slots left to retire; raise "
                f"num_blocks"
            )
        # else: all admits retired at t=1 — their first tokens are the
        # round's only deltas
        return {uid: r.output[n:] for uid, (r, n) in before.items()
                if len(r.output) > n}

    def run(self) -> List[Request]:
        """Serve every queued request to completion; returns them with
        outputs (continuous: per-step retirement + mid-flight admission;
        static: fixed batches decoded in lockstep)."""
        if self.mode == "continuous":
            while not self.drained:
                self.step()
        else:
            while self.queue:
                batch = self.queue[: self.ecfg.max_batch]
                self.queue = self.queue[self.ecfg.max_batch:]
                self.executor.run_batch(batch)
        return self.finished

    def _start(self) -> None:
        """Allocate the contiguous pools lazily (the paged pool lives in
        ``__init__``); junk above the length watermark is never read, so
        one pool serves every run. Under a mesh a drained pool is
        re-placed eagerly: donated decode outputs carry XLA-canonicalized
        shardings that would retrace the warm insert closures."""
        fresh = self.mesh is not None and not self.state.any_live
        if self._cache is None or (fresh and not self.ecfg.paged):
            self._cache = self.state.init_pool()
        if self._spec_k and (self._draft_cache is None or fresh):
            # draft pool: always contiguous, mirrors slot assignment 1:1
            enc_len = self._enc_len if self.cfg.family == "encdec" else 0
            with self._ctx():
                self._draft_cache = D.cache_init(
                    self.draft_params, self.ecfg.draft_config,
                    self.ecfg.max_batch, self.ecfg.max_len,
                    dtype=jnp.float32, enc_len=enc_len)

    def reset_stats(self) -> None:
        """Clear finished requests + telemetry, keeping compiled fns
        warm and the paged prefix index populated (post-warm-up runs)."""
        self.finished = []
        self._zero_counters()
        self.energy.reset()
        if hasattr(self.policy, "deferrals"):
            self.policy.deferrals = 0
        if self._mgr is not None:
            self._mgr.reset_counters()   # telemetry only; pages/index kept

    def reset_counters(self) -> None:
        """Alias for :meth:`reset_stats` (paged-KV manager naming)."""
        self.reset_stats()

    @property
    def energy_tokens(self) -> int:
        return self.energy.tokens

    def energy_report(self, styles=None, occupancy=None) -> Dict[str, Dict]:
        """Modeled per-style totals for the tokens served so far;
        ``occupancy`` overrides the pack-time figure for what-if sweeps
        without re-serving the trace (the serve_bench energy grid)."""
        return self.energy.report(styles=styles, occupancy=occupancy)

    def stats(self) -> Dict[str, float]:
        occ = (self.occupied_slot_steps
               / (self.decode_steps * self.ecfg.max_batch)
               if self.decode_steps else 0.0)
        out = {
            "mode": self.mode,
            "decode_steps": self.decode_steps,
            "host_syncs": self.host_syncs,
            "decode_wall_s": self.decode_wall_s,
            "mean_step_s": (self.decode_wall_s / self.decode_steps
                            if self.decode_steps else 0.0),
            "prefill_calls": self.prefill_calls,
            "prefill_tokens": self.prefill_tokens,
            "prefill_padded_tokens": self.prefill_padded_tokens,
            "cached_prefix_tokens": self.cached_prefix_tokens,
            "mean_slot_occupancy": occ,
            "occupied_slot_steps": self.occupied_slot_steps,
            "admissions": self.admitted,
            "admitted": self.admitted,
            "queue_wait_s": self.queue_wait_s,
            "device_wait_s": self.device_wait_s,
            "mesh": (None if self.mesh is None else
                     "x".join(f"{k}={v}" for k, v in self.mesh.shape.items())),
            "admission_policy": self.policy.name,
            "admission_deferrals": getattr(self.policy, "deferrals", 0),
        }
        # hwmodel energy attribution (zeros before any token is served,
        # and for trees with no MVM layers)
        out.update(self.energy.summary(len(self.finished)))
        if self._spec_k:
            out.update({
                "spec_k": self._spec_k,
                "spec_rounds": self.spec_rounds,
                "spec_proposed": self.spec_proposed,
                "spec_accepted": self.spec_accepted,
                "spec_accept_rate": (self.spec_accepted / self.spec_proposed
                                     if self.spec_proposed else 0.0),
            })
        if self._mgr is not None:
            out["paged"] = self._mgr.stats()
        return out

    # -- shared helpers used by the scheduler / executor layers -------------
    def _sample(self, logits: jax.Array) -> jax.Array:
        if self.ecfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)
        self._key, sub = jax.random.split(self._key)
        return jax.random.categorical(sub, logits / self.ecfg.temperature)

    def _bucket(self, n: int) -> int:
        return min(max(self.ecfg.min_bucket, next_pow2(n)),
                   self.ecfg.max_len)

    def _bucket_of(self, r: Request) -> int:
        return self._bucket(len(r.prompt))

    def _finish(self, r: Request, now: float) -> None:
        r.done, r.t_done = True, now
        self.finished.append(r)

    def _prefill_batch(self, reqs: List[Request], rows: int,
                       toks: np.ndarray, lens: np.ndarray) -> Dict:
        """Build a prefill batch dict with each request's side-input
        rows; shapes depend only on (rows, width, side keys), so
        prefill compiles stay enumerable."""
        b = {"tokens": jnp.asarray(toks), "lengths": jnp.asarray(lens)}
        if self.cfg.family == "encdec":
            b["enc_embeds"] = jnp.asarray(self._extra_rows(
                "enc_embeds", reqs, rows,
                (self._enc_len, self.cfg.d_model)))
        if self.cfg.family == "vlm" and "patch_embeds" in self.extra:
            b["patch_embeds"] = jnp.asarray(
                self._extra_rows("patch_embeds", reqs, rows, None))
        return b

    def _extra_rows(self, key: str, reqs: List[Request], bp: int,
                    default_shape) -> np.ndarray:
        """Per-request side-input rows for a prefill batch: gathered by
        ``Request.extra_idx`` (positional by submission order when
        unset) so every batch gets its OWN rows; padding rows are
        zeros (their outputs are ignored)."""
        arr = self.extra.get(key)
        if arr is None:
            arr = np.zeros((0,) + tuple(default_shape), np.float32)
        arr = np.asarray(arr)
        out = np.zeros((bp,) + arr.shape[1:], arr.dtype)
        for i, r in enumerate(reqs):
            if arr.shape[0] == 0:
                continue                     # no side inputs: zeros rows
            idx = r.extra_idx if r.extra_idx >= 0 else r.uid - 1
            if idx >= arr.shape[0]:
                raise ValueError(
                    f"request uid {r.uid} has no {key} row {idx}: "
                    f"{arr.shape[0]} rows were supplied at engine "
                    f"construction (side inputs are positional by "
                    f"submission order unless submit(extra_idx=...) "
                    f"picks a row)"
                )
            out[i] = arr[idx]
        return out

    @staticmethod
    def _right_pad(reqs: List[Request], rows: int, width: int):
        return right_pad(reqs, rows, width)


def throughput_stats(reqs: List[Request]) -> Dict[str, float]:
    """Aggregate request metrics; robust to empty/never-started requests.

    Never-started requests count toward ``requests`` but not TTFT.
    ``mean_tpot_s`` divides the two REAL timestamps each request has
    (first token at admission, completion at retirement) by its decode
    count — honest at every ``decode_horizon`` (no per-token wall times
    are fabricated inside a device horizon), and equal to true
    per-token latency at horizon 1.
    """
    if not reqs:
        return {}
    total_tokens = sum(len(r.output) for r in reqs)
    t0 = min(r.t_enqueue for r in reqs)
    finished = [r.t_done for r in reqs if r.t_done]
    elapsed = (max(finished) - t0) if finished else 0.0
    started = [r for r in reqs if r.t_first_token > 0.0]
    ttft = [r.t_first_token - r.t_enqueue for r in started]
    tpot = [
        (r.t_done - r.t_first_token) / max(len(r.output) - 1, 1)
        for r in reqs
        if r.t_done and r.t_first_token and len(r.output) > 1
    ]
    return {
        "requests": len(reqs),
        "started": len(started),
        "total_tokens": total_tokens,
        "tokens_per_s": total_tokens / elapsed if elapsed > 0 else 0.0,
        "mean_ttft_s": float(np.mean(ttft)) if ttft else 0.0,
        "mean_tpot_s": float(np.mean(tpot)) if tpot else 0.0,
    }
