"""Scheduling layer: requests, engine config, admission policies.

This module owns every *decision* about which request runs where —
the :class:`ServeEngine` facade (``serve/engine.py``) only wires the
layers together, and the executors (``serve/executor.py``) only run
what admission already placed.

Four pieces:

``Request`` / ``EngineConfig``
    the public request record and engine knob set.
    :meth:`EngineConfig.validate` is the ONE place every invalid knob
    combination raises — the engine calls it once at construction,
    and standalone callers (launchers, tests) can call it directly.

``AdmissionPolicy``
    the protocol behind mid-flight admission. Two implementations:

    * :class:`Pow2BucketFCFS` (default) — the queue head plus any
      later requests sharing its pow2 prompt-length bucket, FIFO
      otherwise, capped by free slots and ``prefill_batch``. This is
      byte-identical to the policy historically inlined in the engine.
    * :class:`CostAwareEnergyBudget` — the same bucket selection,
      additionally budgeted against the modeled per-request serve
      energy (:class:`EnergyModel`, pJ): a request is admitted only
      while the summed worst-case energy of in-flight requests stays
      under ``EngineConfig.energy_budget_pj``. The queue head is
      always admitted when nothing is in flight, so the engine can
      never deadlock on an over-budget head. HCiM's scale-factor
      array makes the energy signal cheap and static (pack-time
      occupancy metadata), which is what makes admission-time pricing
      practical — the cost-model-driven CiM design loop of Andrulis
      et al. (2024) applied to scheduling.

``ContiguousAdmitter`` / ``PagedAdmitter``
    the admission *mechanism*: bucketed prefill batches, slot
    scatter, radix prefix reuse and page-pool headroom math. They
    consult the policy for the take decision and the engine for
    compiled functions and telemetry.

``EngineTelemetry``
    the engine's scalar counters and the accounting hooks admitters
    and executors call, ``fetch`` among them: every device read under
    a ``serve.*.wait`` profiler span.
"""
from __future__ import annotations

import dataclasses
import time
from typing import (
    Any, Callable, Dict, List, Optional, Protocol, Sequence, Tuple,
)

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig
from repro.models import decode as D
from repro.serve.paged_kv import PoolExhausted


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 16
    eos_id: int = -1              # -1: never
    # filled by the engine
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    t_enqueue: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    t_admit: float = 0.0          # left the queue for its prefill
    slot: int = -1                # decode slot served in (continuous mode)
    admit_step: int = -1          # engine decode step when bound to it
    extra_idx: int = -1           # side-input row (-1: positional by uid)


@dataclasses.dataclass
class EngineConfig:
    max_batch: int = 8            # decode slot-pool size (static: batch size)
    max_len: int = 256            # KV capacity per slot
    temperature: float = 0.0      # 0 => greedy
    seed: int = 0
    mode: str = "auto"            # auto | continuous | static
    prefill_batch: int = 4        # max requests per bucketed prefill call
    min_bucket: int = 8           # smallest prompt-length bucket
    eos_id: int = -1              # default EOS for submit() (-1: never)
    # on-device multi-step decode (continuous greedy serving only):
    # one jit call advances every slot up to decode_horizon steps
    # (models.decode.decode_multi_step) — host syncs per horizon, not
    # per token. device_loop=False forces the legacy per-token path.
    decode_horizon: int = 1
    device_loop: bool = True
    # paged KV layout (continuous scheduler only; see docs/memory.md)
    paged: bool = False           # page pool + block tables vs stripes
    block_size: int = 16          # tokens per KV page (divides max_len)
    num_blocks: int = 0           # pool pages; 0 => auto (2x slot capacity)
    prefix_reuse: bool = True     # radix-index shared-prefix reuse
    paged_attn_backend: Optional[str] = None  # None => inline gather path
    # hwmodel accounting style for stats()["energy_pj_total"] etc.
    # (repro.hwmodel.system.serve_energy): adc | quarry | hcim
    energy_style: str = "hcim"
    # speculative decoding (continuous greedy serving only): a draft
    # model proposes spec_k tokens per slot, decode_verify scores them
    # in one forward, rollback is a per-slot length edit. 0 => off.
    # draft_params ride in as a ServeEngine constructor argument.
    spec_k: int = 0
    draft_config: Optional[ArchConfig] = None
    # admission policy (docs/scheduling.md): "fcfs" is the pow2-bucket
    # FIFO wave; "cost-aware" budgets in-flight requests against the
    # modeled serve energy cap below (pJ, worst-case per request).
    admission_policy: str = "fcfs"
    energy_budget_pj: float = 0.0

    def resolve_mode(self) -> str:
        mode = self.mode
        if mode == "auto":
            # every family serves continuously — side inputs included
            # (admission gathers per-request rows; the slot pool carries
            # cross-KV / patch-offset state). "auto" always resolves
            # continuous; "static" remains as an explicit oracle mode.
            return "continuous"
        if mode not in ("continuous", "static"):
            raise ValueError(f"unknown engine mode {mode!r}")
        return mode

    def validate(self, cfg: ArchConfig, *, mode: Optional[str] = None,
                 has_draft_params: bool = False,
                 extra: Optional[Dict[str, Any]] = None) -> str:
        """Raise on every invalid knob combination; returns the resolved
        mode. The single home of engine-config validation — the checks
        run in a fixed order (mode, horizon, spec, energy style, paged
        layout, admission policy) so each invalid combination raises
        the same message regardless of which other knobs are also set.
        """
        extra = extra or {}
        if mode is None:
            mode = self.resolve_mode()
        if self.decode_horizon < 1:
            raise ValueError(
                f"decode_horizon must be >= 1, got {self.decode_horizon}"
            )
        if self.decode_horizon > 1 and self.temperature > 0.0:
            raise ValueError(
                "decode_horizon > 1 runs the on-device greedy loop; "
                "temperature sampling needs the per-token host path "
                "(set decode_horizon=1)"
            )
        if self.decode_horizon > 1 and not self.device_loop:
            raise ValueError(
                "decode_horizon > 1 requires device_loop=True"
            )
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {self.spec_k}")
        if self.spec_k:
            dcfg = self.draft_config
            if dcfg is None or not has_draft_params:
                raise ValueError(
                    "speculative decoding (spec_k > 0) needs both "
                    "EngineConfig.draft_config and a draft_params tree"
                )
            if mode != "continuous":
                raise ValueError(
                    f"speculative decoding requires the continuous "
                    f"scheduler; resolved mode is {mode!r}"
                )
            if cfg.family not in D._SPEC_FAMILIES:
                raise ValueError(
                    f"speculative decoding supports the pure KV-cache "
                    f"families {D._SPEC_FAMILIES}, got {cfg.family!r}: "
                    f"recurrent state folds every token and cannot roll "
                    f"back by a length edit"
                )
            if self.temperature > 0.0:
                raise ValueError(
                    "speculative decoding is greedy-only (acceptance "
                    "compares draft proposals with main-model argmaxes); "
                    "set temperature=0"
                )
            if self.decode_horizon != 1:
                raise ValueError(
                    "speculative decoding replaces the device horizon "
                    "loop; set decode_horizon=1"
                )
            if dcfg.family != cfg.family:
                raise ValueError(
                    f"draft family {dcfg.family!r} must match the target "
                    f"family {cfg.family!r}"
                )
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    "draft and target models must share a vocabulary "
                    f"({dcfg.vocab_size} != {cfg.vocab_size})"
                )
            if cfg.family in ("encdec", "vlm") and dcfg.d_model != cfg.d_model:
                raise ValueError(
                    "side-input families need draft d_model == target "
                    "d_model: enc_embeds/patch_embeds rows feed both "
                    f"models ({dcfg.d_model} != {cfg.d_model})"
                )
        from repro.hwmodel.system import SERVE_STYLES
        if self.energy_style not in SERVE_STYLES:
            raise ValueError(
                f"unknown energy_style {self.energy_style!r}; "
                f"choose from {SERVE_STYLES}"
            )
        if self.paged:
            if cfg.family not in D._PAGED_FAMILIES:
                reason = (
                    "recurrent state has no sequence axis to page — serve "
                    "it through the contiguous continuous scheduler "
                    "(paged=False)"
                    if cfg.family in ("hybrid", "ssm") else
                    "cross-attention KV has no pages — serve it through "
                    "the contiguous continuous scheduler (paged=False)"
                )
                raise ValueError(
                    f"paged KV cache supports attention-KV families "
                    f"{D._PAGED_FAMILIES}, got {cfg.family!r}: {reason}"
                )
            if cfg.family == "vlm" and "patch_embeds" in extra:
                raise ValueError(
                    "paged KV cache does not take per-request "
                    "patch_embeds: the radix prefix index keys on token "
                    "ids alone, so a reused prefix page could alias "
                    "another request's patch context; serve through the "
                    "contiguous continuous scheduler (paged=False)"
                )
            if mode != "continuous":
                raise ValueError(
                    f"paged KV cache requires the continuous scheduler; "
                    f"resolved mode is {mode!r}"
                )
            if self.max_len % self.block_size:
                raise ValueError(
                    f"max_len ({self.max_len}) must be a multiple of "
                    f"block_size ({self.block_size})"
                )
        if self.admission_policy not in ADMISSION_POLICIES:
            raise ValueError(
                f"unknown admission_policy {self.admission_policy!r}; "
                f"choose from {tuple(ADMISSION_POLICIES)}"
            )
        if self.energy_budget_pj < 0:
            raise ValueError(
                f"energy_budget_pj must be >= 0, got "
                f"{self.energy_budget_pj}"
            )
        if self.admission_policy == "cost-aware" and self.energy_budget_pj <= 0:
            raise ValueError(
                "cost-aware admission needs a positive "
                "EngineConfig.energy_budget_pj cap (pJ of modeled "
                "in-flight serve energy; see docs/scheduling.md)"
            )
        return mode


# -- energy pricing ---------------------------------------------------------

def collect_mvm_layers(node, path: str = "") -> List[tuple]:
    """Walk a served param tree and list its MVM layers for the hwmodel.

    Returns ``(name, k, o, occupancy_or_None, quant_cfg_or_None)`` per
    linear — PackedLayer nodes carry their pack-time occupancy metadata
    and QuantConfig; raw param dicts (fp / QAT trees, key ``"w"`` of rank
    2 or 3) are modeled dense. Embedding tables (key ``"table"``) are
    lookups, not MVMs, and are skipped. Stacked rank-3 weights count one
    layer per leading index (scan-over-layers packs; MoE expert banks are
    modeled as all-experts-resident, the PUMA weight-stationary story).
    """
    out: List[tuple] = []
    if node is None:
        return out
    if hasattr(node, "w_codes"):             # PackedLayer (2-D or stacked)
        w = node.w_codes
        if w.ndim == 3:
            for l in range(int(w.shape[0])):
                out.append((f"{path}[{l}]", int(w.shape[1]),
                            int(w.shape[2]), None, node.cfg))
        else:
            out.append((path, int(w.shape[0]), int(w.shape[1]),
                        node.occupancy, node.cfg))
        return out
    if isinstance(node, dict):
        w = node.get("w")
        if getattr(w, "ndim", 0) in (2, 3) and "table" not in node:
            if w.ndim == 3:
                for l in range(int(w.shape[0])):
                    out.append((f"{path}[{l}]", int(w.shape[1]),
                                int(w.shape[2]), None, None))
            else:
                out.append((path, int(w.shape[0]), int(w.shape[1]),
                            None, None))
            return out
        for k in sorted(node):
            out.extend(collect_mvm_layers(node[k], f"{path}/{k}"))
        return out
    if isinstance(node, (list, tuple)):
        for i, v in enumerate(node):
            out.extend(collect_mvm_layers(v, f"{path}[{i}]"))
        return out
    return out


class EnergyModel:
    """hwmodel-in-the-loop energy pricing for one served param tree.

    One pass over the tree at construction collects every MVM shape plus
    its pack-time occupancy metadata; the per-token modeled cost is
    evaluated once (all hwmodel energy terms are linear in ``n_vec``)
    and scaled by the true forward-pass token count. This object is the
    SINGLE energy-accounting hook: admission and the executors call
    :meth:`add` at their prefill/decode boundaries, nothing else touches
    the token counter. It also prices requests for the cost-aware
    admission policy (:meth:`request_cost_pj`).
    """

    def __init__(self, params, style: str):
        from repro.hwmodel.system import serve_energy

        self.style = style
        self.tokens = 0              # true tokens through the model
        self.shapes: List[tuple] = []
        self.occ: Dict[str, float] = {}
        self.kw: Dict[str, Any] = {}
        self.per_token: Optional[Dict[str, Any]] = None
        mvms = collect_mvm_layers(params)
        if not mvms:
            return
        self.shapes = [(name, k, o, 1) for name, k, o, _, _ in mvms]
        self.occ = {
            name: (occ.mean_zero_fraction if occ is not None else 0.0)
            for name, _, _, occ, _ in mvms
        }
        qcfg = next((c for _, _, _, _, c in mvms if c is not None), None)
        if qcfg is not None:
            self.kw = dict(
                xbar_rows=qcfg.xbar_rows,
                n_bits_a=qcfg.spec.n_bits_a,
                n_bits_w=qcfg.spec.n_bits_w,
                n_bits_sf=qcfg.spec.n_bits_sf,
                adc_bits=qcfg.adc_bits,
                levels=qcfg.psq_levels,
            )
        self.per_token = serve_energy(
            self.shapes, occupancy=self.occ, style=style, **self.kw,
        )

    def add(self, n_tokens: int) -> None:
        """Attribute ``n_tokens`` true forward-pass tokens (prefill or
        decode) — the one accounting call site."""
        self.tokens += int(n_tokens)

    def reset(self) -> None:
        self.tokens = 0

    def request_cost_pj(self, r: Request) -> float:
        """Worst-case modeled serve energy of one request: every prompt
        token prefills and the full decode budget is spent. Prefix reuse
        and early EOS only lower the realized figure, so budgeting on
        this keeps the cost-aware cap conservative."""
        if self.per_token is None:
            return 0.0
        return self.per_token["energy_pj"] * (len(r.prompt)
                                              + r.max_new_tokens)

    def summary(self, n_finished: int) -> Dict[str, float]:
        """The ``stats()`` energy fragment (zeros before any token is
        served, and for trees with no MVM layers)."""
        e, tok = self.per_token, self.tokens
        total = e["energy_pj"] * tok if e is not None else 0.0
        return {
            "energy_style": self.style,
            "energy_tokens": tok,
            "energy_pj_per_token": e["energy_pj"] if e is not None else 0.0,
            "energy_pj_total": total,
            "energy_pj_per_request": (total / n_finished
                                      if n_finished else 0.0),
            "edap_total": (total * (e["latency_ns"] * tok) * e["area_mm2"]
                           if e is not None else 0.0),
            "mean_occupancy": e["occupancy"] if e is not None else 0.0,
        }

    def report(self, styles=None, occupancy=None) -> Dict[str, Dict]:
        """Modeled per-style totals for the tokens served so far."""
        from repro.hwmodel.system import SERVE_STYLES, serve_energy

        if not self.shapes:
            return {}
        occ = self.occ if occupancy is None else occupancy
        tok = self.tokens
        rep: Dict[str, Dict] = {}
        for s in (styles or SERVE_STYLES):
            e = serve_energy(self.shapes, occupancy=occ, style=s, **self.kw)
            rep[s] = {
                "energy_pj_per_token": e["energy_pj"],
                "energy_pj_total": e["energy_pj"] * tok,
                "edap_total": (e["energy_pj"] * tok) * (e["latency_ns"] * tok)
                              * e["area_mm2"],
                "occupancy": e["occupancy"],
            }
        return rep


# -- admission policies -----------------------------------------------------

class EngineTelemetry:
    """The serving engine's telemetry: plain scalar counters, so a
    long-running server's never grow, and the hooks admitters and
    executors account through (the single energy/prefill attribution
    sites). :class:`~repro.serve.engine.ServeEngine` inherits it, so the
    counters are engine attributes; it supplies ``energy``, an
    :class:`EnergyModel`."""

    def _zero_counters(self) -> None:
        self.decode_steps = 0
        self.host_syncs = 0              # decode round-trips (jit + drain)
        self.decode_wall_s = 0.0         # wall time inside decode syncs
        self.device_wait_s = 0.0         # blocked in the serve.*.wait reads
        self.prefill_calls = 0
        self.prefill_tokens = 0          # true (unpadded) tokens prefilled
        self.prefill_padded_tokens = 0   # rows x width the prefills computed
        self.cached_prefix_tokens = 0    # prompt tokens served from pages
        self.occupied_slot_steps = 0     # live slots summed over decode steps
        self.admitted = 0                # requests bound to a slot
        self.queue_wait_s = 0.0          # their summed time in the queue
        # speculative decoding telemetry
        self.spec_rounds = 0
        self.spec_proposed = 0           # draft tokens put up for verify
        self.spec_accepted = 0           # draft tokens the verify kept

    def account_prefill(self, n_tokens: int, padded_tokens: int) -> None:
        """One prefill call ran ``n_tokens`` TRUE prompt tokens in a
        block of ``padded_tokens`` (rows x bucket width); reused prefix
        pages cost nothing and are not reported here."""
        self.prefill_calls += 1
        self.prefill_tokens += n_tokens
        self.prefill_padded_tokens += padded_tokens
        self.energy.add(n_tokens)

    def account_admit(self, r: Request) -> None:
        """``r`` was just bound to its slot; its queue wait ran from
        ``submit`` to leaving the queue (``t_admit``)."""
        r.admit_step = self.decode_steps
        self.admitted += 1
        self.queue_wait_s += r.t_admit - r.t_enqueue

    def account_decode(self, n_tokens: int) -> None:
        """A decode round emitted ``n_tokens`` true tokens (masked
        no-op steps of retired rows excluded)."""
        self.energy.add(n_tokens)

    def fetch(self, span: str, *arrays) -> List[np.ndarray]:
        """Read device results to the host under ``span`` (one of the
        ``serve.*.wait`` names); the time blocked counts to
        ``device_wait_s``."""
        t = time.perf_counter()
        with TraceAnnotation(span):
            out = [np.asarray(a) for a in arrays]
        self.device_wait_s += time.perf_counter() - t
        return out


class AdmissionPolicy(Protocol):
    """The admission decision: which queued requests join this wave.

    ``take`` sees the queue in FIFO order, the wave size cap, a
    ``bucket_of`` callable (pow2 prompt-length bucket) and the list of
    in-flight requests; it returns the selected requests in queue order
    (possibly empty — the engine then decodes instead of admitting).
    ``admits_head`` is the single-admission variant used by the paged
    shared-prefix path, which admits the head alone.
    """
    name: str

    def take(self, queue: Sequence[Request], limit: int,
             bucket_of: Callable[[Request], int],
             eligible: Optional[Callable[[Request], bool]] = None,
             live: Sequence[Request] = ()) -> List[Request]: ...

    def admits_head(self, head: Request,
                    live: Sequence[Request]) -> bool: ...


class Pow2BucketFCFS:
    """Default policy: the queue head plus any later requests sharing
    its pow2 prompt-length bucket, FIFO otherwise — one prefill compile
    per (bucket length, bucket batch) pair."""

    name = "fcfs"

    def take(self, queue, limit, bucket_of, eligible=None, live=()):
        head = queue[0]
        w = bucket_of(head)
        take = [head]
        for r in queue[1:]:
            if len(take) >= limit:
                break
            if bucket_of(r) == w and (eligible is None or eligible(r)):
                take.append(r)
        return take

    def admits_head(self, head, live):
        return True


class CostAwareEnergyBudget(Pow2BucketFCFS):
    """FCFS bucket selection gated by a modeled-energy budget.

    In-flight requests hold their worst-case serve energy
    (:meth:`EnergyModel.request_cost_pj`) against ``budget_pj``; a
    candidate joins the wave only while the total stays under the cap.
    Retirement returns a request's share, so deferred requests admit on
    later waves. The queue head is always admitted when nothing is in
    flight and nothing was selected — an over-budget head must not
    deadlock the engine (it simply serves alone).
    """

    name = "cost-aware"

    def __init__(self, budget_pj: float,
                 cost_fn: Callable[[Request], float]):
        if budget_pj <= 0:
            raise ValueError(
                f"cost-aware admission needs a positive budget_pj, "
                f"got {budget_pj}"
            )
        self.budget_pj = float(budget_pj)
        self.cost_fn = cost_fn
        self.deferrals = 0           # requests bumped to a later wave

    def _inflight_pj(self, live) -> float:
        return sum(self.cost_fn(r) for r in live)

    def take(self, queue, limit, bucket_of, eligible=None, live=()):
        base = super().take(queue, limit, bucket_of, eligible, live)
        spent = self._inflight_pj(live)
        out: List[Request] = []
        for r in base:
            c = self.cost_fn(r)
            if spent + c <= self.budget_pj or (not out and not live):
                out.append(r)
                spent += c
            else:
                self.deferrals += 1
        return out

    def admits_head(self, head, live):
        if not live:
            return True
        if self._inflight_pj(live) + self.cost_fn(head) <= self.budget_pj:
            return True
        self.deferrals += 1
        return False


ADMISSION_POLICIES = ("fcfs", "cost-aware")


def resolve_admission_policy(ecfg: EngineConfig,
                             energy: EnergyModel) -> AdmissionPolicy:
    if ecfg.admission_policy == "cost-aware":
        return CostAwareEnergyBudget(ecfg.energy_budget_pj,
                                     energy.request_cost_pj)
    return Pow2BucketFCFS()


# -- admission mechanism ----------------------------------------------------

class ContiguousAdmitter:
    """Fill free slots from the queue with one bucketed prefill call.

    The policy picks the wave (queue head plus bucket-mates under the
    default FCFS); prompts are right-padded to (pow2 batch, pow2 length)
    so prefill shapes stay enumerable, each row's first token is sampled
    from its TRUE last-prompt position, and each row's prefilled state —
    KV, recurrent rows, cross-attention KV — scatters into its slot via
    the :class:`~repro.serve.state.SlotState` insert interface. With
    speculative decoding on, the draft model prefills the SAME batch and
    its rows scatter into the draft pool in lockstep.
    """

    def __init__(self, eng):
        self.eng = eng

    def admit(self, free: List[int]) -> bool:
        eng = self.eng
        queue = eng.queue
        limit = min(len(free), eng.ecfg.prefill_batch)
        live = [s for s in eng.state.slots if s is not None]
        take = eng.policy.take(queue, limit, eng._bucket_of, live=live)
        if not take:
            return False
        now = time.time()
        for r in take:
            queue.remove(r)
            r.t_admit = now

        m = len(take)
        mp = min(next_pow2(m), eng.ecfg.prefill_batch)
        w = eng._bucket_of(take[0])
        toks, lens = right_pad(take, mp, w)
        b = eng._prefill_batch(take, mp, toks, lens)
        logits, pcache = eng._prefill_bucket(eng.params, b)
        dcache = None
        if eng._spec_k:
            _, dcache = eng._draft_prefill(eng.draft_params, b)
        eng.account_prefill(sum(len(r.prompt) for r in take), mp * w)
        # each row's next token comes from its true last prompt position
        idx = jnp.asarray([len(r.prompt) - 1 for r in take]
                          + [0] * (mp - m))
        first = eng.fetch("serve.prefill.wait",
                          eng._sample(logits[jnp.arange(mp), idx]))[0]
        now = time.time()
        for i, r in enumerate(take):
            r.t_first_token = now
            t = int(first[i])
            r.output.append(t)
            if t == r.eos_id or len(r.output) >= r.max_new_tokens:
                eng._finish(r, now)                  # never occupies a slot
                continue
            slot = free.pop(0)
            ln = eng._patch_len + len(r.prompt)
            eng.state.insert(pcache, i, slot, ln)
            if dcache is not None:
                eng._draft_cache = eng._draft_insert(
                    eng._draft_cache, dcache, i, slot, ln)
            eng.state.bind(r, slot, t)
            eng.account_admit(r)
        return True


class PagedAdmitter:
    """Admit from the queue into free slots through the radix index.

    A queue head with a cached shared prefix admits alone: the reused
    pages are ref-bumped into its block table and ONLY the un-cached
    suffix is prefilled against them
    (``models.decode.prefill_paged_suffix``). Cold requests batch
    through the same pow2-bucketed prefill as the contiguous path, then
    scatter into their private pages. Either way, the prompt's full
    pages are published to the index for later requests.

    ``admit`` returns ``progressed``. ``False`` means the page pool (or
    the energy budget) could not hold the queue head: nothing was
    admitted, and the caller must STOP admitting and decode instead —
    retirement frees pages and budget — rather than spin on the head.
    """

    def __init__(self, eng):
        self.eng = eng

    @property
    def mgr(self):
        return self.eng.state.mgr

    def admit(self, free: List[int]) -> bool:
        eng = self.eng
        if self.mgr.match_tokens([int(t) for t in eng.queue[0].prompt]):
            return self._admit_suffix(free)
        return self._admit_cold(free)

    def worst_case_pages(self, r: Request) -> int:
        """Pages ``r`` occupies if it decodes to its full budget: the
        cache length peaks at len(prompt) + max_new_tokens - 1 (the last
        sampled token is never appended). A speculative verify round can
        additionally write spec_k proposal positions past that peak
        before rolling back, so spec engines budget those pages too."""
        end = len(r.prompt) + r.max_new_tokens - 1 + self.eng._spec_k
        return -(-end // self.eng.ecfg.block_size)

    def headroom(self) -> int:
        """Free pages minus the growth still owed to live slots.

        Admission must budget for decode growth, not just the prompt:
        admitting on prompt pages alone can deadlock mid-decode when
        every live slot needs its next page and nothing is retirable.
        Gating on this headroom keeps the invariant that owed growth
        always fits the free list, so ``prepare_append`` cannot exhaust
        the pool between horizon boundaries.
        """
        owed = 0
        for i, s in enumerate(self.eng.state.slots):
            if s is None:
                continue
            owed += max(0, self.worst_case_pages(s)
                        - len(self.mgr.slot_blocks(i)))
        return self.mgr.pool.free_blocks - owed

    def _place(self, r: Request, slot: int, token: int,
               now: float) -> None:
        """Record a freshly-admitted request in its slot (or retire it on
        the spot when the prefill token already finishes it)."""
        eng = self.eng
        r.t_first_token = now
        r.output.append(token)
        if token == r.eos_id or len(r.output) >= r.max_new_tokens:
            eng._finish(r, now)
            self.mgr.retire(slot)  # pages freed; the prefix stays indexed
            return
        eng.state.bind(r, slot, token)
        eng.account_admit(r)

    def _admit_suffix(self, free: List[int]) -> bool:
        # peek, don't pop: if the pool can't hold the head's pages the
        # request must stay queued (admit() rolls its allocation back)
        eng = self.eng
        r = eng.queue[0]
        slot = free[0]
        prompt = [int(t) for t in r.prompt]
        live = [s for s in eng.state.slots if s is not None]
        if not eng.policy.admits_head(r, live):
            return False
        # full shared prefix pages are reused; everything else — the
        # prompt tail AND the decode growth — must fit the headroom
        cached_probe = self.mgr.match_tokens(prompt)
        need = (self.worst_case_pages(r)
                - cached_probe // eng.ecfg.block_size)
        if need > self.headroom():
            return False
        try:
            cached = self.mgr.admit(slot, prompt)
        except PoolExhausted:
            return False
        eng.queue.pop(0)
        r.t_admit = time.time()
        free.pop(0)
        suffix = r.prompt[cached:]
        w = eng._bucket(len(suffix))
        toks = np.zeros((1, w), np.int32)
        toks[0, :len(suffix)] = suffix
        # gather only a pow2 bucket of prefix pages, not the whole
        # table — suffix attention width scales with the prefix, and
        # compile count stays one per (suffix, prefix) bucket pair
        bs = eng.ecfg.block_size
        pb = min(next_pow2(-(-cached // bs)), len(self.mgr.tables[slot]))
        logits, src = eng._prefill_suffix(
            eng.params, jnp.asarray(toks), eng._cache,
            jnp.asarray(self.mgr.tables[slot][:pb])[None],
            np.int32(cached),
        )
        # reused prefix costs nothing — only the suffix runs the model
        eng.account_prefill(len(suffix), w)
        eng.cached_prefix_tokens += cached
        eng._cache = eng._insert_paged(
            eng._cache, src, 0, slot, jnp.asarray(self.mgr.tables[slot]),
            np.int32(cached), len(prompt))
        self.mgr.register(slot, prompt)
        first = eng.fetch("serve.prefill.wait",
                          eng._sample(logits[:, len(suffix) - 1]))[0]
        self._place(r, slot, int(first[0]), time.time())
        if eng._spec_k and eng.state.slots[slot] is r:
            # the draft pool is contiguous and reuses no prefixes: it
            # prefills the FULL prompt even when the main model only
            # ran the suffix
            wf = eng._bucket(len(prompt))
            dt = np.zeros((1, wf), np.int32)
            dt[0, :len(prompt)] = prompt
            db = {"tokens": jnp.asarray(dt),
                  "lengths": jnp.asarray(np.array([len(prompt)], np.int32))}
            _, dc = eng._draft_prefill(eng.draft_params, db)
            eng._draft_cache = eng._draft_insert(
                eng._draft_cache, dc, 0, slot, len(prompt))
        return True

    def _admit_cold(self, free: List[int]) -> bool:
        # same take policy as the contiguous admitter: the queue head
        # plus FIFO-later requests sharing its length bucket — but only
        # other index misses (a hit admits alone through the suffix path)
        eng = self.eng
        limit = min(len(free), eng.ecfg.prefill_batch)
        live = [s for s in eng.state.slots if s is not None]
        take = eng.policy.take(
            eng.queue, limit, eng._bucket_of,
            eligible=lambda r: not self.mgr.match_tokens(
                [int(t) for t in r.prompt]),
            live=live)
        if not take:
            return False
        w = eng._bucket_of(take[0])

        # claim pages first so nothing registers mid-batch: identical
        # prompts inside one cold batch each prefill privately (the
        # second one hits the index only on a LATER admission). A
        # PoolExhausted admit rolls itself back and stops the batch
        # there — only successfully-placed requests leave the queue,
        # the rest wait for retirement to free pages.
        placed = []
        headroom = self.headroom()
        for r in take:
            slot = free[0]
            prompt = [int(t) for t in r.prompt]
            # gate on the full worst case (prompt + decode growth), not
            # just the prompt pages admit() allocates now — earlier
            # batch members' growth stays owed against the same free
            # list until they retire
            need = self.worst_case_pages(r)
            if need > headroom:
                break
            try:
                self.mgr.admit(slot, prompt)
            except PoolExhausted:
                break
            headroom -= need         # prompt pages taken + growth owed
            free.pop(0)
            placed.append((r, slot, prompt))
        if not placed:
            return False
        now = time.time()
        for r, _, _ in placed:
            eng.queue.remove(r)
            r.t_admit = now

        m = len(placed)
        mp = min(next_pow2(m), eng.ecfg.prefill_batch)
        toks, lens = right_pad([r for r, _, _ in placed], mp, w)
        b = {"tokens": jnp.asarray(toks), "lengths": jnp.asarray(lens)}
        logits, pcache = eng._prefill_bucket(eng.params, b)
        dcache = None
        if eng._spec_k:
            _, dcache = eng._draft_prefill(eng.draft_params, b)
        eng.account_prefill(sum(len(r.prompt) for r, _, _ in placed),
                            mp * w)
        idx = jnp.asarray([len(r.prompt) - 1 for r, _, _ in placed]
                          + [0] * (mp - m))
        first = eng.fetch("serve.prefill.wait",
                          eng._sample(logits[jnp.arange(mp), idx]))[0]
        now = time.time()
        for i, (r, slot, prompt) in enumerate(placed):
            eng._cache = eng._insert_paged(
                eng._cache, pcache["kv"], i, slot,
                jnp.asarray(self.mgr.tables[slot]), np.int32(0),
                len(prompt))
            self.mgr.register(slot, prompt)
            self._place(r, slot, int(first[i]), now)
            if dcache is not None and eng.state.slots[slot] is r:
                eng._draft_cache = eng._draft_insert(
                    eng._draft_cache, dcache, i, slot, len(prompt))
        return True


def right_pad(reqs: List[Request], rows: int,
              width: int) -> Tuple[np.ndarray, np.ndarray]:
    """RIGHT-padded token block + true-length vector for a prefill
    batch: the causal mask keeps pad columns out of attention, the
    lengths keep them out of recurrent state (models/decode.prefill).
    Rows beyond ``len(reqs)`` are batch-bucket padding (length 0)."""
    toks = np.zeros((rows, width), np.int32)
    lens = np.zeros((rows,), np.int32)
    for i, r in enumerate(reqs):
        toks[i, : len(r.prompt)] = r.prompt
        lens[i] = len(r.prompt)
    return toks, lens
