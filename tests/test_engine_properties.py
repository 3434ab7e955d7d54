"""Property coverage of the serving-engine lifecycle.

Hypothesis drives randomized admission / retire / readmit traces
through real engines (tiny dense model, CPU) and checks the invariants
the example-based suites pin only at hand-picked points:

- every submitted uid comes back done exactly once, with exactly its
  requested decode budget — no request lost, duplicated, or truncated;
- outputs are never cross-wired between requests: each uid's tokens
  equal the single-slot sequential decode of ITS prompt, whatever slot
  (re)assignment the trace produced;
- admission bookkeeping stays sane: slots in range, one admission per
  uid;
- the paged engine's page pool stays conserved across waves of
  admission and retirement — every page free (ref 0) or live (ref > 0)
  exactly once, and with prefix reuse off a drained engine holds zero
  pages (with reuse on, only the radix index's references remain);
- speculative decoding under randomized accept/reject traces (a random
  1-layer draft makes acceptance data-dependent) keeps all of the
  above: outputs stay the exact sequential tokens (so every per-slot
  length rollback landed on the accepted count), no request is lost,
  duplicated or cross-wired, and the paged pool stays conserved with
  every rejected position's pages released.

Engines and the sequential-reference cache are module-level: jit
caches live on engine closures, so every hypothesis example after the
first replays compiled code (see docs/testing.md). Without hypothesis
installed these tests skip via tests/_hypothesis_compat.
"""
import dataclasses

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.models import init_model
from repro.serve import EngineConfig, ServeEngine
from tests._hypothesis_compat import HealthCheck, given, settings, st

jax.config.update("jax_platform_name", "cpu")

MAX_LEN = 32
SLOTS = 2

_state = {}


def _models():
    if not _state:
        cfg = get_config("tinyllama-1.1b").reduced()
        params = init_model(jax.random.PRNGKey(0), cfg)
        _state["cfg"], _state["params"] = cfg, params
        _state["eng"] = ServeEngine(
            params, cfg, EngineConfig(max_batch=SLOTS, max_len=MAX_LEN))
        _state["ref"] = ServeEngine(
            params, cfg, EngineConfig(max_batch=1, max_len=MAX_LEN))
        _state["paged"] = {
            reuse: ServeEngine(
                params, cfg,
                EngineConfig(max_batch=SLOTS, max_len=MAX_LEN, paged=True,
                             block_size=8, prefix_reuse=reuse))
            for reuse in (False, True)
        }
        # spec engines: a RANDOM 1-layer draft — proposals rarely match
        # the main argmax, so hypothesis traces exercise full rejection,
        # partial acceptance and the occasional full acceptance
        dcfg = dataclasses.replace(cfg, n_layers=1)
        dparams = init_model(jax.random.PRNGKey(1), dcfg)
        _state["spec"] = ServeEngine(
            params, cfg,
            EngineConfig(max_batch=SLOTS, max_len=MAX_LEN, spec_k=2,
                         draft_config=dcfg),
            draft_params=dparams)
        _state["spec_paged"] = ServeEngine(
            params, cfg,
            EngineConfig(max_batch=SLOTS, max_len=MAX_LEN, paged=True,
                         block_size=8, spec_k=2, draft_config=dcfg),
            draft_params=dparams)
        _state["ref_cache"] = {}
    return _state


def _sequential(prompt, mnew):
    """Single-slot reference outputs, memoized across examples."""
    s = _models()
    key = (tuple(int(t) for t in prompt), mnew)
    if key not in s["ref_cache"]:
        uid = s["ref"].submit(np.asarray(prompt, np.int32),
                              max_new_tokens=mnew)
        # run() returns the cumulative completed list — select by uid
        s["ref_cache"][key] = next(
            r.output for r in s["ref"].run() if r.uid == uid)
    return s["ref_cache"][key]


# a trace: 1..6 requests of (prompt-seed, prompt-len, decode-budget).
# Budgets stay under MAX_LEN - longest prompt so nothing truncates and
# the budget check below is exact.
TRACES = st.lists(
    st.tuples(st.integers(0, 3), st.integers(2, 10), st.integers(1, 5)),
    min_size=1, max_size=6,
)


def _prompts(trace, vocab):
    out = []
    for seed, plen, mnew in trace:
        rng = np.random.RandomState(seed)
        out.append((rng.randint(0, vocab, size=plen), mnew))
    return out


@settings(max_examples=12, deadline=None)
@given(trace=TRACES)
def test_lifecycle_conserves_requests_and_slots(trace):
    s = _models()
    eng, cfg = s["eng"], s["cfg"]
    reqs = _prompts(trace, cfg.vocab_size)
    uids = [eng.submit(p, max_new_tokens=mn) for p, mn in reqs]
    admitted0 = eng.admitted
    # run() returns the engine's cumulative completed list; a reused
    # engine (jit caches warm across examples) includes prior waves
    results = eng.run()
    returned = [r.uid for r in results]
    assert all(returned.count(uid) == 1 for uid in uids), \
        "requests lost or duplicated"
    done = {r.uid: r for r in results if r.uid in set(uids)}
    bound = 0
    for uid, (_, mnew) in zip(uids, reqs):
        r = done[uid]
        assert r.done
        assert len(r.output) == mnew, \
            f"uid {uid}: budget {mnew}, got {len(r.output)} tokens"
        if mnew == 1:
            # the prefill token exhausts the budget: retired on the
            # spot, never occupies a slot, never recorded as admitted
            assert r.admit_step == -1 and r.slot == -1
        else:
            assert r.admit_step >= 0 and 0 <= r.slot < SLOTS
            bound += 1
    # each slot-bound request was admitted exactly once
    assert eng.admitted - admitted0 == bound


@settings(max_examples=8, deadline=None)
@given(trace=TRACES)
def test_outputs_never_cross_wire(trace):
    s = _models()
    eng, cfg = s["eng"], s["cfg"]
    reqs = _prompts(trace, cfg.vocab_size)
    uids = [eng.submit(p, max_new_tokens=mn) for p, mn in reqs]
    done = {r.uid: r.output for r in eng.run() if r.uid in set(uids)}
    for uid, (p, mnew) in zip(uids, reqs):
        assert done[uid] == _sequential(p, mnew), \
            f"uid {uid} decoded another request's tokens"


@settings(max_examples=8, deadline=None)
@given(trace=TRACES, reuse=st.booleans())
def test_paged_pool_conserved_across_waves(trace, reuse):
    s = _models()
    eng, cfg = s["paged"][reuse], s["cfg"]
    reqs = _prompts(trace, cfg.vocab_size)
    for wave in range(2):                      # admission + readmission
        uids = [eng.submit(p, max_new_tokens=mn) for p, mn in reqs]
        done = {r.uid: r.output for r in eng.run() if r.uid in set(uids)}
        assert sorted(done) == sorted(uids)
        mgr = eng._mgr
        mgr.check_invariants()
        mgr.pool.check_invariants()
        if not reuse:
            assert mgr.pool.used_blocks == 0, \
                f"wave {wave}: drained engine leaked pages"
        else:
            # only the radix index may hold pages, one ref each from
            # the index itself (slots are all retired)
            for node in mgr.index._by_id.values():
                assert mgr.pool.refcount(node.block) == 1
            assert mgr.pool.used_blocks == len(mgr.index)
    for uid, (p, mnew) in zip(uids, reqs):
        assert done[uid] == _sequential(p, mnew), \
            "paged readmission cross-wired outputs"


@settings(max_examples=8, deadline=None)
@given(trace=TRACES)
def test_spec_rollback_matches_sequential(trace):
    """Speculative accept/reject/rollback is invisible in the outputs:
    whatever prefix of each round's proposals was accepted, every uid
    gets exactly its budget of exactly the sequential tokens — which
    can only happen if each rollback's per-slot length edit equals the
    accepted-token count, every round."""
    s = _models()
    eng, cfg = s["spec"], s["cfg"]
    reqs = _prompts(trace, cfg.vocab_size)
    uids = [eng.submit(p, max_new_tokens=mn) for p, mn in reqs]
    results = eng.run()
    returned = [r.uid for r in results]
    assert all(returned.count(uid) == 1 for uid in uids), \
        "spec decode lost or duplicated requests"
    done = {r.uid: r for r in results if r.uid in set(uids)}
    for uid, (p, mnew) in zip(uids, reqs):
        assert done[uid].done
        assert len(done[uid].output) == mnew, \
            f"uid {uid}: budget {mnew}, got {len(done[uid].output)}"
        assert done[uid].output == _sequential(p, mnew), \
            f"uid {uid}: spec rollback corrupted the decode state"
    st_ = eng.stats()
    assert 0 <= st_["spec_accepted"] <= st_["spec_proposed"]
    assert 0.0 <= st_["spec_accept_rate"] <= 1.0


@settings(max_examples=8, deadline=None)
@given(trace=TRACES)
def test_spec_paged_rollback_conserves_pool(trace):
    """Every rejected proposal's pre-reserved page slots are released
    by the truncate rollback: across admission waves the pool stays
    balanced (block tables consistent, refcounts exact), a drained
    engine holds only the radix index's pages, and outputs are still
    the sequential tokens."""
    s = _models()
    eng, cfg = s["spec_paged"], s["cfg"]
    reqs = _prompts(trace, cfg.vocab_size)
    for wave in range(2):                      # admission + readmission
        uids = [eng.submit(p, max_new_tokens=mn) for p, mn in reqs]
        done = {r.uid: r.output for r in eng.run() if r.uid in set(uids)}
        assert sorted(done) == sorted(uids)
        mgr = eng._mgr
        mgr.check_invariants()
        mgr.pool.check_invariants()
        assert np.all(np.asarray(mgr.lengths) == 0), \
            f"wave {wave}: a drained slot kept a nonzero length"
        # prefix reuse (the default) may keep index pages warm; each
        # holds exactly the index's own reference
        for node in mgr.index._by_id.values():
            assert mgr.pool.refcount(node.block) == 1
        assert mgr.pool.used_blocks == len(mgr.index), \
            f"wave {wave}: spec rollback leaked pages"
    for uid, (p, mnew) in zip(uids, reqs):
        assert done[uid] == _sequential(p, mnew), \
            "paged spec decode cross-wired outputs"
