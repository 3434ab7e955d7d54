"""Executor layer: compiled step functions + one ``run_round()`` per
serving strategy.

The scheduler (``serve/scheduler.py``) decides WHAT is admitted; the
slot state (``serve/state.py``) owns WHERE it lives; this module owns
HOW a decode round actually executes. Three continuous-mode executors
share one interface — ``run_round()`` advances every live slot at least
one token, drains device results, stamps boundary timestamps and
retires finished slots:

:class:`DeviceHorizonExecutor`
    greedy serving's default: one jit call takes up to
    ``decode_horizon`` on-device steps (``models.decode
    .decode_multi_step[_paged]``) with on-device argmax and per-slot
    EOS/budget flags — the host syncs once per horizon.

:class:`HostLoopExecutor`
    the legacy per-token round-trip (temperature sampling, or
    ``device_loop=False``): one decode step, host-side sampling,
    EOS/budget checks and retirement.

:class:`SpecRoundExecutor`
    speculative decoding: the draft proposes ``spec_k`` tokens, the
    main model verifies them in one masked forward, the longest
    argmax-matching prefix plus a bonus token is emitted, and the
    rollback is a per-slot length stamp through the slot-state
    interface (paged: plus page truncation).

:class:`StaticBatchExecutor`
    the static oracle mode: a fixed batch prefills together and
    decodes in lockstep until every member finishes.

Every continuous-mode round traces the same three host spans, on the
profiler's clock beside the device's ops (about half a microsecond
each when no profiler runs): ``serve.decode.dispatch`` (host prep,
transfers and the jitted calls), ``serve.decode.wait`` (blocked
reading the results, ``fetch``) and ``serve.decode.drain`` (token
hand-out, retirement, accounting).

Executors never touch the queue or the admission policy, which is what
makes prefill/decode disaggregation a scheduler-level change: two
engines running different executors can pass paged blocks without
either one learning new step logic.

:func:`build_compiled` is the single factory for every jitted closure
(prefill, insert, decode, horizon loop, paged and speculative
variants) — fresh closures per engine so compile-cache accounting
(``_cache_size``) is per-instance, and donation/static-argnum choices
live in exactly one place.
"""
from __future__ import annotations

import time
from types import SimpleNamespace
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models import decode as D
from repro.serve.scheduler import next_pow2, right_pad


def build_compiled(eng) -> SimpleNamespace:
    """Build every jitted closure the engine's executors use.

    The cache-donating jits update the slot pool in place (the same
    trick as launch/dryrun.py's decode cells) — donation survives
    sharding because in/out slot-pool leaves keep the same
    NamedSharding. Horizon/propose step counts are static argnums: one
    compile per value.
    """
    cfg, ecfg = eng.cfg, eng.ecfg
    fns = SimpleNamespace()

    if ecfg.paged:
        def _decode_paged(p, tok, cache, bt):
            with eng._ctx():
                return D.decode_step_paged(
                    p, cfg, tok, cache, bt,
                    attn_backend=ecfg.paged_attn_backend,
                )

        def _insert_paged(cache, src_kv, row, slot, slot_row, start,
                          total):
            with eng._ctx():
                return D.paged_cache_insert(
                    cache, src_kv, row, slot, slot_row, start, total
                )

        def _prefill_suffix(p, toks, cache, slot_row, plen):
            with eng._ctx():
                return D.prefill_paged_suffix(
                    p, cfg, toks, cache, slot_row, plen
                )

        def _copy_page(cache, src, dst):
            # copy-on-write: duplicate one page across all layers
            kv = cache["kv"]
            return {**cache, "kv": {
                "k": kv["k"].at[:, dst].set(kv["k"][:, src]),
                "v": kv["v"].at[:, dst].set(kv["v"][:, src]),
            }}

        def _decode_multi_paged(p, cache, bt, last, live, eos, budget,
                                horizon):
            with eng._ctx():
                return D.decode_multi_step_paged(
                    p, cfg, cache, bt, last, live, eos, budget,
                    horizon, attn_backend=ecfg.paged_attn_backend,
                )

        fns.decode_paged = jax.jit(_decode_paged, donate_argnums=(2,))
        fns.insert_paged = jax.jit(_insert_paged, donate_argnums=(0,))
        fns.prefill_suffix = jax.jit(_prefill_suffix)
        fns.copy_page = jax.jit(_copy_page, donate_argnums=(0,))
        # horizon is static: one compile per horizon value
        fns.decode_multi_paged = jax.jit(
            _decode_multi_paged, donate_argnums=(1,), static_argnums=(7,))

    # static path: prefill allocates the full decode-capacity cache
    def _prefill_full(p, b):
        with eng._ctx():
            return D.prefill(p, cfg, b, ecfg.max_len, dtype=jnp.float32)

    # continuous path: prefill only covers the prompt bucket — the
    # rows are scattered into the long-lived slot cache afterwards.
    # Per-row true lengths ride along so recurrent-state families
    # return exact final states under right-padding (attention
    # families need only the causal mask and ignore them). The batch
    # dict may carry side inputs (enc_embeds/patch_embeds rows
    # gathered per request): one compile per (bucket shapes, side
    # keys) combination, both fixed per engine.
    def _prefill_bucket(p, b):
        with eng._ctx():
            return D.prefill(
                p, cfg, b, b["tokens"].shape[1], dtype=jnp.float32
            )

    def _decode(p, tok, cache):
        with eng._ctx():
            return D.decode_step(p, cfg, tok, cache)

    def _insert(dst, src, row, slot, ln):
        with eng._ctx():
            return D.cache_insert(dst, src, row, slot, ln)

    # the on-device horizon loop: up to `horizon` greedy steps per
    # call, cache donated across the whole loop
    def _decode_multi(p, cache, last, live, eos, budget, horizon):
        with eng._ctx():
            return D.decode_multi_step(
                p, cfg, cache, last, live, eos, budget, horizon
            )

    fns.prefill_full = jax.jit(_prefill_full)
    fns.prefill_bucket = jax.jit(_prefill_bucket)
    fns.decode = jax.jit(_decode, donate_argnums=(2,))
    fns.insert = jax.jit(_insert, donate_argnums=(0,))
    # horizon is static: one compile per horizon value
    fns.decode_multi = jax.jit(
        _decode_multi, donate_argnums=(1,), static_argnums=(6,))

    # speculative decoding: draft prefill/propose + main-model verify,
    # plus the tiny length-edit that IS the rollback
    if eng._spec_k:
        dcfg = ecfg.draft_config

        def _draft_prefill(p, b):
            with eng._ctx():
                return D.prefill(p, dcfg, b, b["tokens"].shape[1],
                                 dtype=jnp.float32)

        def _draft_insert(dst, src, row, slot, ln):
            with eng._ctx():
                return D.cache_insert(dst, src, row, slot, ln)

        def _draft_propose(p, cache, last, live, k_steps):
            with eng._ctx():
                return D.decode_propose(p, dcfg, cache, last, live,
                                        k_steps)

        # verify tokens are [pending, d1 .. d_{k-1}]: the last draft
        # proposal exists only to keep the draft cache one position
        # ahead (decode_propose), so props[:, :-1] drops it
        def _verify(p, cache, last, props):
            with eng._ctx():
                toks = jnp.concatenate(
                    [last[:, None], props[:, :-1]], axis=1)
                return D.decode_verify(p, cfg, toks, cache)

        def _set_len(cache, lens):
            return {**cache, "length": lens}

        fns.draft_prefill = jax.jit(_draft_prefill)
        fns.draft_insert = jax.jit(_draft_insert, donate_argnums=(0,))
        fns.draft_propose = jax.jit(
            _draft_propose, donate_argnums=(1,), static_argnums=(4,))
        fns.verify = jax.jit(_verify, donate_argnums=(1,))
        fns.set_len = jax.jit(_set_len, donate_argnums=(0,))
        if ecfg.paged:
            def _verify_paged(p, cache, bt, live, last, props):
                with eng._ctx():
                    toks = jnp.concatenate(
                        [last[:, None], props[:, :-1]], axis=1)
                    logits, kv_new = D.prefill_paged_suffix(
                        p, cfg, toks, cache, bt, cache["length"],
                        per_token_ffn=True)
                    kv = D.paged_verify_commit(
                        cache["kv"], kv_new, cache["length"], bt, live)
                    return logits, {**cache, "kv": kv}

            fns.verify_paged = jax.jit(_verify_paged, donate_argnums=(1,))
    return fns


class _Executor:
    """Shared executor plumbing: engine/slot-state handles and the
    boundary retirement that every strategy performs the same way."""

    def __init__(self, eng):
        self.eng = eng

    @property
    def state(self):
        return self.eng.state

    def retire(self, slot: int, request, now: float) -> None:
        self.eng._finish(request, now)
        self.state.retire(slot)     # paged: releases page refcounts

    def run_round(self) -> None:
        raise NotImplementedError


class DeviceHorizonExecutor(_Executor):
    """One host round-trip: up to ``decode_horizon`` decode steps on
    device (``models.decode.decode_multi_step[_paged]``), then drain
    the returned token buffer, stamp ONE boundary timestamp, and
    retire finished slots. The loop exits early on device once every
    live slot is done, so short tails don't burn horizon steps."""

    def run_round(self) -> None:
        eng = self.eng
        t0 = time.time()
        with TraceAnnotation("serve.decode.dispatch"):
            out = self._dispatch()
        buf, emitted, done, last, steps = eng.fetch(
            "serve.decode.wait", *out)
        with TraceAnnotation("serve.decode.drain"):
            self._drain(buf, emitted, done, last, int(steps), t0)

    def _dispatch(self):
        """Host prep and the horizon call; returns its device results
        ``(buf, emitted, done, last, steps)`` unread."""
        eng = self.eng
        slots = self.state.slots
        n = eng.ecfg.max_batch
        h = eng.ecfg.decode_horizon
        paged = eng.ecfg.paged
        live = self.state.live_flags()
        budget = np.zeros((n,), np.int32)
        eos = np.full((n,), -1, np.int32)
        for i, r in enumerate(slots):
            if r is None:
                continue
            budget[i] = r.max_new_tokens - len(r.output)
            eos[i] = r.eos_id
        if paged:
            mgr = self.state.mgr
            # a CoW valve can only resolve on the host; if one would
            # trigger past the first position (reachable via fork()
            # only — full-page publishing keeps shared pages full),
            # fall back to a single-step round
            if any(mgr.mid_horizon_cow(i, min(h, int(budget[i])))
                   for i, s in enumerate(slots) if s is not None):
                h = 1

            # never pre-reserve past the pool: shrink this round's
            # horizon until the worst-case fresh-page demand fits the
            # free list (halving keeps the static-horizon compile set
            # at O(log H) entries under sustained pressure)
            bs = eng.ecfg.block_size

            def _new_pages(hh: int) -> int:
                need = 0
                for i, s in enumerate(slots):
                    if s is None:
                        continue
                    end = int(mgr.lengths[i]) + min(hh, int(budget[i]))
                    need += max(0, -(-end // bs)
                                - len(mgr.slot_blocks(i)))
                return need

            while h > 1 and _new_pages(h) > mgr.pool.free_blocks:
                h //= 2
            # pre-reserve the whole horizon: grow each live slot's
            # table min(h, budget) tokens ahead (fresh pages at block
            # boundaries, eager copy-on-write when shared) so the
            # device loop never needs the host mid-horizon
            for i, s in enumerate(slots):
                if s is None:
                    continue
                for _ in range(min(h, int(budget[i]))):
                    self.state.prepare_append(i)
            buf, emitted, done, last, cache, steps = eng._decode_multi_paged(
                eng.params, eng._cache, jnp.asarray(mgr.tables),
                jnp.asarray(self.state.last_tok), jnp.asarray(live),
                jnp.asarray(eos), jnp.asarray(budget), h)
        else:
            buf, emitted, done, last, cache, steps = eng._decode_multi(
                eng.params, eng._cache, jnp.asarray(self.state.last_tok),
                jnp.asarray(live), jnp.asarray(eos), jnp.asarray(budget), h)
        eng._cache = cache
        return buf, emitted, done, last, steps

    def _drain(self, buf, emitted, done, last, steps: int,
               t0: float) -> None:
        """Hand out each slot's tokens, retire finished slots and
        account the round."""
        eng = self.eng
        slots = self.state.slots
        now = time.time()
        eng.host_syncs += 1
        eng.decode_wall_s += now - t0
        eng.decode_steps += steps
        # slot i was live at step s of the horizon iff it emitted more
        # than s tokens, so the live slots summed over the steps are the
        # tokens emitted
        eng.occupied_slot_steps += int(np.minimum(emitted, steps).sum())
        new_tokens = 0
        for i, r in enumerate(slots):
            if r is None:
                continue
            r.output.extend(int(t) for t in buf[i, :emitted[i]])
            # energy: only tokens a live slot actually emitted (retired
            # rows keep stepping under the no-op mask — burned compute on
            # the TPU, but no modeled crossbar work is attributed)
            new_tokens += int(emitted[i])
            self.state.last_tok[i] = int(last[i])
            if done[i]:
                self.retire(i, r, now)       # freed at THIS boundary
        eng.account_decode(new_tokens)


class HostLoopExecutor(_Executor):
    """Legacy per-token round-trip (temperature sampling, or
    ``device_loop=False``): one decode step, host-side sampling,
    EOS/budget checks and retirement."""

    def run_round(self) -> None:
        eng = self.eng
        slots = self.state.slots
        paged = eng.ecfg.paged
        t0 = time.time()
        with TraceAnnotation("serve.decode.dispatch"):
            if paged:
                # grow each live slot's table by one token (a fresh
                # page at block boundaries, copy-on-write if shared)
                for i, s in enumerate(slots):
                    if s is None:
                        continue
                    self.state.prepare_append(i)
                logits, cache = eng._decode_paged(
                    eng.params, jnp.asarray(self.state.last_tok)[:, None],
                    eng._cache, jnp.asarray(self.state.mgr.tables))
            else:
                logits, cache = eng._decode(
                    eng.params, jnp.asarray(self.state.last_tok)[:, None],
                    eng._cache)
            eng._cache = cache
            nxt = eng._sample(logits[:, 0])
        nxt, = eng.fetch("serve.decode.wait", nxt)
        with TraceAnnotation("serve.decode.drain"):
            eng.decode_steps += 1
            eng.host_syncs += 1
            now = time.time()
            eng.decode_wall_s += now - t0
            eng.occupied_slot_steps += sum(s is not None for s in slots)
            new_tokens = 0
            for i, r in enumerate(slots):
                if r is None:
                    continue
                t = int(nxt[i])
                r.output.append(t)
                new_tokens += 1
                self.state.last_tok[i] = t
                if t == r.eos_id or len(r.output) >= r.max_new_tokens:
                    self.retire(i, r, now)       # freed THIS step
            eng.account_decode(new_tokens)


class SpecRoundExecutor(_Executor):
    """One speculative round: draft proposes, the main model verifies,
    the longest argmax-matching proposal prefix plus one bonus token is
    emitted, and both caches roll back to the accepted length.

    The draft runs k+1 masked steps so its cache holds every position a
    full acceptance needs (``decode_propose``); the verify commits k+1
    K/V positions but leaves lengths untouched, so the rollback is the
    single set-lengths stamp at the end (paged: plus
    ``PagedKVManager.truncate`` page releases). Paged rounds pre-reserve
    all k+1 positions per live slot BEFORE the verify; if the fresh-page
    demand exceeds the free list the round runs at width 1 — exactly a
    vanilla decode step (the admission headroom invariant guarantees one
    position always fits) — which keeps the draft cache in lockstep
    under pool pressure. Every emitted token is a main-model argmax at
    the same cache state vanilla decode would have, so outputs are
    token-identical to vanilla greedy serving.
    """

    def run_round(self) -> None:
        eng = self.eng
        slots = self.state.slots
        n = eng.ecfg.max_batch
        k = eng._spec_k
        paged = eng.ecfg.paged
        live = self.state.live_flags()
        n_live = int(live.sum())
        t0 = time.time()
        with TraceAnnotation("serve.decode.dispatch"):
            k_round = k
            base_len = None
            if paged:
                mgr = self.state.mgr
                bs = eng.ecfg.block_size
                base_len = [int(mgr.lengths[i]) for i in range(n)]
                need = 0
                for i, s in enumerate(slots):
                    if s is None:
                        continue
                    end = base_len[i] + k + 1
                    need += max(0, -(-end // bs)
                                - len(mgr.slot_blocks(i)))
                if need > mgr.pool.free_blocks:
                    k_round = 0
                for i, s in enumerate(slots):
                    if s is None:
                        continue
                    for _ in range(k_round + 1):
                        self.state.prepare_append(i)
            live_dev = jnp.asarray(live)
            last_dev = jnp.asarray(self.state.last_tok)
            props, eng._draft_cache = eng._draft_propose(
                eng.draft_params, eng._draft_cache, last_dev, live_dev,
                k_round + 1)
            if paged:
                logits, eng._cache = eng._verify_paged(
                    eng.params, eng._cache,
                    jnp.asarray(self.state.mgr.tables),
                    live_dev, last_dev, props)
            else:
                logits, eng._cache = eng._verify(eng.params, eng._cache,
                                                 last_dev, props)
            m = jnp.argmax(logits, axis=-1)             # (n, k_round+1)
        # one host sync per round: the proposals and the verify argmaxes
        # land together (async dispatch keeps the draft/verify pipelined)
        m, props = eng.fetch("serve.decode.wait", m, props)
        with TraceAnnotation("serve.decode.drain"):
            now = time.time()
            eng.host_syncs += 1
            eng.decode_wall_s += now - t0
            eng.decode_steps += 1
            eng.spec_rounds += 1
            eng.occupied_slot_steps += n_live
            new_tokens = 0
            for i in range(n):
                r = slots[i]
                if r is None:
                    continue
                a = 0
                while a < k_round and props[i, a] == m[i, a]:
                    a += 1
                eng.spec_proposed += k_round
                eng.spec_accepted += a
                for t in m[i, :a + 1]:
                    t = int(t)
                    r.output.append(t)
                    new_tokens += 1
                    self.state.last_tok[i] = t
                    if t == r.eos_id or len(r.output) >= r.max_new_tokens:
                        self.retire(i, r, now)
                        break
                if paged and slots[i] is not None:
                    self.state.truncate(i, base_len[i] + a + 1)
            eng.account_decode(new_tokens)
            # the rollback: both caches' lengths snap to the accepted
            # position (free slots to 0); junk K/V above the watermark is
            # never attended and the next round overwrites it in place
            lens = np.zeros((n,), np.int32)
            for i, r in enumerate(slots):
                if r is not None:
                    lens[i] = (eng._patch_len + len(r.prompt)
                               + len(r.output) - 1)
            self.state.set_lengths(lens)
            eng._draft_cache = eng._set_len(eng._draft_cache,
                                            jnp.asarray(lens))


class StaticBatchExecutor(_Executor):
    """The static oracle mode: one batch prefills together (batch dim
    pow2-bucketed so compiles stay enumerable) and decodes in lockstep
    until every member finishes."""

    def run_batch(self, reqs: List) -> None:
        eng = self.eng
        nreq = len(reqs)
        # pow2-bucket the batch dim: _prefill_full compiles once per
        # (batch bucket, padded length) pair instead of once per exact
        # admitted batch size (batch rows are independent everywhere in
        # the model, so padding rows are inert)
        bp = min(next_pow2(nreq), eng.ecfg.max_batch)
        # RIGHT-pad every family to a pow2 length bucket + per-row true
        # lengths: the causal mask keeps pad columns out of attention,
        # the lengths make recurrent prefill exact, and decode advances
        # each row at its own position (vector cache lengths) — so
        # mixed-length static batches decode bit-exactly with the
        # sequential and continuous paths. (The historical left-pad
        # variant was NOT exact for mixed lengths: pad positions sat
        # inside the causal window and leaked into attention.)
        w = eng._bucket(max(len(r.prompt) for r in reqs))
        toks, lens = right_pad(reqs, bp, w)
        b = eng._prefill_batch(reqs, bp, toks, lens)
        logits, cache = eng._prefill_full(eng.params, b)
        eng.account_prefill(sum(len(r.prompt) for r in reqs), bp * w)
        # each row's first token comes from its true last prompt position
        nxt = eng._sample(
            logits[jnp.arange(bp), jnp.maximum(b["lengths"] - 1, 0)])
        first = np.asarray(nxt)
        t_first = time.time()
        for i, r in enumerate(reqs):
            t = int(first[i])
            r.output.append(t)
            r.t_first_token = t_first
            if t == r.eos_id or len(r.output) >= r.max_new_tokens:
                r.done, r.t_done = True, t_first
        # submit() bounds every request's own writes (side/spec overhead
        # included), so live rows never clamp; a finished row that keeps
        # stepping only touches its own junk tail — batch rows are
        # independent and the cache dies with the batch
        max_new = max(r.max_new_tokens for r in reqs)
        for _ in range(max_new - 1):
            # occupancy relative to the slot pool a continuous scheduler
            # would have: retired-but-held and unfilled slots count as idle
            n_alive = sum(
                not r.done and len(r.output) < r.max_new_tokens for r in reqs
            )
            if n_alive == 0:
                break
            eng.occupied_slot_steps += n_alive
            logits, cache = eng._decode(
                eng.params, jnp.asarray(nxt)[:, None], cache
            )
            eng.decode_steps += 1
            nxt = eng._sample(logits[:, 0])
            arr = np.asarray(nxt)
            now = time.time()
            new_tokens = 0
            for i, r in enumerate(reqs):
                if r.done or len(r.output) >= r.max_new_tokens:
                    continue
                t = int(arr[i])
                r.output.append(t)
                new_tokens += 1
                if t == r.eos_id or len(r.output) >= r.max_new_tokens:
                    r.done, r.t_done = True, now
            eng.account_decode(new_tokens)
        now = time.time()
        for r in reqs:
            r.done = True
            r.t_done = r.t_done or now
            eng.finished.append(r)
