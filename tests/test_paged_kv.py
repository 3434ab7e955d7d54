"""Paged KV cache: pool/refcount/CoW invariants, radix index, engine
parity (paged vs contiguous, prefix reuse on vs off), kernel conformance,
jit stability."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import registry
from repro.kernels.paged_attention import (
    paged_attention_kernel,
    paged_attention_ref,
)
from repro.launch.mesh import make_mesh
from repro.models import init_model
from repro.serve import EngineConfig, ServeEngine
from repro.serve.paged_kv import (
    BlockPool,
    PagedKVManager,
    PoolExhausted,
    RadixPrefixIndex,
    TRASH_BLOCK,
)
from tests._hypothesis_compat import given, settings, st

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# host-side pool / index
# ---------------------------------------------------------------------------

class TestBlockPool:
    def test_alloc_release_cycle(self):
        pool = BlockPool(4)
        a, b, c = pool.alloc(), pool.alloc(), pool.alloc()
        assert sorted([a, b, c]) == [1, 2, 3]       # page 0 reserved
        assert pool.free_blocks == 0
        with pytest.raises(PoolExhausted):
            pool.alloc()
        assert pool.release(b)
        assert pool.free_blocks == 1
        assert pool.alloc() == b                    # recycled
        pool.check_invariants()

    def test_refcounts_share_and_free(self):
        pool = BlockPool(3)
        a = pool.alloc()
        pool.retain(a)
        assert pool.refcount(a) == 2
        assert not pool.release(a)                  # still shared
        assert pool.release(a)                      # last ref frees
        assert pool.free_blocks == 2
        pool.check_invariants()

    def test_trash_block_never_allocated(self):
        pool = BlockPool(3)
        assert {pool.alloc(), pool.alloc()} == {1, 2}
        assert pool.refcount(TRASH_BLOCK) == 1


class TestRadixPrefixIndex:
    def _mk(self, num_blocks=16, bs=4):
        pool = BlockPool(num_blocks)
        return pool, RadixPrefixIndex(pool, bs)

    def test_longest_prefix_match(self):
        pool, idx = self._mk()
        blocks = [pool.alloc() for _ in range(3)]
        prompt = list(range(12))
        idx.insert(prompt, blocks)
        assert len(idx) == 3
        # full match, prefix match, diverging match, no match
        assert idx.lookup(prompt) == blocks
        assert idx.lookup(prompt[:9]) == blocks[:2]     # 9 // 4 = 2 pages
        assert idx.lookup(prompt[:8] + [99, 98, 97, 96]) == blocks[:2]
        assert idx.lookup([99] + prompt[1:]) == []
        pool.check_invariants()

    def test_lookup_limit_guards_full_match(self):
        pool, idx = self._mk()
        blocks = [pool.alloc() for _ in range(2)]
        prompt = list(range(8))
        idx.insert(prompt, blocks)
        # limit len-1: a fully-cached prompt still re-prefills one page
        assert idx.lookup(prompt, limit=len(prompt) - 1) == blocks[:1]

    def test_lookup_retains_for_caller(self):
        pool, idx = self._mk()
        blocks = [pool.alloc() for _ in range(2)]
        idx.insert(list(range(8)), blocks)
        got = idx.lookup(list(range(8)))
        assert [pool.refcount(b) for b in got] == [3, 3]  # alloc+index+caller

    def test_insert_keeps_existing_nodes(self):
        pool, idx = self._mk()
        blocks = [pool.alloc() for _ in range(2)]
        idx.insert(list(range(8)), blocks)
        dup = [pool.alloc() for _ in range(2)]
        assert idx.insert(list(range(8)), dup) == 0     # nothing new
        assert idx.lookup(list(range(8))) == blocks

    def test_eviction_is_lru_and_leaf_first(self):
        pool, idx = self._mk()
        b_old = [pool.alloc() for _ in range(2)]
        b_new = [pool.alloc()]
        idx.insert(list(range(8)), b_old)          # chain of 2 (leaf: page 2)
        idx.insert([50, 51, 52, 53], b_new)        # separate leaf
        # release the allocation refs: only the index holds the pages now
        for b in b_old + b_new:
            pool.release(b)
        idx.lookup([50, 51, 52, 53])               # touch -> most recent
        pool.release(b_new[0])                     # drop the lookup ref
        assert idx.evict(1) == 1
        # LRU leaf was the TAIL of the old chain, never its interior
        assert idx.lookup(list(range(8))) == b_old[:1]
        pool.release(b_old[0])                     # drop the lookup ref
        assert idx.evict(10) == 2                  # rest is evictable
        assert pool.free_blocks == pool.num_blocks - 1
        pool.check_invariants()


class TestPagedKVManager:
    def _mk(self, n_slots=2, bs=4, nb=12, mb=4, reuse=True):
        return PagedKVManager(n_slots, bs, nb, mb, prefix_reuse=reuse)

    def test_admit_register_reuse_retire(self):
        mgr = self._mk()
        p = [1, 2, 3, 4, 5, 6, 7, 8, 9]
        assert mgr.admit(0, p) == 0
        mgr.register(0, p)
        assert mgr.admit(1, p) == 8                # both full pages reused
        assert mgr.slot_blocks(1)[:2] == mgr.slot_blocks(0)[:2]
        assert mgr.slot_blocks(1)[2] != mgr.slot_blocks(0)[2]
        mgr.retire(0)
        mgr.retire(1)
        assert (mgr.tables == TRASH_BLOCK).all()
        assert mgr.stats()["indexed_blocks"] == 2  # prefix outlives slots
        mgr.check_invariants()

    def test_exactly_full_prompt_keeps_one_page_uncached(self):
        mgr = self._mk()
        p = list(range(8))                         # exactly 2 pages
        mgr.admit(0, p)
        mgr.register(0, p)
        mgr.retire(0)
        assert mgr.admit(1, p) == 4                # last page re-prefilled

    def test_prepare_append_allocates_at_boundary(self):
        mgr = self._mk()
        mgr.admit(0, [1, 2, 3])                    # 3 tokens in 1 page
        assert mgr.prepare_append(0) is None       # position 3: same page
        assert len(mgr.slot_blocks(0)) == 1
        assert mgr.prepare_append(0) is None       # position 4: new page
        assert len(mgr.slot_blocks(0)) == 2
        assert mgr.lengths[0] == 5

    def test_cow_on_shared_page_write(self):
        mgr = self._mk()
        mgr.admit(0, [1, 2, 3])
        mgr.fork(0, 1)
        src = mgr.slot_blocks(0)[0]
        assert mgr.pool.refcount(src) == 2
        cow = mgr.prepare_append(1)                # write into shared page
        assert cow is not None and cow[0] == src
        assert mgr.slot_blocks(1)[0] == cow[1] != src
        assert mgr.pool.refcount(src) == 1
        assert mgr.stats()["cow_copies"] == 1
        mgr.check_invariants()

    def test_mid_horizon_cow_probe(self):
        """Non-mutating probe for the device-loop engine: True iff a
        horizon position PAST the first would land in a shared page
        (only reachable via fork — the first position's CoW resolves on
        the host before the loop launches)."""
        mgr = self._mk(reuse=False)
        mgr.admit(0, [1, 2, 3, 4, 5, 6])           # pages [b0, b1], len 6
        mgr.fork(0, 1)
        free_before = mgr.pool.free_blocks
        # first write (pos 6) is host-resolvable: 1-step rounds are safe
        assert not mgr.mid_horizon_cow(1, 1)
        # but position 7 hits the still-shared second page mid-loop
        assert mgr.mid_horizon_cow(1, 2)
        assert mgr.pool.free_blocks == free_before     # probe mutated nothing
        cow = mgr.prepare_append(1)                # pos 6: CoW resolves now
        assert cow is not None
        assert not mgr.mid_horizon_cow(1, 4)       # all private/fresh ahead
        mgr.check_invariants()

    def test_failed_admit_rolls_back_all_page_refs(self):
        """PoolExhausted mid-admit must release lookup-retained prefix
        pages AND already-allocated private pages — no permanent leak."""
        mgr = self._mk(n_slots=1, bs=4, nb=2, mb=4)    # 1 usable page
        with pytest.raises(PoolExhausted):
            mgr.admit(0, list(range(9)))               # needs 3 pages
        assert mgr.pool.free_blocks == 1               # fully rolled back
        assert mgr.slot_blocks(0) == []
        mgr.check_invariants()
        assert mgr.admit(0, [1, 2, 3]) == 0            # pool still usable

    def test_pool_pressure_evicts_index(self):
        mgr = self._mk(n_slots=1, bs=4, nb=3, mb=2)   # 2 usable pages
        p1 = [1, 2, 3, 4, 5]
        mgr.admit(0, p1)
        mgr.register(0, p1)
        mgr.retire(0)                              # page [1..4] stays indexed
        mgr.admit(0, [9, 9, 9, 9, 9])              # needs both pages
        assert mgr.stats()["evictions"] == 1
        mgr.check_invariants()

    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 3), st.integers(1, 11),
                              st.integers(0, 6)),
                    min_size=1, max_size=40))
    def test_random_lifecycle_invariants(self, ops):
        """Random admit/append/fork/retire sequences keep every refcount,
        free-list and table entry consistent."""
        mgr = PagedKVManager(4, 4, 40, 4, prefix_reuse=True)
        rng = np.random.RandomState(0)
        live = [False] * 4
        for op, plen, slot_b in ops:
            slot = op % 4
            kind = slot_b % 3
            if not live[slot]:
                if kind == 2 and any(live):
                    src = next(i for i in range(4) if live[i])
                    mgr.fork(src, slot)
                else:
                    plen = min(plen, 4 * 4 - 4)    # leave decode headroom
                    p = rng.randint(0, 5, size=plen).tolist()
                    mgr.admit(slot, p)
                    mgr.register(slot, p)
                live[slot] = True
            elif kind == 0 and mgr.lengths[slot] < 4 * 4:
                mgr.prepare_append(slot)
            else:
                mgr.retire(slot)
                live[slot] = False
            mgr.check_invariants()
        for slot in range(4):
            if live[slot]:
                mgr.retire(slot)
        mgr.check_invariants()


# ---------------------------------------------------------------------------
# engine parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama-1.1b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def shared_prompts(tiny):
    cfg, _ = tiny
    rng = np.random.RandomState(5)
    sys_prompt = rng.randint(0, cfg.vocab_size, size=24)
    return [np.concatenate([sys_prompt, rng.randint(0, cfg.vocab_size,
                                                    size=n)])
            for n in (3, 7, 5, 9, 4, 6)]


def _run_engine(params, cfg, prompts, mesh=None, max_new=6, **ecfg_kw):
    eng = ServeEngine(params, cfg,
                      EngineConfig(max_batch=4, max_len=64, block_size=8,
                                   **ecfg_kw),
                      mesh=mesh)
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new)
    out = [r.output for r in sorted(eng.run(), key=lambda r: r.uid)]
    return out, eng


class TestPagedEngineParity:
    def test_paged_matches_contiguous_bit_exact(self, tiny, shared_prompts):
        """Same trace, paging on vs off (reuse disabled): token-for-token
        identical greedy outputs — the gathered page view is
        value-identical to the contiguous stripe."""
        cfg, params = tiny
        base, _ = _run_engine(params, cfg, shared_prompts)
        paged, _ = _run_engine(params, cfg, shared_prompts,
                               paged=True, prefix_reuse=False)
        assert paged == base

    def test_prefix_reuse_parity_and_prefill_reduction(self, tiny,
                                                       shared_prompts):
        """Prefix reuse on vs off: identical outputs, strictly fewer
        prefill tokens (the shared system prompt is served from pages)."""
        cfg, params = tiny
        off, e_off = _run_engine(params, cfg, shared_prompts,
                                 paged=True, prefix_reuse=False)
        on, e_on = _run_engine(params, cfg, shared_prompts,
                               paged=True, prefix_reuse=True)
        assert on == off
        s_on, s_off = e_on.stats(), e_off.stats()
        assert s_on["cached_prefix_tokens"] > 0
        assert s_on["prefill_tokens"] < s_off["prefill_tokens"]
        assert s_off["cached_prefix_tokens"] == 0
        assert s_on["paged"]["indexed_blocks"] > 0
        # cold admissions batch through the bucketed prefill like the
        # contiguous path — fewer prefill calls than requests
        assert s_off["prefill_calls"] < len(shared_prompts)

    def test_prefix_index_survives_runs(self, tiny, shared_prompts):
        """A second run on a warm engine serves (almost) every prompt
        from the index and still matches the cold outputs."""
        cfg, params = tiny
        base, _ = _run_engine(params, cfg, shared_prompts)
        _, eng = _run_engine(params, cfg, shared_prompts,
                             paged=True, prefix_reuse=True)
        eng.reset_stats()
        for p in shared_prompts:
            eng.submit(p, max_new_tokens=6)
        out2 = [r.output for r in sorted(eng.run(), key=lambda r: r.uid)]
        assert out2 == base
        s = eng.stats()
        assert s["cached_prefix_tokens"] > s["prefill_tokens"]

    def test_paged_attn_kernel_backend_parity(self, tiny, shared_prompts):
        """Engine decode routed through the registered pallas-interpret
        paged-attention kernel produces the same greedy tokens."""
        cfg, params = tiny
        base, _ = _run_engine(params, cfg, shared_prompts[:3], max_new=4)
        out, _ = _run_engine(params, cfg, shared_prompts[:3], max_new=4,
                             paged=True,
                             paged_attn_backend="pallas-interpret")
        assert out == base

    def test_paged_requires_continuous_family(self):
        cfg = get_config("xlstm-350m").reduced()
        with pytest.raises(ValueError, match="continuous"):
            ServeEngine(None, cfg, EngineConfig(paged=True))

    def test_paged_rejects_indivisible_block_size(self, tiny):
        cfg, params = tiny
        with pytest.raises(ValueError, match="block_size"):
            ServeEngine(params, cfg,
                        EngineConfig(max_len=60, paged=True, block_size=16))

    def test_no_recompile_after_warmup_paged(self, tiny, shared_prompts,
                                             compile_counts):
        """The paged decode step compiles once; a repeated workload adds
        zero compilations across decode/prefill/suffix/insert."""
        cfg, params = tiny
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=4, max_len=64, paged=True,
                                       block_size=8))
        fns = [eng._decode_multi_paged, eng._prefill_bucket,
               eng._prefill_suffix, eng._insert_paged]
        for p in shared_prompts:
            eng.submit(p, max_new_tokens=5)
        eng.run()
        warm = compile_counts(*fns)
        assert warm[0] == 1, "paged decode step must compile exactly once"
        for p in shared_prompts:
            eng.submit(p, max_new_tokens=5)
        eng.run()
        assert compile_counts(*fns) == warm, \
            "re-running an already-seen workload must not recompile"

    @pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices")
    def test_paged_sharded_parity(self, tiny, shared_prompts):
        """kv_blocks->data sharding of the page pool: mesh-sharded paged
        engine == single-device engine, token for token."""
        cfg, params = tiny
        base, _ = _run_engine(params, cfg, shared_prompts, max_new=4)
        mesh = make_mesh((2, 1), ("data", "model"))
        out, eng = _run_engine(params, cfg, shared_prompts, mesh=mesh,
                               max_new=4, paged=True, prefix_reuse=True)
        assert out == base
        assert eng.stats()["mesh"] == "data=2xmodel=1"


class TestPoolPressure:
    """A page pool smaller than the queue's concurrent demand must
    degrade to serialized serving, never hang or crash."""

    def _prompts(self, cfg, n=4, plen=12, seed=11):
        rng = np.random.RandomState(seed)
        return [rng.randint(0, cfg.vocab_size, size=plen) for _ in range(n)]

    @pytest.mark.parametrize("horizon", [1, 8])
    def test_tiny_pool_admission_stall_decodes_through(self, tiny, horizon):
        """Regression (busy-spin): with a deliberately tiny num_blocks
        pool, the admission loop used to spin forever once admit rolled
        back on PoolExhausted while free slots stayed open. The engine
        must instead break to decode — retirement frees pages — and
        still serve every request with the unconstrained outputs."""
        cfg, params = tiny
        prompts = self._prompts(cfg)    # each needs 3 pages (12+8 tok)
        base, _ = _run_engine(params, cfg, prompts, max_new=8)
        # 6 usable pages => at most two requests in flight; reuse off so
        # retired pages return to the free list immediately
        out, eng = _run_engine(params, cfg, prompts, max_new=8, paged=True,
                               num_blocks=7, prefix_reuse=False,
                               decode_horizon=horizon)
        assert out == base
        assert eng.stats()["paged"]["free_blocks"] == 6   # nothing leaked

    def test_pool_too_small_for_one_request_raises(self, tiny):
        """An admission stall with NO live slots to retire can never
        resolve — the engine must surface PoolExhausted instead of
        spinning on the queue head forever."""
        cfg, params = tiny
        rng = np.random.RandomState(12)
        prompt = rng.randint(0, cfg.vocab_size, size=20)  # needs 3 pages
        eng = ServeEngine(params, cfg,
                          EngineConfig(max_batch=2, max_len=64, paged=True,
                                       block_size=8, num_blocks=3,
                                       prefix_reuse=False))
        eng.submit(prompt, max_new_tokens=4)
        with pytest.raises(PoolExhausted, match="num_blocks"):
            eng.run()


# ---------------------------------------------------------------------------
# kernel conformance
# ---------------------------------------------------------------------------

class TestPagedAttentionKernel:
    def _case(self, b=3, heads=4, hk=2, d=8, nb=9, bs=4, mb=4, seed=0):
        rng = np.random.RandomState(seed)
        f = lambda *s: rng.randn(*s).astype(np.float32)
        q = f(b, heads, d)
        k_pool, v_pool = f(nb, bs, hk, d), f(nb, bs, hk, d)
        k_new, v_new = f(b, hk, d), f(b, hk, d)
        bt = rng.randint(1, nb, size=(b, mb)).astype(np.int32)
        lengths = np.array([0, 5, mb * bs], np.int32)[:b]
        return q, k_pool, v_pool, bt, lengths, k_new, v_new

    def test_interpret_kernel_matches_reference(self):
        args = self._case()
        ref = paged_attention_ref(*args)
        ker = paged_attention_kernel(*map(jnp.asarray, args), interpret=True)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_gqa_grouping(self):
        args = self._case(b=2, heads=8, hk=2, d=4, seed=3)
        ref = paged_attention_ref(*args)
        ker = paged_attention_kernel(*map(jnp.asarray, args), interpret=True)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_registry_exposes_paged_attention(self):
        for name in ("reference", "pallas-interpret", "pallas"):
            backend = registry._REGISTRY[name]
            assert backend.paged_attention is not None, name
        args = self._case(seed=7)
        ref = registry.get_backend("reference").paged_attention(*args)
        ker = registry.get_backend("pallas-interpret").paged_attention(
            *map(jnp.asarray, args))
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_reference_matches_contiguous_decode_semantics(self):
        """Zero-length slots attend only the new token; full slots attend
        everything — matching decode_attention's mask convention."""
        q, kp, vp, bt, lengths, kn, vn = self._case(seed=1)
        out = np.asarray(paged_attention_ref(q, kp, vp, bt, lengths, kn, vn))
        # length 0: softmax collapses onto the new-token column -> v_new
        g = q.shape[1] // kn.shape[1]
        expect = np.repeat(vn[0][:, None], g, axis=1).reshape(-1, vn.shape[-1])
        np.testing.assert_allclose(out[0], expect, atol=1e-5)
