"""Continuous-batching engine over the paged KV pool: page-allocator
invariants (property-based), scheduler invariants, byte-identity vs the
lock-step oracle across page geometries, and a fragmentation regression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.models import lm_head, transformer
from repro.models.config import ModelConfig
from repro.serve import (CandidateCache, ContinuationStore, Engine,
                         PagedPool, Request, ServeConfig, lockstep_decode)
from repro.serve.traffic import (TrafficConfig, drive, make_heavy_tail_mix,
                                 make_shared_prefix_burst, make_workload)

pytestmark = pytest.mark.serve

CFG = ModelConfig(
    name="engine-test", num_layers=1, d_model=32, d_ff=64, vocab_size=100,
    num_heads=2, num_kv_heads=2, vocab_pad_multiple=128, gen_feature_dim=8,
    dtype="float32", remat=False)
HCFG = lm_head.head_config(CFG, "adversarial_ns")
PARAMS = transformer.init_params(jax.random.PRNGKey(0), CFG)
HEAD_STATE = lm_head.default_head_state(jax.random.PRNGKey(1), CFG,
                                        "adversarial_ns")
MAX_LEN = 12
BEAM = 8
N_SLOTS = 2


_ENGINES = {}


def shared_engine(page_len: int = 0, batched: bool = True,
                  n_pages: int = 0) -> Engine:
    """One shared engine per geometry (jit caches stay warm across tests);
    between runs all lanes/pages are free and the queues empty, so state
    carry-over is only the candidate cache — which never changes outputs,
    only skips work. (A plain helper, not a pytest fixture: the hypothesis
    fallback shim hides fixture params from pytest's resolver.)"""
    key = (page_len, batched, n_pages)
    if key not in _ENGINES:
        _ENGINES[key] = Engine(CFG, HCFG, PARAMS, HEAD_STATE, ServeConfig(
            n_slots=N_SLOTS, max_len=MAX_LEN, beam=BEAM, page_len=page_len,
            n_pages=n_pages, batched_prefill=batched,
            cache_dtype=jnp.float32))
    return _ENGINES[key]


def _prompts(rng, n, lo=2, hi=4):
    return [rng.integers(0, CFG.vocab_size,
                         rng.integers(lo, hi + 1)).astype(np.int32)
            for _ in range(n)]


def _lockstep(prompts, gen_tokens, beam):
    """Reference decode: the shared fixed-batch oracle from repro.serve."""
    return lockstep_decode(CFG, HCFG, PARAMS, HEAD_STATE, prompts,
                           gen_tokens, topk_beam=beam)


# ---------------------------------------------------------------------------
# Page allocator: hypothesis property suite
# ---------------------------------------------------------------------------

def _fresh_pool(n_lanes=3, n_pages=8, page_len=3, max_len=9):
    return PagedPool(CFG, n_lanes, n_pages, page_len, max_len,
                     dtype=jnp.float32)


def _drive_allocator(pool, seed, n_ops):
    """Random alloc/release interleaving; returns the live lane->pages map
    mirror kept independently of the pool's own bookkeeping."""
    rng = np.random.default_rng(seed)
    live = {}
    for _ in range(n_ops):
        if live and (rng.random() < 0.5 or not pool.num_free_lanes):
            lane = list(live)[rng.integers(0, len(live))]
            got = pool.release(lane)
            assert sorted(got) == sorted(live.pop(lane)), \
                "release must reclaim exactly the request's pages"
        else:
            need = int(rng.integers(1, pool.max_pages + 1))
            expect = pool.can_admit(need)
            out = pool.alloc(need)
            assert (out is not None) == expect, \
                "alloc must succeed exactly when can_admit says so"
            if out is not None:
                lane, pages = out
                assert len(pages) == need
                live[lane] = pages
        pool.check_invariants()
    return live


class TestPageAllocator:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**20), n_ops=st.integers(1, 40))
    def test_free_and_mapped_partition_pages(self, seed, n_ops):
        """After ANY interleaving: free + mapped pages partition
        range(n_pages) and no page is double-mapped across live lanes
        (check_invariants asserts both at every step)."""
        pool = _fresh_pool()
        live = _drive_allocator(pool, seed, n_ops)
        mapped = {p for pages in live.values() for p in pages}
        assert len(mapped) == sum(len(v) for v in live.values())
        assert pool.num_mapped_pages == len(mapped)
        assert pool.num_free_pages == pool.n_pages - len(mapped)

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**20), n_ops=st.integers(1, 40))
    def test_drained_pool_is_indistinguishable_from_fresh(self, seed,
                                                          n_ops):
        """Any interleaving that ends with every request retired leaves
        allocator state identical to a fresh pool's (sets of free pages/
        lanes; page tables all-sink)."""
        pool = _fresh_pool()
        live = _drive_allocator(pool, seed, n_ops)
        for lane in list(live):
            pool.release(lane)
        fresh = _fresh_pool()
        assert set(pool._free_pages) == set(fresh._free_pages)
        assert set(pool._free_lanes) == set(fresh._free_lanes)
        assert pool._pages_of == {}
        np.testing.assert_array_equal(pool.page_table, fresh.page_table)

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**20), n_lanes=st.integers(1, 4),
           page_len=st.sampled_from([1, 2, 3, 5, 9]))
    def test_alloc_never_exceeds_capacity(self, seed, n_lanes, page_len):
        """Greedy allocation saturates at exactly min(lane, page) capacity;
        the pool never over-grants and page tables never alias."""
        max_len = 9
        n_pages = max(-(-max_len // page_len), 5)
        pool = PagedPool(CFG, n_lanes, n_pages, page_len, max_len,
                         dtype=jnp.float32)
        rng = np.random.default_rng(seed)
        granted = 0
        while True:
            need = int(rng.integers(1, pool.max_pages + 1))
            out = pool.alloc(need)
            if out is None:
                assert (pool.num_free_lanes == 0
                        or pool.num_free_pages < need)
                break
            granted += len(out[1])
            pool.check_invariants()
        assert granted == pool.num_mapped_pages <= n_pages

    @settings(max_examples=25, deadline=None)
    @given(total_len=st.integers(1, 9), page_len=st.sampled_from([1, 2, 3,
                                                                  4, 9]))
    def test_pages_needed_covers_exactly(self, total_len, page_len):
        """pages_needed is the minimal page count covering total_len."""
        pool = PagedPool(CFG, 2, 12, page_len, 9, dtype=jnp.float32)
        need = pool.pages_needed(total_len)
        assert need * page_len >= total_len
        assert (need - 1) * page_len < total_len

    def test_double_release_and_bad_lane_rejected(self):
        pool = _fresh_pool()
        lane, pages = pool.alloc(2)
        assert pool.release(lane) == pages
        with pytest.raises(AssertionError):    # double release
            pool.release(lane)
        with pytest.raises(AssertionError):    # never-allocated lane
            pool.release(pool.n_lanes - 1)

    def test_sink_page_outside_allocator_range(self):
        """The sink page is a physical arena row the allocator never hands
        out — free lanes' garbage writes cannot alias a live mapping."""
        pool = _fresh_pool(n_pages=4)
        assert pool.sink == 4
        assert pool.cache["k"].shape[1] == 5      # n_pages + sink
        seen = set()
        while pool.can_admit(1):
            seen.update(pool.alloc(1)[1])
        assert pool.sink not in seen
        assert (pool.page_table <= pool.sink).all()


# ---------------------------------------------------------------------------
# Scheduler invariants
# ---------------------------------------------------------------------------

class TestSchedulerInvariants:
    @settings(max_examples=15, deadline=None)
    @given(seed=st.integers(0, 2**20), n=st.integers(1, 6),
           gen=st.integers(1, 4), use_eos=st.sampled_from([False, True]))
    def test_every_request_retires_exactly_once(self, seed, n, gen,
                                                use_eos):
        engine = shared_engine(page_len=3)
        rng = np.random.default_rng(seed)
        completed_before = len(engine.completed)
        handles = [engine.submit(Request(
            prompt=p, max_new_tokens=gen,
            eos_id=int(rng.integers(0, CFG.vocab_size)) if use_eos
            else None)) for p in _prompts(rng, n)]
        order_before = list(engine.admission_order)
        engine.run()

        # Every admitted request retired exactly once.
        new_completed = list(engine.completed)[completed_before:]
        assert sorted(h.request_id for h in new_completed) == \
            sorted(h.request_id for h in handles)
        for h in handles:
            assert h.done and h.finished_at is not None
            assert 1 <= len(h.tokens) <= gen
            if len(h.tokens) < gen:     # early retirement must be EOS
                assert h.eos_hit
            assert all(0 <= t < CFG.vocab_size for t in h.tokens)

        # No lane or page leaked or double-assigned.
        engine.pool.check_invariants()
        assert engine.pool.num_free_lanes == N_SLOTS
        assert engine.pool.num_mapped_pages == 0
        assert engine.num_active == 0 and engine.num_pending == 0

        # FIFO admission fairness: admitted in submission order.
        new_order = list(engine.admission_order)[len(order_before):]
        assert new_order == [h.request_id for h in handles]


# ---------------------------------------------------------------------------
# Byte-identity oracle across page geometries
# ---------------------------------------------------------------------------

class TestGeometryOracle:
    """Engine output must be byte-identical to the lock-step decode for
    EVERY page geometry: paging changes physical addressing only, never
    the positions the softmax sees."""

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(0, 2**20))
    def test_byte_identical_to_lockstep_beam(self, seed):
        """Engine (2 lanes, mixed admission, page_len 3) == lock-step batch
        decode, token for token, for the same seed/prompts."""
        engine = shared_engine(page_len=3)
        rng = np.random.default_rng(seed)
        b, pl, gen = 3, 3, 3
        prompts = rng.integers(0, CFG.vocab_size, (b, pl)).astype(np.int32)
        ref = _lockstep(prompts, gen, BEAM)
        handles = [engine.submit(Request(prompt=p, max_new_tokens=gen))
                   for p in prompts]
        engine.run()
        out = np.stack([h.result() for h in handles])
        np.testing.assert_array_equal(out, ref)

    @pytest.mark.parametrize("page_len", [1, MAX_LEN])
    @pytest.mark.parametrize("batched", [True, False])
    def test_geometry_sweep_beam(self, page_len, batched):
        self._run_geometry(page_len, batched, beam=BEAM)

    @pytest.mark.slow
    @pytest.mark.parametrize("page_len", [3, 7])
    @pytest.mark.parametrize("batched", [True, False])
    def test_geometry_sweep_beam_odd_pages(self, page_len, batched):
        self._run_geometry(page_len, batched, beam=BEAM)

    def _run_geometry(self, page_len, batched, beam):
        rng = np.random.default_rng(1000 * page_len + batched)
        b, gen = 4, 3
        prompts = _prompts(rng, b, lo=2, hi=5)
        refs = [
            _lockstep(p[None], gen, beam)[0] for p in prompts]
        engine = shared_engine(page_len=page_len, batched=batched)
        handles = [engine.submit(Request(prompt=p, max_new_tokens=gen))
                   for p in prompts]
        engine.run()
        for h, ref in zip(handles, refs):
            np.testing.assert_array_equal(h.result(), ref)
        engine.pool.check_invariants()

    def test_byte_identical_to_lockstep_dense(self):
        rng = np.random.default_rng(7)
        b, pl, gen = 3, 3, 3
        prompts = rng.integers(0, CFG.vocab_size, (b, pl)).astype(np.int32)
        ref = _lockstep(prompts, gen, 0)
        for page_len in (1, 3, MAX_LEN):
            eng = Engine(CFG, HCFG, PARAMS, HEAD_STATE, ServeConfig(
                n_slots=2, max_len=MAX_LEN, beam=0, page_len=page_len,
                cache_dtype=jnp.float32))
            handles = [eng.submit(Request(prompt=p, max_new_tokens=gen))
                       for p in prompts]
            eng.run()
            np.testing.assert_array_equal(
                np.stack([h.result() for h in handles]), ref)

    def test_batched_prefill_one_launch_for_burst(self):
        """A burst admitted together prefills in ONE padded call (vs one
        per request sequentially) and still matches the oracle."""
        rng = np.random.default_rng(41)
        gen = 2
        prompts = _prompts(rng, N_SLOTS, lo=2, hi=4)
        refs = [_lockstep(p[None], gen, BEAM)[0] for p in prompts]
        for batched, expect_calls in ((True, 1), (False, N_SLOTS)):
            eng = Engine(CFG, HCFG, PARAMS, HEAD_STATE, ServeConfig(
                n_slots=N_SLOTS, max_len=MAX_LEN, beam=BEAM, page_len=3,
                batched_prefill=batched, cache_dtype=jnp.float32))
            handles = [eng.submit(Request(prompt=p, max_new_tokens=gen))
                       for p in prompts]
            eng.step()          # single admission round for the burst
            assert eng.prefill_calls == expect_calls
            eng.run()
            for h, ref in zip(handles, refs):
                np.testing.assert_array_equal(h.result(), ref)


# ---------------------------------------------------------------------------
# Fragmentation / undersized-pool regression
# ---------------------------------------------------------------------------

class TestFragmentation:
    def test_half_size_paged_pool_serves_mixed_trace(self):
        """Poisson traffic of mixed lengths through a paged pool sized to
        ~half the monolithic pool's bytes: the whole trace completes (no
        deadlock), occupancy never exceeds n_pages, and outputs still
        match the oracle."""
        page_len = 3
        # Monolithic bytes: N_SLOTS * MAX_LEN positions. Half, in pages:
        n_pages = (N_SLOTS * MAX_LEN // 2) // page_len          # 4 pages
        assert n_pages * page_len * 2 == N_SLOTS * MAX_LEN
        tcfg = TrafficConfig(
            n_requests=12, rate=500.0, prompt_len=4, gen_tokens=2,
            prompt_len_choices=(2, 3, 4), gen_tokens_choices=(1, 2, 3),
            vocab_size=CFG.vocab_size, seed=5)
        workload = make_workload(tcfg)
        engine = Engine(CFG, HCFG, PARAMS, HEAD_STATE, ServeConfig(
            n_slots=N_SLOTS, max_len=MAX_LEN, beam=BEAM, page_len=page_len,
            n_pages=n_pages, cache_dtype=jnp.float32))
        res = drive(engine, workload, time_scale=0.0)
        assert res["n_requests"] == tcfg.n_requests
        stats = engine.stats()
        assert stats["completed"] >= tcfg.n_requests
        assert 0 < stats["peak_pages_in_use"] <= n_pages
        assert stats["pages_in_use"] == 0       # drained
        engine.pool.check_invariants()
        # Byte-identity survives the undersized pool.
        for h in list(engine.completed)[-tcfg.n_requests:]:
            ref = _lockstep(h.request.prompt[None],
                            h.request.max_new_tokens, BEAM)[0]
            np.testing.assert_array_equal(h.result(), ref)

    def test_internal_fragmentation_reported(self):
        """stats() fragmentation: mapped-but-unwritten positions over
        mapped bytes, in (0, 1) while a short request holds a long page."""
        engine = Engine(CFG, HCFG, PARAMS, HEAD_STATE, ServeConfig(
            n_slots=1, max_len=MAX_LEN, beam=0, page_len=MAX_LEN,
            cache_dtype=jnp.float32))
        rng = np.random.default_rng(43)
        prompt = rng.integers(0, CFG.vocab_size, 2).astype(np.int32)
        engine.submit(Request(prompt=prompt, max_new_tokens=6))
        engine.step()       # admitted: 2-3 positions used of a 12-page
        frag = engine.stats()["internal_fragmentation"]
        assert 0.0 < frag < 1.0
        engine.run()
        assert engine.stats()["internal_fragmentation"] == 0.0


# ---------------------------------------------------------------------------
# Candidate cache on the paged path
# ---------------------------------------------------------------------------

class TestCandidateCachePath:
    def test_repeat_prefix_hits_and_identical_outputs(self):
        engine = shared_engine(page_len=3)
        rng = np.random.default_rng(11)
        prompt = rng.integers(0, CFG.vocab_size, 4).astype(np.int32)
        h1 = engine.submit(Request(prompt=prompt, max_new_tokens=4))
        engine.run()
        skips_before = engine.descent_skips
        hits_before = engine.candidate_cache.hits
        h2 = engine.submit(Request(prompt=prompt, max_new_tokens=4))
        engine.run()
        assert h2.tokens == h1.tokens
        assert engine.candidate_cache.hits > hits_before
        assert engine.descent_skips > skips_before

    def test_head_swap_invalidates_cached_candidates(self):
        """A generator/head refresh must not serve candidates descended
        under the old tree: swap_head_state bumps the cache version, so
        the same prompt re-descends (no descent skip) and the outputs
        match an engine built with the new head state from scratch."""
        new_head = lm_head.default_head_state(jax.random.PRNGKey(2), CFG,
                                              "adversarial_ns")

        def fresh(head_state):
            return Engine(CFG, HCFG, PARAMS, head_state, ServeConfig(
                n_slots=1, max_len=MAX_LEN, beam=BEAM, page_len=3,
                cache_dtype=jnp.float32))

        rng = np.random.default_rng(19)
        prompt = rng.integers(0, CFG.vocab_size, 4).astype(np.int32)
        eng = fresh(HEAD_STATE)
        eng.submit(Request(prompt=prompt, max_new_tokens=4))
        eng.run()
        # Sanity: with no swap the repeat skips descents via the cache.
        skips0 = eng.descent_skips
        eng.submit(Request(prompt=prompt, max_new_tokens=4))
        eng.run()
        assert eng.descent_skips > skips0

        eng.swap_head_state(new_head)
        skips1 = eng.descent_skips
        h = eng.submit(Request(prompt=prompt, max_new_tokens=4))
        eng.run()
        # Old entries are unreachable: every step re-descended.
        assert eng.descent_skips == skips1
        stats = eng.candidate_cache.stats()
        assert stats["version"] == 1 and stats["invalidations"] == 1
        # And the decode is what the new head produces, not a stale mix.
        ref_eng = fresh(new_head)
        ref = ref_eng.submit(Request(prompt=prompt, max_new_tokens=4))
        ref_eng.run()
        assert h.tokens == ref.tokens

    def test_cache_disabled_engine_matches(self):
        rng = np.random.default_rng(13)
        prompt = rng.integers(0, CFG.vocab_size, 4).astype(np.int32)
        outs = []
        for use_cache in (True, False):
            eng = Engine(CFG, HCFG, PARAMS, HEAD_STATE, ServeConfig(
                n_slots=1, max_len=MAX_LEN, beam=BEAM, page_len=3,
                use_candidate_cache=use_cache, cache_dtype=jnp.float32))
            h = eng.submit(Request(prompt=prompt, max_new_tokens=4))
            h2 = eng.submit(Request(prompt=prompt, max_new_tokens=4))
            eng.run()
            outs.append((h.tokens, h2.tokens))
            assert (eng.candidate_cache is not None) == use_cache
        assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# Retirement
# ---------------------------------------------------------------------------

class TestRetirement:
    def test_per_request_max_new_tokens(self):
        engine = shared_engine(page_len=3)
        rng = np.random.default_rng(17)
        prompts = _prompts(rng, 3)
        lens = [1, 3, 2]
        handles = [engine.submit(Request(prompt=p, max_new_tokens=g))
                   for p, g in zip(prompts, lens)]
        engine.run()
        assert [len(h.tokens) for h in handles] == lens

    def test_eos_stops_early_and_frees_lane_and_pages(self):
        engine = shared_engine(page_len=3)
        rng = np.random.default_rng(19)
        prompt = rng.integers(0, CFG.vocab_size, 3).astype(np.int32)
        h_ref = engine.submit(Request(prompt=prompt, max_new_tokens=5))
        engine.run()
        assert len(h_ref.tokens) == 5
        eos = h_ref.tokens[2]
        first = h_ref.tokens.index(eos)      # eos may repeat earlier
        h = engine.submit(Request(prompt=prompt, max_new_tokens=5,
                                  eos_id=eos))
        engine.run()
        assert h.eos_hit and len(h.tokens) == first + 1
        assert h.tokens == h_ref.tokens[:first + 1]
        assert engine.pool.num_free_lanes == N_SLOTS
        assert engine.pool.num_mapped_pages == 0

    def test_oversized_request_rejected(self):
        engine = shared_engine(page_len=3)
        prompt = np.zeros((MAX_LEN,), np.int32)
        with pytest.raises(ValueError):
            engine.submit(Request(prompt=prompt, max_new_tokens=1))

    def test_zero_budget_request_rejected(self):
        """The engine always decodes >= 1 token; a zero budget would write
        one position past the request's page reservation."""
        engine = shared_engine(page_len=3)
        with pytest.raises(ValueError):
            engine.submit(Request(prompt=np.zeros((2,), np.int32),
                                  max_new_tokens=0))

    def test_streaming_matches_result(self):
        engine = shared_engine(page_len=3)
        rng = np.random.default_rng(23)
        prompt = rng.integers(0, CFG.vocab_size, 3).astype(np.int32)
        h = engine.submit(Request(prompt=prompt, max_new_tokens=4))
        streamed = list(engine.stream(h))
        assert streamed == list(h.result())


# ---------------------------------------------------------------------------
# Pool / cache / traffic units
# ---------------------------------------------------------------------------

class TestPrefillErrors:
    def test_exception_kept_on_the_handle(self, monkeypatch):
        """With no fault plan, a prefill that raises (a compile error, an
        HBM OOM) fails its request with the exception attached."""
        eng = Engine(CFG, HCFG, PARAMS, HEAD_STATE, ServeConfig(
            n_slots=N_SLOTS, max_len=MAX_LEN, cache_dtype=jnp.float32))

        def failing_prefill(handles):
            raise RuntimeError("RESOURCE_EXHAUSTED: prefill does not fit")

        monkeypatch.setattr(eng, "_launch_prefill", failing_prefill)
        hs = [eng.submit(Request(prompt=np.array([1, 2, 3], np.int32),
                                 max_new_tokens=2)) for _ in range(2)]
        eng.run()
        for h in hs:
            assert h.done and h.status == "error"
            assert "RESOURCE_EXHAUSTED" in h.error
        assert eng.pool.num_free_lanes == N_SLOTS


class TestPagedPoolUnit:
    def test_arena_shape(self):
        pool = PagedPool(CFG, 4, 6, 4, 16, dtype=jnp.float32)
        # +1 physical page: the sink.
        assert pool.cache["k"].shape == (
            CFG.num_layers, 7, 4, CFG.num_kv_heads, CFG.resolved_head_dim)
        assert pool.max_pages == 4
        assert pool.page_table.shape == (4, 4)

    def test_lifo_reuse(self):
        pool = _fresh_pool()
        lane, pages = pool.alloc(2)
        pool.release(lane)
        lane2, pages2 = pool.alloc(2)
        assert lane2 == lane                 # LIFO lane reuse
        assert pages2 == pages[::-1]         # LIFO page reuse

    def test_pool_too_small_for_max_len_rejected(self):
        with pytest.raises(AssertionError):
            PagedPool(CFG, 2, 2, 3, MAX_LEN, dtype=jnp.float32)


class TestCandidateCacheUnit:
    def test_lru_eviction_and_stats(self):
        cc = CandidateCache(capacity=2)
        c = np.arange(4, dtype=np.int32)
        lp = np.zeros(4, np.float32)
        cc.put((1,), c, lp)
        cc.put((2,), c, lp)
        assert cc.get((1,)) is not None      # (1,) now most-recent
        cc.put((3,), c, lp)                  # evicts (2,)
        assert cc.get((2,)) is None
        assert cc.get((3,)) is not None
        assert cc.evictions == 1
        assert cc.stats()["hits"] == 2 and cc.stats()["misses"] == 1

    def test_hit_returns_stored_arrays(self):
        cc = CandidateCache(capacity=4)
        c = np.array([5, 7, -1], np.int32)
        lp = np.array([-0.5, -1.5, -np.inf], np.float32)
        cc.put((0, 1, 2), c, lp)
        got_c, got_lp = cc.get((0, 1, 2))
        np.testing.assert_array_equal(got_c, c)
        np.testing.assert_array_equal(got_lp, lp)


class TestTraffic:
    def test_workload_shapes_and_repeats(self):
        tcfg = TrafficConfig(n_requests=32, rate=100.0, prompt_len=5,
                             gen_tokens=3, vocab_size=50, repeat_frac=0.5,
                             n_shared_prompts=1, seed=3)
        wl = make_workload(tcfg)
        assert len(wl) == 32
        arrivals = [t for t, _ in wl]
        assert arrivals == sorted(arrivals) and arrivals[0] == 0.0
        keys = {tuple(r.prompt.tolist()) for _, r in wl}
        assert len(keys) < 32               # shared prompts actually repeat
        for _, r in wl:
            assert r.prompt.shape == (5,) and r.max_new_tokens == 3

    def test_mixed_length_workload(self):
        tcfg = TrafficConfig(n_requests=64, rate=100.0, prompt_len=8,
                             gen_tokens=4, prompt_len_choices=(2, 5, 8),
                             gen_tokens_choices=(1, 4), vocab_size=50,
                             seed=9)
        wl = make_workload(tcfg)
        assert {r.prompt.shape[0] for _, r in wl} == {2, 5, 8}
        assert {r.max_new_tokens for _, r in wl} == {1, 4}


class TestSSMEngine:
    @pytest.mark.slow
    def test_ssm_engine_matches_oracle_mixed_lengths(self):
        """SSM models through the paged engine: recurrent state is NOT
        position-local, so batched prefill must group by exact prompt
        length instead of length-padding (padding tokens would keep
        updating the carried state). Mixed lengths — including one shorter
        than the conv window, the seed bug the left-pad in
        ssm.ssm_block's prefill conv_state fixes — must match the
        per-request oracle byte for byte."""
        import dataclasses
        from repro import configs as cfg_lib
        cfg = dataclasses.replace(cfg_lib.reduced_config("mamba2-370m"),
                                  dtype="float32", remat=False)
        params = transformer.init_params(jax.random.PRNGKey(0), cfg)
        hs = lm_head.default_head_state(jax.random.PRNGKey(1), cfg,
                                        "adversarial_ns")
        hcfg = lm_head.head_config(cfg, "adversarial_ns")
        rng = np.random.default_rng(3)
        # 2 < ssm_conv_width - 1: the short-prompt conv-state case.
        prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                   for n in (5, 3, 5, 2)]
        refs = [lockstep_decode(cfg, hcfg, params, hs, p[None], 3,
                                topk_beam=0)[0] for p in prompts]
        eng = Engine(cfg, hcfg, params, hs, ServeConfig(
            n_slots=4, max_len=12, beam=0, page_len=3,
            cache_dtype=jnp.float32))
        # Pure-SSM has no K/V arena: the requested page geometry is pinned
        # to one nominal page per lane so pages never gate admission.
        assert eng.pool.page_len == 12 and eng.pool.n_pages == 4
        handles = [eng.submit(Request(prompt=p, max_new_tokens=3))
                   for p in prompts]
        eng.run()
        for h, ref in zip(handles, refs):
            np.testing.assert_array_equal(h.result(), ref)
        # One prefill launch per distinct prompt length in the burst —
        # but the FIFO audit trail stays in submission order.
        assert eng.prefill_calls == 3
        assert list(eng.admission_order) == [h.request_id for h in handles]
        eng.pool.check_invariants()


class TestMeshScoring:
    def test_sharded_score_fn_matches_plain(self):
        """make_serve_step(mesh=...) (1-shard mesh here; multi-shard runs in
        test_parallel's subprocess launcher test) == plain scoring."""
        from jax.sharding import AxisType
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto, AxisType.Auto))
        rng = np.random.default_rng(29)
        b, pl, gen = 2, 3, 3
        prompts = rng.integers(0, CFG.vocab_size, (b, pl)).astype(np.int32)
        ref = _lockstep(prompts, gen, BEAM)
        sharded = lockstep_decode(CFG, HCFG, PARAMS, HEAD_STATE, prompts,
                                  gen, topk_beam=BEAM, mesh=mesh)
        np.testing.assert_array_equal(sharded, ref)

    def test_engine_mesh_paged_arena_matches(self):
        """Engine(mesh=...) with the paged arena device_put through
        paged_cache_shardings still reproduces the oracle."""
        from jax.sharding import AxisType
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto, AxisType.Auto))
        rng = np.random.default_rng(31)
        prompts = rng.integers(0, CFG.vocab_size, (2, 3)).astype(np.int32)
        ref = _lockstep(prompts, 3, BEAM)
        eng = Engine(CFG, HCFG, PARAMS, HEAD_STATE, ServeConfig(
            n_slots=2, max_len=MAX_LEN, beam=BEAM, page_len=3, mesh=mesh,
            cache_dtype=jnp.float32))
        handles = [eng.submit(Request(prompt=p, max_new_tokens=3))
                   for p in prompts]
        eng.run()
        np.testing.assert_array_equal(
            np.stack([h.result() for h in handles]), ref)


# ---------------------------------------------------------------------------
# Refcounted / shared pages (PR 9): hypothesis property suite
# ---------------------------------------------------------------------------

def _fill_arena(pool, seed):
    """Overwrite the K/V arena with recognizable random bytes so byte-
    identity checks on page ops are meaningful."""
    rng = np.random.default_rng(seed)
    cache = dict(pool.cache)
    for key in ("k", "v"):
        cache[key] = jnp.asarray(
            rng.normal(size=cache[key].shape).astype(np.float32))
    pool.cache = cache


def _drive_shared_allocator(pool, seed, n_ops):
    """Random interleaving of alloc / alloc_shared / cow / release /
    register / unregister / cached-revival, mirroring refcounts
    independently of the pool's bookkeeping."""
    rng = np.random.default_rng(seed)
    live = {}                       # lane -> pages in logical order
    for _ in range(n_ops):
        r = rng.random()
        if live and r < 0.25:
            lane = sorted(live)[rng.integers(0, len(live))]
            got = pool.release(lane)
            assert sorted(got) == sorted(live.pop(lane)), \
                "release must unref exactly the lane's pages"
        elif live and r < 0.40:
            # Share a live lane's prefix (+ maybe private pages).
            donor = sorted(live)[rng.integers(0, len(live))]
            pages = pool.lane_pages(donor)
            k = int(rng.integers(1, len(pages) + 1))
            npriv = int(rng.integers(0, pool.max_pages - k + 1))
            out = pool.alloc_shared(pages[:k], npriv)
            if out is not None:
                lane, priv = out
                live[lane] = pages[:k] + priv
        elif live and r < 0.50:
            # COW a genuinely-shared page (rc > 1) when a copy target
            # exists.
            cands = [(lane, i) for lane, pages in live.items()
                     for i, p in enumerate(pages) if pool.refcount(p) > 1]
            if cands and pool.num_free_pages:
                lane, i = cands[rng.integers(0, len(cands))]
                live[lane][i] = pool.cow(lane, i)
        elif live and r < 0.60:
            lane = sorted(live)[rng.integers(0, len(live))]
            pages = live[lane]
            pool.register(pages[:int(rng.integers(1, len(pages) + 1))])
        elif r < 0.68:
            regs = sorted(pool._registered)
            if regs:
                pool.unregister([regs[rng.integers(0, len(regs))]])
        elif pool._cached and r < 0.76:
            # Revive cached (rc == 0, bytes pinned) pages into a new lane.
            cached = sorted(pool._cached)
            k = int(rng.integers(1, min(len(cached), pool.max_pages) + 1))
            out = pool.alloc_shared(cached[:k], 0)
            if out is not None:
                lane, _ = out
                live[lane] = cached[:k]
        else:
            need = int(rng.integers(1, pool.max_pages + 1))
            expect = pool.can_admit(need)
            out = pool.alloc(need)
            assert (out is not None) == expect
            if out is not None:
                lane, pages = out
                live[lane] = pages
        pool.check_invariants()
        # Refcount == number of mapping lanes, for every page.
        counts = {}
        for pages in live.values():
            for p in pages:
                counts[p] = counts.get(p, 0) + 1
        for p in range(pool.n_pages):
            assert pool.refcount(p) == counts.get(p, 0)
            # rc == 0  <=>  free-list or cached (never both, never lost).
            in_free = p in pool._free_pages
            assert (pool.refcount(p) == 0) == (in_free or pool.is_cached(p))
            assert not (in_free and pool.is_cached(p))
    return live


class TestRefcountedPool:
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**20), n_ops=st.integers(1, 50))
    def test_sharing_preserves_partition_and_refcounts(self, seed, n_ops):
        pool = _fresh_pool(n_lanes=4, n_pages=8, page_len=3, max_len=9)
        _drive_shared_allocator(pool, seed, n_ops)

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**20), n_ops=st.integers(1, 50))
    def test_drained_unregistered_pool_matches_fresh(self, seed, n_ops):
        """Release every lane, drop every registration: the pool must be
        indistinguishable from fresh (no page leaked through sharing,
        caching, or COW)."""
        pool = _fresh_pool(n_lanes=4, n_pages=8, page_len=3, max_len=9)
        live = _drive_shared_allocator(pool, seed, n_ops)
        for lane in list(live):
            pool.release(lane)
        pool.unregister(sorted(pool._registered))
        fresh = _fresh_pool(n_lanes=4, n_pages=8, page_len=3, max_len=9)
        assert set(pool._free_pages) == set(fresh._free_pages)
        assert len(pool._free_pages) == pool.n_pages     # no double-free
        assert set(pool._free_lanes) == set(fresh._free_lanes)
        assert pool._pages_of == {} and pool._refcount == {}
        assert pool._cached == set()
        np.testing.assert_array_equal(pool.page_table, fresh.page_table)

    def test_cached_lifecycle_and_eviction_accounting(self):
        """register → release parks pages as cached (not free); cached
        pages satisfy can_admit_evicting but not can_admit; retain revives
        them; unregister frees them."""
        pool = _fresh_pool(n_lanes=2, n_pages=4, page_len=3, max_len=9)
        lane, pages = pool.alloc(3)
        pool.register(pages[:2])
        pool.release(lane)
        assert pool.num_cached_pages == 2 and pool.num_free_pages == 2
        assert not pool.can_admit(3) and pool.can_admit_evicting(3)
        # Revive one cached page into a new lane without touching bytes.
        lane2, _ = pool.alloc_shared(pages[:1], 1)
        assert pool.refcount(pages[0]) == 1
        assert not pool.is_cached(pages[0])
        pool.release(lane2)
        pool.unregister(pages[:2])
        assert pool.num_cached_pages == 0
        assert pool.num_free_pages == pool.n_pages
        pool.check_invariants()

    def test_retain_of_free_page_rejected(self):
        pool = _fresh_pool()
        free_page = pool._free_pages[-1]
        with pytest.raises(AssertionError):
            pool.retain(free_page)

    def test_cow_copies_bytes_and_preserves_donor(self):
        """COW gives the caller a private byte-identical page; the donor
        lane keeps its mapping and the refcounts split 2 -> 1 + 1."""
        pool = _fresh_pool()
        _fill_arena(pool, seed=3)
        lane_a, pages = pool.alloc(2)
        lane_b, priv = pool.alloc_shared(pages, 0)
        assert priv == [] and pool.refcount(pages[1]) == 2
        src = pages[1]
        before = {k: np.asarray(pool.cache[k][:, src]) for k in ("k", "v")}
        new = pool.cow(lane_b, 1)
        assert new != src
        assert pool.lane_pages(lane_a) == pages          # donor untouched
        assert pool.lane_pages(lane_b) == [pages[0], new]
        assert pool.refcount(src) == 1 and pool.refcount(new) == 1
        for k in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(pool.cache[k][:, new]), before[k])
        pool.check_invariants()

    def test_spill_restore_byte_identity(self):
        """spill → clobber the arena → restore reproduces the lane's pages
        byte for byte in freshly-allocated pages."""
        pool = _fresh_pool(n_lanes=2, n_pages=6, page_len=3, max_len=9)
        _fill_arena(pool, seed=5)
        lane, pages = pool.alloc(3)
        idx = np.asarray(pages)
        expect = {k: np.asarray(pool.cache[k][:, idx]) for k in ("k", "v")}
        img = pool.spill(lane)
        assert img.n_pages == 3 and img.nbytes() > 0
        for k in ("k", "v"):
            np.testing.assert_array_equal(img.pages[k], expect[k])
        pool.release(lane)
        pool.cache = {k: jnp.zeros_like(v) for k, v in pool.cache.items()}
        lane2, pages2 = pool.restore(img)
        for k in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(pool.cache[k][:, np.asarray(pages2)]),
                expect[k])
        pool.check_invariants()


# ---------------------------------------------------------------------------
# Prefix sharing: byte-identity, COW tails, trie eviction
# ---------------------------------------------------------------------------

def _mt_engine(**kw):
    base = dict(n_slots=N_SLOTS, max_len=MAX_LEN, beam=BEAM, page_len=3,
                n_pages=8, cache_dtype=jnp.float32)
    base.update(kw)
    return Engine(CFG, HCFG, PARAMS, HEAD_STATE, ServeConfig(**base))


class TestPrefixSharing:
    def test_shared_template_byte_identity_and_hits(self):
        """Requests sharing a 6-token template through 2 lanes: later
        admissions map the template's pages instead of re-prefilling, and
        every output still matches the per-request oracle."""
        rng = np.random.default_rng(101)
        template = rng.integers(0, CFG.vocab_size, 6).astype(np.int32)
        prompts = [
            template.copy(),
            np.concatenate([template,
                            rng.integers(0, CFG.vocab_size, 2)]),
            np.concatenate([template,
                            rng.integers(0, CFG.vocab_size, 3)]),
            template.copy(),
        ]
        prompts = [np.asarray(p, np.int32) for p in prompts]
        gen = 3
        refs = [_lockstep(p[None], gen, BEAM)[0] for p in prompts]
        eng = _mt_engine(prefix_sharing=True)
        handles = [eng.submit(Request(prompt=p, max_new_tokens=gen))
                   for p in prompts]
        eng.run()
        for h, ref in zip(handles, refs):
            np.testing.assert_array_equal(h.result(), ref)
        st = eng.stats()
        assert st["prefix"]["hits"] >= 1
        assert st["prefix"]["pages_reused"] >= 1
        assert st["prefix"]["prefill_tokens_saved"] > 0
        # Cached prefix pages are accounted apart from live ones.
        assert st["pages_in_use"] == 0 and st["pages_cached"] > 0
        eng.pool.check_invariants()

    def test_exact_repeat_takes_cow_tail(self):
        """An exact prompt repeat (5 tokens = 1 full chunk + 2-token tail
        at page_len 3) revives the cached chunk AND COWs the partial tail
        page — zero prefill — and still matches the oracle."""
        rng = np.random.default_rng(103)
        prompt = rng.integers(0, CFG.vocab_size, 5).astype(np.int32)
        gen = 3
        ref = _lockstep(prompt[None], gen, BEAM)[0]
        eng = _mt_engine(prefix_sharing=True)
        h1 = eng.submit(Request(prompt=prompt, max_new_tokens=gen))
        eng.run()
        h2 = eng.submit(Request(prompt=prompt.copy(), max_new_tokens=gen))
        eng.run()
        np.testing.assert_array_equal(h1.result(), ref)
        np.testing.assert_array_equal(h2.result(), ref)
        st = eng.stats()["prefix"]
        assert st["cow_copies"] >= 1 and st["hits"] >= 1
        assert st["prefill_tokens_saved"] >= prompt.size
        eng.pool.check_invariants()

    def test_trie_eviction_under_page_pressure(self):
        """A pool too small to cache every retired prefix must evict LRU
        trie entries to admit new prompts — and keep serving correctly."""
        rng = np.random.default_rng(107)
        gen = 3
        eng = _mt_engine(prefix_sharing=True, n_slots=1, n_pages=4)
        for _ in range(3):
            p = rng.integers(0, CFG.vocab_size, 4).astype(np.int32)
            ref = _lockstep(p[None], gen, BEAM)[0]
            h = eng.submit(Request(prompt=p, max_new_tokens=gen))
            eng.run()
            np.testing.assert_array_equal(h.result(), ref)
        assert eng.stats()["prefix"]["evictions"] >= 1
        eng.pool.check_invariants()


# ---------------------------------------------------------------------------
# Speculative decode: byte-identity over draft lengths x geometries
# ---------------------------------------------------------------------------

class TestSpeculativeDecode:
    def _run_twice(self, eng, prompts, refs, gen):
        """Cold pass then warm pass (replay drafts live) — byte-identical
        both times."""
        for _ in range(2):
            handles = [eng.submit(Request(prompt=p, max_new_tokens=gen))
                       for p in prompts]
            eng.run()
            for h, ref in zip(handles, refs):
                np.testing.assert_array_equal(h.result(), ref)

    @pytest.mark.parametrize("max_draft,page_len", [(1, 3), (4, 3)])
    def test_byte_identity_drafts_x_geometry(self, max_draft, page_len,
                                             n_pages=8):
        rng = np.random.default_rng(113 + max_draft)
        gen = 4
        prompts = _prompts(rng, 3, lo=2, hi=5)
        refs = [_lockstep(p[None], gen, BEAM)[0] for p in prompts]
        eng = _mt_engine(spec_decode=True, max_draft=max_draft,
                         page_len=page_len, n_pages=n_pages)
        self._run_twice(eng, prompts, refs, gen)
        st = eng.stats()["spec"]
        assert st["verify_steps"] > 0
        assert st["drafts_accepted"] > 0        # warm pass replayed
        assert st["mean_emitted_per_step"] > 1.0
        eng.pool.check_invariants()

    @pytest.mark.slow
    @pytest.mark.parametrize("max_draft,page_len", [(3, 1), (2, 7)])
    def test_byte_identity_odd_geometries(self, max_draft, page_len):
        # n_pages=0: full per-lane reservation (page_len=1 needs 12/lane).
        self.test_byte_identity_drafts_x_geometry(max_draft, page_len,
                                                  n_pages=0)

    def test_dense_head_spec_identity(self):
        rng = np.random.default_rng(127)
        gen = 4
        prompts = _prompts(rng, 2, lo=3, hi=4)
        refs = [_lockstep(p[None], gen, 0)[0] for p in prompts]
        eng = _mt_engine(spec_decode=True, max_draft=3, beam=0)
        self._run_twice(eng, prompts, refs, gen)

    def test_sharing_and_spec_together(self):
        """Both tentpole features on at once (the production shape):
        repeats share pages AND replay whole draft chains."""
        rng = np.random.default_rng(129)
        prompt = rng.integers(0, CFG.vocab_size, 6).astype(np.int32)
        gen = 4
        ref = _lockstep(prompt[None], gen, BEAM)[0]
        eng = _mt_engine(prefix_sharing=True, spec_decode=True, max_draft=3)
        for _ in range(2):
            h = eng.submit(Request(prompt=prompt.copy(),
                                   max_new_tokens=gen))
            eng.run()
            np.testing.assert_array_equal(h.result(), ref)
        st = eng.stats()
        assert st["prefix"]["hits"] >= 1
        assert st["spec"]["drafts_accepted"] > 0
        eng.pool.check_invariants()


class TestContinuationStore:
    def test_chain_lru_and_version(self):
        cs = ContinuationStore(capacity=3)
        cs.put((1, 2), 3)
        cs.put((1, 2, 3), 4)
        cs.put((1, 2, 3, 4), 5)
        assert cs.chain((1, 2), 5) == [3, 4, 5]     # walk caps at stored
        cs.put((9,), 9)                             # evicts LRU (1, 2)
        assert cs.get((1, 2)) is None
        assert len(cs._map) == 3

    def test_bump_version_orphans_stale_entries(self):
        """A head-state swap must make every recorded continuation
        unreachable — the tree that produced them no longer serves."""
        cs = ContinuationStore(capacity=8)
        cs.put((1, 2), 3)
        assert cs.get((1, 2)) == 3
        cs.bump_version()
        assert cs.get((1, 2)) is None
        cs.put((1, 2), 7)                  # new-version entry is reachable
        assert cs.chain((1, 2), 2) == [7]

    def test_ctx_window_bounds_key_length(self):
        from repro.serve.spec import CTX_WINDOW
        cs = ContinuationStore(capacity=4)
        long_ctx = tuple(range(CTX_WINDOW + 50))
        cs.put(long_ctx, 1)
        # Any context agreeing on the trailing window hits the same entry.
        assert cs.get((99,) * 7 + long_ctx[-CTX_WINDOW:]) == 1


# ---------------------------------------------------------------------------
# SLA scheduling: priority classes, preemption, on-demand growth
# ---------------------------------------------------------------------------

class TestSlaScheduling:
    def test_priority_class_admitted_before_fifo_order(self):
        """With one lane, a later-submitted higher class is admitted
        first; outputs are unaffected (scheduling is work order only)."""
        rng = np.random.default_rng(131)
        pa = rng.integers(0, CFG.vocab_size, 3).astype(np.int32)
        pb = rng.integers(0, CFG.vocab_size, 3).astype(np.int32)
        refs = [_lockstep(p[None], 2, BEAM)[0] for p in (pa, pb)]
        eng = _mt_engine(n_slots=1)
        lo = eng.submit(Request(prompt=pa, max_new_tokens=2, priority=0))
        hi = eng.submit(Request(prompt=pb, max_new_tokens=2, priority=5))
        eng.run()
        assert list(eng.admission_order) == [hi.request_id, lo.request_id]
        np.testing.assert_array_equal(lo.result(), refs[0])
        np.testing.assert_array_equal(hi.result(), refs[1])

    def test_preemption_spill_restore_byte_identity(self):
        """A low-class whale holding most of the pool is spilled for a
        high-class arrival and restored after — BOTH outputs byte-match
        the oracle (restore is exact, not a re-prefill)."""
        rng = np.random.default_rng(137)
        whale_p = rng.integers(0, CFG.vocab_size, 5).astype(np.int32)
        quick_p = rng.integers(0, CFG.vocab_size, 4).astype(np.int32)
        ref_w = _lockstep(whale_p[None], 7, BEAM)[0]
        ref_q = _lockstep(quick_p[None], 3, BEAM)[0]
        eng = _mt_engine(n_pages=6, preemption=True)
        hw = eng.submit(Request(prompt=whale_p, max_new_tokens=7,
                                priority=0))
        eng.step()                  # whale admitted: reserves 4 of 6 pages
        hq = eng.submit(Request(prompt=quick_p, max_new_tokens=3,
                                priority=1))
        eng.run()
        np.testing.assert_array_equal(hw.result(), ref_w)
        np.testing.assert_array_equal(hq.result(), ref_q)
        st = eng.stats()["sched"]
        assert st["preemptions"] >= 1 and st["restores"] >= 1
        assert hw.preempted >= 1 and hq.preempted == 0
        eng.pool.check_invariants()
        assert eng.pool.num_in_use == 0

    def test_ondemand_growth_packs_more_lanes(self):
        """Two requests whose worst-case reservations exceed the pool:
        "reserve" serializes them, "ondemand" runs both concurrently
        (growing at page boundaries, spilling itself if the pool fills) —
        same bytes out either way."""
        rng = np.random.default_rng(139)
        prompts = [rng.integers(0, CFG.vocab_size, 4).astype(np.int32)
                   for _ in range(2)]
        gen = 3
        refs = [_lockstep(p[None], gen, BEAM)[0] for p in prompts]
        active_after_admit = {}
        for growth in ("reserve", "ondemand"):
            eng = _mt_engine(n_pages=4, page_growth=growth)
            handles = [eng.submit(Request(prompt=p, max_new_tokens=gen))
                       for p in prompts]
            eng.step()
            active_after_admit[growth] = eng.num_active
            eng.run()
            for h, ref in zip(handles, refs):
                np.testing.assert_array_equal(h.result(), ref)
            if growth == "ondemand":
                assert eng.stats()["sched"]["page_grows"] >= 1
            eng.pool.check_invariants()
        # The packing claim: same pool, same traffic, more concurrency.
        assert active_after_admit["reserve"] == 1
        assert active_after_admit["ondemand"] == 2

    def test_reserved_unwritten_pages_reported(self):
        """stats() splits reserved-but-unwritten pages from pages_in_use:
        a freshly-admitted request under worst-case reservation holds
        whole pages it has not written into yet."""
        eng = _mt_engine(n_slots=1)
        rng = np.random.default_rng(141)
        prompt = rng.integers(0, CFG.vocab_size, 2).astype(np.int32)
        eng.submit(Request(prompt=prompt, max_new_tokens=7))
        eng.step()      # admitted: 3 pages reserved, 1 written (2 tokens)
        st = eng.stats()
        assert st["pages_in_use"] == 3
        assert st["pages_reserved_unwritten"] == 2
        eng.run()
        assert eng.stats()["pages_reserved_unwritten"] == 0

    def test_deadline_miss_counted(self):
        eng = _mt_engine(n_slots=1)
        rng = np.random.default_rng(143)
        prompt = rng.integers(0, CFG.vocab_size, 3).astype(np.int32)
        h = eng.submit(Request(prompt=prompt, max_new_tokens=2,
                               deadline_s=1e-9))
        eng.run()
        assert h.done
        assert eng.stats()["sched"]["deadline_misses"] >= 1


# ---------------------------------------------------------------------------
# Adversarial traffic generators
# ---------------------------------------------------------------------------

class TestAdversarialTraffic:
    def test_shared_prefix_burst_shape(self):
        tcfg = TrafficConfig(
            n_requests=40, rate=100.0, gen_tokens=2, vocab_size=50,
            n_templates=4, template_len=6, suffix_len=2,
            exact_repeat_frac=0.3, burst=4, interactive_frac=0.5,
            interactive_priority=2, seed=11)
        wl = make_shared_prefix_burst(tcfg)
        assert len(wl) == 40
        arrivals = [t for t, _ in wl]
        assert arrivals == sorted(arrivals) and arrivals[0] == 0.0
        # Bursty: arrival instants repeat `burst` at a time.
        assert len(set(arrivals)) <= len(wl) // tcfg.burst + 1
        lens = {r.prompt.shape[0] for _, r in wl}
        assert lens == {6, 8}               # template | template + suffix
        # Zipf templates actually repeat, including exact prompt repeats.
        keys = [tuple(r.prompt.tolist()) for _, r in wl]
        assert len(set(keys)) < len(keys)
        assert {r.priority for _, r in wl} == {0, 2}

    def test_heavy_tail_mix_shape(self):
        tcfg = TrafficConfig(
            n_requests=64, rate=100.0, prompt_len=3, gen_tokens=2,
            prompt_len_choices=(3, 5, 8), gen_tokens_choices=(2, 4),
            vocab_size=50, interactive_frac=0.6, interactive_priority=1,
            interactive_deadline_s=0.5, tail_alpha=1.2, seed=13)
        wl = make_heavy_tail_mix(tcfg)
        assert len(wl) == 64
        pris = {r.priority for _, r in wl}
        assert pris == {0, 1}
        for _, r in wl:
            if r.priority == 1:             # interactive probe
                assert r.prompt.shape[0] == 3 and r.max_new_tokens == 2
                assert r.deadline_s == 0.5
            else:                           # batch job from the buckets
                assert r.prompt.shape[0] in (3, 5, 8)
                assert r.max_new_tokens in (2, 4)
                assert r.deadline_s is None

    def test_drive_reports_per_class_latency(self):
        tcfg = TrafficConfig(
            n_requests=8, rate=500.0, prompt_len=3, gen_tokens=2,
            prompt_len_choices=(2, 3), gen_tokens_choices=(1, 2),
            vocab_size=CFG.vocab_size, interactive_frac=0.5,
            interactive_priority=1, seed=17)
        wl = make_heavy_tail_mix(tcfg)
        eng = _mt_engine()
        res = drive(eng, wl, time_scale=0.0)
        assert res["n_requests"] == 8
        classes = res["per_class"]
        assert set(classes) == {r.priority for _, r in wl}
        for snap in classes.values():
            assert snap["n"] >= 1
            assert snap["latency_p99_ms"] >= snap["latency_p50_ms"] >= 0
        assert sum(s["n"] for s in classes.values()) == 8
