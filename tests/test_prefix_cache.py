"""Fleet-wide prefix cache tests: interning, COW seam, affinity routing.

The ISSUE-11 contract: shared-prefix reuse is a *memory and compute*
optimization, never an approximation. Tier-1 pins (a) the hash chain's
page-aligned cumulative semantics, (b) PagePool intern/refcount
conservation under randomized map/intern/release/evict churn, (c)
hit-vs-cold engine TOKEN EXACTNESS — greedy and sampled, partial-page
and fully page-aligned boundaries — with zero decode retraces, (d)
quarantine of a sharing slot leaving co-tenants and the interned pages
intact, (e) LRU eviction under page pressure followed by re-intern, and
(f) the router's prefix-affinity discount being bounded (a hot replica
still sheds to cold peers). The compile-bound crosses (tp=2 sharded
prefix parity, supervisor restart over shared pages) sit in the slow
tier per the ROADMAP tier policy.
"""

import jax
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.ops import _support
from apex_tpu.serving import (
    EngineConfig,
    EngineSupervisor,
    InferenceEngine,
    PageError,
    PagePool,
    Request,
    SamplingParams,
)
from apex_tpu.serving.fleet import FleetConfig, Router
from apex_tpu.serving.fleet.router import _Replica
from apex_tpu.serving.prefix import (
    adapter_salt,
    common_chain_len,
    prefix_hash_chain,
    prefix_salt,
)
from apex_tpu.testing_faults import ServingFaultInjector
from serving_reference import reference_stream


@pytest.fixture(autouse=True)
def _pallas_off(monkeypatch):
    """Pin the jnp reference dispatch (same rationale as the paged
    suite): the bitwise hit-vs-cold claims below hold for the reference
    path; the interpret-mode kernel has its own tolerance tests."""
    monkeypatch.setenv("APEX_TPU_FORCE_PALLAS", "off")
    _support.pallas_mode.cache_clear()
    yield
    _support.pallas_mode.cache_clear()


@pytest.fixture(scope="module")
def small():
    model = GPTModel(TransformerConfig(
        num_layers=2, hidden_size=32, num_attention_heads=4, vocab_size=64,
        max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0))
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _prompts(lens, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 64, size=n).tolist() for n in lens]


# ---------------------------------------------------------------------------
# hash chain semantics (pure host-side)


class TestPrefixHash:
    def test_full_pages_only(self):
        toks = list(range(11))
        assert len(prefix_hash_chain(toks, 4)) == 2       # 11 // 4
        assert prefix_hash_chain(toks[:3], 4) == ()       # no full page
        # the trailing partial page never contributes: 8..10 ignored
        assert prefix_hash_chain(toks, 4) == prefix_hash_chain(toks[:8], 4)

    def test_cumulative_divergence(self):
        a = list(range(16))
        b = list(a)
        b[5] = 63                                         # inside page 1
        ca, cb = prefix_hash_chain(a, 4), prefix_hash_chain(b, 4)
        assert ca[0] == cb[0]
        assert ca[1] != cb[1] and ca[2] != cb[2] and ca[3] != cb[3]
        assert common_chain_len(ca, cb) == 1

    def test_salt_separates_models(self):
        toks = list(range(8))
        assert prefix_hash_chain(toks, 4, "a") != \
            prefix_hash_chain(toks, 4, "b")

    def test_salt_is_sampling_invariant(self):
        """The salt fingerprints architecture dims only — greedy and
        sampled requests over one model MUST share pages."""
        cfg = TransformerConfig(
            num_layers=2, hidden_size=32, num_attention_heads=4,
            vocab_size=64, max_position_embeddings=64)
        s = prefix_salt(cfg)
        assert str(cfg.num_layers) in s.split(":")[0]
        assert prefix_salt(cfg) == s                      # deterministic

    def test_adapter_salt_regression_naive_salt_aliases_tenants(self):
        """REGRESSION (multi-LoRA): adapter deltas make K/V
        adapter-specific, so the model-only salt is NOT enough — two
        tenants with identical prompts would alias each other's interned
        pages and silently read another adapter's K/V. First demonstrate
        the trap (naive chains collide), then that ``adapter_salt``
        separates tenants while base traffic (``adapter_id=None``) keeps
        the plain salt and still shares."""
        toks = list(range(12))
        base = "model-fingerprint"
        # the bug the fold exists to prevent: same prompt, same naive
        # salt, different adapters -> IDENTICAL chains (full aliasing)
        naive_a = prefix_hash_chain(toks, 4, base)
        naive_b = prefix_hash_chain(toks, 4, base)
        assert naive_a == naive_b
        chain_a = prefix_hash_chain(toks, 4, adapter_salt(base, "tenant-a"))
        chain_b = prefix_hash_chain(toks, 4, adapter_salt(base, "tenant-b"))
        assert chain_a != chain_b                 # tenants never share
        assert chain_a != naive_a                 # nor with base traffic
        assert common_chain_len(chain_a, chain_b) == 0
        # None is base traffic: plain salt unchanged, base still shares
        assert adapter_salt(base, None) == base
        assert prefix_hash_chain(toks, 4, adapter_salt(base)) == naive_a


# ---------------------------------------------------------------------------
# PagePool: intern index, refcounts, eviction


class TestInternPool:
    def test_intern_outlives_writer_and_is_shared(self):
        pool = PagePool(n_pages=8, page_size=4, pages_per_slot=4,
                        lru_capacity=8)
        chain = prefix_hash_chain(list(range(8)), 4)
        owned = pool.map_slot(0, 8)                       # 2 pages
        assert pool.intern_prefix(chain, owned)
        assert pool.release_slot(0) == []                 # entry holds refs
        assert pool.free_count == 6
        assert pool.reclaimable_count == 2
        pages, matched = pool.match_prefix(chain)
        assert matched == 2 and pages == owned
        # a second tenant pins the shared pages + one private
        mapped = pool.map_slot(1, 9, shared=pages)
        assert mapped[:2] == pages and len(mapped) == 3
        pool.check()
        freed = pool.release_slot(1)
        assert len(freed) == 1 and freed[0] not in pages  # private only
        pool.check()

    def test_intern_off_at_zero_capacity(self):
        pool = PagePool(n_pages=4, page_size=4, pages_per_slot=4,
                        lru_capacity=0)
        owned = pool.map_slot(0, 8)
        assert not pool.intern_prefix((1, 2), owned)
        assert pool.interned_count == 0
        assert pool.release_slot(0) == owned              # nothing held

    def test_lru_capacity_evicts_oldest(self):
        pool = PagePool(n_pages=8, page_size=4, pages_per_slot=4,
                        lru_capacity=2)
        chains = [prefix_hash_chain([i] * 4, 4) for i in range(3)]
        for slot, chain in enumerate(chains):
            pages = pool.map_slot(slot, 4)
            assert pool.intern_prefix(chain, pages)
            pool.release_slot(slot)
        assert pool.interned_count == 2
        assert pool.evictions == 1
        assert pool.match_prefix(chains[0])[1] == 0       # oldest gone
        assert pool.match_prefix(chains[2])[1] == 1
        assert pool.free_count + pool.reclaimable_count == 8
        pool.check()

    def test_longer_chain_subsumes_shorter(self):
        pool = PagePool(n_pages=8, page_size=4, pages_per_slot=4,
                        lru_capacity=8)
        toks = list(range(12))
        short, full = prefix_hash_chain(toks[:8], 4), \
            prefix_hash_chain(toks, 4)
        pages = pool.map_slot(0, 12)
        assert pool.intern_prefix(short, pages[:2])
        assert pool.intern_prefix(full, pages)            # upgrades
        assert pool.interned_count == 1
        assert pool.evictions == 0                        # upgrade, not evict
        assert pool.match_prefix(full)[1] == 3
        pool.release_slot(0)
        pool.check()

    def test_pressure_evicts_reclaimable_not_slot_held(self):
        pool = PagePool(n_pages=4, page_size=4, pages_per_slot=4,
                        lru_capacity=8)
        chain = prefix_hash_chain(list(range(8)), 4)
        pool.intern_prefix(chain, pool.map_slot(0, 8))
        pool.release_slot(0)                              # 2 reclaimable
        assert pool.map_slot(1, 12) is not None           # needs 3: evicts
        assert pool.evictions == 1
        assert pool.match_prefix(chain)[1] == 0
        pool.check()
        # now every referenced page is slot-held: nothing evictable
        assert pool.map_slot(2, 8) is None
        pool.check()

    def test_randomized_intern_churn_conserves(self):
        """Random arrivals x cancellations x interning x pressure
        evictions: refcounts recomputed from memberships match at every
        step, and pages partition into free/referenced exactly."""
        rng = np.random.RandomState(47)
        pool = PagePool(n_pages=16, page_size=4, pages_per_slot=4,
                        lru_capacity=4)
        live = {}                                         # slot -> chain
        for _ in range(400):
            op = rng.randint(4)
            slot = int(rng.randint(6))
            if op == 0 and slot not in live:
                toks = rng.randint(0, 8, size=rng.randint(4, 14)).tolist()
                chain = prefix_hash_chain(toks, 4)
                shared, matched = pool.match_prefix(chain)
                mapped = pool.map_slot(slot, len(toks),
                                       shared=shared or None)
                if mapped is not None:
                    live[slot] = (chain, mapped)
            elif op == 1 and slot in live:
                chain, mapped = live[slot]
                if chain:
                    pool.intern_prefix(chain, mapped[:len(chain)])
            elif op == 2 and slot in live:
                pool.release_slot(slot)
                del live[slot]
            elif op == 3 and slot in live:
                pool.extend_slot(slot, int(rng.randint(1, 17)))
            assert pool.free_count + pool.in_use_count == 16
            pool.check()
        for slot in list(live):
            pool.release_slot(slot)
        assert pool.free_count + pool.reclaimable_count == 16
        pool.reset()
        assert pool.free_count == 16 and pool.interned_count == 0
        pool.check()


# ---------------------------------------------------------------------------
# engine: hit-vs-cold token exactness, COW seam, quarantine, eviction


def _shared_prefix_requests(seed=19):
    """Mixed traffic over one 8-token prefix (2 full pages at page_size
    4): a miss that interns, a fully page-aligned hit (the skip_first
    COW seam), and partial-page-suffix hits, greedy AND sampled."""
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, 64, size=8).tolist()

    def req(extra, max_new, sampling):
        return Request(
            prompt=prefix + rng.randint(0, 64, size=extra).tolist()
            if extra else list(prefix),
            max_new_tokens=max_new, sampling=sampling)

    return prefix, [
        req(3, 5, SamplingParams()),                       # miss, interns
        req(0, 6, SamplingParams()),                       # aligned hit
        req(5, 4, SamplingParams(temperature=0.8, top_k=8, seed=3)),
        req(1, 5, SamplingParams(temperature=1.1, seed=9)),
    ]


class TestPrefixEngine:
    def test_hit_vs_cold_token_exact(self, small):
        """The acceptance bar: identical shared-prefix traffic through
        ``prefix_cache=True`` and ``prefix_cache=False`` engines is
        TOKEN-EXACT — greedy and sampled, aligned and partial-page
        boundaries — with zero decode retraces, hits + misses == paged
        prefills, and every page free or interned after drain."""
        model, params = small
        _, reqs = _shared_prefix_requests()
        cold = InferenceEngine(model, params, EngineConfig(
            max_slots=2, max_len=32, page_size=4, prefix_cache=False))
        with cold:
            _, cold_reqs = _shared_prefix_requests()
            ref = cold.serve(cold_reqs)
            assert cold.decode_retraces == 0
            assert cold.metrics.counters()["prefix_hits"] == 0
            assert cold.pages.interned_count == 0
        hot = InferenceEngine(model, params, EngineConfig(
            max_slots=2, max_len=32, page_size=4))
        with hot:
            out = hot.serve(reqs)
            assert hot.decode_retraces == 0
            c = hot.metrics.counters()
            assert c["prefix_misses"] == 1
            assert c["prefix_hits"] == 3
            assert c["prefix_hits"] + c["prefix_misses"] == c["prefills"]
            assert c["prefix_pages_shared"] == 6          # 2 pages x 3 hits
            assert hot.pages.interned_count >= 1
            assert hot.pages.free_count + hot.pages.reclaimable_count == \
                hot.pages.n_pages
            hot.pages.check()
            hot.slots.check()
        for a, b in zip(ref, out):
            assert a.finish_reason == b.finish_reason
            assert a.tokens == b.tokens, (a.request_id, a.tokens, b.tokens)
        for r, req in zip(out, reqs):
            assert r.tokens == reference_stream(model, params, req, 32)

    @pytest.mark.slow  # COW edge-seam sweep: slow tier (ROADMAP)

    def test_partial_page_boundary_cow(self, small):
        """Two prompts sharing full pages but diverging INSIDE the
        trailing partial page: the second maps the shared run and
        prefills its divergent suffix into private pages only — serving
        the first prompt again (now a hit itself) stays token-exact,
        proving the divergent tenant never wrote the shared pages."""
        model, params = small
        rng = np.random.RandomState(23)
        base = rng.randint(0, 64, size=10).tolist()       # 2 pages + 2
        fork = list(base)
        fork[9] = (fork[9] + 1) % 64                      # partial page only
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=2, max_len=32, page_size=4))
        with eng:
            for prompt in (base, fork, base):
                req = Request(prompt=list(prompt), max_new_tokens=5)
                res = eng.serve([req])
                assert res[0].tokens == reference_stream(
                    model, params, req, 32), prompt
            c = eng.metrics.counters()
            assert c["prefix_misses"] == 1 and c["prefix_hits"] == 2
            assert eng.decode_retraces == 0
            eng.pages.check()

    @pytest.mark.slow  # quarantine x prefix feature-cross: slow tier (ROADMAP)

    def test_quarantine_sharing_slot_leaves_co_tenants_exact(self, small):
        """Poisoned decode on one of two slots sharing interned prefix
        pages: the victim quarantines (only its PRIVATE freed pages are
        scrubbed), the co-tenant finishes token-exact, and a later
        request still HITS the interned prefix and decodes exactly —
        shared pages survive a sharing tenant's quarantine untouched."""
        model, params = small
        rng = np.random.RandomState(29)
        prefix = rng.randint(0, 64, size=8).tolist()
        survivor = Request(prompt=prefix + [3, 4], max_new_tokens=6)
        victim = Request(prompt=prefix + [9], max_new_tokens=6)
        # slot 1 = the second prefill (the victim); poison a decode call
        # late enough that both tenants are mid-decode
        inj = ServingFaultInjector(poison_decode={3: (1, "nonfinite")})
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=2, max_len=32, page_size=4), faults=inj)
        with eng:
            results = {r.request_id: r
                       for r in eng.serve([survivor, victim])}
            assert results[victim.request_id].finish_reason == "error"
            assert results[survivor.request_id].tokens == reference_stream(
                model, params, survivor, 32)
            assert eng.metrics.counters()["slots_quarantined"] == 1
            eng.pages.check()
            assert eng.pages.free_count + eng.pages.reclaimable_count == \
                eng.pages.n_pages
            late = Request(prompt=prefix + [7, 8, 9], max_new_tokens=5)
            res = eng.serve([late])
            assert res[0].tokens == reference_stream(model, params,
                                                     late, 32)
            c = eng.metrics.counters()
            assert c["prefix_hits"] >= 2                  # victim + late
            assert eng.decode_retraces == 0

    @pytest.mark.slow  # eviction stress sweep: slow tier (ROADMAP)

    def test_lru_eviction_under_pressure_then_reintern(self, small):
        """A pool sized so distinct prefixes cannot all stay interned:
        admission keeps working (eviction instead of shedding), the
        ``prefix_evictions`` counter advances, conservation holds, and
        the evicted prefix re-interns on its next miss, token-exact."""
        model, params = small
        rng = np.random.RandomState(37)
        prefixes = [rng.randint(0, 64, size=8).tolist() for _ in range(4)]
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=2, max_len=16, page_size=4, n_pages=8))
        with eng:
            for p in prefixes:                            # distinct misses
                req = Request(prompt=list(p), max_new_tokens=4)
                res = eng.serve([req])
                assert res[0].tokens == reference_stream(
                    model, params, req, 16)
            c = eng.metrics.counters()
            assert c["prefix_evictions"] >= 1             # pressure evicted
            assert c["prefix_misses"] == 4
            eng.pages.check()
            assert eng.pages.free_count + eng.pages.reclaimable_count == \
                eng.pages.n_pages
            # the first prefix was evicted: a repeat misses, re-interns,
            # and an immediate second repeat hits
            again = Request(prompt=list(prefixes[0]), max_new_tokens=4)
            res = eng.serve([again])
            assert res[0].tokens == reference_stream(
                model, params, again, 16)
            hit = Request(prompt=list(prefixes[0]), max_new_tokens=4)
            res = eng.serve([hit])
            assert res[0].tokens == reference_stream(model, params, hit, 16)
            c = eng.metrics.counters()
            assert c["prefix_hits"] >= 1
            assert eng.decode_retraces == 0

    def test_close_clears_intern_index(self, small):
        model, params = small
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=2, max_len=16, page_size=4))
        eng.serve([Request(prompt=_prompts([8])[0], max_new_tokens=3)])
        assert eng.pages.interned_count == 1
        eng.close()
        assert eng.pages.free_count == eng.pages.n_pages
        assert eng.pages.interned_count == 0


# ---------------------------------------------------------------------------
# router: bounded prefix-affinity discount


class _StubSup:
    def __init__(self, queued, active, service):
        self.queued_count = queued
        self.active_count = active
        self.service_estimate_s = service


def _stub_replica(rid, queued, active, service):
    return _Replica(rid, _StubSup(queued, active, service))


class TestRouterAffinity:
    def test_resident_match_wins_equal_load(self):
        rt = Router(affinity_weight=0.3)
        chain = (11, 22, 33)
        rt.note_dispatch(1, chain)
        a = _stub_replica(0, queued=2, active=0, service=0.5)
        b = _stub_replica(1, queued=2, active=0, service=0.5)
        assert rt.pick([a, b], chain=chain).replica_id == 1
        # no chain / no match: id still breaks the tie deterministically
        assert rt.pick([a, b]).replica_id == 0
        assert rt.pick([a, b], chain=(99,)).replica_id == 0

    def test_partial_match_scores_fractionally(self):
        rt = Router(affinity_weight=0.5)
        rt.note_dispatch(0, (1, 2))
        assert rt.affinity(0, (1, 2, 3, 4)) == 0.5
        assert rt.affinity(0, (7, 8)) == 0.0
        assert rt.affinity(1, (1, 2)) == 0.0              # not resident

    def test_bonus_is_bounded_load_still_sheds(self):
        """The discount can never beat a big enough load gap: with
        weight w a full match scales cost by (1 - w) > 0, so a hot
        resident replica still loses to an idle cold peer."""
        rt = Router(affinity_weight=0.3)
        chain = (1, 2, 3)
        rt.note_dispatch(0, chain)
        hot = _stub_replica(0, queued=6, active=0, service=0.5)   # 3.0->2.1
        cold = _stub_replica(1, queued=1, active=0, service=0.5)  # 0.5
        assert rt.pick([hot, cold], chain=chain).replica_id == 1

    def test_invalidate_forgets_residency(self):
        rt = Router(affinity_weight=0.3)
        rt.note_dispatch(0, (1, 2))
        rt.invalidate(0)
        assert rt.affinity(0, (1, 2)) == 0.0

    def test_residency_is_bounded_lru(self):
        rt = Router(affinity_weight=0.3, residency_capacity=2)
        for i in range(5):
            rt.note_dispatch(0, (i,))
        assert rt.affinity(0, (0,)) == 0.0                # evicted
        assert rt.affinity(0, (4,)) == 1.0

    def test_weight_validation(self):
        with pytest.raises(ValueError, match="affinity_weight"):
            Router(affinity_weight=1.0)
        with pytest.raises(ValueError, match="prefix_affinity_weight"):
            FleetConfig(prefix_affinity_weight=-0.1)
        with pytest.raises(ValueError, match="prefix_affinity_weight"):
            FleetConfig(prefix_affinity_weight=1.5)


# ---------------------------------------------------------------------------
# slow tier: compile-bound crosses (ROADMAP tier policy)


class TestPrefixResilience:
    @pytest.mark.slow
    def test_supervisor_restart_over_shared_pages_token_exact(self, small):
        """A decode crash while two requests share interned prefix
        pages: the supervisor rebuild (fresh pool, EMPTY intern index)
        re-prefills through the same prefix-cache admit path and every
        request stays token-exact — recovery and reuse compose."""
        model, params = small
        rng = np.random.RandomState(43)
        prefix = rng.randint(0, 64, size=8).tolist()
        reqs = [Request(prompt=prefix + [1, 2], max_new_tokens=6),
                Request(prompt=prefix + [5], max_new_tokens=8)]
        inj = ServingFaultInjector(decode_raise_calls={3})
        sup = EngineSupervisor(
            model, params,
            EngineConfig(max_slots=2, max_len=32, page_size=4),
            faults=inj)
        with sup:
            results = {r.request_id: r for r in sup.serve(reqs)}
        assert sup.restarts == 1
        for req in reqs:
            assert results[req.request_id].tokens == reference_stream(
                model, params, req, 32)
        eng = sup.engine
        assert eng.pages.free_count + eng.pages.reclaimable_count == \
            eng.pages.n_pages
        eng.pages.check()

    @pytest.mark.slow
    def test_tp2_sharded_prefix_hits_vs_unsharded(self, small):
        """ShardedEngine (tp=2, prefix cache ON, suffix prefill
        shard_mapped) against the unsharded engine with the prefix cache
        OFF (every prefill whole) and against each request served alone
        by the per-request reference, on shared-prefix traffic:
        token-exact with real prefix hits on the sharded side — the mesh
        cannot hide in the reuse path nor vice versa."""
        from apex_tpu.serving import ShardedEngine
        from apex_tpu.transformer import parallel_state

        model, params = small
        _, reqs = _shared_prefix_requests(seed=59)
        cold = InferenceEngine(model, params, EngineConfig(
            max_slots=2, max_len=32, page_size=4, prefix_cache=False))
        with cold:
            ref = cold.serve(_shared_prefix_requests(seed=59)[1])

        parallel_state.destroy_model_parallel()
        try:
            parallel_state.initialize_model_parallel(
                tensor_model_parallel_size=2)
            sharded = ShardedEngine(model, params, EngineConfig(
                max_slots=2, max_len=32, page_size=4))
            with sharded:
                out = sharded.serve(reqs)
                assert sharded.decode_retraces == 0
                c = sharded.metrics.counters()
                assert c["prefix_hits"] == 3
                assert c["prefix_hits"] + c["prefix_misses"] == \
                    c["prefills"]
                assert sharded.pages.free_count + \
                    sharded.pages.reclaimable_count == sharded.pages.n_pages
                sharded.pages.check()
        finally:
            parallel_state.destroy_model_parallel()
        for a, b, req in zip(ref, out, reqs):
            assert a.finish_reason == b.finish_reason
            assert a.tokens == b.tokens, (a.request_id, a.tokens, b.tokens)
            assert a.tokens == reference_stream(model, params, req, 32)
