"""Paged KV cache + fused decode-step kernel tests.

The ISSUE-10 contract: the paged engine is a memory-layout optimization,
never an approximation. Tier-1 pins (a) PagePool free-list invariants
(conservation asserted like slot leaks), (b) fused-kernel-vs-reference
attention parity in interpret mode, (c) engine TOKEN EXACTNESS against
the per-request reference (``serving_reference``) — greedy and sampled
— with zero decode retraces, (d) the
``pages_exhausted`` admission shed + kv-page gauges reconciling in the
monitor report, and (e) quarantine scrubbing and releasing pages. The
compile-bound cases (supervisor restart, tp=2 sharded against
unsharded) sit in the slow tier per the ROADMAP tier policy.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.observability import (
    InMemorySink,
    JsonlSink,
    MetricsRegistry,
    build_report,
    render_report,
)
from apex_tpu.observability.report import SERVING_SHED_COUNTERS
from apex_tpu.ops import _support, fused_paged_decode_attention, \
    paged_pages_for
from apex_tpu.ops import decode_attention
from apex_tpu.ops.decode_attention import (
    _pallas,
    _reference,
    paged_quant_fill,
)
from apex_tpu.serving import (
    EngineConfig,
    EngineSupervisor,
    InferenceEngine,
    PageError,
    PagePool,
    Request,
    SamplingParams,
)
from apex_tpu.testing_faults import ServingFaultInjector
from serving_reference import reference_stream


@pytest.fixture(autouse=True)
def _pallas_off(monkeypatch):
    """Pin the jnp reference path: other test modules export
    ``APEX_TPU_FORCE_PALLAS=interpret`` process-wide at import, and the
    bitwise token-exactness claims below hold for the reference dispatch
    (the interpret-mode kernel is compared to tolerance, explicitly)."""
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
# PagePool free-list invariants


class TestPagePool:
    def test_map_release_conservation(self):
        pool = PagePool(n_pages=8, page_size=4, pages_per_slot=4)
        a = pool.map_slot(0, 7)                 # 2 pages
        b = pool.map_slot(1, 9)                 # 3 pages
        assert len(a) == 2 and len(b) == 3
        assert set(a).isdisjoint(b)
        assert pool.free_count == 3
        assert pool.in_use_count == 5
        assert pool.release_slot(0) == a
        assert pool.free_count == 5
        pool.check()

    def test_pages_for(self):
        pool = PagePool(n_pages=4, page_size=4, pages_per_slot=4)
        assert pool.pages_for(1) == 1
        assert pool.pages_for(4) == 1
        assert pool.pages_for(5) == 2
        assert paged_pages_for(5, 4) == 2

    def test_exhaustion_returns_none_not_partial(self):
        pool = PagePool(n_pages=3, page_size=4, pages_per_slot=4)
        assert pool.map_slot(0, 8) is not None  # 2 pages
        # 2 more pages needed, 1 free: no partial grab, pool untouched
        assert pool.map_slot(1, 8) is None
        assert pool.free_count == 1
        pool.check()

    def test_double_map_raises(self):
        pool = PagePool(n_pages=4, page_size=4, pages_per_slot=4)
        pool.map_slot(0, 4)
        with pytest.raises(PageError, match="already"):
            pool.map_slot(0, 4)

    def test_need_beyond_pages_per_slot_raises(self):
        pool = PagePool(n_pages=8, page_size=4, pages_per_slot=2)
        with pytest.raises(PageError, match="pages_per_slot"):
            pool.map_slot(0, 12)                # 3 pages > pps=2

    def test_extend_on_demand(self):
        pool = PagePool(n_pages=4, page_size=4, pages_per_slot=4)
        first = list(pool.map_slot(0, 3))
        assert len(first) == 1
        assert pool.extend_slot(0, 4) == []     # still fits page 0
        grown = pool.extend_slot(0, 5)          # crosses into page 1
        assert len(grown) == 1 and grown[0] not in first
        assert pool.slot_pages(0) == first + grown
        pool.check()

    def test_extend_exhausted_returns_none(self):
        pool = PagePool(n_pages=2, page_size=4, pages_per_slot=4)
        pool.map_slot(0, 4)
        pool.map_slot(1, 4)
        assert pool.extend_slot(0, 5) is None   # no page left
        assert pool.slot_pages(0) == [0]        # ownership unchanged
        pool.check()

    def test_reset_restores_free_list(self):
        pool = PagePool(n_pages=6, page_size=4, pages_per_slot=3)
        pool.map_slot(0, 12)
        pool.map_slot(1, 4)
        pool.reset()
        assert pool.free_count == 6
        assert pool.in_use_count == 0
        pool.check()

    def test_randomized_conservation(self):
        """Random map/extend/release traffic: pages are conserved at
        every step — the page analog of the slot-leak assertion."""
        rng = np.random.RandomState(41)
        pool = PagePool(n_pages=16, page_size=4, pages_per_slot=4)
        tokens = {}
        for _ in range(300):
            op = rng.randint(3)
            slot = int(rng.randint(6))
            if op == 0 and slot not in tokens:
                if pool.map_slot(slot, int(rng.randint(1, 13))) is not None:
                    tokens[slot] = True
            elif op == 1 and slot in tokens:
                pool.extend_slot(slot, int(rng.randint(1, 17)))
            elif op == 2 and slot in tokens:
                pool.release_slot(slot)
                del tokens[slot]
            assert pool.free_count + pool.in_use_count == 16
            pool.check()
        for slot in list(tokens):
            pool.release_slot(slot)
        assert pool.free_count == 16


# ---------------------------------------------------------------------------
# fused kernel vs reference (interpret mode — the tier-1 hardware proxy)


def _rand_paged_case(seed, b=3, kvh=2, group=2, dh=64, page_size=8, pps=4,
                     dtype=jnp.float32):
    # pages of whole 8 x 128 tiles (the smallest the compiled kernel
    # takes), so the parity tests below run interpreted here and under
    # Mosaic on the chip
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    n_pages = b * pps + 2
    hl = kvh * group
    f = kvh * dh
    q = jax.random.normal(keys[0], (b, hl, dh), dtype)
    k_new = jax.random.normal(keys[1], (b, f), dtype)
    v_new = jax.random.normal(keys[2], (b, f), dtype)
    k_pages = jax.random.normal(keys[3], (n_pages, page_size, f), dtype)
    v_pages = jax.random.normal(keys[4], (n_pages, page_size, f), dtype)
    # positions straddle page boundaries; each slot maps exactly the
    # pages its position needs, the rest carry the unmapped sentinel
    positions = jnp.asarray([0, page_size - 1, 2 * page_size + 3])[:b]
    pt = np.full((b, pps), n_pages, np.int32)
    perm = np.random.RandomState(seed).permutation(b * pps)
    next_page = 0
    for r in range(b):
        for j in range(paged_pages_for(int(positions[r]) + 1, page_size)):
            pt[r, j] = perm[next_page]
            next_page += 1
    return q, k_new, v_new, k_pages, v_pages, jnp.asarray(pt), positions


def _single(fn, case, group, sliding_window=None):
    """Call the windowed internals (:func:`_pallas` / :func:`_reference`)
    on an old-style single-token case: w == 1, no quantization."""
    q, k_new, v_new, kp, vp, pt, pos = case
    ctx, kk, vk, _, _ = fn(q[:, None], k_new[:, None], v_new[:, None],
                           kp, vp, None, None, pt, pos,
                           group=group, sliding_window=sliding_window)
    return ctx[:, 0], kk, vk


class TestFusedKernelParity:
    def test_interpret_matches_reference(self, pallas_kernels):
        case = _rand_paged_case(0)
        ctx_k, kk, vk = _single(_pallas, case, group=2)
        ctx_r, kr, vr = _single(_reference, case, group=2)
        np.testing.assert_allclose(ctx_k, ctx_r, atol=2e-5, rtol=2e-5)
        # the append is the same scatter on both paths: exact
        np.testing.assert_array_equal(kk, kr)
        np.testing.assert_array_equal(vk, vr)

    def test_interpret_sliding_window(self, pallas_kernels):
        case = _rand_paged_case(1)
        ctx_k, _, _ = _single(_pallas, case, group=2, sliding_window=5)
        ctx_r, _, _ = _single(_reference, case, group=2, sliding_window=5)
        np.testing.assert_allclose(ctx_k, ctx_r, atol=2e-5, rtol=2e-5)

    def test_interpret_mha_group_one(self, pallas_kernels):
        case = _rand_paged_case(2, kvh=4, group=1)
        ctx_k, _, _ = _single(_pallas, case, group=1)
        ctx_r, _, _ = _single(_reference, case, group=1)
        np.testing.assert_allclose(ctx_k, ctx_r, atol=2e-5, rtol=2e-5)

    # what the in-kernel page walk adds (PR 31): each case gives every
    # slot's position (None: the slot holds no request, its table row is
    # the sentinel), the window rows ``w`` and the layer's window. Pages
    # of 8 rows, 8 pages a slot, THREE pages a DMA round (8 is no
    # multiple of it).
    @pytest.mark.parametrize("positions,w,sliding_window", [
        ([0, 7, 8], 1, None),          # position 0; the page's last row;
                                       # the next page's first
        ([63, 5, 62], 1, None),        # slots on their last table page
        ([33, 39, 56], 1, None),       # 5, 5 and 8 pages: not whole rounds
        ([3, 12, 9], 1, None),         # fewer live pages than a round
        ([20, None, 45], 1, None),     # an idle slot between live ones
        ([None, None, 17], 1, None),   # the call opens on idle slots
        ([41, 2, None], 1, None),      # ... and closes on one
        ([None, None, None], 1, None),  # nothing to read at all
        ([6, 22, 47], 3, None),        # a verify window over a page edge
        ([60, None, 14], 3, None),     # ... on the last page, beside idle
        ([62, 10, None], 3, None),     # ... running past the table
        ([45, 30, 61], 1, 12),         # windows whose first page is not 0
        ([45, 7, 58], 3, 20),          # ... under a verify window
    ])
    def test_interpret_page_walk_edges_match_reference(
            self, pallas_kernels, monkeypatch, positions, w,
            sliding_window):
        b, kvh, group, dh, ps, pps = 3, 2, 2, 64, 8, 8
        f, hl = kvh * dh, kvh * group
        monkeypatch.setattr(decode_attention, "_BUFFER_BYTES",
                            3 * ps * f * 4)
        decode_attention._pallas.clear_cache()
        assert decode_attention._pages_per_round(ps, f, jnp.float32,
                                                 pps) == 3
        keys = jax.random.split(jax.random.PRNGKey(11), 5)
        n_pages = b * pps + 2
        q = jax.random.normal(keys[0], (b, w, hl, dh))
        k_new = jax.random.normal(keys[1], (b, w, f))
        v_new = jax.random.normal(keys[2], (b, w, f))
        k_pages = jax.random.normal(keys[3], (n_pages, ps, f))
        v_pages = jax.random.normal(keys[4], (n_pages, ps, f))
        live = np.array([p is not None for p in positions])
        pos = np.array([p or 0 for p in positions], np.int32)
        pt = np.full((b, pps), n_pages, np.int32)
        perm = iter(np.random.RandomState(3).permutation(n_pages))
        for r in np.flatnonzero(live):
            for j in range(min(pps, paged_pages_for(int(pos[r]) + w, ps))):
                pt[r, j] = next(perm)
        args = (q, k_new, v_new, k_pages, v_pages, None, None,
                jnp.asarray(pt), jnp.asarray(pos))
        try:
            got = _pallas(*args, group=group, sliding_window=sliding_window)
        finally:
            decode_attention._pallas.clear_cache()
        want = _reference(*args, group, sliding_window)
        np.testing.assert_allclose(np.asarray(got[0])[live],
                                   np.asarray(want[0])[live],
                                   atol=2e-5, rtol=2e-5)
        # a slot that holds no request reads no page and writes zeros
        assert not np.asarray(got[0])[~live].any()
        np.testing.assert_array_equal(got[1], want[1])
        np.testing.assert_array_equal(got[2], want[2])

    @pytest.mark.parametrize("quantized", [False, True])
    def test_interpret_nonfinite_slot_stays_alone(self, pallas_kernels,
                                                  monkeypatch, quantized):
        """The engine's quarantine promise at the kernel: a slot whose
        pages went non-finite (NaN K/V rows; NaN scales on int8 pools)
        perturbs no co-tenant. A round's uncopied buffer rows still hold
        an EARLIER slot's pages, the table's sentinel entries clamp to a
        pool row the poisoned slot owns, and a masked weight of 0 times
        NaN is NaN: the kernel has to keep both out of ``P @ V``. Three
        pages a round; the poisoned slots' 7 pages leave theirs in both
        buffers, the neighbours' last rounds are partial, idle slots lie
        between."""
        b, kvh, group, dh, ps, pps = 7, 2, 2, 64, 8, 8
        f, hl = kvh * dh, kvh * group
        itemsize = 1 if quantized else 4
        monkeypatch.setattr(decode_attention, "_BUFFER_BYTES",
                            3 * ps * f * itemsize)
        decode_attention._pallas.clear_cache()
        # slots 0 and 5 are poisoned; 1 and 3 hold no request
        positions = [55, None, 36, None, 4, 55, 12]
        poisoned = np.array([True, False, False, False, False, True, False])
        live = np.array([p is not None for p in positions])
        pos = np.array([p or 0 for p in positions], np.int32)
        keys = jax.random.split(jax.random.PRNGKey(23), 5)
        n_pages = b * pps + 2
        q = jax.random.normal(keys[0], (b, 1, hl, dh))
        k_new = jax.random.normal(keys[1], (b, 1, f))
        v_new = jax.random.normal(keys[2], (b, 1, f))
        k_pages = jax.random.normal(keys[3], (n_pages, ps, f))
        v_pages = jax.random.normal(keys[4], (n_pages, ps, f))
        pt = np.full((b, pps), n_pages, np.int32)
        # the last pool row (where sentinel entries clamp) first: slot 0's
        free = iter([n_pages - 1] + list(
            np.random.RandomState(9).permutation(n_pages - 1)))
        for r in np.flatnonzero(live):
            for j in range(paged_pages_for(int(pos[r]) + 1, ps)):
                pt[r, j] = next(free)
        bad_pages = pt[poisoned][pt[poisoned] < n_pages]
        k_scales = v_scales = None
        if quantized:
            zero = jnp.zeros((n_pages, ps, f), jnp.int8)
            none = jnp.zeros((n_pages, kvh), jnp.float32)
            dest = jnp.arange(n_pages, dtype=jnp.int32)
            k_pages, k_scales = paged_quant_fill(zero, none, k_pages, dest)
            v_pages, v_scales = paged_quant_fill(zero, none, v_pages, dest)

        def call(fn, poison):
            kp, vp, ks, vs, kn, vn = (k_pages, v_pages, k_scales, v_scales,
                                      k_new, v_new)
            if poison:
                nan = jnp.float32(jnp.nan)
                kn = jnp.where(poisoned[:, None, None], nan, kn)
                vn = jnp.where(poisoned[:, None, None], nan, vn)
                if quantized:
                    ks = ks.at[bad_pages].set(nan)
                    vs = vs.at[bad_pages].set(nan)
                else:
                    kp = kp.at[bad_pages].set(nan)
                    vp = vp.at[bad_pages].set(nan)
            return fn(q, kn, vn, kp, vp, ks, vs, jnp.asarray(pt),
                      jnp.asarray(pos), group=group, sliding_window=None)

        try:
            got = np.asarray(call(_pallas, poison=True)[0])
        finally:
            decode_attention._pallas.clear_cache()
        want = np.asarray(call(jax.jit(_reference, static_argnames=(
            "group", "sliding_window")), poison=False)[0])
        others = live & ~poisoned
        assert np.isnan(got[poisoned]).all()      # the fault was planted
        assert np.isfinite(got[others]).all()
        np.testing.assert_allclose(got[others], want[others],
                                   atol=2e-5, rtol=2e-5)
        assert not got[~live].any()

    def test_cpu_dispatch_is_reference(self):
        """With pallas off (the CPU default) the public entry point IS
        the reference — what makes the engine's parity with the per-request
        reference bitwise."""
        case = _rand_paged_case(3)
        ctx, kk, vk = fused_paged_decode_attention(
            *case, queries_per_group=2)
        ctx_r, kr, vr = _single(_reference, case, group=2)
        np.testing.assert_array_equal(ctx, ctx_r)
        np.testing.assert_array_equal(kk, kr)

    def test_appended_row_lands_at_position(self):
        case = _rand_paged_case(4)
        q, k_new, v_new, k_pages, _, pt, positions = case
        page_size = k_pages.shape[1]
        _, kk, _ = fused_paged_decode_attention(*case, queries_per_group=2)
        for r in range(q.shape[0]):
            page = int(pt[r, int(positions[r]) // page_size])
            np.testing.assert_array_equal(
                kk[page, int(positions[r]) % page_size], k_new[r])

    def test_shape_validation(self):
        case = _rand_paged_case(5)
        with pytest.raises(ValueError, match="queries_per_group"):
            fused_paged_decode_attention(*case, queries_per_group=3)
        q = case[0]
        with pytest.raises(ValueError, match="pool minor dim"):
            fused_paged_decode_attention(
                q, case[1], case[2], case[3][:, :, :-1], case[4][:, :, :-1],
                case[5], case[6], queries_per_group=2)


# ---------------------------------------------------------------------------
# paged engine: token exactness, shedding, gauges, quarantine


class TestPagedEngine:
    def _requests(self, seed=7):
        specs = [(4, 6, SamplingParams()),
                 (6, 5, SamplingParams(temperature=0.8, top_k=8, seed=3)),
                 (3, 8, SamplingParams()),
                 (5, 4, SamplingParams(temperature=1.1, seed=9)),
                 (2, 6, SamplingParams(temperature=0.7, top_k=16, seed=5))]
        prompts = _prompts([n for n, _, _ in specs], seed=seed)
        return [Request(prompt=p, max_new_tokens=m, sampling=s)
                for p, (_, m, s) in zip(prompts, specs)]

    def test_paged_token_exact_vs_reference(self, small):
        """The acceptance bar: mixed greedy/sampled traffic through the
        engine is TOKEN-EXACT, every row, against each request served
        alone by the per-request reference (``serving_reference``), with
        zero decode retraces, and the run returns every page. max_len
        divisible by page_size keeps the logical reduction lengths
        identical, so parity is bitwise."""
        model, params = small
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=3, max_len=16, page_size=4))
        with eng:
            out = eng.serve(self._requests())
            assert eng.decode_retraces == 0
            # drained: every page is free or held only by the prefix
            # intern index (entries survive their writer for reuse)
            assert eng.pages.free_count + \
                eng.pages.reclaimable_count == eng.pages.n_pages
            eng.pages.check()
            eng.slots.check()
        for r, req in zip(out, self._requests()):
            assert r.finish_reason == "length"
            assert r.tokens == reference_stream(model, params, req, 16), \
                (r.request_id, req.sampling)

    @pytest.mark.parametrize("act", ["swiglu", "geglu"])
    def test_gated_weight_held_apart_token_exact_vs_reference(self, act):
        """An engine built from the interleaved params of a gated model
        holds each layer's gate/up weight as ``[2, ffn, h]``, says so in
        ``decode_weights_relaid_bytes``, and serves the tokens of the
        per-request reference, which decodes on the interleaved params
        as they were handed over."""
        model = GPTModel(TransformerConfig(
            num_layers=2, hidden_size=32, num_attention_heads=4,
            vocab_size=64, max_position_embeddings=64, hidden_dropout=0.0,
            attention_dropout=0.0, activation=act,
            untie_embeddings_and_output_weights=True, init_method_std=0.3))
        params = model.init(jax.random.PRNGKey(0))
        reg = MetricsRegistry()
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=3, max_len=16, page_size=4), metrics=reg)
        given = params["transformer"]["layers"]["mlp"]["dense_h_to_4h"][
            "weight"]
        assert given.shape == (2, 2 * 128, 32)     # the caller's: as it was
        for i, layer in enumerate(eng._params["transformer"]["layers"]):
            held = layer["mlp"]["dense_h_to_4h"]["weight"]
            assert held.shape == (2, 128, 32)
            np.testing.assert_array_equal(held[0], given[i, 0::2])
            np.testing.assert_array_equal(held[1], given[i, 1::2])
        assert reg.gauges()["decode_weights_relaid_bytes"] == given.nbytes
        with eng:
            out = eng.serve(self._requests())
            assert eng.decode_retraces == 0
        streams = set()
        for r, req in zip(out, self._requests()):
            assert r.tokens == reference_stream(model, params, req, 16), \
                (r.request_id, req.sampling)
            streams.add(tuple(r.tokens[:3]))
        assert len(streams) > 1       # streams that depend on the prompt

    def test_model_that_is_not_gated_has_nothing_relaid(self, small):
        model, params = small
        reg = MetricsRegistry()
        with InferenceEngine(model, params, EngineConfig(
                max_slots=3, max_len=16, page_size=4), metrics=reg) as eng:
            w = eng._params["transformer"]["layers"][0]["mlp"][
                "dense_h_to_4h"]["weight"]
            assert w.shape == (4 * 32, 32)
        assert reg.gauges()["decode_weights_relaid_bytes"] == 0

    def test_close_resets_page_pool(self, small):
        model, params = small
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=2, max_len=16, page_size=4))
        eng.serve([Request(prompt=_prompts([4])[0], max_new_tokens=3)])
        eng.close()
        assert eng.pages.free_count == eng.pages.n_pages
        assert eng._reserved_pages == 0
        assert (eng._page_table_h == eng.pages.n_pages).all()

    def test_pages_exhausted_shed_and_monitor(self, small, tmp_path):
        """A request whose worst-case reservation exceeds the WHOLE pool
        sheds as ``pages_exhausted`` (own counter + event reason, the
        supervisor-shed convention); a fitting request completes; the kv
        page gauges/histogram render and reconcile in the monitor."""
        model, params = small
        log = tmp_path / "paged.jsonl"
        sink = InMemorySink()
        reg = MetricsRegistry([sink, JsonlSink(str(log))])
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=2, max_len=16, page_size=4, n_pages=2), metrics=reg)
        fits = Request(prompt=_prompts([3])[0], max_new_tokens=4)   # 2 pages
        doomed = Request(prompt=_prompts([8], seed=9)[0],
                         max_new_tokens=6)                          # 4 pages
        with eng:
            results = {r.request_id: r for r in eng.serve([fits, doomed])}
        assert results[doomed.request_id].finish_reason == "rejected"
        assert results[fits.request_id].finish_reason == "length"
        assert results[fits.request_id].tokens == reference_stream(
            model, params, fits, 16)
        counters = reg.counters()
        assert counters["requests_shed_pages"] == 1
        sheds = [r for r in sink.of_kind("event")
                 if r.get("event") == "request_shed"]
        assert [s["reason"] for s in sheds] == ["pages_exhausted"]
        assert sheds[0]["pages_needed"] == 4
        assert SERVING_SHED_COUNTERS["pages_exhausted"] == \
            "requests_shed_pages"
        report = build_report(str(log))
        gauges = report["gauges"]
        assert gauges["kv_pages_in_use"] == 0       # final tick: drained
        assert gauges["kv_pages_free"] == 2
        occ = report["histograms"]["kv_page_occupancy"]
        assert occ["count"] >= 1 and occ["max"] <= 1.0
        text = render_report(report)
        assert "kv pages:" in text
        # the real CLI parses the same log (pure stdlib)
        proc = subprocess.run(
            [sys.executable, "-m", "apex_tpu.monitor", str(log), "--json"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stderr
        cli = json.loads(proc.stdout)
        assert cli["counters"]["requests_shed_pages"] == 1
        assert cli["gauges"]["kv_pages_free"] == 2

    def test_quarantine_scrubs_and_releases_pages(self, small):
        """Poisoned decode output on a paged engine: the victim's pages
        return to the free list AND the scrub zeroes the pool rows it
        owned, so the poison cannot leak into a later tenant's pages."""
        model, params = small
        inj = ServingFaultInjector(poison_decode={0: (0, "nonfinite")})
        # prefix_cache=False: the all-rows-zero sweep below relies on the
        # one-owner pool (no intern index keeping prefill K/V resident);
        # quarantine WITH shared pages is covered in test_prefix_cache.py
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=1, max_len=16, page_size=4, prefix_cache=False),
            faults=inj)
        victim = Request(prompt=_prompts([5], seed=29)[0], max_new_tokens=6)
        with eng:
            res = eng.serve([victim])
            assert res[0].finish_reason == "error"
            assert eng.pages.free_count == eng.pages.n_pages
            eng.pages.check()
            assert eng.metrics.counters()["slots_quarantined"] == 1
            # only the victim ever wrote: every pool row must be zero
            for k_pages, v_pages in eng._caches:
                assert not np.asarray(k_pages).any()
                assert not np.asarray(v_pages).any()
            # the scrubbed pool serves a fresh request token-exact
            clean = Request(prompt=_prompts([4], seed=31)[0],
                            max_new_tokens=5)
            res2 = eng.serve([clean])
        assert res2[0].tokens == reference_stream(model, params, clean, 16)
        assert eng.decode_retraces == 0

    def test_randomized_arrivals_cancellations_no_page_leaks(self, small):
        """Seeded random arrivals + mid-flight cancellations on one paged
        engine: every request terminal, zero retraces, and the page pool
        drains back to full — conservation under churn."""
        model, params = small
        rng = np.random.RandomState(53)
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=3, max_len=16, page_size=4))
        reqs = [Request(prompt=rng.randint(0, 64,
                                           size=rng.randint(1, 9)).tolist(),
                        max_new_tokens=int(rng.randint(1, 8)))
                for _ in range(12)]
        with eng:
            done = {}
            pending = list(reqs)
            ticks = 0
            while pending or eng.active_count or eng.queued_count:
                while pending and eng.queued_count < 4:
                    eng.submit(pending.pop(0))
                for res in eng.tick():
                    done[res.request_id] = res
                ticks += 1
                if ticks % 5 == 0 and eng.active_count:
                    # cancel a random in-flight request
                    req, _, _ = eng.inflight()[
                        int(rng.randint(eng.active_count))]
                    eng.cancel(req.request_id)
                assert eng.pages.free_count + eng.pages.in_use_count == \
                    eng.pages.n_pages
            assert eng.decode_retraces == 0
            eng.pages.check()
            eng.slots.check()
            assert eng.pages.free_count + eng.pages.reclaimable_count == \
                eng.pages.n_pages
        assert len(done) == len(reqs)
        assert all(r.finish_reason in ("length", "eos", "cancelled")
                   for r in done.values())


# ---------------------------------------------------------------------------
# slow tier: supervisor restart + tp=2 sharded (compile-bound, ROADMAP)


class TestPagedResilience:
    @pytest.mark.slow
    def test_supervisor_restart_token_exact_on_paged(self, small):
        """A decode exception mid-flight on the PAGED engine: the
        supervisor rebuild (fresh PagePool + page tables + jit) and
        prompt+tokens re-prefill stays token-exact."""
        model, params = small
        reqs = [Request(prompt=p, max_new_tokens=n)
                for p, n in zip(_prompts([3, 5], seed=31), (6, 8))]
        inj = ServingFaultInjector(decode_raise_calls={2})
        sup = EngineSupervisor(
            model, params,
            EngineConfig(max_slots=2, max_len=16, page_size=4),
            faults=inj)
        with sup:
            results = {r.request_id: r for r in sup.serve(reqs)}
        assert sup.restarts == 1
        for req in reqs:
            assert results[req.request_id].tokens == reference_stream(
                model, params, req, 16)
        eng = sup.engine
        assert eng.pages.free_count + eng.pages.reclaimable_count == \
            eng.pages.n_pages
        eng.pages.check()

    @pytest.mark.slow
    def test_tp2_sharded_vs_unsharded_paged(self, small):
        """ShardedEngine (tp=2, page pools sharded on the heads-minor
        dim, page table replicated) against the UNSHARDED engine and
        against each request served alone by the per-request reference
        — token-exact, greedy and sampled, zero decode retraces."""
        from apex_tpu.serving import ShardedEngine
        from apex_tpu.transformer import parallel_state

        model, params = small
        rng = np.random.RandomState(61)
        specs = [(4, 6, SamplingParams()),
                 (7, 5, SamplingParams(temperature=0.8, top_k=8, seed=3)),
                 (3, 8, SamplingParams()),
                 (5, 4, SamplingParams(temperature=1.1, seed=9))]
        prompts = [rng.randint(0, 64, size=n).tolist() for n, _, _ in specs]

        def requests():
            return [Request(prompt=p, max_new_tokens=m, sampling=s)
                    for p, (_, m, s) in zip(prompts, specs)]

        unsharded = InferenceEngine(model, params, EngineConfig(
            max_slots=4, max_len=32, page_size=8))
        with unsharded:
            ref = unsharded.serve(requests())

        parallel_state.destroy_model_parallel()
        try:
            parallel_state.initialize_model_parallel(
                tensor_model_parallel_size=2)
            sharded = ShardedEngine(model, params, EngineConfig(
                max_slots=4, max_len=32, page_size=8))
            with sharded:
                out = sharded.serve(requests())
                assert sharded.decode_retraces == 0
                assert sharded.pages.free_count + \
                    sharded.pages.reclaimable_count == sharded.pages.n_pages
                sharded.pages.check()
        finally:
            parallel_state.destroy_model_parallel()
        for a, b, req in zip(ref, out, requests()):
            assert a.finish_reason == b.finish_reason
            assert a.tokens == b.tokens, (a.request_id, a.tokens, b.tokens)
            assert a.tokens == reference_stream(model, params, req, 32)
