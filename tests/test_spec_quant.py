"""int8 KV quantization + self-speculative decoding (PR 12).

Four contracts, layered bottom-up:

- **Windowed op**: a ``w``-row verify window through
  :func:`fused_paged_decode_attention` is BITWISE ``w`` sequential
  single-row calls on the float path (the claim the engine's
  speculative acceptance rests on), and the interpret-mode kernel
  matches the reference on quantized pools to numerical tolerance.
- **int8 engine**: greedy traffic through the ``kv_dtype="int8"``
  engine is token-exact against the bf16 default, and the per-page
  scale sidecar honors the page lifecycle (fresh pages enter at scale
  0, quarantine scrubs zero content AND scales —
  ``PagePool.check(k_scales, v_scales)`` asserts it).
- **Speculative engine**: greedy AND seeded sampled streams are
  token-for-token what the non-speculative engine emits — speculation
  may only change HOW MANY forwards produced them (``decode_steps``
  strictly drops on repeated text while ``tokens_generated``
  reconciles) — and the sampled stream's frequencies match the target
  distribution (seeded chi-square, deterministic by construction).
- **Observability**: draft counters, the ``spec_accept_rate``
  histogram, and the ``kv_bytes_per_step`` gauge flow through the
  JSONL log and render in ``python -m apex_tpu.monitor`` key-for-key
  with the registry.

Slow tier: the tp=2 quantized ShardedEngine cross and speculation
under a supervisor restart (compile-bound; ROADMAP).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.models.generation import _cached_forward, init_kv_caches
from apex_tpu.observability import (
    InMemorySink,
    JsonlSink,
    MetricsRegistry,
    build_report,
    render_report,
)
from apex_tpu.ops import _support, fused_paged_decode_attention
from apex_tpu.ops.decode_attention import (
    _pallas,
    _reference,
    paged_pages_for,
    paged_quant_fill,
    paged_quant_scatter,
)
from apex_tpu.serving import (
    EngineConfig,
    EngineSupervisor,
    InferenceEngine,
    Request,
    SamplingParams,
)
from apex_tpu.serving.speculation import propose_draft
from apex_tpu.testing_faults import ServingFaultInjector


@pytest.fixture(autouse=True)
def _pallas_off(monkeypatch):
    """Pin the jnp reference path (same rationale as
    tests/test_serving_paged.py): the bitwise claims below hold for the
    reference dispatch; the interpret-mode kernel is compared to
    tolerance, explicitly."""
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


def _repeated_prompt(period, length):
    return (list(period) * (length // len(period) + 1))[:length]


def _mixed_requests(seed=7):
    """Repeated-text prompts (the speculation-friendly shape), mixed
    greedy/sampled — the cross-engine parity traffic."""
    rng = np.random.RandomState(seed)
    specs = [(12, 8, SamplingParams()),
             (16, 6, SamplingParams(temperature=0.8, top_k=8, seed=3)),
             (8, 10, SamplingParams()),
             (12, 5, SamplingParams(temperature=1.1, seed=9)),
             (16, 7, SamplingParams(temperature=0.7, top_k=16, seed=5))]
    out = []
    for n, m, s in specs:
        period = rng.randint(0, 64, size=4).tolist()
        out.append(Request(prompt=_repeated_prompt(period, n),
                           max_new_tokens=m, sampling=s))
    return out


# ---------------------------------------------------------------------------
# n-gram drafter


class TestProposeDraft:
    def test_repeated_text_continues_the_period(self):
        ctx = [3, 7, 9, 3, 7, 9, 3, 7]
        assert propose_draft(ctx, 3) == [9, 3, 7]

    def test_prefers_longest_matching_suffix(self):
        # suffix [5, 1] last recurred before a 2; the shorter [1] also
        # occurs before a 9 — the longer order must win
        ctx = [5, 1, 2, 9, 1, 9, 5, 1]
        assert propose_draft(ctx, 1) == [2]

    def test_no_match_repeats_last_token(self):
        assert propose_draft([1, 2, 3, 4], 2) == [4, 4]

    def test_zero_and_empty(self):
        assert propose_draft([1, 2, 3], 0) == []
        assert propose_draft([], 2) == [0, 0]


# ---------------------------------------------------------------------------
# the windowed / quantized op


def _window_case(seed, b=3, kvh=2, group=2, dh=64, page_size=8, pps=4, w=3):
    """A w-row window case: each slot's page table covers its window.
    Pages are whole 8 x 128 tiles, the smallest the compiled kernel
    takes: the kernel tests run interpreted here, under Mosaic on chip."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    n_pages = b * pps + 2
    hl = kvh * group
    f = kvh * dh
    q = jax.random.normal(keys[0], (b, w, hl, dh), jnp.float32)
    k_new = jax.random.normal(keys[1], (b, w, f), jnp.float32)
    v_new = jax.random.normal(keys[2], (b, w, f), jnp.float32)
    k_pages = jax.random.normal(keys[3], (n_pages, page_size, f))
    v_pages = jax.random.normal(keys[4], (n_pages, page_size, f))
    positions = jnp.asarray([0, page_size - 1, 2 * page_size + 3])[:b]
    pt = np.full((b, pps), n_pages, np.int32)
    perm = np.random.RandomState(seed).permutation(b * pps)
    nxt = 0
    for r in range(b):
        for j in range(paged_pages_for(int(positions[r]) + w, page_size)):
            pt[r, j] = perm[nxt]
            nxt += 1
    return q, k_new, v_new, k_pages, v_pages, jnp.asarray(pt), positions


def _quantize_pools(k_pages, v_pages):
    """Round-trip float pools into (int8 pool, scale sidecar) pairs."""
    n_pages, ps, f = k_pages.shape
    kvh = 2
    zk = jnp.zeros((n_pages, ps, f), jnp.int8)
    zs = jnp.zeros((n_pages, kvh), jnp.float32)
    dest = jnp.arange(n_pages, dtype=jnp.int32)
    k_q, k_s = paged_quant_fill(zk, zs, k_pages, dest)
    v_q, v_s = paged_quant_fill(zk, zs, v_pages, dest)
    return k_q, k_s, v_q, v_s


class TestWindowedOp:
    def test_window_matches_sequential_rows_bitwise(self):
        """The acceptance rule's foundation: context row ``t`` of one
        w=3 windowed call is BITWISE the single-row call at
        ``positions + t`` (float pools; reference dispatch)."""
        q, k_new, v_new, kp, vp, pt, pos = _window_case(0)
        w = q.shape[1]
        ctx_w, kw, vw = fused_paged_decode_attention(
            q, k_new, v_new, kp, vp, pt, pos, queries_per_group=2)
        kp_s, vp_s = kp, vp
        for t in range(w):
            ctx_t, kp_s, vp_s = fused_paged_decode_attention(
                q[:, t], k_new[:, t], v_new[:, t], kp_s, vp_s, pt,
                pos + t, queries_per_group=2)
            np.testing.assert_array_equal(np.asarray(ctx_w[:, t]),
                                          np.asarray(ctx_t))
        np.testing.assert_array_equal(np.asarray(kw), np.asarray(kp_s))
        np.testing.assert_array_equal(np.asarray(vw), np.asarray(vp_s))

    @pytest.mark.slow
    def test_quantized_reference_close_to_float(self):
        """int8 pools with per-page scales reproduce the float context
        to quantization tolerance (the dequantize-inside-the-op
        contract)."""
        q, k_new, v_new, kp, vp, pt, pos = _window_case(1)
        ctx_f, _, _ = fused_paged_decode_attention(
            q, k_new, v_new, kp, vp, pt, pos, queries_per_group=2)
        k_q, k_s, v_q, v_s = _quantize_pools(kp, vp)
        ctx_q, _, _, _, _ = fused_paged_decode_attention(
            q, k_new, v_new, k_q, v_q, pt, pos, queries_per_group=2,
            k_scales=k_s, v_scales=v_s)
        np.testing.assert_allclose(np.asarray(ctx_q), np.asarray(ctx_f),
                                   atol=0.08, rtol=0.1)

    def test_interpret_kernel_quantized_matches_reference(self,
                                                          pallas_kernels):
        q, k_new, v_new, kp, vp, pt, pos = _window_case(2)
        k_q, k_s, v_q, v_s = _quantize_pools(kp, vp)
        out_k = _pallas(q, k_new, v_new, k_q, v_q, k_s, v_s, pt, pos,
                        group=2, sliding_window=None)
        # jitted like _pallas: the rescale-on-append is bitwise the same
        # on both sides only when both are compiled programs (op-by-op
        # eager dispatch lands one scale an ulp away from the fused one)
        out_r = jax.jit(_reference, static_argnames=(
            "group", "sliding_window"))(
                q, k_new, v_new, k_q, v_q, k_s, v_s, pt, pos,
                group=2, sliding_window=None)
        np.testing.assert_allclose(np.asarray(out_k[0]),
                                   np.asarray(out_r[0]),
                                   atol=2e-5, rtol=2e-5)
        for a, b in zip(out_k[1:], out_r[1:]):   # pools + scales
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("positions,w", [
        ([0, 7, 19], 3),        # the window case's own positions
        ([31, 4, 26], 1),       # 4, 1 and 4 pages in rounds of three
        ([29, None, 12], 3),    # an idle slot between live ones
    ])
    def test_interpret_kernel_quantized_rounds_match_reference(
            self, pallas_kernels, monkeypatch, positions, w):
        """int8 pools through the in-kernel page walk at THREE pages a
        DMA round (4 pages a slot is no multiple of it): a round spreads
        three scale columns over its block, the last one fewer."""
        from apex_tpu.ops import decode_attention

        q, k_new, v_new, kp, vp, _, _ = _window_case(4, w=w)
        b, ps, f = q.shape[0], kp.shape[1], kp.shape[2]
        pps, n_pages = 4, kp.shape[0]
        monkeypatch.setattr(decode_attention, "_BUFFER_BYTES", 3 * ps * f)
        decode_attention._pallas.clear_cache()
        assert decode_attention._pages_per_round(ps, f, jnp.int8, pps) == 3
        live = np.array([p is not None for p in positions])
        pos = np.array([p or 0 for p in positions], np.int32)
        pt = np.full((b, pps), n_pages, np.int32)
        perm = iter(np.random.RandomState(5).permutation(n_pages))
        for r in np.flatnonzero(live):
            for j in range(paged_pages_for(int(pos[r]) + w, ps)):
                pt[r, j] = next(perm)
        k_q, k_s, v_q, v_s = _quantize_pools(kp, vp)
        args = (q, k_new, v_new, k_q, v_q, k_s, v_s, jnp.asarray(pt),
                jnp.asarray(pos))
        try:
            out_k = _pallas(*args, group=2, sliding_window=None)
        finally:
            decode_attention._pallas.clear_cache()
        out_r = jax.jit(_reference, static_argnames=(
            "group", "sliding_window"))(*args, group=2, sliding_window=None)
        np.testing.assert_allclose(np.asarray(out_k[0])[live],
                                   np.asarray(out_r[0])[live],
                                   atol=2e-5, rtol=2e-5)
        assert not np.asarray(out_k[0])[~live].any()
        for a, b_ in zip(out_k[1:], out_r[1:]):   # pools + scales
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    def test_scale_grows_monotonically_and_rescales_residents(self):
        """Rescale-on-append: a page's scale only ever grows; resident
        rows are rescaled by old/new so their dequantized values
        survive; a zero-scale (fresh) page quantizes at exactly the
        incoming rows' absmax / 127."""
        ps, f, kvh = 4, 8, 2
        pages = jnp.zeros((2, ps, f), jnp.int8)
        scales = jnp.zeros((2, kvh), jnp.float32)
        row0 = jnp.full((1, f), 0.5, jnp.float32)
        pages, scales = paged_quant_scatter(
            pages, scales, row0, jnp.asarray([0]), jnp.asarray([0]))
        np.testing.assert_allclose(np.asarray(scales[0]), 0.5 / 127.0)
        deq0 = np.asarray(pages[0, 0], np.float32) * \
            np.repeat(np.asarray(scales[0]), f // kvh)
        np.testing.assert_allclose(deq0, 0.5, rtol=1e-2)
        # a larger row lands on the same page: scale grows, row 0's
        # dequantized value is preserved through the resident rescale
        row1 = jnp.full((1, f), 2.0, jnp.float32)
        pages, scales = paged_quant_scatter(
            pages, scales, row1, jnp.asarray([0]), jnp.asarray([1]))
        np.testing.assert_allclose(np.asarray(scales[0]), 2.0 / 127.0)
        deq0 = np.asarray(pages[0, 0], np.float32) * \
            np.repeat(np.asarray(scales[0]), f // kvh)
        np.testing.assert_allclose(deq0, 0.5, rtol=0.05)
        # untouched page: still zero scale, zero content
        assert not np.asarray(scales[1]).any()
        assert not np.asarray(pages[1]).any()

    def test_sentinel_window_rows_drop(self):
        """Window rows landing past the page table's span (or on
        unmapped sentinel entries) drop instead of clobbering the
        slot's own last mapped page."""
        q, k_new, v_new, kp, vp, _, _ = _window_case(3, b=1)
        before = np.asarray(kp)
        # a fully-unmapped 1-page table at a position past its span:
        # all three window rows must drop, the pool is untouched
        _, kk, _ = fused_paged_decode_attention(
            q, k_new, v_new, kp, vp,
            jnp.full((1, 1), kp.shape[0], jnp.int32),
            jnp.asarray([2 * kp.shape[1] + 3]), queries_per_group=2)
        np.testing.assert_array_equal(np.asarray(kk), before)


# ---------------------------------------------------------------------------
# int8 engine


class TestInt8Engine:
    def test_int8_greedy_token_exact_vs_bf16(self, small):
        """The acceptance bar: greedy traffic through the int8 pool is
        TOKEN-EXACT against the bf16 default (argmax margins of the
        logits dominate the quantization error), zero retraces."""
        model, params = small

        def greedy():
            return [r for r in _mixed_requests()
                    if r.sampling.temperature == 0.0]

        ref_eng = InferenceEngine(model, params, EngineConfig(
            max_slots=3, max_len=32, page_size=4))
        with ref_eng:
            ref = ref_eng.serve(greedy())
        q_eng = InferenceEngine(model, params, EngineConfig(
            max_slots=3, max_len=32, page_size=4, kv_dtype="int8"))
        with q_eng:
            out = q_eng.serve(greedy())
            assert q_eng.decode_retraces == 0
            q_eng.pages.check()
        for a, b in zip(ref, out):
            assert a.finish_reason == b.finish_reason
            assert a.tokens == b.tokens, (a.tokens, b.tokens)

    @pytest.mark.slow  # quarantine x int8 feature-cross: slow tier (ROADMAP)

    def test_quarantine_scrubs_scales_and_check_asserts_it(self, small):
        """Poisoned decode on the int8 engine: the scrub zeroes the
        victim's pages AND their scale sidecar rows;
        ``PagePool.check(k_scales, v_scales)`` — the invariant extended
        for quantized pools — passes after, and a synthetic dirty scale
        on a scrubbed free page makes it throw."""
        from apex_tpu.serving.slots import PageError

        model, params = small
        inj = ServingFaultInjector(poison_decode={0: (0, "nonfinite")})
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=1, max_len=16, page_size=4, prefix_cache=False,
            kv_dtype="int8"), faults=inj)
        victim = Request(prompt=_repeated_prompt([9, 2, 5, 1], 6),
                         max_new_tokens=6)
        with eng:
            res = eng.serve([victim])
            assert res[0].finish_reason == "error"
            assert eng.pages.free_count == eng.pages.n_pages
            for (kq, ks), (vq, vs) in eng._caches:
                assert not np.asarray(kq).any()
                assert not np.asarray(vq).any()
                eng.pages.check(np.asarray(ks), np.asarray(vs))
            # check() genuinely bites: a dirty scale on a scrubbed page
            dirty = np.asarray(eng._caches[0][0][1]).copy()
            dirty[next(iter(eng.pages._scrubbed)), 0] = 0.25
            with pytest.raises(PageError, match="scale"):
                eng.pages.check(dirty, dirty)
            # the scrubbed pool serves a fresh request, token-exact

            def clean():
                return Request(prompt=_repeated_prompt([3, 8], 4),
                               max_new_tokens=5)

            ref_eng = InferenceEngine(model, params, EngineConfig(
                max_slots=1, max_len=16, page_size=4,
                prefix_cache=False))
            with ref_eng:
                expect = ref_eng.serve([clean()])[0].tokens
            assert eng.serve([clean()])[0].tokens == expect

    @pytest.mark.slow  # int8 x prefix-cache cross: slow-tier composition
    def test_prefix_sharing_carries_scales(self, small):
        """Two prompts sharing an interned prefix on the int8 engine:
        the second request's suffix-only prefill reads the shared pages
        through their scales — token streams match the bf16 engine's."""
        model, params = small
        shared = _repeated_prompt([4, 11, 7, 2], 8)

        def reqs():
            return [Request(prompt=shared + [5, 9], max_new_tokens=6),
                    Request(prompt=shared + [1], max_new_tokens=6)]

        ref_eng = InferenceEngine(model, params, EngineConfig(
            max_slots=2, max_len=32, page_size=4))
        with ref_eng:
            ref = ref_eng.serve(reqs())
        q_eng = InferenceEngine(model, params, EngineConfig(
            max_slots=2, max_len=32, page_size=4, kv_dtype="int8"))
        with q_eng:
            out = q_eng.serve(reqs())
            assert q_eng.metrics.counters()["prefix_hits"] >= 1
        for a, b in zip(ref, out):
            assert a.tokens == b.tokens


# ---------------------------------------------------------------------------
# speculative engine


class TestSpeculativeEngine:
    def _serve(self, small, cfg_kwargs, reqs, metrics=None):
        model, params = small
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=3, max_len=32, page_size=4, **cfg_kwargs),
            metrics=metrics)
        with eng:
            out = eng.serve(reqs)
            assert eng.decode_retraces == 0
            counters = eng.metrics.counters()
        return out, counters

    def test_greedy_and_sampled_token_exact_with_acceptance(self, small):
        """THE speculation contract: identical mixed traffic through
        ``speculation=3`` and the plain engine is token-exact (greedy
        and seeded-sampled rows alike), with a strictly smaller
        ``decode_steps`` and a nonzero acceptance on repeated text —
        same tokens, fewer forwards."""
        ref, ref_c = self._serve(small, {}, _mixed_requests())
        out, spec_c = self._serve(small, {"speculation": 3},
                                  _mixed_requests())
        for a, b in zip(ref, out):
            assert a.finish_reason == b.finish_reason
            assert a.tokens == b.tokens, (a.tokens, b.tokens)
        # reconciliation, key-for-key: same tokens out of fewer steps
        assert spec_c["tokens_generated"] == ref_c["tokens_generated"]
        assert spec_c["decode_steps"] < ref_c["decode_steps"]
        assert spec_c["draft_tokens_accepted"] > 0
        assert spec_c["draft_tokens_accepted"] <= \
            spec_c["draft_tokens_proposed"]
        # the plain engine declares the draft counters too (zero-valued)
        assert ref_c["draft_tokens_proposed"] == 0

    @pytest.mark.slow  # speculation x int8 cross: slow-tier composition
    def test_spec_with_int8_token_exact(self, small):
        """Both tentpole knobs at once: int8 pool + speculation, still
        token-exact against the plain bf16 engine."""
        ref, _ = self._serve(small, {}, _mixed_requests(seed=11))
        out, c = self._serve(small, {"speculation": 3,
                                     "kv_dtype": "int8"},
                             _mixed_requests(seed=11))
        for a, b in zip(ref, out):
            assert a.tokens == b.tokens, (a.tokens, b.tokens)
        assert c["draft_tokens_accepted"] > 0

    @pytest.mark.slow  # statistical-distribution sweep: slow tier (ROADMAP)

    def test_sampled_frequencies_match_target_distribution(self, small):
        """Distribution preservation, measured: many seeds sample the
        SECOND generated token (the first one emitted from a verify
        window) of the same repeated-text prompt; its empirical
        frequencies must match the conditional target distribution
        (temperature-scaled, top-k-truncated softmax given the prompt
        plus each request's own first token) under a chi-square at
        alpha = 0.001. Every draw is seeded, so the verdict is
        deterministic — this fails only if the sampling law itself
        drifts."""
        model, params = small
        prompt = _repeated_prompt([5, 9, 3, 7], 16)
        temp, top_k, n_req = 1.0, 8, 200
        reqs = [Request(prompt=prompt, max_new_tokens=3,
                        sampling=SamplingParams(temperature=temp,
                                                top_k=top_k, seed=i))
                for i in range(n_req)]
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=4, max_len=32, page_size=4, speculation=3))
        with eng:
            results = eng.serve(reqs)
            assert eng.metrics.counters()["draft_tokens_proposed"] > 0

        def target_probs(ids):
            caches = init_kv_caches(model, 1, 32, stacked=False)
            logits, _ = _cached_forward(
                model, params, caches,
                jnp.asarray([ids], jnp.int32), 0, last_only=True)
            row = np.asarray(logits[0, 0], np.float64) / temp
            kth = np.sort(row)[-top_k]
            row[row < kth] = -np.inf
            e = np.exp(row - row.max())
            return e / e.sum()

        # conditional mixture: expected counts sum each first-token
        # group's target distribution for the second token
        firsts = {}
        for r in results:
            firsts.setdefault(r.tokens[0], []).append(r.tokens[1])
        expected = np.zeros(64)
        observed = np.zeros(64)
        for t0, seconds in firsts.items():
            p = target_probs(prompt + [t0])
            assert all(p[t1] > 0 for t1 in seconds), \
                "a sampled token fell outside the top-k support"
            expected += len(seconds) * p
            for t1 in seconds:
                observed[t1] += 1
        # bin tails with expected < 5 into one category (chi-square
        # validity), then test at alpha = 0.001 via Wilson–Hilferty
        big = expected >= 5.0
        obs = np.append(observed[big], observed[~big].sum())
        exp = np.append(expected[big], expected[~big].sum())
        chi2 = float(((obs - exp) ** 2 / np.maximum(exp, 1e-9)).sum())
        df = len(obs) - 1
        crit = df * (1.0 - 2.0 / (9 * df)
                     + 3.09 * np.sqrt(2.0 / (9 * df))) ** 3
        assert chi2 < crit, (chi2, crit, df)

    def test_monitor_renders_spec_and_kv_bytes(self, small, tmp_path):
        """The observability satellite end-to-end: draft counters, the
        spec_accept_rate histogram, and the kv_bytes_per_step gauge
        land in the JSONL log, reconcile key-for-key with the registry,
        and render in the monitor report."""
        model, params = small
        log = tmp_path / "spec.jsonl"
        sink = InMemorySink()
        reg = MetricsRegistry([sink, JsonlSink(str(log))])
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=3, max_len=32, page_size=4, speculation=3,
            kv_dtype="int8"), metrics=reg)
        with eng:
            eng.serve(_mixed_requests())
            page_read = eng._page_read_bytes
        counters = reg.counters()
        report = build_report(str(log))
        for key in ("draft_tokens_proposed", "draft_tokens_accepted"):
            assert report["counters"][key] == counters[key]
        assert counters["draft_tokens_accepted"] > 0
        hist = report["histograms"]["spec_accept_rate"]
        assert hist["count"] >= 1 and 0.0 <= hist["mean"] <= 1.0
        gauge = report["gauges"]["kv_bytes_per_step"]
        assert gauge > 0 and gauge % page_read == 0
        text = render_report(report)
        assert "speculation: proposed=" in text
        assert "kv bytes/step" in text
        rate = counters["draft_tokens_accepted"] \
            / counters["draft_tokens_proposed"]
        assert f"accept_rate={rate:.1%}" in text


# ---------------------------------------------------------------------------
# slow tier: compile-bound crosses (tp=2 quantized, spec under restart)


class TestSpecQuantSlow:
    @pytest.mark.slow
    def test_tp2_quantized_and_spec_token_exact(self, small):
        """ShardedEngine (tp=2) with the int8 pool and speculation on:
        token-exact against the unsharded bf16 plain engine — the scale
        sidecar shards per-head, the windowed decode body shard_maps
        with the same specs as the plain one."""
        from apex_tpu.serving import ShardedEngine
        from apex_tpu.transformer import parallel_state

        model, params = small
        ref_eng = InferenceEngine(model, params, EngineConfig(
            max_slots=3, max_len=32, page_size=4))
        with ref_eng:
            ref = ref_eng.serve(_mixed_requests(seed=13))
        parallel_state.destroy_model_parallel()
        try:
            parallel_state.initialize_model_parallel(
                tensor_model_parallel_size=2)
            sharded = ShardedEngine(model, params, EngineConfig(
                max_slots=3, max_len=32, page_size=4, kv_dtype="int8",
                speculation=3))
            with sharded:
                out = sharded.serve(_mixed_requests(seed=13))
                assert sharded.decode_retraces == 0
                assert sharded.metrics.counters()[
                    "draft_tokens_accepted"] > 0
        finally:
            parallel_state.destroy_model_parallel()
        for a, b in zip(ref, out):
            assert a.finish_reason == b.finish_reason
            assert a.tokens == b.tokens, (a.tokens, b.tokens)

    @pytest.mark.slow
    def test_spec_supervisor_restart_token_exact(self, small):
        """A decode exception mid-flight with speculation on: the
        supervisor rebuild + re-prefill replays token-exact — restart
        recovery is windowed-decode-agnostic."""
        model, params = small
        ref_eng = InferenceEngine(model, params, EngineConfig(
            max_slots=3, max_len=32, page_size=4))
        with ref_eng:
            expect = [r.tokens
                      for r in ref_eng.serve(_mixed_requests(seed=17)[:3])]
        inj = ServingFaultInjector(decode_raise_calls={2})
        sup = EngineSupervisor(
            model, params,
            EngineConfig(max_slots=3, max_len=32, page_size=4,
                         speculation=3),
            faults=inj)
        with sup:
            results = sup.serve(_mixed_requests(seed=17)[:3])
        assert sup.restarts == 1
        assert [r.tokens for r in results] == expect
