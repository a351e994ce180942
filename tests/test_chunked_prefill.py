"""Chunked prefill: token-budgeted mixed ticks (docs/serving.md#chunked-prefill).

Correctness anchor: with ``prefill_token_budget`` set, a prompt
prefills as a sequence of fixed-shape chunk programs interleaved with
co-tenant decode steps — and the engine's output must stay TOKEN-EXACT
against the monolithic (unchunked) engine, greedy AND sampled, across
every KV configuration chunking composes with (bf16 and int8 pages,
speculation, prefix cache, LoRA). The scheduling property rides along:
a long prompt can no longer monopolize a tick, so co-tenant decode
advances every tick while the long prompt is mid-prefill.
"""

import numpy as np
import pytest

import jax

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.observability import (
    InMemorySink,
    MetricsRegistry,
    build_report,
    render_report,
)
from apex_tpu.observability.trace import check_span_conservation
from apex_tpu.serving import (
    EngineConfig,
    EngineSupervisor,
    FCFSScheduler,
    InferenceEngine,
    Request,
    SamplingParams,
    SchedulerConfig,
)
from apex_tpu.testing_faults import ServingFaultInjector
from serving_reference import reference_stream


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


def _serve(model, params, cfg, requests, *, metrics=None, on_tick=None):
    eng = InferenceEngine(model, params, cfg, metrics=metrics)
    try:
        results = eng.serve(requests, on_tick=on_tick)
    finally:
        eng.close()
    return eng, {r.request_id: r for r in results}


def _mixed_requests(prompts, *, sampled=False):
    reqs = []
    for i, p in enumerate(prompts):
        sp = SamplingParams(temperature=0.9, top_k=8, seed=100 + i) \
            if sampled and i % 2 else SamplingParams()
        reqs.append(Request(prompt=p, max_new_tokens=6, sampling=sp,
                            request_id=i))
    return reqs


class TestConfigValidation:
    def test_budget_below_one_rejected(self):
        with pytest.raises(ValueError, match="prefill_token_budget"):
            EngineConfig(max_slots=2, max_len=16, prefill_token_budget=0)

    def test_paged_budget_below_page_size_rejected(self):
        with pytest.raises(ValueError, match="page-aligned"):
            EngineConfig(max_slots=2, max_len=32, page_size=8,
                         prefill_token_budget=4)


class TestTokenExactness:
    """Chunked == monolithic, token for token."""

    def test_greedy_and_sampled_exact(self, small):
        model, params = small
        prompts = _prompts((23, 5, 11, 17), seed=41)
        mono_cfg = EngineConfig(max_slots=4, max_len=64, page_size=4,
                                n_pages=96)
        chunk_cfg = EngineConfig(max_slots=4, max_len=64, page_size=4,
                                 n_pages=96, prefill_token_budget=8)
        _, mono = _serve(model, params, mono_cfg,
                         _mixed_requests(prompts, sampled=True))
        eng, chunked = _serve(model, params, chunk_cfg,
                              _mixed_requests(prompts, sampled=True))
        for rid, m in mono.items():
            c = chunked[rid]
            assert c.tokens == m.tokens, rid
            assert c.finish_reason == m.finish_reason
        # the 23-token prompt could not fit one 8-token tick budget
        assert chunked[0].prefill_chunks and chunked[0].prefill_chunks > 1
        # monolithic results never carry the field
        assert all(m.prefill_chunks is None for m in mono.values())
        assert eng.decode_retraces == 0
        assert eng.chunk_compiles <= len(eng.buckets)

    @pytest.mark.slow  # parity vs the reference: slow-tier family (ROADMAP)
    def test_chunked_matches_per_request_reference(self, small):
        """Chunked output, greedy and sampled, equals each request
        served alone by the per-request reference — not just the
        monolithic engine (guards against a bug both engines share)."""
        model, params = small
        prompts = _prompts((19, 6), seed=43)
        cfg = EngineConfig(max_slots=2, max_len=64, page_size=4,
                           prefill_token_budget=4)
        reqs = _mixed_requests(prompts, sampled=True)
        _, out = _serve(model, params, cfg, reqs)
        for req in reqs:
            assert out[req.request_id].tokens == reference_stream(
                model, params, req, 64), req.request_id


@pytest.mark.slow  # compile-bound feature-cross parity: slow tier;
# tier-1 keeps the chunked-vs-monolithic exactness above
class TestComposition:
    def test_int8_paged_exact(self, small):
        """Page-aligned chunk boundaries keep int8 quantization bitwise:
        every fresh page is filled whole by one scatter, so scales —
        and therefore tokens — match the monolithic engine."""
        model, params = small
        prompts = _prompts((21, 9), seed=47)
        base = dict(max_slots=2, max_len=64, page_size=4,
                    n_pages=64, kv_dtype="int8")
        _, mono = _serve(model, params, EngineConfig(**base),
                         _mixed_requests(prompts, sampled=True))
        _, chunked = _serve(
            model, params,
            EngineConfig(prefill_token_budget=8, **base),
            _mixed_requests(prompts, sampled=True))
        for rid, m in mono.items():
            assert chunked[rid].tokens == m.tokens, rid

    def test_prefix_cache_exact_and_counted(self, small):
        """Chunked prefill interns and reuses shared prefixes exactly
        like the monolithic path; hit/miss counters reconcile with
        prefills even though the hit is stamped at completion."""
        model, params = small
        rng = np.random.RandomState(53)
        shared = rng.randint(0, 64, size=12).tolist()
        prompts = [shared + rng.randint(0, 64, size=6).tolist(),
                   shared + rng.randint(0, 64, size=9).tolist()]
        base = dict(max_slots=2, max_len=64, page_size=4,
                    n_pages=64, prefix_cache=True,
                    scheduler=SchedulerConfig(max_prefills_per_tick=1))
        _, mono = _serve(model, params, EngineConfig(**base),
                         _mixed_requests(prompts))
        reg = MetricsRegistry()
        eng, chunked = _serve(
            model, params, EngineConfig(prefill_token_budget=8, **base),
            _mixed_requests(prompts), metrics=reg)
        for rid, m in mono.items():
            assert chunked[rid].tokens == m.tokens, rid
        counters = reg.counters()
        assert counters["prefix_hits"] >= 1
        assert counters["prefix_hits"] + counters["prefix_misses"] == \
            counters["prefills"]

    def test_speculation_exact(self, small):
        model, params = small
        prompts = _prompts((18, 7), seed=59)
        base = dict(max_slots=2, max_len=64, page_size=4,
                    n_pages=64, speculation=3)
        _, mono = _serve(model, params, EngineConfig(**base),
                         _mixed_requests(prompts))
        _, chunked = _serve(
            model, params, EngineConfig(prefill_token_budget=8, **base),
            _mixed_requests(prompts))
        for rid, m in mono.items():
            assert chunked[rid].tokens == m.tokens, rid

    def test_lora_exact(self, small):
        """Chunked prefill resolves the adapter row once at admission
        and feeds it to every chunk — per-tenant output matches the
        monolithic engine."""
        from apex_tpu.lora import AdapterStore, random_adapter

        model, params = small
        adapters = AdapterStore(model.config, 2, max_adapters=2)
        adapters.load("t0", random_adapter(model.config, 2,
                                           jax.random.PRNGKey(5)))
        prompts = _prompts((17, 6), seed=61)

        def reqs():
            return [Request(prompt=p, max_new_tokens=5, request_id=i,
                            sampling=SamplingParams(
                                adapter_id="t0" if i == 0 else None))
                    for i, p in enumerate(prompts)]

        base = dict(max_slots=2, max_len=64, page_size=4,
                    n_pages=64)

        def run(cfg):
            eng = InferenceEngine(model, params, cfg, adapters=adapters)
            try:
                return {r.request_id: r for r in eng.serve(reqs())}
            finally:
                eng.close()

        mono = run(EngineConfig(**base))
        chunked = run(EngineConfig(prefill_token_budget=8, **base))
        for rid, m in mono.items():
            assert chunked[rid].tokens == m.tokens, rid


class TestMixedTicks:
    def test_cotenant_decode_advances_during_long_prefill(self, small):
        """The tentpole scheduling property, deterministically: while a
        long prompt is mid-chunked-prefill, a co-tenant that is already
        decoding emits a token EVERY tick — the long prefill never
        stalls it. (The monolithic engine runs the whole long prefill
        inside one tick instead.)"""
        model, params = small
        short = Request(prompt=_prompts([3], seed=67)[0],
                        max_new_tokens=20, request_id=0)
        long_p = Request(prompt=_prompts([40], seed=68)[0],
                         max_new_tokens=4, request_id=1)
        cfg = EngineConfig(max_slots=2, max_len=64, page_size=4,
                           n_pages=64,
                           prefill_token_budget=8)
        eng = InferenceEngine(model, params, cfg)
        try:
            eng.submit(short)
            eng.tick()                      # short prefills, step 0 goes out
            eng.submit(long_p)
            progress = []
            while long_p.request_id not in eng.completed:
                mid_prefill = bool(eng._prefilling)
                before = len(eng._active[0].tokens) \
                    if 0 in eng._active else None
                eng.tick()
                after = len(eng._active[0].tokens) \
                    if 0 in eng._active else None
                if mid_prefill and before is not None \
                        and after is not None:
                    progress.append(after - before)
            # 40 tokens / 8-token budget = 5 chunk ticks; the short
            # request gained one token on every one of them
            assert len(progress) >= 4
            assert all(p == 1 for p in progress), progress
            res = eng.completed[long_p.request_id]
            assert res.prefill_chunks == 5
        finally:
            eng.close()

    def test_budget_bounds_tokens_per_tick(self, small):
        model, params = small
        reg = MetricsRegistry()
        cfg = EngineConfig(max_slots=4, max_len=64, page_size=4,
                           prefill_token_budget=8)
        _serve(model, params, cfg,
               _mixed_requests(_prompts((23, 11, 5, 9), seed=71)),
               metrics=reg)
        hist = reg.histogram("prefill_tokens_per_tick")
        assert hist is not None and hist.count > 0
        assert hist.max <= 8
        # counter/histogram reconciliation: every chunked token is
        # observed exactly once, so the histogram total is the chunked
        # prompt-token volume
        assert hist.sum == 23 + 11 + 5 + 9

    def test_ttft_stamped_at_emitting_tick(self, small):
        """Satellite: under multi-tick prefill, ttft_s is stamped when
        the FINAL chunk emits token #1 — it equals queue_s + prefill_s
        (which now spans several ticks), never just the first chunk."""
        model, params = small
        cfg = EngineConfig(max_slots=2, max_len=64, page_size=4,
                           prefill_token_budget=4)
        req = Request(prompt=_prompts([20], seed=73)[0],
                      max_new_tokens=3, request_id=0)
        _, out = _serve(model, params, cfg, [req])
        res = out[0]
        assert res.prefill_chunks == 5
        assert res.ttft_s is not None
        assert res.ttft_s == pytest.approx(
            res.queue_s + res.prefill_s, abs=0.05)

    @pytest.mark.slow  # ordering sweep over full engine builds: slow tier (ROADMAP)

    def test_fcfs_admission_order_preserved(self, small):
        """Token-budget admission stays strictly FCFS: the admission
        log lists requests in submit order even when budget starvation
        delays later heads by several ticks."""
        model, params = small
        cfg = EngineConfig(max_slots=4, max_len=64, page_size=4,
                           prefill_token_budget=4)
        reqs = _mixed_requests(_prompts((15, 3, 9, 4), seed=79))
        eng, _ = _serve(model, params, cfg, reqs)
        assert eng.admission_log == [r.request_id for r in reqs]


class TestTracing:
    def test_multi_segment_prefill_conserves(self, small):
        """A chunked request's prefill phase is one span per chunk —
        contiguous, chunk-indexed, and exactly conserving total_s; the
        loadtest gate's checker accepts the log."""
        model, params = small
        sink = InMemorySink()
        reg = MetricsRegistry([sink])
        cfg = EngineConfig(max_slots=2, max_len=64, page_size=4,
                           prefill_token_budget=4)
        req = Request(prompt=_prompts([13], seed=83)[0],
                      max_new_tokens=3, request_id=0)
        _, out = _serve(model, params, cfg, [req], metrics=reg)
        assert check_span_conservation(sink.records) == []
        spans = [r for r in sink.records if r.get("kind") == "span"
                 and r.get("span") == "prefill"]
        assert len(spans) == out[0].prefill_chunks == 4
        assert [s["chunk"] for s in spans] == [0, 1, 2, 3]
        # segments tile [prefill_start, prefill_end] exactly
        for a, b in zip(spans, spans[1:]):
            assert a["end_s"] == b["start_s"]

    def test_monolithic_span_shape_unchanged(self, small):
        """Without a budget the timeline is bit-for-bit the pre-chunking
        one: a single un-indexed prefill span."""
        model, params = small
        sink = InMemorySink()
        reg = MetricsRegistry([sink])
        cfg = EngineConfig(max_slots=2, max_len=64)
        req = Request(prompt=_prompts([13], seed=83)[0],
                      max_new_tokens=3, request_id=0)
        _serve(model, params, cfg, [req], metrics=reg)
        spans = [r for r in sink.records if r.get("kind") == "span"
                 and r.get("span") == "prefill"]
        assert len(spans) == 1 and "chunk" not in spans[0]

    def test_report_renders_chunk_audit(self, small, tmp_path):
        """Satellite: the monitor report renders the chunk counter, the
        per-request record sum, and the tokens-per-tick histogram, all
        reconciling key-for-key."""
        from apex_tpu.observability import JsonlSink

        model, params = small
        log = tmp_path / "chunked.jsonl"
        reg = MetricsRegistry([JsonlSink(str(log))])
        cfg = EngineConfig(max_slots=2, max_len=64, page_size=4,
                           prefill_token_budget=4)
        _, out = _serve(model, params, cfg,
                        _mixed_requests(_prompts((13, 6), seed=89)),
                        metrics=reg)
        reg.close()
        report = build_report(str(log))
        total = sum(r.prefill_chunks or 0 for r in out.values())
        assert report["counters"]["prefill_chunks"] == total
        assert report["requests"]["prefill_chunks"] == total
        text = render_report(report)
        assert f"chunked prefill: chunks={total}" in text
        assert "tokens/tick" in text


class TestLifecycle:
    def test_deadline_expiry_mid_prefill(self, small):
        """A request whose deadline elapses between chunks retires as a
        timeout, releases its slot and pages, and leaves the engine
        serving the co-tenants."""
        import time

        model, params = small
        cfg = EngineConfig(max_slots=2, max_len=64, page_size=4,
                           n_pages=64,
                           prefill_token_budget=4)
        eng = InferenceEngine(model, params, cfg)
        try:
            doomed = Request(prompt=_prompts([30], seed=97)[0],
                             max_new_tokens=4, deadline_s=0.05,
                             request_id=0)
            eng.submit(doomed)
            eng.tick()                    # first chunk runs
            assert eng._prefilling
            time.sleep(0.1)
            eng.tick()                    # deadline check fires
            res = eng.completed[doomed.request_id]
            assert res.finish_reason == "timeout"
            assert res.tokens == []
            assert res.prefill_chunks == 1
            assert not eng._prefilling
            eng.slots.check()
            assert eng.pages.in_use_count == 0
            # a fresh request still serves cleanly on the freed slot
            ok = Request(prompt=_prompts([5], seed=98)[0],
                         max_new_tokens=3, request_id=1)
            out = eng.serve([ok])
            assert out[0].finish_reason in ("eos", "length")
        finally:
            eng.close()

    def test_cancel_mid_prefill(self, small):
        model, params = small
        cfg = EngineConfig(max_slots=2, max_len=64, page_size=4,
                           prefill_token_budget=4)
        eng = InferenceEngine(model, params, cfg)
        try:
            req = Request(prompt=_prompts([30], seed=101)[0],
                          max_new_tokens=4, request_id=0)
            eng.submit(req)
            eng.tick()
            assert eng._prefilling
            eng.cancel(req.request_id)
            eng.tick()
            res = eng.completed[req.request_id]
            assert res.finish_reason == "cancelled"
            assert not eng._prefilling
            eng.slots.check()
        finally:
            eng.close()

    @pytest.mark.slow  # restart x chunking feature-cross: slow tier (ROADMAP)

    def test_supervisor_restart_mid_prefill_token_exact(self, small):
        """A crash between chunks re-prefills the request from its
        prompt through the same admit path (the per-slot prefill state
        is host data, not jit-trace state) — the recovered output is
        token-exact."""
        model, params = small
        req = Request(prompt=_prompts([20], seed=103)[0],
                      max_new_tokens=5, request_id=0)
        cfg = EngineConfig(max_slots=2, max_len=64, page_size=4,
                           prefill_token_budget=4)
        # prefill call 2 = the long prompt's THIRD chunk: the crash
        # lands mid-chunked-prefill, with two chunks already resident
        inj = ServingFaultInjector(prefill_raise_calls={2})
        sup = EngineSupervisor(model, params, cfg, faults=inj)
        try:
            results = sup.serve([Request(prompt=req.prompt,
                                         max_new_tokens=5,
                                         request_id=0)])
        finally:
            sup.close()
        assert sup.restarts == 1
        assert ("prefill_raise", 2) in inj.log
        assert results[0].tokens == reference_stream(model, params, req, 64)
        assert results[0].finish_reason == "length"


@pytest.fixture
def tp2_mesh():
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2)
    yield mesh
    parallel_state.destroy_model_parallel()


@pytest.mark.slow  # TP model parity: the slow-tier class (ROADMAP)
class TestShardedChunked:
    def test_tp2_chunked_token_exact(self, small, tp2_mesh):
        """Chunked prefill on a tp=2 mesh is token-exact vs the
        unsharded MONOLITHIC engine and vs each request served alone by
        the per-request reference — the chunks ride the suffix
        program's sharded wiring."""
        from apex_tpu.serving.fleet import ShardedEngine

        model, params = small
        prompts = _prompts((19, 6, 11), seed=113)
        base = dict(max_slots=4, max_len=64, page_size=4, n_pages=64)
        _, mono = _serve(model, params, EngineConfig(**base),
                         _mixed_requests(prompts, sampled=True))
        sharded = ShardedEngine(
            model, params, EngineConfig(prefill_token_budget=8, **base))
        with sharded:
            out = {r.request_id: r
                   for r in sharded.serve(
                       _mixed_requests(prompts, sampled=True))}
            assert sharded.decode_retraces == 0
            assert sharded.chunk_compiles <= len(sharded.buckets)
        for req in _mixed_requests(prompts, sampled=True):
            rid = req.request_id
            assert out[rid].tokens == mono[rid].tokens, rid
            assert out[rid].finish_reason == mono[rid].finish_reason
            assert out[rid].tokens == reference_stream(
                model, params, req, 64), rid
        assert out[0].prefill_chunks > 1


class TestTokenAwareLoad:
    def test_scheduler_queued_tokens(self):
        sched = FCFSScheduler(SchedulerConfig(max_queue=8))
        assert sched.queued_tokens == 0
        for n in (5, 11, 3):
            sched.submit(Request(prompt=list(range(1, n + 1)),
                                 max_new_tokens=2), now=0.0)
        assert sched.queued_tokens == 19
        sched.pop_admissible(1, False)
        assert sched.queued_tokens == 11 + 3

    def test_supervisor_excess_zero_until_measured(self, small):
        model, params = small
        sup = EngineSupervisor(model, params,
                               EngineConfig(max_slots=2, max_len=16))
        try:
            assert sup.queued_token_excess_s == 0.0
            assert sup.queued_prompt_tokens == 0
        finally:
            sup.close()

    def test_supervisor_excess_bounded_and_additive(self, small):
        """The token surcharge prices only the tokens BEYOND depth x
        avg-prompt, at the measured per-token prefill rate — zero for a
        typical backlog, positive for a long-prompt one, never
        negative."""
        model, params = small
        sup = EngineSupervisor(model, params,
                               EngineConfig(max_slots=2, max_len=64,
                                            scheduler=SchedulerConfig(
                                                max_queue=16)))
        try:
            sup._prefill_s_per_token = 0.01
            sup._avg_prompt_tokens = 4.0
            # 2 queued requests x 4 avg tokens = 8 expected; a 40-token
            # backlog carries 32 excess tokens -> 0.32s surcharge
            for p in _prompts((20, 20), seed=107):
                sup.engine.scheduler.submit(
                    Request(prompt=p, max_new_tokens=1), now=0.0)
            assert sup.queued_prompt_tokens == 40
            assert sup.queued_token_excess_s == pytest.approx(0.32)
        finally:
            sup.close()

    def test_supervisor_short_backlog_no_discount(self, small):
        model, params = small
        sup = EngineSupervisor(model, params,
                               EngineConfig(max_slots=2, max_len=64))
        try:
            sup._prefill_s_per_token = 0.01
            sup._avg_prompt_tokens = 16.0
            sup.engine.scheduler.submit(
                Request(prompt=[1, 2], max_new_tokens=1), now=0.0)
            # 2 tokens vs 16 expected: excess clamps at zero — short
            # prompts never discount below the depth-based estimate
            assert sup.queued_token_excess_s == 0.0
        finally:
            sup.close()

    @pytest.mark.slow  # compile-bound load-measurement sweep: slow tier (ROADMAP)

    def test_harvest_measures_token_rate(self, small):
        model, params = small
        sup = EngineSupervisor(model, params,
                               EngineConfig(max_slots=2, max_len=32))
        try:
            sup.serve([Request(prompt=p, max_new_tokens=3)
                       for p in _prompts((6, 12), seed=109)])
            assert sup._prefill_s_per_token is not None
            assert sup._prefill_s_per_token > 0
            assert sup._avg_prompt_tokens is not None
            assert 6 <= sup._avg_prompt_tokens <= 12
        finally:
            sup.close()

    def test_router_cost_prices_queued_tokens(self):
        """Two replicas at equal depth and service estimate: the one
        whose queue holds the long-prompt backlog costs more — and a
        fresh replica (no estimates) still costs exactly zero."""
        from apex_tpu.serving.fleet.router import Router

        class _Sup:
            def __init__(self, excess):
                self.queued_count = 2
                self.active_count = 0
                self.service_estimate_s = 0.5
                self.queued_token_excess_s = excess

        class _Rep:
            def __init__(self, rid, excess):
                self.replica_id = rid
                self.supervisor = _Sup(excess)

        short = _Rep(0, 0.0)
        long_ = _Rep(1, 0.4)
        assert Router.cost(short) < Router.cost(long_)
        assert Router().pick([long_, short]) is short

        class _Fresh:
            replica_id = 2

            class supervisor:
                queued_count = 0
                active_count = 0
                service_estimate_s = None
                queued_token_excess_s = 0.0

        assert Router.cost(_Fresh())[0] == 0.0


class TestNoSlivers:
    """A chunk that is not a prompt's last is a quarter of the budget or
    more and leaves as much behind: the chunk programs that can compile
    are those of whole prompts and of the top two octaves of the budget."""

    def test_chunks_are_whole_prompts_or_the_budgets_top_octaves(self):
        model = GPTModel(TransformerConfig(
            num_layers=1, hidden_size=32, num_attention_heads=4,
            vocab_size=64, max_position_embeddings=256, hidden_dropout=0.0,
            attention_dropout=0.0))
        params = model.init(jax.random.PRNGKey(1))
        cfg = EngineConfig(max_slots=6, max_len=256, page_size=4,
                           prefill_token_budget=32,
                           scheduler=SchedulerConfig(max_prefills_per_tick=3))
        eng = InferenceEngine(model, params, cfg)
        seen = []
        run_chunk = eng._run_chunk

        def spy(rec, budget_left, finished):
            before = rec.prefill_pos
            ran = run_chunk(rec, budget_left, finished)
            if ran:
                seen.append((ran, rec.request.prompt_len - before - ran,
                             budget_left))
            return ran

        eng._run_chunk = spy
        lens = [9, 33, 41, 70, 37, 100, 12, 65, 34, 129, 47, 95]
        reqs = [Request(prompt=p, max_new_tokens=4, request_id=i)
                for i, p in enumerate(_prompts(lens, seed=3))]
        try:
            results = eng.serve(reqs)
        finally:
            eng.close()
        assert all(r.finish_reason == "length" for r in results)
        assert sum(ran for ran, _, _ in seen) == sum(lens)
        floor = 32 // 4
        for ran, left, budget_left in seen:
            assert ran <= budget_left
            if left:                  # not the prompt's last chunk
                assert ran >= floor and left >= floor, (ran, left)
        # a last chunk is a whole prompt or what such a chunk left behind
        assert min(ran for ran, _, _ in seen) >= floor
        # several prompts shared a tick's budget, and some budget went
        # unused rather than to a sliver
        assert any(budget_left < 32 for _, _, budget_left in seen)
        assert eng.chunk_compiles <= 4        # buckets 8, 16, 32 (+ 9 -> 16)
