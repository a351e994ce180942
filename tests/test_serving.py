"""Serving-engine tests: scheduler invariants + continuous-batching
correctness.

Correctness anchor: for any request set, greedy engine output must be
TOKEN-EXACT against per-request ``generate()`` calls — continuous
batching is a scheduling optimization, never an approximation. The
structural invariants ride along: no slot leaks, FCFS admission order,
prefill compile count bounded by the bucket set, and a decode step that
NEVER retraces as requests come and go (asserted through the engine's
``RetraceWatchdog``).
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.models.generation import generate
from apex_tpu.observability import (
    InMemorySink,
    JsonlSink,
    MetricsRegistry,
    build_report,
    render_report,
)
from apex_tpu.serving import (
    EngineConfig,
    FCFSScheduler,
    InferenceEngine,
    QueueFullError,
    Request,
    SamplingParams,
    SchedulerConfig,
    SlotError,
    SlotPool,
    bucket_for,
    prefill_buckets,
)
from apex_tpu.serving.engine import _sample_tokens
from serving_reference import pick_token, reference_stream


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
# the per-request reference every token-exactness test compares with
# (tests/serving_reference.py), checked itself


class TestReference:
    def test_greedy_stream_is_generates(self, small):
        model, params = small
        (prompt,) = _prompts([5])
        out = np.asarray(generate(model, params, jnp.asarray([prompt]), 8,
                                  max_len=16))[0, 5:].tolist()
        req = Request(prompt=prompt, max_new_tokens=8)
        assert reference_stream(model, params, req, 16) == out
        # and it stops at, and keeps, the first eos_token
        eos = Request(prompt=prompt, max_new_tokens=8, eos_token=out[2])
        assert reference_stream(model, params, eos, 16) == \
            out[:out.index(out[2]) + 1]

    @pytest.mark.parametrize("temperature,top_k", [
        (0.0, None), (0.8, 8), (1.1, None)],
        ids=["greedy", "top-k", "no-top-k"])
    def test_pick_token_is_the_engines_sampler(self, temperature, top_k):
        """Over a seeded [8, 64] block of logits, row by row at 8 seeds
        and positions: the rule written with a sort and
        ``_sample_tokens`` (which finds the k-th largest without one,
        batched under vmap) pick the same."""
        logits = 3.0 * jax.random.normal(jax.random.PRNGKey(11), (8, 64))
        seeds = np.arange(8, dtype=np.int32) * 7 + 1
        steps = np.arange(8, dtype=np.int32) + 5
        engine = np.asarray(_sample_tokens(
            logits, jnp.full(8, temperature, jnp.float32),
            jnp.full(8, top_k or 64, jnp.int32), jnp.asarray(seeds),
            jnp.asarray(steps)))
        plain = [pick_token(logits[i], SamplingParams(
            temperature=temperature, top_k=top_k, seed=int(seeds[i])),
            int(steps[i])) for i in range(8)]
        assert plain == engine.tolist()
        if temperature:
            assert plain != np.asarray(jnp.argmax(logits, -1)).tolist()


class TestRequestValidation:
    def test_empty_prompt_rejected(self):
        with pytest.raises(ValueError, match="non-empty"):
            Request(prompt=[], max_new_tokens=1)

    def test_max_new_tokens_zero_rejected(self):
        with pytest.raises(ValueError, match="max_new_tokens"):
            Request(prompt=[1], max_new_tokens=0)

    def test_top_k_zero_rejected(self):
        with pytest.raises(ValueError, match="top_k"):
            SamplingParams(top_k=0)

    def test_negative_temperature_rejected(self):
        with pytest.raises(ValueError, match="temperature"):
            SamplingParams(temperature=-0.1)

    def test_nonpositive_deadline_rejected(self):
        with pytest.raises(ValueError, match="deadline"):
            Request(prompt=[1], max_new_tokens=1, deadline_s=0.0)


class TestBuckets:
    def test_powers_of_two_plus_max(self):
        assert prefill_buckets(16) == (1, 2, 4, 8, 16)
        assert prefill_buckets(12) == (1, 2, 4, 8, 12)

    def test_bucket_for_picks_smallest_fit(self):
        assert bucket_for(1, 16) == 1
        assert bucket_for(3, 16) == 4
        assert bucket_for(9, 12) == 12
        with pytest.raises(ValueError):
            bucket_for(17, 16)


class TestSlotPool:
    def test_lowest_first_and_no_leak(self):
        pool = SlotPool(3)
        assert [pool.allocate() for _ in range(3)] == [0, 1, 2]
        assert pool.allocate() is None
        pool.release(1)
        assert pool.allocate() == 1
        pool.check()

    def test_double_release_raises(self):
        pool = SlotPool(2)
        s = pool.allocate()
        pool.release(s)
        with pytest.raises(SlotError):
            pool.release(s)


class TestScheduler:
    def test_fcfs_order_and_bounded_queue(self):
        sched = FCFSScheduler(SchedulerConfig(max_queue=3))
        reqs = [Request(prompt=[1], max_new_tokens=1) for _ in range(3)]
        for r in reqs:
            sched.submit(r, now=0.0)
        with pytest.raises(QueueFullError):
            sched.submit(Request(prompt=[1], max_new_tokens=1), now=0.0)
        got = sched.pop_admissible(free_slots=8, decoding=False)
        assert [r.request_id for r, _ in got] == \
            [r.request_id for r in reqs]

    def test_decode_starvation_cap(self):
        sched = FCFSScheduler(SchedulerConfig(max_prefills_per_tick=2))
        for _ in range(5):
            sched.submit(Request(prompt=[1], max_new_tokens=1), now=0.0)
        assert len(sched.pop_admissible(5, decoding=True)) == 2
        assert len(sched.pop_admissible(5, decoding=False)) == 3

    def test_admission_hook_defers_head_blocks_line(self):
        allow = {"ok": False}
        sched = FCFSScheduler(SchedulerConfig(
            admission_hook=lambda r: allow["ok"]))
        sched.submit(Request(prompt=[1], max_new_tokens=1), now=0.0)
        sched.submit(Request(prompt=[1], max_new_tokens=1), now=0.0)
        assert sched.pop_admissible(4, decoding=False) == []
        allow["ok"] = True
        assert len(sched.pop_admissible(4, decoding=False)) == 2

    def test_expire_pops_overdue_only(self):
        sched = FCFSScheduler()
        keep = Request(prompt=[1], max_new_tokens=1)
        drop = Request(prompt=[1], max_new_tokens=1, deadline_s=0.5)
        sched.submit(keep, now=0.0)
        sched.submit(drop, now=0.0)
        expired = sched.expire(now=1.0)
        assert [r.request_id for r, _ in expired] == [drop.request_id]
        assert sched.depth == 1

    def test_cancel_removes_queued(self):
        sched = FCFSScheduler()
        r = Request(prompt=[1], max_new_tokens=1)
        sched.submit(r, now=0.0)
        assert sched.cancel(r.request_id) is not None
        assert sched.cancel(r.request_id) is None
        assert sched.depth == 0


class TestEngine:
    @pytest.mark.slow
    def test_matches_per_request_generate(self, small):
        """More requests than slots: arrivals and retirements happen
        mid-flight, output must still be token-exact vs generate()."""
        model, params = small
        reqs = [Request(prompt=p, max_new_tokens=n)
                for p, n in zip(_prompts([3, 5, 8, 4, 6, 2]),
                                [6, 4, 5, 7, 3, 8])]
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=2, max_len=16))
        results = eng.serve(reqs)
        assert [r.request_id for r in results] == \
            [r.request_id for r in reqs]
        for req, res in zip(reqs, results):
            assert res.finish_reason == "length"
            assert res.tokens == reference_stream(model, params, req, 16)
        # FCFS admission, no slot leaks, bounded compile count, and the
        # one-compile decode invariant straight from the watchdog
        assert eng.admission_log == [r.request_id for r in reqs]
        eng.slots.check()
        assert eng.slots.free_count == eng.config.max_slots
        assert eng.decode_retraces == 0
        used = {bucket_for(r.prompt_len, 16) for r in reqs}
        assert eng.prefill_compiles <= len(used)

    def test_eos_retires_slot_and_matches(self, small):
        model, params = small
        (prompt,) = _prompts([4], seed=3)
        probe = generate(model, params, jnp.asarray([prompt], jnp.int32),
                         8, max_len=16)
        eos = int(probe[0, 5])   # second generated token (greedy repeats,
        #                          so it may equal the first — both fine)
        req = Request(prompt=prompt, max_new_tokens=8, eos_token=eos)
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=2, max_len=16))
        (res,) = eng.serve([req])
        assert res.finish_reason == "eos"
        assert res.tokens == reference_stream(model, params, req, 16)
        assert res.tokens[-1] == eos
        assert eng.slots.free_count == eng.config.max_slots

    @pytest.mark.slow  # sampling-independence property sweep: slow tier (ROADMAP)

    def test_sampled_stream_independent_of_cotenants(self, small):
        """A sampled request's tokens depend only on (seed, prompt,
        positions) — never on what shares the batch: alone vs co-batched
        with other traffic must draw the identical stream."""
        model, params = small
        (p0, p1, p2) = _prompts([4, 3, 5], seed=11)
        sampled = dict(prompt=p0, max_new_tokens=6,
                       sampling=SamplingParams(temperature=1.0, top_k=5,
                                               seed=123))
        eng1 = InferenceEngine(model, params,
                               EngineConfig(max_slots=3, max_len=16))
        (alone,) = eng1.serve([Request(**sampled)])
        eng2 = InferenceEngine(model, params,
                               EngineConfig(max_slots=3, max_len=16))
        mixed = eng2.serve([Request(prompt=p1, max_new_tokens=7),
                            Request(**sampled),
                            Request(prompt=p2, max_new_tokens=5)])
        assert mixed[1].tokens == alone.tokens
        assert eng2.decode_retraces == 0

    def test_queue_full_rejection(self, small):
        model, params = small
        sink = InMemorySink()
        reg = MetricsRegistry([sink])
        eng = InferenceEngine(
            model, params,
            EngineConfig(max_slots=1, max_len=16,
                         scheduler=SchedulerConfig(max_queue=2)),
            metrics=reg)
        p = _prompts([2, 2, 2], seed=5)
        eng.submit(Request(prompt=p[0], max_new_tokens=2))
        eng.submit(Request(prompt=p[1], max_new_tokens=2))
        rejected = Request(prompt=p[2], max_new_tokens=2)
        with pytest.raises(QueueFullError):
            eng.submit(rejected)
        assert reg.counters()["requests_rejected"] == 1
        res = eng.completed[rejected.request_id]
        assert res.finish_reason == "rejected" and res.tokens == []
        assert any(r.get("event") == "request_rejected"
                   for r in sink.of_kind("event"))
        # the engine still drains the admitted work
        while eng.active_count or eng.queued_count:
            eng.tick()
        eng.slots.check()

    def test_cancel_mid_flight_keeps_partial_tokens(self, small):
        model, params = small
        reqs = [Request(prompt=p, max_new_tokens=12)
                for p in _prompts([3, 4], seed=9)]
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=2, max_len=16))

        def chaos(engine, tick):
            if tick == 2:
                assert engine.cancel(reqs[0].request_id)

        results = eng.serve(reqs, on_tick=chaos)
        cancelled, survivor = results
        assert cancelled.finish_reason == "cancelled"
        assert 0 < cancelled.new_tokens < 12
        expected = reference_stream(model, params, reqs[0], 16)
        assert cancelled.tokens == expected[:cancelled.new_tokens]
        assert survivor.finish_reason == "length"
        assert survivor.tokens == reference_stream(model, params,
                                                   reqs[1], 16)
        eng.slots.check()
        assert eng.slots.free_count == 2

    def test_deadline_timeouts_queued_and_active(self, small):
        model, params = small
        p = _prompts([3, 3], seed=13)
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=1, max_len=16))
        # slow holds the only slot; starved times out while QUEUED
        slow = Request(prompt=p[0], max_new_tokens=12)
        starved = Request(prompt=p[1], max_new_tokens=2, deadline_s=1e-4)
        eng.submit(slow)
        eng.submit(starved)
        eng.tick()                       # admits slow (prefill compiles)
        eng.tick()                       # starved is now overdue
        res = eng.completed[starved.request_id]
        assert res.finish_reason == "timeout" and res.tokens == []
        # ACTIVE timeout: retired mid-decode with its partial tokens
        eng2 = InferenceEngine(model, params,
                               EngineConfig(max_slots=1, max_len=16))
        active = Request(prompt=p[0], max_new_tokens=12, deadline_s=0.05)

        def stall(engine, tick):
            time.sleep(0.06)

        (res2,) = eng2.serve([active], on_tick=stall)
        assert res2.finish_reason == "timeout"
        assert res2.new_tokens >= 1
        assert eng2.slots.free_count == 1

    def test_mid_serve_submission_never_retraces(self, small):
        model, params = small
        first = [Request(prompt=p, max_new_tokens=6)
                 for p in _prompts([3, 5], seed=21)]
        late = Request(prompt=_prompts([4], seed=22)[0], max_new_tokens=4)
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=2, max_len=16))

        def arrive(engine, tick):
            if tick == 2:
                engine.submit(late)

        eng.serve(first, on_tick=arrive)
        while eng.active_count or eng.queued_count:
            eng.tick()
        assert eng.decode_retraces == 0
        res = eng.completed[late.request_id]
        assert res.tokens == reference_stream(model, params, late, 16)

    def test_overflowing_request_rejected_at_submit(self, small):
        model, params = small
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=1, max_len=8))
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(Request(prompt=[1, 2, 3, 4], max_new_tokens=5))

    @pytest.mark.slow  # report-level reconciliation integration: slow tier (ROADMAP)

    def test_request_records_reconcile_with_monitor_report(
            self, small, tmp_path):
        """Acceptance: per-request JSONL rows reconcile with the engine's
        completion counters in the monitor report — through the real
        ``python -m apex_tpu.monitor`` CLI."""
        model, params = small
        log = tmp_path / "serving.jsonl"
        reg = MetricsRegistry([JsonlSink(str(log))])
        eng = InferenceEngine(
            model, params,
            EngineConfig(max_slots=2, max_len=16,
                         scheduler=SchedulerConfig(max_queue=2)),
            metrics=reg)
        reqs = [Request(prompt=p, max_new_tokens=n)
                for p, n in zip(_prompts([3, 6, 4], seed=17), [4, 3, 12])]
        eng.submit(reqs[0])
        eng.submit(reqs[1])
        with pytest.raises(QueueFullError):   # bounded-queue backpressure
            eng.submit(reqs[2])
        cancel_me = Request(prompt=_prompts([5], seed=18)[0],
                            max_new_tokens=11)
        cancel_submitted = False
        ticks = 0
        while (eng.active_count or eng.queued_count
               or not cancel_submitted):
            eng.tick()
            ticks += 1
            if not cancel_submitted and eng.queued_count < 2:
                eng.submit(cancel_me)
                cancel_submitted = True
            elif cancel_submitted and ticks > 4 and \
                    cancel_me.request_id not in eng.completed:
                eng.cancel(cancel_me.request_id)
        eng.close()
        report = build_report(str(log))
        counters = report["counters"]
        req_sec = report["requests"]
        assert req_sec is not None
        by_reason = req_sec["by_finish_reason"]
        # key-for-key reconciliation: every terminal record is counted by
        # exactly one requests_<reason> counter, and vice versa
        for reason in ("eos", "length", "cancelled", "timeout", "rejected"):
            assert counters[f"requests_{reason}"] == \
                by_reason.get(reason, 0), reason
        assert req_sec["count"] == sum(by_reason.values())
        assert counters["requests_submitted"] == req_sec["count"]
        assert req_sec["total_s"]["count"] == req_sec["count"]
        text = render_report(report)
        assert "serving requests" in text and "finish:" in text
        # the real CLI parses the same log (pure stdlib, no jax import)
        proc = subprocess.run(
            [sys.executable, "-m", "apex_tpu.monitor", str(log), "--json"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stderr
        cli = json.loads(proc.stdout)
        assert cli["requests"]["by_finish_reason"] == by_reason

    def test_histograms_populated(self, small):
        model, params = small
        reg = MetricsRegistry()
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=2, max_len=16),
                              metrics=reg)
        eng.serve([Request(prompt=p, max_new_tokens=3)
                   for p in _prompts([3, 4], seed=19)])
        hists = reg.histograms()
        for name in ("request_queue_s", "request_prefill_s",
                     "request_decode_s", "request_total_s",
                     "request_ttft_s", "request_tpot_s",
                     "slot_occupancy", "decode_batch_size"):
            assert name in hists and hists[name].count > 0, name

    def test_ttft_tpot_first_class(self, small):
        """Satellite contract: TTFT/TPOT are stamped from the engine's
        own token timestamps — not reconstructed by adding the coarse
        queue/prefill/decode buckets — and land in the JSONL record."""
        model, params = small
        reg = MetricsRegistry([InMemorySink()])
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=2, max_len=16),
                              metrics=reg)
        multi, single = [
            Request(prompt=p, max_new_tokens=n) for p, n in
            zip(_prompts([4, 3], seed=23), (5, 1))]
        results = {r.request_id: r for r in eng.serve([multi, single])}
        res = results[multi.request_id]
        # first token arrives with the prefill result: TTFT brackets the
        # queue+prefill span and precedes the total latency
        assert res.ttft_s is not None and 0 < res.ttft_s <= res.total_s
        assert res.ttft_s == pytest.approx(
            res.queue_s + res.prefill_s, abs=0.05)
        # 5 tokens -> 4 inter-token gaps spanning the decode phase
        assert res.tpot_s is not None and res.tpot_s >= 0
        assert res.tpot_s * (res.new_tokens - 1) <= res.decode_s + 0.05
        # a single-token request has a TTFT but no inter-token interval
        one = results[single.request_id]
        assert one.ttft_s is not None and one.tpot_s is None
        sink = reg._sinks[0]
        recs = {r["request_id"]: r for r in sink.of_kind("request")}
        assert recs[multi.request_id]["ttft_s"] == res.ttft_s
        assert recs[multi.request_id]["tpot_s"] == res.tpot_s
        assert "tpot_s" not in recs[single.request_id]

    def test_rejected_request_has_no_ttft(self, small):
        model, params = small
        eng = InferenceEngine(model, params, EngineConfig(
            max_slots=1, max_len=16,
            scheduler=SchedulerConfig(max_queue=1)))
        p = _prompts([3, 3], seed=29)
        eng.submit(Request(prompt=p[0], max_new_tokens=2))
        rejected = Request(prompt=p[1], max_new_tokens=2)
        with pytest.raises(QueueFullError):   # queue of 1 already full
            eng.submit(rejected)
        res = eng.completed[rejected.request_id]
        assert res.ttft_s is None and res.tpot_s is None


@pytest.mark.slow
class TestServingSweep:
    def test_randomized_continuous_batching_parity(self, small):
        """Property-style sweep: randomized arrivals, lengths, and
        cancellations — no slot leaks, FCFS admission, compile count
        bounded by the bucket set, zero decode retraces, and token-exact
        greedy parity for every request that ran to completion."""
        model, params = small
        rng = np.random.RandomState(0)
        max_len = 24
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=3, max_len=max_len))
        reqs = []
        for _ in range(12):
            pl = int(rng.randint(1, 13))
            mn = int(rng.randint(1, 1 + min(8, max_len - pl)))
            reqs.append(Request(
                prompt=rng.randint(0, 64, size=pl).tolist(),
                max_new_tokens=mn,
                eos_token=(int(rng.randint(0, 64))
                           if rng.rand() < 0.3 else None)))
        cancel_at = {reqs[4].request_id: 3, reqs[9].request_id: 5}

        def chaos(engine, tick):
            for rid, t in cancel_at.items():
                if tick == t:
                    engine.cancel(rid)

        results = eng.serve(reqs, on_tick=chaos)
        eng.slots.check()
        assert eng.slots.free_count == eng.config.max_slots
        assert eng.decode_retraces == 0
        assert eng.prefill_compiles <= len(eng.buckets)
        queue_cancelled = {r.request_id for r in results
                           if r.finish_reason == "cancelled"
                           and r.prefill_s == 0.0}
        assert eng.admission_log == [
            r.request_id for r in reqs
            if r.request_id not in queue_cancelled]
        assert len(results) == len(reqs)
        for req, res in zip(reqs, results):
            expected = reference_stream(model, params, req, max_len)
            if res.finish_reason in ("eos", "length"):
                assert res.tokens == expected, req.request_id
            elif res.finish_reason == "cancelled":
                assert res.tokens == expected[:res.new_tokens]
