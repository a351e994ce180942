"""Serving-resilience tests: supervisor recovery, quarantine, admission.

The robustness contract on top of test_serving.py's correctness anchor:
under injected faults (poisoned slot, decode/prefill exceptions with
engine restart, hung tick) every submitted request reaches a terminal
state — no request silently lost, no slot leaks — and unaffected
co-tenants stay TOKEN-EXACT against a fault-free greedy run. Overload
is bounded: the circuit breaker fails submits fast while open, deadline
shedding rejects doomed work at the edge, and every incident reconciles
key-for-key between the monitor report and the registry counters.
"""

import time

import jax
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
from apex_tpu.observability.report import (
    SERVING_INCIDENT_COUNTERS,
    SERVING_SHED_COUNTERS,
)
from apex_tpu.serving import (
    BREAKER_CLOSED,
    BREAKER_HALF_OPEN,
    BREAKER_OPEN,
    DeadlineExpiredError,
    EngineConfig,
    EngineSupervisor,
    EngineUnavailableError,
    FINISH_REASONS,
    InferenceEngine,
    Request,
    SamplingParams,
    SlotError,
    SlotPool,
    SupervisorConfig,
)
from apex_tpu.serving.clock import VirtualClock, use_clock
from apex_tpu.testing_faults import InjectedEngineFault, ServingFaultInjector
from serving_reference import reference_stream


@pytest.fixture(scope="module")
def small():
    # 1 layer on purpose: these tests build MANY engines (every
    # supervisor restart recompiles prefill+decode), and recovery
    # semantics do not depend on depth — compile cost does
    model = GPTModel(TransformerConfig(
        num_layers=1, hidden_size=32, num_attention_heads=4, vocab_size=64,
        max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0))
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _prompts(lens, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 64, size=n).tolist() for n in lens]


class TestSlotPoolReset:
    def test_reset_rebuilds_free_list(self):
        pool = SlotPool(3)
        for _ in range(3):
            pool.allocate()
        assert pool.free_count == 0
        pool.reset()
        assert pool.free_count == 3 and pool.active_count == 0
        pool.check()
        # deterministic lowest-first order is restored too
        assert [pool.allocate() for _ in range(3)] == [0, 1, 2]

    def test_reset_idempotent_on_clean_pool(self):
        pool = SlotPool(2)
        pool.reset()
        pool.reset()
        pool.check()
        assert pool.free_count == 2

    def test_double_release_still_raises_after_reset(self):
        pool = SlotPool(2)
        s = pool.allocate()
        pool.reset()
        with pytest.raises(SlotError):
            pool.release(s)


class TestContextManagers:
    def test_engine_context_manager_releases_slots(self, small):
        model, params = small
        with InferenceEngine(model, params,
                             EngineConfig(max_slots=2, max_len=16)) as eng:
            eng.submit(Request(prompt=_prompts([3])[0], max_new_tokens=8))
            eng.tick()               # prefill holds a slot
            assert eng.active_count == 1
        eng.slots.check()
        assert eng.slots.free_count == 2
        eng.close()                  # second close is a no-op
        with pytest.raises(RuntimeError, match="closed"):
            eng.submit(Request(prompt=[1], max_new_tokens=1))
        with pytest.raises(RuntimeError, match="closed"):
            eng.tick()

    def test_engine_closes_on_exception_path(self, small):
        model, params = small
        with pytest.raises(ValueError):
            with InferenceEngine(model, params,
                                 EngineConfig(max_slots=1,
                                              max_len=16)) as eng:
                raise ValueError("boom")
        assert eng.slots.free_count == 1

    def test_supervisor_context_manager(self, small):
        model, params = small
        with EngineSupervisor(model, params,
                              EngineConfig(max_slots=1, max_len=16)) as sup:
            (res,) = sup.serve([Request(prompt=_prompts([3])[0],
                                        max_new_tokens=2)])
            assert res.finish_reason == "length"
        sup.close()                  # idempotent
        with pytest.raises(RuntimeError, match="closed"):
            sup.submit(Request(prompt=[1], max_new_tokens=1))


class TestDeadlineFastFail:
    def test_expired_at_submit_rejected_not_queued(self, small):
        model, params = small
        sink = InMemorySink()
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=1, max_len=16),
                              metrics=MetricsRegistry([sink]))
        stale = Request(prompt=_prompts([3])[0], max_new_tokens=2,
                        deadline_s=0.05,
                        arrival_ts=time.monotonic() - 1.0)
        with pytest.raises(DeadlineExpiredError):
            eng.submit(stale)
        assert eng.queued_count == 0          # never queued
        res = eng.completed[stale.request_id]
        assert res.finish_reason == "rejected" and res.tokens == []
        assert eng.metrics.counters()["requests_rejected"] == 1
        events = [r for r in sink.of_kind("event")
                  if r.get("event") == "request_rejected"]
        assert events and events[0]["reason"] == "deadline_expired"

    def test_fresh_deadline_still_queues(self, small):
        model, params = small
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=1, max_len=16))
        eng.submit(Request(prompt=_prompts([3])[0], max_new_tokens=2,
                           deadline_s=60.0,
                           arrival_ts=time.monotonic()))
        assert eng.queued_count == 1


class TestQuarantine:
    @pytest.mark.parametrize("kind", [
        "nonfinite",
        # same quarantine machinery from a different poison; slow tier
        pytest.param("oov", marks=pytest.mark.slow),
    ])
    def test_poisoned_slot_quarantined_cotenant_exact(self, small, kind):
        """Poison slot 0's decode output: its request retires with
        ``error`` (partial tokens intact), the co-tenant in slot 1 stays
        token-exact vs the fault-free run, and the slot is reusable."""
        model, params = small
        reqs = [Request(prompt=p, max_new_tokens=6)
                for p in _prompts([3, 5], seed=23)]
        sink = InMemorySink()
        inj = ServingFaultInjector(poison_decode={1: (0, kind)})
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=2, max_len=16),
                              metrics=MetricsRegistry([sink]), faults=inj)
        victim, cotenant = eng.serve(reqs)
        expected0 = reference_stream(model, params, reqs[0], 16)
        assert victim.finish_reason == "error"
        # prefill token + decode call 0's token survived; the poisoned
        # token was never appended
        assert victim.tokens == expected0[:victim.new_tokens]
        assert 0 < victim.new_tokens < 6
        assert cotenant.finish_reason == "length"
        assert cotenant.tokens == reference_stream(model, params,
                                                   reqs[1], 16)
        eng.slots.check()
        assert eng.slots.free_count == 2
        assert eng.decode_retraces == 0       # quarantine never retraces
        counters = eng.metrics.counters()
        assert counters["slots_quarantined"] == 1
        assert counters["requests_error"] == 1
        causes = [r.get("cause") for r in sink.of_kind("event")
                  if r.get("event") == "slot_quarantined"]
        assert causes == [
            "nonfinite_logits" if kind == "nonfinite"
            else "out_of_vocab_token"]

    @pytest.mark.slow
    def test_quarantined_slot_reused_cleanly(self, small):
        """A later request decoding in the scrubbed slot is token-exact —
        the poison does not outlive its victim."""
        model, params = small
        (p0, p1) = _prompts([3, 4], seed=29)
        inj = ServingFaultInjector(poison_decode={0: (0, "nonfinite")})
        eng = InferenceEngine(model, params,
                              EngineConfig(max_slots=1, max_len=16),
                              faults=inj)
        first = Request(prompt=p0, max_new_tokens=6)
        second = Request(prompt=p1, max_new_tokens=6)
        res = eng.serve([first, second])
        assert res[0].finish_reason == "error"
        assert res[1].finish_reason == "length"
        assert res[1].tokens == reference_stream(model, params, second, 16)
        eng.slots.check()


class TestSupervisorRecovery:
    @pytest.mark.slow
    def test_decode_exception_restart_token_exact(self, small):
        """The tentpole acceptance path: a decode exception mid-flight
        kills the engine; the supervisor rebuilds it and re-prefills both
        in-flight requests from prompt + generated tokens — final outputs
        are token-exact vs the fault-free greedy run."""
        model, params = small
        reqs = [Request(prompt=p, max_new_tokens=n)
                for p, n in zip(_prompts([3, 5], seed=31), (6, 8))]
        inj = ServingFaultInjector(decode_raise_calls={2})
        sup = EngineSupervisor(model, params,
                               EngineConfig(max_slots=2, max_len=16),
                               faults=inj)
        results = sup.serve(reqs)
        for req, res in zip(reqs, results):
            assert res.finish_reason == "length"
            assert res.tokens == reference_stream(model, params, req, 16)
            assert res.prompt_len == req.prompt_len   # original, stitched
        counters = sup.metrics.counters()
        assert counters["engine_restarts"] == 1
        assert counters["tick_failures"] == 1
        assert counters["requests_recovered"] == 2
        assert counters["requests_submitted"] == 2    # resubmits not double-counted
        sup.engine.slots.check()
        assert sup.engine.slots.free_count == 2

    @pytest.mark.slow
    def test_sampled_stream_survives_restart(self, small):
        """Sampling keys on the absolute position, so a restart resumes
        even a sampled request token-exact."""
        model, params = small
        (prompt,) = _prompts([4], seed=37)
        kw = dict(prompt=prompt, max_new_tokens=6,
                  sampling=SamplingParams(temperature=1.0, top_k=5,
                                          seed=123))
        clean_sup = EngineSupervisor(model, params,
                                     EngineConfig(max_slots=2, max_len=16))
        (clean,) = clean_sup.serve([Request(**kw)])
        inj = ServingFaultInjector(decode_raise_calls={1})
        sup = EngineSupervisor(model, params,
                               EngineConfig(max_slots=2, max_len=16),
                               faults=inj)
        (faulted,) = sup.serve([Request(**kw)])
        assert sup.restarts == 1
        assert faulted.tokens == clean.tokens

    @pytest.mark.slow
    def test_prefill_exception_recovers_without_slot_leak(self, small):
        model, params = small
        reqs = [Request(prompt=p, max_new_tokens=4)
                for p in _prompts([3, 5], seed=41)]
        inj = ServingFaultInjector(prefill_raise_calls={1})
        sup = EngineSupervisor(model, params,
                               EngineConfig(max_slots=2, max_len=16),
                               faults=inj)
        results = sup.serve(reqs)
        for req, res in zip(reqs, results):
            assert res.finish_reason == "length"
            assert res.tokens == reference_stream(model, params, req, 16)
        sup.engine.slots.check()
        assert sup.metrics.counters()["engine_restarts"] == 1

    def test_hung_tick_triggers_restart(self, small):
        model, params = small
        (prompt,) = _prompts([3], seed=43)
        # a budget that a tick starved by the other test workers does not
        # pass (at 30 ms the whole-suite run counted 2-3 "hung" ticks)
        inj = ServingFaultInjector(decode_hang={1: 0.6})
        sup = EngineSupervisor(
            model, params, EngineConfig(max_slots=2, max_len=16),
            supervisor=SupervisorConfig(hung_tick_s=0.25), faults=inj)
        (res,) = sup.serve([Request(prompt=prompt, max_new_tokens=6)])
        req = Request(prompt=prompt, max_new_tokens=6)
        assert res.finish_reason == "length"
        assert res.tokens == reference_stream(model, params, req, 16)
        assert sup.restarts == 1                 # exactly the hung tick;
        #                                          compile warmups exempt
        assert sup.metrics.counters()["tick_failures"] == 1

    def test_retry_budget_exhausted_retires_with_error(self, small):
        """A persistently-failing engine never silently loses a request:
        past the per-request restart budget it retires with ``error``,
        carrying the tokens recovered so far."""
        model, params = small
        inj = ServingFaultInjector(decode_raise_calls=set(range(100)))
        sup = EngineSupervisor(
            model, params, EngineConfig(max_slots=2, max_len=16),
            supervisor=SupervisorConfig(max_restarts_per_request=1,
                                        breaker_threshold=100),
            faults=inj)
        (res,) = sup.serve([Request(prompt=_prompts([3], seed=47)[0],
                                    max_new_tokens=6)])
        assert res.finish_reason == "error"
        assert res.new_tokens >= 1               # prefill tokens kept
        assert sup.restarts == 2                 # budget + the last straw
        assert sup.inflight_count == 0
        sup.engine.slots.check()


class TestCircuitBreaker:
    def test_open_half_open_close_cycle(self, small):
        model, params = small
        # virtual time: the cool-down is waited out by advancing the
        # clock the supervisor reads, not by racing a real sleep against
        # a rebuild
        with use_clock(VirtualClock()) as vc:
            inj = ServingFaultInjector(decode_raise_calls={0, 1})
            sup = EngineSupervisor(
                model, params, EngineConfig(max_slots=2, max_len=16),
                supervisor=SupervisorConfig(breaker_threshold=2,
                                            breaker_cooldown_s=0.05,
                                            max_restarts_per_request=5),
                faults=inj)
            victim = Request(prompt=_prompts([3], seed=53)[0],
                             max_new_tokens=6)
            sup.submit(victim)
            sup.tick()
            assert sup.breaker_state == BREAKER_CLOSED  # 1 failure < threshold
            sup.tick()
            assert sup.breaker_state == BREAKER_OPEN     # 2nd consecutive
            # fast-fail while open: terminal immediately, engine untouched
            shed = Request(prompt=_prompts([4], seed=54)[0],
                           max_new_tokens=3)
            with pytest.raises(EngineUnavailableError):
                sup.submit(shed)
            assert sup.completed[shed.request_id].finish_reason == "rejected"
            vc.advance(0.06)                             # cooldown elapses
            sup.tick()                                # half-open probe: clean
            assert sup.breaker_state == BREAKER_CLOSED
            while sup.inflight_count:
                sup.tick()
            # the victim survived the whole episode, token-exact
            res = sup.completed[victim.request_id]
            assert res.tokens == reference_stream(model, params, victim, 16)
            counters = sup.metrics.counters()
            assert counters["breaker_opens"] == 1
            assert counters["breaker_half_opens"] == 1
            assert counters["breaker_closes"] == 1
            assert counters["requests_shed_breaker"] == 1

    @pytest.mark.slow
    def test_failed_probe_reopens(self, small):
        model, params = small
        inj = ServingFaultInjector(decode_raise_calls={0, 1, 2})
        sup = EngineSupervisor(
            model, params, EngineConfig(max_slots=2, max_len=16),
            supervisor=SupervisorConfig(breaker_threshold=2,
                                        breaker_cooldown_s=0.02,
                                        max_restarts_per_request=10),
            faults=inj)
        sup.submit(Request(prompt=_prompts([3], seed=59)[0],
                           max_new_tokens=4))
        sup.tick()
        sup.tick()
        assert sup.breaker_state == BREAKER_OPEN
        time.sleep(0.03)
        sup.tick()                                   # probe fails (call 2)
        assert sup.breaker_state == BREAKER_OPEN
        assert sup.metrics.counters()["breaker_opens"] == 2
        while sup.inflight_count:
            sup.tick()                               # drains clean


class TestDeadlineShedding:
    def test_projected_wait_sheds_at_submit(self, small):
        model, params = small
        sup = EngineSupervisor(model, params,
                               EngineConfig(max_slots=1, max_len=16))
        sup._service_s = 50.0        # observed: ~50s per request
        sup.submit(Request(prompt=_prompts([3], seed=61)[0],
                           max_new_tokens=8))
        sup.submit(Request(prompt=_prompts([4], seed=62)[0],
                           max_new_tokens=8))       # 1 deep in queue
        doomed = Request(prompt=_prompts([3], seed=63)[0],
                         max_new_tokens=2, deadline_s=1.0)
        with pytest.raises(EngineUnavailableError, match="deadline"):
            sup.submit(doomed)
        res = sup.completed[doomed.request_id]
        assert res.finish_reason == "rejected" and res.tokens == []
        assert sup.metrics.counters()["requests_shed_deadline"] == 1
        # no-deadline traffic is never shed by the estimate
        sup.submit(Request(prompt=_prompts([3], seed=64)[0],
                           max_new_tokens=2))

    def test_no_shedding_before_first_observation(self, small):
        model, params = small
        sup = EngineSupervisor(model, params,
                               EngineConfig(max_slots=1, max_len=16))
        assert sup._service_s is None
        sup.submit(Request(prompt=_prompts([3], seed=65)[0],
                           max_new_tokens=2, deadline_s=30.0))
        assert sup.inflight_count == 1


class TestMonitorReconciliation:
    @pytest.mark.slow  # report-level reconciliation integration: slow tier (ROADMAP)
    def test_incidents_reconcile_with_counters(self, small, tmp_path):
        """Acceptance: drive restarts, quarantine, breaker transitions,
        and sheds in one run — the monitor report's serving-incidents
        counts must reconcile key-for-key with the registry counters,
        and every submitted request must reach exactly one terminal
        record."""
        model, params = small
        log = tmp_path / "resilient_serving.jsonl"
        reg = MetricsRegistry([JsonlSink(str(log))])
        inj = ServingFaultInjector(decode_raise_calls={1, 2},
                                   poison_decode={4: (0, "nonfinite")})
        sup = EngineSupervisor(
            model, params, EngineConfig(max_slots=2, max_len=16),
            supervisor=SupervisorConfig(breaker_threshold=2,
                                        breaker_cooldown_s=0.01,
                                        max_restarts_per_request=5),
            metrics=reg, faults=inj)
        reqs = [Request(prompt=p, max_new_tokens=n)
                for p, n in zip(_prompts([3, 5, 4], seed=67), (6, 8, 4))]
        sup.serve(reqs)
        # one extra shed while we force the breaker open state into the
        # log: reopen it artificially is not possible — instead verify
        # whatever transitions actually happened reconcile
        sup.close()
        report = build_report(str(log))
        counters = report["counters"]
        inc = report["serving_incidents"]
        assert inc is not None
        # key-for-key: every incident type's event count equals its
        # counter, including zero-count types (declared up front)
        for event, counter in SERVING_INCIDENT_COUNTERS.items():
            assert inc["counts"].get(event, 0) == counters[counter], event
        # .get on the counter side: the mapping also names fleet-tier
        # counters (requests_shed_fleet) that a supervisor-only run
        # never declares — absent must reconcile with zero sheds
        for reason, counter in SERVING_SHED_COUNTERS.items():
            assert inc["shed_by_reason"].get(reason, 0) == \
                counters.get(counter, 0), reason
        assert counters["engine_restarts"] >= 1
        assert counters["slots_quarantined"] == 1
        # request-level conservation: one submit == one terminal record
        req_sec = report["requests"]
        by_reason = req_sec["by_finish_reason"]
        assert set(by_reason) <= set(FINISH_REASONS)
        assert req_sec["count"] == sum(by_reason.values())
        assert counters["requests_submitted"] == req_sec["count"]
        for reason in FINISH_REASONS:
            assert counters[f"requests_{reason}"] == \
                by_reason.get(reason, 0), reason
        text = render_report(report)
        assert "serving incidents" in text
        assert "engine_restart" in text

    @pytest.mark.slow
    def test_every_result_terminal_under_faults(self, small):
        model, params = small
        inj = ServingFaultInjector(decode_raise_calls={3},
                                   poison_decode={1: (1, "oov")})
        sup = EngineSupervisor(model, params,
                               EngineConfig(max_slots=2, max_len=16),
                               faults=inj)
        reqs = [Request(prompt=p, max_new_tokens=4)
                for p in _prompts([3, 5, 2, 4], seed=71)]
        results = sup.serve(reqs)
        assert len(results) == len(reqs)
        assert all(r.finish_reason in FINISH_REASONS for r in results)
        assert sup.inflight_count == 0
        sup.engine.slots.check()


@pytest.mark.slow
class TestServingChaosSweep:
    def test_randomized_faults_arrivals_cancellations(self, small):
        """Chaos acceptance: randomized fault schedules (poison, raises,
        hangs) x randomized arrivals x cancellations. Every submitted
        request reaches a terminal state, no slot leaks, supervisor
        always drains."""
        model, params = small
        rng = np.random.RandomState(1)
        max_len = 24
        for round_i in range(3):
            poison = {int(rng.randint(1, 12)):
                      (int(rng.randint(0, 3)),
                       "nonfinite" if rng.rand() < 0.5 else "oov")}
            raises = {int(rng.randint(1, 10))}
            hangs = {int(rng.randint(2, 10)): 0.06}
            inj = ServingFaultInjector(
                poison_decode=poison, decode_raise_calls=raises,
                decode_hang=hangs)
            sup = EngineSupervisor(
                model, params,
                EngineConfig(max_slots=3, max_len=max_len),
                supervisor=SupervisorConfig(hung_tick_s=0.03,
                                            breaker_threshold=4,
                                            breaker_cooldown_s=0.02,
                                            max_restarts_per_request=3),
                faults=inj)
            reqs = []
            for _ in range(10):
                pl = int(rng.randint(1, 10))
                mn = int(rng.randint(1, 1 + min(8, max_len - pl)))
                reqs.append(Request(
                    prompt=rng.randint(0, 64, size=pl).tolist(),
                    max_new_tokens=mn,
                    eos_token=(int(rng.randint(0, 64))
                               if rng.rand() < 0.25 else None),
                    deadline_s=(30.0 if rng.rand() < 0.3 else None)))
            cancel_at = {reqs[3].request_id: 2, reqs[7].request_id: 4}

            def chaos(supervisor, tick):
                for rid, t in cancel_at.items():
                    if tick == t:
                        supervisor.cancel(rid)

            results = sup.serve(reqs, on_tick=chaos)
            assert len(results) == len(reqs), round_i
            for res in results:
                assert res.finish_reason in FINISH_REASONS, res
            assert sup.inflight_count == 0
            sup.engine.slots.check()
            assert sup.engine.slots.free_count == 3
            counters = sup.metrics.counters()
            assert counters["requests_submitted"] == sum(
                counters[f"requests_{r}"] for r in FINISH_REASONS)
            sup.close()
