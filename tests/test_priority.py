"""Priority-aware overload survival (ISSUE 20): preemptive scheduling,
per-tenant quotas, and the brownout degradation ladder.

The contract under test, end to end:

- `SamplingParams.priority` selects a class; the scheduler dispatches
  strict-priority across classes, FCFS inside one, with an aging floor
  that keeps batch deferred-but-never-starved.
- Preemption parks a running lower-class slot (slot + private pages
  released, host-side token cursor kept) and resumes it later
  TOKEN-EXACT — greedy and sampled — under the request's ORIGINAL
  ids and deadline clock, with exactly one terminal record.
- `QuotaLedger` token-bucket / inflight / page math is deterministic
  in the caller's clock; hard limits shed, soft limits defer.
- The brownout ladder escalates batch-first with hysteresis and emits
  a typed record + counter/event pair per transition, reconciling
  key-for-key in the monitor report.
- A committed pre-PR20 run log (no priority fields, no brownout rows)
  still builds, renders without the new sections, and stays
  span-conservation clean.
"""

import random

import pytest

from apex_tpu.analysis.mc.sim import SimEngine, SimModel, sim_stream
from apex_tpu.observability import (
    InMemorySink,
    MetricsRegistry,
    build_report,
    render_report,
)
from apex_tpu.serving import clock
from apex_tpu.serving.clock import VirtualClock, use_clock
from apex_tpu.serving.engine import EngineConfig
from apex_tpu.serving.fleet.brownout import (
    BROWNOUT_RUNGS,
    BrownoutConfig,
    BrownoutController,
)
from apex_tpu.serving.fleet.quota import (
    BASE_TENANT,
    QUOTA_ADMIT,
    QUOTA_DEFER,
    QUOTA_SHED,
    QuotaConfig,
    QuotaLedger,
    TenantQuota,
)
from apex_tpu.serving.request import (
    PRIORITIES,
    PRIORITY_BATCH,
    PRIORITY_INTERACTIVE,
    PRIORITY_STANDARD,
    Request,
    SamplingParams,
)
from apex_tpu.serving.scheduler import FCFSScheduler, SchedulerConfig


def _req(prompt, max_new=4, priority=PRIORITY_STANDARD, rid=None,
         adapter=None, deadline=None, **sampling):
    kwargs = {} if rid is None else {"request_id": rid}
    return Request(prompt=list(prompt), max_new_tokens=max_new,
                   sampling=SamplingParams(priority=priority,
                                           adapter_id=adapter, **sampling),
                   deadline_s=deadline, **kwargs)


# ---------------------------------------------------------------------------
# priority classes + class-aware scheduler (pure host-side)
# ---------------------------------------------------------------------------

class TestPriorityClasses:
    def test_default_is_standard(self):
        assert SamplingParams().priority == PRIORITY_STANDARD

    def test_invalid_priority_rejected(self):
        with pytest.raises(ValueError, match="priority"):
            SamplingParams(priority="urgent")

    def test_strict_priority_across_classes_fcfs_within(self):
        sched = FCFSScheduler(SchedulerConfig(max_queue=16))
        order = [("b1", PRIORITY_BATCH), ("s1", PRIORITY_STANDARD),
                 ("b2", PRIORITY_BATCH), ("i1", PRIORITY_INTERACTIVE),
                 ("s2", PRIORITY_STANDARD)]
        ids = {}
        for i, (name, prio) in enumerate(order):
            r = _req([1, 2], priority=prio)
            ids[r.request_id] = name
            sched.submit(r, now=float(i))
        popped = []
        while sched.depth:
            (got,) = sched.pop_admissible(1, False, now=10.0)
            popped.append(ids[got[0].request_id])
        assert popped == ["i1", "s1", "s2", "b1", "b2"]

    def test_batch_aging_promotes_to_standard_rank(self):
        sched = FCFSScheduler(SchedulerConfig(max_queue=8,
                                              batch_aging_s=5.0))
        aged = _req([1], priority=PRIORITY_BATCH)
        fresh = _req([1], priority=PRIORITY_STANDARD)
        sched.submit(aged, now=0.0)
        sched.submit(fresh, now=8.0)
        # past the aging floor the batch head competes at standard rank;
        # FCFS inside that rank makes the older batch head win
        head, _ = sched.head(now=9.0)
        assert head.request_id == aged.request_id
        # without aging (young head) standard dispatches first
        assert sched.head(now=1.0)[0].request_id == fresh.request_id

    def test_admission_floor_pauses_lower_classes(self):
        sched = FCFSScheduler(SchedulerConfig(max_queue=8))
        b = _req([1], priority=PRIORITY_BATCH)
        s = _req([1], priority=PRIORITY_STANDARD)
        sched.submit(b, now=0.0)
        sched.submit(s, now=0.0)
        sched.set_admission_floor(PRIORITY_STANDARD)
        got = sched.pop_admissible(4, False, now=1.0)
        assert [g[0].request_id for g in got] == [s.request_id]
        assert sched.depth_by_class()[PRIORITY_BATCH] == 1
        sched.set_admission_floor(None)
        (got,) = sched.pop_admissible(4, False, now=1.0)
        assert got[0].request_id == b.request_id

    def test_queued_tokens_split_per_class(self):
        sched = FCFSScheduler(SchedulerConfig(max_queue=8))
        sched.submit(_req([1] * 5, priority=PRIORITY_BATCH), now=0.0)
        sched.submit(_req([1] * 3, priority=PRIORITY_INTERACTIVE), now=0.0)
        by = sched.queued_tokens_by_class()
        assert by[PRIORITY_BATCH] == 5
        assert by[PRIORITY_INTERACTIVE] == 3
        assert by[PRIORITY_STANDARD] == 0
        assert sched.queued_tokens == 8


# ---------------------------------------------------------------------------
# quota bucket math (pure host-side)
# ---------------------------------------------------------------------------

class TestQuotaMath:
    def _ledger(self, **quota):
        cfg = QuotaConfig(tenants={"t": TenantQuota(**quota)})
        return QuotaLedger(cfg)

    def test_unlisted_tenant_unlimited(self):
        led = QuotaLedger(QuotaConfig(tenants={"t": TenantQuota(
            max_inflight=1)}))
        for _ in range(50):
            assert led.verdict("other", 0.0) == (QUOTA_ADMIT, None)
            led.commit("other", 0.0)

    def test_default_applies_to_unlisted(self):
        led = QuotaLedger(QuotaConfig(
            default=TenantQuota(max_inflight=1)))
        assert led.verdict("x", 0.0) == (QUOTA_ADMIT, None)
        led.commit("x", 0.0)
        assert led.verdict("x", 0.0) == (QUOTA_SHED, "inflight")

    def test_bucket_burst_then_refill(self):
        led = self._ledger(rate_rps=2.0, burst=3.0)
        # a quiet tenant lands its full burst at one instant
        for _ in range(3):
            assert led.verdict("t", 10.0)[0] == QUOTA_ADMIT
            led.commit("t", 10.0)
        assert led.verdict("t", 10.0) == (QUOTA_SHED, "rate")
        # refill is linear in elapsed time, capped at burst
        assert led.bucket_tokens("t", 10.25) == pytest.approx(0.5)
        assert led.verdict("t", 10.25)[0] == QUOTA_SHED
        assert led.verdict("t", 10.5)[0] == QUOTA_ADMIT   # 1 token back
        led.commit("t", 10.5)
        assert led.bucket_tokens("t", 100.0) == pytest.approx(3.0)

    def test_inflight_cap_and_release(self):
        led = self._ledger(max_inflight=2)
        led.commit("t", 0.0)
        led.commit("t", 0.0)
        assert led.verdict("t", 0.0) == (QUOTA_SHED, "inflight")
        led.release("t")
        assert led.verdict("t", 0.0)[0] == QUOTA_ADMIT
        # release is floored at zero, never negative
        for _ in range(5):
            led.release("t")
        assert led.inflight("t") == 0

    def test_page_cap_worst_case(self):
        led = self._ledger(max_pages=4)
        assert led.verdict("t", 0.0, pages=3)[0] == QUOTA_ADMIT
        led.commit("t", 0.0, pages=3)
        assert led.verdict("t", 0.0, pages=2) == (QUOTA_SHED, "pages")
        assert led.verdict("t", 0.0, pages=1)[0] == QUOTA_ADMIT
        led.release("t", pages=3)
        assert led.pages_held("t") == 0

    def test_soft_quota_defers_instead_of_shedding(self):
        led = self._ledger(rate_rps=1.0, burst=1.0, soft=True)
        led.commit("t", 0.0)
        assert led.verdict("t", 0.0) == (QUOTA_DEFER, "rate")
        assert led.verdict("t", 1.5)[0] == QUOTA_ADMIT

    def test_verdict_is_pure_commit_consumes(self):
        led = self._ledger(rate_rps=1.0, burst=1.0)
        for _ in range(10):    # verdicts never burn bucket tokens
            assert led.verdict("t", 0.0)[0] == QUOTA_ADMIT
        led.commit("t", 0.0)
        assert led.verdict("t", 0.0)[0] == QUOTA_SHED

    def test_tenant_key_is_adapter_or_base(self):
        assert QuotaLedger.tenant(_req([1], adapter="a0")) == "a0"
        assert QuotaLedger.tenant(_req([1])) == BASE_TENANT

    def test_knob_validation(self):
        with pytest.raises(ValueError, match="burst"):
            TenantQuota(burst=0.5)
        with pytest.raises(ValueError, match="rate_rps"):
            TenantQuota(rate_rps=-1.0)


# ---------------------------------------------------------------------------
# park / resume mechanics + page conservation (SimEngine, no jax)
# ---------------------------------------------------------------------------

def _sim_engine(metrics=None, max_slots=2, max_queue=8, page_size=4):
    cfg = EngineConfig(max_slots=max_slots, max_len=64,
                       page_size=page_size,
                       scheduler=SchedulerConfig(
                           max_queue=max_queue, max_prefills_per_tick=1))
    if metrics is None:
        metrics = MetricsRegistry(sinks=[InMemorySink()])
    return SimEngine(SimModel(), {}, cfg, metrics=metrics, replica_id=0)


class TestParkReleaseConservation:
    def test_park_releases_slot_and_pages(self):
        with use_clock(VirtualClock()):
            eng = _sim_engine()
            r = _req([1, 2, 3], max_new=8, priority=PRIORITY_BATCH)
            eng.submit(r)
            eng.tick()
            assert eng.active_count == 1 and eng.pool.used > 0
            assert eng.park_class(PRIORITY_BATCH, cause="test") == 1
            assert eng.active_count == 0 and eng.pool.used == 0
            assert eng.parked_count == 1
            ((req, toks, _ts),) = eng.take_parked()
            assert req.request_id == r.request_id
            assert toks == sim_stream(r.prompt, 8)[:len(toks)]
            eng.close()

    def test_parked_request_cancel_and_deadline(self):
        with use_clock(VirtualClock()) as vc:
            eng = _sim_engine()
            dead = _req([1, 2], max_new=30, priority=PRIORITY_BATCH,
                        deadline=0.5)
            keep = _req([3, 4], max_new=30, priority=PRIORITY_BATCH)
            eng.submit(dead)
            eng.submit(keep)
            eng.tick()    # max_prefills_per_tick=1: one admit per tick
            eng.tick()
            assert eng.park_class(PRIORITY_BATCH, cause="test") == 2
            # cancel while parked: terminal immediately
            assert eng.cancel(keep.request_id)
            assert eng.completed[keep.request_id].finish_reason == "cancelled"
            # the deadline clock never stopped while parked
            vc.advance(1.0)
            finished = eng.tick()
            (res,) = [r for r in finished
                      if r.request_id == dead.request_id]
            assert res.finish_reason == "timeout"
            assert eng.parked_count == 0 and eng.pool.used == 0
            eng.close()

    def test_randomized_park_churn_conserves_pages(self):
        """Under seeded random submit/park/cancel/tick churn the page
        pool balances every step (used == live requests' footprint,
        allocs - frees == used) and drains to zero."""
        rng = random.Random(20)
        with use_clock(VirtualClock()) as vc:
            metrics = MetricsRegistry(sinks=[InMemorySink()])
            eng = _sim_engine(metrics=metrics, max_slots=2, max_queue=16)
            eng.resume_consumer = True     # let _maybe_preempt fire too
            live = []
            for step in range(120):
                op = rng.randrange(6)
                if op <= 1 and eng.queued_count < 15:
                    r = _req([1 + rng.randrange(6)] * (1 + rng.randrange(5)),
                             max_new=1 + rng.randrange(6),
                             priority=PRIORITIES[rng.randrange(3)])
                    eng.submit(r)
                    live.append(r.request_id)
                elif op == 2:
                    eng.park_class(PRIORITIES[1 + rng.randrange(2)],
                                   cause="churn")
                elif op == 3 and live:
                    eng.cancel(live[rng.randrange(len(live))])
                else:
                    vc.advance(0.01)
                    eng.tick()
                # parked requests hold no pages; actives account for all
                want = sum(eng.pool.pages_for(rec.request)
                           for rec in eng._active.values())
                assert eng.pool.used == want
                assert (eng.pool.total_allocs - eng.pool.total_frees
                        == eng.pool.used)
            # drain everything: parked cursors must resume via a
            # consumer in real life — here the churn drains by restart
            for _, toks, _ in eng.take_parked():
                pass
            for _ in range(200):
                vc.advance(0.01)
                if not eng.tick() and eng.inflight() == 0:
                    break
            assert eng.pool.used == 0
            eng.close()


class TestSimPreemptResume:
    """The preemption rule + token-exact resume on the sim engine (the
    same code path the mc checker explores; the jax engine's exactness
    is covered by the slow-tier cross below and the priority_storm
    scenario gate)."""

    def test_interactive_head_parks_lowest_class(self):
        with use_clock(VirtualClock()):
            metrics = MetricsRegistry(sinks=[sink := InMemorySink()])
            eng = _sim_engine(metrics=metrics, max_slots=2)
            eng.resume_consumer = True
            b = _req([1, 2], max_new=20, priority=PRIORITY_BATCH)
            s = _req([3, 4], max_new=20, priority=PRIORITY_STANDARD)
            eng.submit(b)
            eng.submit(s)
            eng.tick()        # one admit per tick -> two ticks
            eng.tick()        # both admitted, slots full
            hi = _req([5, 6], max_new=2, priority=PRIORITY_INTERACTIVE)
            eng.submit(hi)
            eng.tick()        # preempts ONE slot: the batch one
            parked = eng.take_parked()
            assert [p[0].request_id for p in parked] == [b.request_id]
            assert metrics.counters()["requests_preempted"] == 1
            events = [r for r in sink.records
                      if r.get("kind") == "event"
                      and r.get("event") == "request_preempted"]
            assert len(events) == 1
            assert events[0]["priority"] == PRIORITY_BATCH
            eng.close()

    def test_no_preemption_without_consumer_or_free_slots(self):
        with use_clock(VirtualClock()):
            eng = _sim_engine(max_slots=2)
            assert eng.resume_consumer is False
            b = _req([1, 2], max_new=20, priority=PRIORITY_BATCH)
            eng.submit(b)
            eng.tick()
            eng.submit(_req([5], max_new=2,
                            priority=PRIORITY_INTERACTIVE))
            eng.tick()
            # a free slot admitted the head — nothing was parked; and
            # without a resume consumer the engine never parks on its own
            assert eng.parked_count == 0
            eng.close()

    def test_resume_token_exact_through_fleet(self):
        """Fleet + supervisor end to end on sim engines: a parked batch
        request resumes TOKEN-EXACT (canonical sim stream), keeps its
        original trace_id, and is terminal exactly once."""
        from apex_tpu.analysis.mc.harness import MCConfig, FleetHarness

        with use_clock(VirtualClock()):
            h = FleetHarness(MCConfig(replicas=1, preempt=True))
            try:
                b = _req([2, 3], max_new=6, priority=PRIORITY_BATCH,
                         rid=900001)
                h.fleet.submit(b)
                h._tick_once()      # admit + first token
                (replica,) = h.fleet.replicas
                assert replica.supervisor.preempt_class(
                    PRIORITY_BATCH, cause="test") == 1
                for _ in range(100):
                    h._tick_once()
                    if b.request_id in h.fleet.completed:
                        break
                res = h.fleet.completed[b.request_id]
                assert res.finish_reason == "length"
                assert list(res.tokens) == sim_stream(b.prompt, 6)
                assert res.trace_id == b.trace_id
                counters = h.registry.counters()
                assert counters["requests_preempted"] == 1
                assert counters["requests_resumed"] == 1
                terminal = [r for r in h.sink.records
                            if r.get("kind") == "request"
                            and r.get("request_id") == b.request_id]
                assert len(terminal) == 1
                marks = [r for r in h.sink.records
                         if r.get("kind") == "span"
                         and r.get("span") in ("preempt", "resume")]
                assert [m["span"] for m in marks] == ["preempt", "resume"]
                assert all(m["trace_id"] == b.trace_id for m in marks)
            finally:
                h.cleanup()


# ---------------------------------------------------------------------------
# brownout ladder (pure controller + telemetry reconciliation)
# ---------------------------------------------------------------------------

class _StubFleetMetrics:
    """Scripted signals stream: full control of the pressure the
    controller sees, poll by poll."""

    def __init__(self, fleet, depths):
        self.fleet = fleet
        self.depths = list(depths)

    def signals(self):
        return {"queue_depth": self.depths.pop(0),
                "replicas_dispatchable": 1}


class _StubFleet:
    def __init__(self, registry):
        self.replicas = []
        self.metrics = registry


class TestBrownoutLadder:
    CFG = BrownoutConfig(poll_interval_s=1.0, queue_depth_high=8.0,
                         queue_depth_low=2.0, hot_polls=2, cool_polls=2,
                         clamp_max_new_tokens=4)

    def _drive(self, depths):
        registry = MetricsRegistry(sinks=[sink := InMemorySink()])
        fleet = _StubFleet(registry)
        ctrl = BrownoutController(self.CFG)
        ctrl._fm = _StubFleetMetrics(fleet, depths)
        rungs = []
        for i in range(len(depths)):
            ctrl.maybe_step(fleet, now=float(i))
            rungs.append(ctrl.rung)
        return ctrl, rungs, registry, sink

    def test_escalation_needs_hot_streak(self):
        ctrl, rungs, _, _ = self._drive([9, 9, 9, 9])
        # hot_polls=2: first hot poll arms, second moves — one rung per
        # streak completion
        assert rungs == [0, 1, 1, 2]
        assert ctrl.rung_name == BROWNOUT_RUNGS[2]

    def test_neutral_zone_resets_streaks(self):
        # hot, neutral, hot, hot: the neutral poll resets the streak so
        # escalation needs two MORE consecutive hot polls
        _, rungs, _, _ = self._drive([9, 5, 9, 9])
        assert rungs == [0, 0, 0, 1]

    def test_recovery_one_rung_with_hysteresis(self):
        ctrl, rungs, _, _ = self._drive(
            [9, 9, 9, 9, 1, 1, 1, 1, 5, 1, 1])
        assert rungs[:4] == [0, 1, 1, 2]
        # cool streaks step down one rung at a time; the neutral poll
        # (depth 5) resets the cool streak too
        assert rungs[4:8] == [2, 1, 1, 0]
        assert rungs[8:] == [0, 0, 0]
        assert ctrl.rung == 0

    def test_poll_interval_enforced(self):
        registry = MetricsRegistry(sinks=[InMemorySink()])
        fleet = _StubFleet(registry)
        ctrl = BrownoutController(self.CFG)
        ctrl._fm = _StubFleetMetrics(fleet, [9, 9, 9])
        ctrl.maybe_step(fleet, now=0.0)
        ctrl.maybe_step(fleet, now=0.5)   # under poll_interval_s: no poll
        assert len(ctrl._fm.depths) == 2
        ctrl.maybe_step(fleet, now=1.0)
        assert ctrl.rung == 1

    def test_admission_floor_per_rung(self):
        ctrl = BrownoutController(self.CFG)
        floors = []
        for rung in range(len(BROWNOUT_RUNGS)):
            ctrl.rung = rung
            floors.append(ctrl.admission_floor())
        assert floors == [None, PRIORITY_STANDARD, PRIORITY_STANDARD,
                          PRIORITY_STANDARD, PRIORITY_INTERACTIVE]

    def test_clamp_batch_only_at_rung3(self):
        ctrl = BrownoutController(self.CFG)
        batch = _req([1, 2], max_new=50, priority=PRIORITY_BATCH,
                     rid=777)
        std = _req([1, 2], max_new=50, priority=PRIORITY_STANDARD)
        ctrl.rung = 2
        assert ctrl.clamp(batch) is batch      # below clamp rung
        ctrl.rung = 3
        clamped = ctrl.clamp(batch)
        assert clamped.max_new_tokens == 4
        # same identity: ids, trace, deadline clock are untouched
        assert clamped.request_id == 777
        assert clamped.trace_id == batch.trace_id
        assert ctrl.clamp(std) is std          # never non-batch
        short = _req([1], max_new=2, priority=PRIORITY_BATCH)
        assert ctrl.clamp(short) is short      # already under the cap

    def test_transitions_emit_record_counter_event_triples(self):
        _, _, registry, sink = self._drive(
            [9, 9, 9, 9, 1, 1, 1, 1, 1, 1])
        counters = registry.counters()
        assert counters["brownouts_escalated"] == 2
        assert counters["brownouts_recovered"] == 2
        recs = [r for r in sink.records if r.get("kind") == "brownout"]
        assert [r["action"] for r in recs] == ["escalate", "escalate",
                                               "recover", "recover"]
        assert [r["rung"] for r in recs] == [1, 2, 1, 0]
        for name, want in (("brownout_escalate", 2),
                           ("brownout_recover", 2)):
            events = [r for r in sink.records
                      if r.get("kind") == "event"
                      and r.get("event") == name]
            assert len(events) == want

    def test_config_validation(self):
        with pytest.raises(ValueError, match="queue_depth_low"):
            BrownoutConfig(queue_depth_high=2.0, queue_depth_low=3.0)
        with pytest.raises(ValueError, match="hot_polls"):
            BrownoutConfig(hot_polls=0)
        with pytest.raises(ValueError, match="max_rung"):
            BrownoutConfig(max_rung=99)

    def test_pure_batch_storm_breathes_instead_of_wedging(self):
        """Pressure counts only ADMISSIBLE queued work: once rung 1
        pauses batch, a pure-batch backlog stops counting, so the
        ladder recovers instead of escalating on its own backpressure
        and starving batch at the top rung forever."""
        from apex_tpu.analysis.mc.harness import MCConfig, FleetHarness

        with use_clock(VirtualClock()):
            h = FleetHarness(MCConfig(replicas=1, preempt=True,
                                      max_queue=32))
            try:
                ctrl = BrownoutController(BrownoutConfig(
                    poll_interval_s=0.01, queue_depth_high=4.0,
                    queue_depth_low=1.0, hot_polls=2, cool_polls=2))
                h.fleet.brownout = ctrl
                for i in range(10):
                    h.fleet.submit(_req([2, 3], max_new=8,
                                        priority=PRIORITY_BATCH,
                                        rid=910000 + i))
                for _ in range(600):
                    h._tick_once()
                    assert ctrl.rung <= 1   # never past pause_batch
                    if ctrl.rung == 0 and len(h.fleet.completed) == 10:
                        break
                # everything completed and the ladder came back down
                assert ctrl.rung == 0
                assert len(h.fleet.completed) == 10
                actions = [t[1] for t in ctrl.transitions]
                assert "escalate" in actions and "recover" in actions
            finally:
                h.cleanup()

    def test_fleet_integration_pauses_and_preempts_batch(self):
        """A real (sim-engine) fleet: batch slots running, then a
        standard-class storm. The ladder pauses batch admissions
        (rung 1), then parks the RUNNING batch slots (rung 2) to hand
        their slots to the admissible storm — and the floor is
        re-asserted on every poll (autoscaled replicas inherit it)."""
        from apex_tpu.analysis.mc.harness import MCConfig, FleetHarness

        with use_clock(VirtualClock()):
            h = FleetHarness(MCConfig(replicas=1, preempt=True,
                                      max_queue=64))
            try:
                ctrl = BrownoutController(BrownoutConfig(
                    poll_interval_s=0.01, queue_depth_high=4.0,
                    queue_depth_low=1.0, hot_polls=2, cool_polls=2))
                h.fleet.brownout = ctrl
                for i in range(2):      # long batch work holds the slots
                    h.fleet.submit(_req([2, 3], max_new=40,
                                        priority=PRIORITY_BATCH,
                                        rid=910000 + i))
                h._tick_once()
                h._tick_once()
                for i in range(16):     # admissible standard storm
                    h.fleet.submit(_req([4, 5], max_new=6,
                                        priority=PRIORITY_STANDARD,
                                        rid=920000 + i))
                for _ in range(30):
                    h._tick_once()
                    if ctrl.rung >= 2:
                        break
                assert ctrl.rung >= 2
                # the floor is asserted on every replica each poll
                # (the fleet may have autoscaled mid-storm)
                floors = [eng.admission_floor for eng in h.engines]
                assert PRIORITY_STANDARD in floors
                assert h.registry.counters().get(
                    "requests_preempted", 0) >= 1
                # drain: pressure falls, the ladder recovers to normal
                # and every request — parked batch included — completes
                for _ in range(800):
                    h._tick_once()
                    if ctrl.rung == 0 and len(h.fleet.completed) == 18:
                        break
                assert ctrl.rung == 0
                assert len(h.fleet.completed) == 18
            finally:
                h.cleanup()


# ---------------------------------------------------------------------------
# monitor reconciliation + pre-PR20 back-compat
# ---------------------------------------------------------------------------

def _report_from(records, tmp_path):
    import json

    path = tmp_path / "run.jsonl"
    with open(path, "w", encoding="utf-8") as f:
        for rec in records:
            f.write(json.dumps(rec) + "\n")
    return build_report(str(path))


class TestMonitorReconciliation:
    def test_preempt_resume_quota_reconcile_key_for_key(self, tmp_path):
        from apex_tpu.analysis.mc.harness import MCConfig, FleetHarness
        from apex_tpu.analysis.mc.events import Event

        with use_clock(VirtualClock()):
            h = FleetHarness(MCConfig(replicas=1, preempt=True))
            try:
                h.apply(Event("arrive", a=2, b=2))       # standard
                h.apply(Event("preempt", a=0, b=1))
                h.apply(Event("quota_exceeded", a=1, b=0))
                h.settle()
                counters = h.registry.counters()
                assert counters["requests_preempted"] >= 1
                assert counters["requests_shed_quota"] >= 1

                def events(name):
                    return [r for r in h.sink.records
                            if r.get("kind") == "event"
                            and r.get("event") == name]

                for counter, event in (
                        ("requests_preempted", "request_preempted"),
                        ("requests_resumed", "request_resumed")):
                    assert counters.get(counter, 0) == len(events(event))
                quota_sheds = [e for e in events("request_shed")
                               if e.get("reason") == "quota"]
                assert counters["requests_shed_quota"] == len(quota_sheds)

                report = _report_from(list(h.sink.records), tmp_path)
                by_prio = report["requests"]["by_priority"]
                assert sum(by_prio.values()) == report["requests"]["count"]
                text = render_report(report)
                assert "priority:" in text
            finally:
                h.cleanup()

    def test_brownout_section_in_report(self, tmp_path):
        registry = MetricsRegistry(sinks=[sink := InMemorySink()])
        fleet = _StubFleet(registry)
        ctrl = BrownoutController(TestBrownoutLadder.CFG)
        ctrl._fm = _StubFleetMetrics(fleet, [9, 9, 9, 1, 1, 1])
        for i in range(6):
            ctrl.maybe_step(fleet, now=float(i))
        registry.flush()    # the kind="counters" snapshot row
        report = _report_from(list(sink.records), tmp_path)
        section = report["brownout"]
        assert section is not None
        assert section["by_action"] == {"escalate": 1, "recover": 1}
        assert section["counters"]["brownouts_escalated"] == 1
        assert section["final_rung"] == ctrl.rung
        text = render_report(report)
        assert "brownout ladder" in text


class TestPrePr20BackCompat:
    import os
    PRE_PR20 = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "data", "pre_pr20_run.jsonl")

    def test_renders_without_priority_or_brownout_sections(self):
        """A committed pre-priority log (PR-19 vintage: anomaly rows
        present, NO priority fields on request rows, no brownout /
        preempt / quota rows or counters, torn last line) builds and
        renders with no priority split and no brownout ladder — the
        new sections only appear when their rows exist."""
        report = build_report(self.PRE_PR20)
        assert report["requests"]["count"] == 3
        assert report["requests"]["by_priority"] == {}
        assert report["brownout"] is None
        text = render_report(report)
        assert "priority:" not in text
        assert "brownout ladder" not in text
        # the era's own sections are untouched by the new readers
        assert "drift anomalies" in text

    def test_span_conservation_vacuous_clean(self):
        from apex_tpu.observability.report import read_records
        from apex_tpu.observability.trace import check_span_conservation

        records = read_records(self.PRE_PR20)
        assert check_span_conservation(records) == []


# ---------------------------------------------------------------------------
# jax engine: preempt/resume token-exactness (greedy + sampled), and the
# paged+int8+LoRA cross on the slow tier
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small():
    import jax
    from apex_tpu.models import GPTModel, TransformerConfig

    model = GPTModel(TransformerConfig(
        num_layers=1, hidden_size=32, num_attention_heads=4, vocab_size=64,
        max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0))
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _serve_with_preempt(model, params, victim, hi, cfg, adapters=None):
    """Run victim (low class, long budget) until it is mid-decode, then
    submit hi (interactive); tick to completion. Returns
    (results_by_id, registry, sink)."""
    from apex_tpu.serving import EngineSupervisor

    registry = MetricsRegistry(sinks=[sink := InMemorySink()])
    results = {}
    with EngineSupervisor(model, params, cfg, metrics=registry,
                          adapters=adapters) as sup:
        sup.submit(victim)
        for _ in range(200):
            for res in sup.tick():
                results[res.request_id] = res
            if sup.engine.active_count and len(
                    sup.engine._active[
                        next(iter(sup.engine._active))].tokens) >= 2:
                break
        sup.submit(hi)
        for _ in range(400):
            for res in sup.tick():
                results[res.request_id] = res
            if len(results) == 2:
                break
    return results, registry, sink


def _serve_alone(model, params, request, cfg, adapters=None):
    from apex_tpu.serving import EngineSupervisor

    with EngineSupervisor(model, params, cfg,
                          adapters=adapters) as sup:
        (res,) = sup.serve([request])
    return res


class TestEnginePreemptResume:
    def _cfg(self, **kw):
        kw.setdefault("max_slots", 1)
        kw.setdefault("max_len", 48)
        kw.setdefault("page_size", 4)
        kw.setdefault("scheduler",
                      SchedulerConfig(max_queue=8,
                                      max_prefills_per_tick=1))
        return EngineConfig(**kw)

    def test_greedy_resume_token_exact(self, small):
        model, params = small
        cfg = self._cfg()
        victim = _req([5, 9, 3], max_new=10, priority=PRIORITY_BATCH,
                      rid=400001)
        hi = _req([7, 2], max_new=2, priority=PRIORITY_INTERACTIVE)
        results, registry, sink = _serve_with_preempt(
            model, params, victim, hi, cfg)
        counters = registry.counters()
        assert counters["requests_preempted"] == 1
        assert counters["requests_resumed"] == 1
        res = results[victim.request_id]
        assert res.finish_reason == "length"
        assert res.trace_id == victim.trace_id
        assert res.priority == PRIORITY_BATCH
        # token-exact vs the same request served alone, un-preempted
        alone = _serve_alone(model, params,
                             _req([5, 9, 3], max_new=10,
                                  priority=PRIORITY_BATCH), cfg)
        assert list(res.tokens) == list(alone.tokens)
        # exactly one terminal record, preempt/resume marks on the
        # ORIGINAL trace
        terminal = [r for r in sink.records
                    if r.get("kind") == "request"
                    and r.get("request_id") == victim.request_id]
        assert len(terminal) == 1
        marks = [r for r in sink.records if r.get("kind") == "span"
                 and r.get("span") in ("preempt", "resume")]
        assert {m["trace_id"] for m in marks} == {victim.trace_id}

    @pytest.mark.slow
    def test_sampled_resume_token_exact(self, small):
        model, params = small
        cfg = self._cfg()
        mk = lambda: _req([4, 8, 1], max_new=10, priority=PRIORITY_BATCH,
                          temperature=0.9, top_k=8, seed=1234)
        victim = mk()
        hi = _req([7, 2], max_new=2, priority=PRIORITY_INTERACTIVE)
        results, registry, _ = _serve_with_preempt(
            model, params, victim, hi, cfg)
        assert registry.counters()["requests_preempted"] == 1
        res = results[victim.request_id]
        alone = _serve_alone(model, params, mk(), cfg)
        # sampling keys on absolute position: the resumed stream is
        # bitwise the un-preempted one
        assert list(res.tokens) == list(alone.tokens)

    @pytest.mark.slow
    def test_paged_int8_lora_cross_resume_exact(self, small):
        import jax
        from apex_tpu.lora import AdapterStore, random_adapter

        model, params = small
        store = AdapterStore(model.config, 4, max_adapters=2)
        store.load("a", random_adapter(model.config, 4,
                                       jax.random.PRNGKey(3)))
        cfg = self._cfg(kv_dtype="int8")
        mk = lambda: _req([6, 2, 9], max_new=10, priority=PRIORITY_BATCH,
                          adapter="a", temperature=0.8, top_k=8,
                          seed=77)
        victim = mk()
        hi = _req([7, 2], max_new=2, priority=PRIORITY_INTERACTIVE)
        results, registry, _ = _serve_with_preempt(
            model, params, victim, hi, cfg, adapters=store)
        assert registry.counters()["requests_preempted"] == 1
        res = results[victim.request_id]
        assert res.finish_reason == "length"
        alone = _serve_alone(model, params, mk(), cfg, adapters=store)
        assert list(res.tokens) == list(alone.tokens)
