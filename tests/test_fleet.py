"""Fleet serving tests: router, draining restarts, sharded decode.

Three contracts on top of the single-supervisor stack:

- **Routing**: least-loaded dispatch (``queue_depth × EWMA(service_s)``)
  is deterministic, sticky for in-flight requests, and fleet-wide
  admission removes an open-breaker replica from the dispatch set
  instead of fast-failing the caller — ``FleetUnavailableError`` only
  when NO replica can take work.
- **Draining restarts**: a replica rebuild quiesces, migrates in-flight
  work TOKEN-EXACT to a peer (the supervisor's re-prefill continuations
  fleet-wide), health-probes, and rejoins — capacity never below N−1,
  every request terminal exactly once, the monitor fleet section
  reconciling key-for-key with the counters.
- **Sharded decode**: :class:`~apex_tpu.serving.fleet.ShardedEngine` on
  a tp=2 CPU mesh is token-exact against the unsharded engine (greedy
  AND sampled) with zero decode retraces — the multichip parity bar
  applied to serving.
"""

import json
import os

import jax
import numpy as np
import pytest

from apex_tpu.loadtest import Scenario, run_scenario
from apex_tpu.loadtest.__main__ import EXIT_OK, main as loadtest_main
from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.observability import (
    InMemorySink,
    JsonlSink,
    MetricsRegistry,
    build_report,
    render_report,
)
from apex_tpu.observability.report import FLEET_INCIDENT_COUNTERS
from apex_tpu.serving import (
    BREAKER_OPEN,
    EngineConfig,
    EngineSupervisor,
    EngineUnavailableError,
    FINISH_REASONS,
    InferenceEngine,
    Request,
    SamplingParams,
    SchedulerConfig,
    SupervisorConfig,
)
from apex_tpu.serving.fleet import (
    REPLICA_ACTIVE,
    REPLICA_DRAINING,
    REPLICA_PROBING,
    FleetConfig,
    FleetUnavailableError,
    ReplicaFleet,
    Router,
    ShardedEngine,
)
from apex_tpu.serving.fleet.router import _Replica
from apex_tpu.testing_faults import ServingFaultInjector
from serving_reference import reference_stream


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLEET_SCENARIO = os.path.join(REPO, "benchmarks", "scenarios",
                              "fleet_smoke.json")


@pytest.fixture(scope="module")
def small():
    # 1 layer on purpose (same rationale as the resilience suite): fleet
    # tests build MANY engines — every replica and every rebuild is a
    # fresh prefill+decode compile — and routing/drain semantics do not
    # depend on depth
    model = GPTModel(TransformerConfig(
        num_layers=1, hidden_size=32, num_attention_heads=4, vocab_size=64,
        max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0))
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _prompts(lens, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 64, size=n).tolist() for n in lens]


def _fleet(model, params, n=2, *, max_slots=2, max_len=32, faults=None,
           fleet_cfg=None, supervisor=None, metrics=None, max_queue=16):
    return ReplicaFleet(
        model, params,
        EngineConfig(max_slots=max_slots, max_len=max_len,
                     scheduler=SchedulerConfig(max_queue=max_queue)),
        supervisor=supervisor, metrics=metrics, faults=faults,
        fleet=fleet_cfg or FleetConfig(n_replicas=n))


# ---------------------------------------------------------------------------
# router policy (no engines: stub supervisors)


class _StubSup:
    def __init__(self, queued, active, service):
        self.queued_count = queued
        self.active_count = active
        self.service_estimate_s = service


def _stub_replica(rid, queued, active, service):
    r = _Replica.__new__(_Replica)
    r.replica_id = rid
    r.supervisor = _StubSup(queued, active, service)
    r.state = REPLICA_ACTIVE
    r.dispatches = 0
    r.probe_id = None
    r.probe_attempts = 0
    return r


class TestRouter:
    def test_least_loaded_wins(self):
        a = _stub_replica(0, queued=4, active=2, service=0.5)   # cost 3.0
        b = _stub_replica(1, queued=1, active=1, service=0.5)   # cost 1.0
        assert Router().pick([a, b]).replica_id == 1

    def test_ewma_weighs_depth(self):
        # deeper-but-faster beats shallower-but-slower
        fast = _stub_replica(0, queued=4, active=0, service=0.1)  # 0.4
        slow = _stub_replica(1, queued=1, active=0, service=1.0)  # 1.0
        assert Router().pick([fast, slow]).replica_id == 0

    def test_unknown_service_attracts_traffic(self):
        # a fresh (just rebuilt) replica has no EWMA yet: cost 0 — it
        # deliberately wins over any measured replica
        fresh = _stub_replica(1, queued=3, active=0, service=None)
        busy = _stub_replica(0, queued=1, active=0, service=0.01)
        assert Router().pick([busy, fresh]).replica_id == 1

    def test_ties_break_by_depth_then_id(self):
        a = _stub_replica(0, queued=2, active=0, service=None)
        b = _stub_replica(1, queued=1, active=0, service=None)
        assert Router().pick([a, b]).replica_id == 1
        c = _stub_replica(2, queued=1, active=0, service=None)
        assert Router().pick([b, c]).replica_id == 1  # id breaks the tie

    def test_empty_candidates_rejected(self):
        with pytest.raises(ValueError, match="no candidates"):
            Router().pick([])


class TestFleetConfig:
    def test_validation(self):
        with pytest.raises(ValueError, match="n_replicas"):
            FleetConfig(n_replicas=0)
        with pytest.raises(ValueError, match="max_rebuild_probes"):
            FleetConfig(max_rebuild_probes=0)

    def test_unknown_fault_replica_rejected(self, small):
        model, params = small
        with pytest.raises(ValueError, match="unknown replica ids"):
            ReplicaFleet(model, params, EngineConfig(max_slots=2,
                                                     max_len=16),
                         fleet=FleetConfig(n_replicas=2),
                         faults={5: ServingFaultInjector()})


# ---------------------------------------------------------------------------
# dispatch, stickiness, fleet-wide admission


class TestFleetDispatch:
    def test_spreads_load_and_labels_results(self, small):
        """Arrivals spread across replicas; every result and record
        carries the replica that served it; dispatch counters split
        exactly."""
        model, params = small
        reg = MetricsRegistry([InMemorySink()])
        fleet = _fleet(model, params, metrics=reg)
        reqs = [Request(prompt=p, max_new_tokens=4)
                for p in _prompts([4, 5, 3, 6], seed=11)]
        with fleet:
            results = fleet.serve(reqs)
        assert [r.finish_reason for r in results] == ["length"] * 4
        homes = {r.replica_id for r in results}
        assert homes == {0, 1}          # both replicas served work
        counters = reg.counters()
        assert counters["fleet_dispatches"] == 4
        assert (counters["replica0_dispatches"]
                + counters["replica1_dispatches"]) == 4
        assert counters["requests_submitted"] == 4

    def test_sticky_cancel_follows_the_request(self, small):
        model, params = small
        fleet = _fleet(model, params)
        reqs = [Request(prompt=p, max_new_tokens=16)
                for p in _prompts([4, 4], seed=13)]
        with fleet:
            for r in reqs:
                fleet.submit(r)
            fleet.tick()
            assert fleet.cancel(reqs[1].request_id)
            while fleet.inflight_count:
                fleet.tick()
            res = fleet.completed[reqs[1].request_id]
            assert res.finish_reason == "cancelled"
            assert fleet.completed[reqs[0].request_id].finish_reason \
                == "length"
        assert not fleet.cancel(reqs[0].request_id)  # already terminal

    def test_open_breaker_leaves_dispatch_set(self, small):
        """A failing replica's breaker removes it from routing; traffic
        flows to the healthy peer instead of fast-failing."""
        model, params = small
        # replica 0's decode always raises: supervisor restarts burn out
        # and its breaker opens; replica 1 is clean
        inj = ServingFaultInjector(decode_raise_calls=range(0, 64))
        fleet = _fleet(
            model, params, faults={0: inj},
            supervisor=SupervisorConfig(breaker_threshold=1,
                                        breaker_cooldown_s=60.0,
                                        max_restarts_per_request=1))
        with fleet:
            victim = Request(prompt=_prompts([4], seed=17)[0],
                             max_new_tokens=4)
            fleet.submit(victim)        # routed to replica 0 (empty)
            for _ in range(8):
                fleet.tick()
                if fleet.replicas[0].supervisor.breaker_state \
                        == BREAKER_OPEN:
                    break
            assert fleet.replicas[0].supervisor.breaker_state \
                == BREAKER_OPEN
            assert [r.replica_id for r in fleet.dispatch_set()] == [1]
            after = Request(prompt=_prompts([4], seed=19)[0],
                            max_new_tokens=3)
            fleet.submit(after)
            while fleet.inflight_count:
                fleet.tick()
            res = fleet.completed[after.request_id]
            assert res.finish_reason == "length"
            assert res.replica_id == 1

    def test_fleet_unavailable_when_all_replicas_open(self, small):
        """Only when EVERY replica is out does the front door reject —
        terminally recorded, reason='fleet'."""
        model, params = small
        reg = MetricsRegistry([InMemorySink()])
        inj = {i: ServingFaultInjector(decode_raise_calls=range(0, 64))
               for i in range(2)}
        fleet = _fleet(
            model, params, faults=inj, metrics=reg,
            # max_engine_restarts=1: the second rebuild retires every
            # survivor, so the drain loop below stays cheap (each
            # rebuild is a fresh compile)
            supervisor=SupervisorConfig(breaker_threshold=1,
                                        breaker_cooldown_s=60.0,
                                        max_restarts_per_request=1,
                                        max_engine_restarts=1))
        with fleet:
            doomed = [Request(prompt=p, max_new_tokens=4)
                      for p in _prompts([4, 4], seed=23)]
            for r in doomed:
                fleet.submit(r)
            for _ in range(10):
                fleet.tick()
                if not fleet.dispatch_set():
                    break
            assert not fleet.dispatch_set()
            shed = Request(prompt=_prompts([3], seed=29)[0],
                           max_new_tokens=2)
            with pytest.raises(FleetUnavailableError):
                fleet.submit(shed)
            assert fleet.completed[shed.request_id].finish_reason \
                == "rejected"
            guard = 0
            while fleet.inflight_count and guard < 50:
                fleet.tick()    # retry budgets exhaust -> error retire
                guard += 1
            assert not fleet.inflight_count
        counters = reg.counters()
        assert counters["requests_shed_fleet"] == 1
        # conservation: 2 doomed + 1 shed, each exactly one terminal
        assert counters["requests_submitted"] == 3
        terminal = sum(counters[f"requests_{r}"] for r in FINISH_REASONS)
        assert terminal == 3


# ---------------------------------------------------------------------------
# draining restarts


class TestDrainingRestart:
    @pytest.mark.slow  # migration parity vs generate(): slow-tier class
    def test_migrated_request_is_token_exact(self, small):
        """Drain mid-generation: in-flight work re-prefills on the peer
        and the stitched stream equals a fault-free greedy run; the
        rebuilt replica rejoins and serves again; the EWMA is carried."""
        model, params = small
        reg = MetricsRegistry([InMemorySink()])
        fleet = _fleet(model, params, metrics=reg)
        warm = [Request(prompt=p, max_new_tokens=3)
                for p in _prompts([4, 4], seed=31)]
        with fleet:
            fleet.serve(warm)           # seeds both replicas' EWMAs
            ewma_before = fleet.replicas[0].supervisor.service_estimate_s
            assert ewma_before is not None
            victim = Request(prompt=_prompts([5], seed=37)[0],
                             max_new_tokens=10)
            fleet.submit(victim)
            for _ in range(3):          # partial decode on its replica
                fleet.tick()
            assert victim.request_id not in fleet.completed
            victim_home = fleet._tracked[victim.request_id].replica_id
            fleet.drain_restart(victim_home)
            min_dispatchable = []
            while fleet.inflight_count:
                fleet.tick()
                min_dispatchable.append(len(fleet.dispatch_set()))
            # capacity never below N-1 while draining/rebuilding/probing
            assert min(min_dispatchable) >= fleet.n_replicas - 1
            res = fleet.completed[victim.request_id]
            assert res.finish_reason == "length"
            assert res.replica_id == 1 - victim_home  # finished on peer
            assert res.tokens == reference_stream(model, params, victim,
                                                  32)
            # the rebuilt replica rejoined with the carried estimate
            rebuilt = fleet.replicas[victim_home]
            assert rebuilt.state == REPLICA_ACTIVE
            assert rebuilt.supervisor.service_estimate_s is not None
            again = Request(prompt=_prompts([4], seed=41)[0],
                            max_new_tokens=2)
            fleet.serve([again])
            assert fleet.completed[again.request_id].finish_reason \
                == "length"
        counters = reg.counters()
        assert counters["replica_drains"] == 1
        assert counters["replica_rebuilds"] == 1
        assert counters["requests_migrated"] == 1
        for r in fleet.replicas:        # no slot leaks anywhere
            r.supervisor.engine.slots.check()

    @pytest.mark.slow  # drain-in-place parity vs generate(): slow tier
    def test_drain_without_migration_finishes_in_place(self, small):
        model, params = small
        reg = MetricsRegistry([InMemorySink()])
        fleet = _fleet(model, params, metrics=reg,
                       fleet_cfg=FleetConfig(n_replicas=2,
                                             migrate_on_drain=False))
        with fleet:
            req = Request(prompt=_prompts([4], seed=43)[0],
                          max_new_tokens=6)
            fleet.submit(req)
            fleet.tick()
            home = fleet._tracked[req.request_id].replica_id
            fleet.drain_restart(home)
            assert fleet.replicas[home].state == REPLICA_DRAINING
            while fleet.inflight_count:
                fleet.tick()
            res = fleet.completed[req.request_id]
            # finished on its ORIGINAL replica, then the rebuild happened
            assert res.replica_id == home
            assert res.tokens == reference_stream(model, params, req, 32)
            assert fleet.replicas[home].state == REPLICA_ACTIVE
        counters = reg.counters()
        assert counters["requests_migrated"] == 0
        assert counters["replica_rebuilds"] == 1

    def test_one_drain_at_a_time(self, small):
        model, params = small
        # no migration: the drain lingers while the victim replica
        # finishes its own work, holding the draining state open
        fleet = _fleet(model, params,
                       fleet_cfg=FleetConfig(n_replicas=2,
                                             migrate_on_drain=False,
                                             probe_on_rebuild=False))
        with fleet:
            req = Request(prompt=_prompts([4], seed=47)[0],
                          max_new_tokens=8)
            fleet.submit(req)
            fleet.tick()
            home = fleet._tracked[req.request_id].replica_id
            peer = 1 - home
            fleet.drain_restart(home)
            with pytest.raises(RuntimeError, match="one.*at a time"):
                fleet.drain_restart(peer)
            with pytest.raises(RuntimeError, match="not active"):
                fleet.drain_restart(home)
            with pytest.raises(ValueError, match="no replica"):
                fleet.drain_restart(7)
            while fleet.inflight_count:
                fleet.tick()

    def test_probe_gates_rejoin(self, small):
        """After a rebuild the replica serves a real one-token probe
        before taking traffic — the probe is a counted, recorded request
        (conservation holds)."""
        model, params = small
        reg = MetricsRegistry([InMemorySink()])
        fleet = _fleet(model, params, metrics=reg)
        with fleet:
            fleet.drain_restart(0)      # idle drain: immediate rebuild
            assert fleet.replicas[0].state == REPLICA_PROBING
            assert fleet.inflight_count == 1     # the probe itself
            while fleet.inflight_count:
                fleet.tick()
            assert fleet.replicas[0].state == REPLICA_ACTIVE
        counters = reg.counters()
        assert counters["requests_submitted"] == 1   # just the probe
        assert counters["requests_length"] == 1


class TestServiceEstimateCarry:
    def test_constructor_seed(self, small):
        model, params = small
        sup = EngineSupervisor(model, params,
                               EngineConfig(max_slots=1, max_len=16),
                               service_s=0.125)
        assert sup.service_estimate_s == 0.125
        sup.close()

    def test_survives_engine_rebuild(self, small):
        """The EWMA is supervisor state: an engine restart must NOT
        reset it (the first post-restart submits would be admitted with
        no service estimate)."""
        model, params = small
        sup = EngineSupervisor(model, params,
                               EngineConfig(max_slots=1, max_len=16))
        with sup:
            sup.serve([Request(prompt=_prompts([4], seed=53)[0],
                               max_new_tokens=3)])
            before = sup.service_estimate_s
            assert before is not None
            sup._restart("test: forced rebuild")
            assert sup.service_estimate_s == before


# ---------------------------------------------------------------------------
# the committed fleet smoke scenario (acceptance)


class TestFleetSmokeScenario:
    @pytest.mark.slow
    def test_fleet_smoke_conserves_and_reconciles(self, tmp_path):
        """Acceptance: N=2 replicas, one scheduled draining restart
        mid-run — every submitted request reaches a terminal state
        exactly once, ZERO error finishes, and the monitor fleet
        section reconciles key-for-key with the telemetry counters."""
        scn = Scenario.load(FLEET_SCENARIO)
        model, params = None, None
        from apex_tpu.loadtest.runner import build_model
        model, params = build_model(scn.model)
        log = str(tmp_path / "fleet_smoke.jsonl")
        run = run_scenario(scn, model=model, params=params, log_path=log)
        assert not run.aborted
        assert run.submitted == scn.total_requests
        assert run.ok, run.slo.as_dict()

        report = build_report(log)
        counters = report["counters"]
        req = report["requests"]
        # conservation: one counted submit == one terminal record, and
        # nothing finished as an error
        assert counters["requests_submitted"] == req["count"]
        assert req["by_finish_reason"].get("error", 0) == 0
        assert counters["requests_error"] == 0
        terminal = sum(counters[f"requests_{r}"] for r in FINISH_REASONS)
        assert terminal == req["count"]
        # every SCHEDULED request is terminal exactly once in the
        # runner's results (records may add fleet-internal probes)
        sched_ids = [s.request.request_id for s in run.schedule]
        assert len(sched_ids) == len(set(sched_ids))
        for rid in sched_ids:
            assert rid in run.results, rid
            assert run.results[rid].finish_reason in FINISH_REASONS
            assert run.results[rid].finish_reason != "error"

        # the drain actually happened and the fleet section reconciles
        # key-for-key: each incident event count equals its counter, and
        # the per-replica dispatch split sums to the total
        fleet = report["fleet"]
        assert fleet is not None
        assert counters["replica_drains"] == 1
        assert counters["replica_rebuilds"] >= 1
        for event, counter in FLEET_INCIDENT_COUNTERS.items():
            assert fleet["counts"].get(event, 0) == counters[counter], \
                event
        split = [v for k, v in fleet["dispatches"].items()
                 if k != "fleet_dispatches"]
        assert sum(split) == counters["fleet_dispatches"]
        # every terminal record is attributed to a replica (nothing was
        # shed at the fleet level in the smoke)
        assert sum(fleet["requests_by_replica"].values()) == req["count"]
        text = render_report(report)
        assert "fleet:" in text and "requests by replica" in text

        # and the gate goes green against a fresh baseline (CLI
        # plumbing over a real fleet run log)
        base = str(tmp_path / "base.json")
        assert loadtest_main([FLEET_SCENARIO, "--from-log", log,
                              "--baseline", base,
                              "--update-baseline"]) == EXIT_OK
        assert loadtest_main([FLEET_SCENARIO, "--from-log", log,
                              "--check", "--baseline", base]) == EXIT_OK

    def test_fleet_block_round_trips(self):
        scn = Scenario.load(FLEET_SCENARIO)
        assert scn.fleet is not None and scn.fleet.n_replicas == 2
        assert scn.fleet.drain_restarts == ((2.0, 0),)
        again = Scenario.from_dict(scn.to_dict())
        assert again.to_dict() == scn.to_dict()

    def test_fleet_block_validation(self):
        d = json.load(open(FLEET_SCENARIO))
        d["fleet"]["drain_restarts"] = [{"at_s": 1.0, "replica": 9}]
        with pytest.raises(ValueError, match="out of range"):
            Scenario.from_dict(d)
        d["fleet"] = {"n_replicas": 2, "bogus": 1}
        with pytest.raises(ValueError, match="unknown fleet keys"):
            Scenario.from_dict(d)


# ---------------------------------------------------------------------------
# sharded decode (tp=2 over the virtual CPU mesh)


@pytest.fixture
def tp2_mesh():
    from apex_tpu.transformer import parallel_state

    parallel_state.destroy_model_parallel()
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2)
    yield mesh
    parallel_state.destroy_model_parallel()


class TestShardedEngine:
    def test_indivisible_heads_fail_fast(self, tp2_mesh):
        model = GPTModel(TransformerConfig(
            num_layers=1, hidden_size=32, num_attention_heads=4,
            num_query_groups=1, vocab_size=64,
            max_position_embeddings=64, hidden_dropout=0.0,
            attention_dropout=0.0))
        params = model.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="divisible"):
            ShardedEngine(model, params,
                          EngineConfig(max_slots=2, max_len=16))

    def test_indivisible_vocab_fails_fast(self, tp2_mesh):
        model = GPTModel(TransformerConfig(
            num_layers=1, hidden_size=32, num_attention_heads=4,
            vocab_size=97, max_position_embeddings=64,
            hidden_dropout=0.0, attention_dropout=0.0))
        params = model.init(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="vocab_size.*divisible"):
            ShardedEngine(model, params,
                          EngineConfig(max_slots=2, max_len=16))

    def test_tp2_two_tick_stream_feeds_the_carried_token(self, small,
                                                          tp2_mesh):
        """The sharded decode program takes the carried token vector and
        its mask replicated: a stream of three tokens (the prefill's,
        then two decode ticks, the second step fed on the device from
        the first) equals the per-request reference, greedy and sampled,
        and the program compiled once: the first carried vector is
        placed as the program returns the later ones."""
        model, params = small
        reqs = [Request(prompt=p, max_new_tokens=3, sampling=s)
                for p, s in zip(_prompts([4, 6], seed=71), (
                    SamplingParams(),
                    SamplingParams(temperature=0.9, top_k=8, seed=5)))]
        with ShardedEngine(model, params,
                           EngineConfig(max_slots=2, max_len=16)) as eng:
            out = eng.serve(reqs)
            assert eng.decode_compiles == 1 and eng.decode_retraces == 0
            counters = eng.metrics.counters()
        for req, res in zip(reqs, out):
            assert res.tokens == reference_stream(model, params, req, 16)
        assert counters["decode_steps_overlapped"] >= 1
        assert counters["decode_rows_dropped"] == 0

    def test_tp2_gated_weight_shards_its_ffn_axis(self, tp2_mesh):
        """A gated model under tensor parallelism: the engine holds the
        gate/up weight halves apart, ``[2, ffn, h]``, sharded on ``ffn``
        (every rank: matched gate/up pairs, and the rows its slice of
        ``dense_4h_to_h`` reads), and serves the tokens of the
        per-request reference on the interleaved params."""
        from jax.sharding import PartitionSpec as P

        model = GPTModel(TransformerConfig(
            num_layers=1, hidden_size=32, num_attention_heads=4,
            vocab_size=64, max_position_embeddings=64, hidden_dropout=0.0,
            attention_dropout=0.0, activation="swiglu",
            untie_embeddings_and_output_weights=True, init_method_std=0.3))
        params = model.init(jax.random.PRNGKey(0))
        reqs = [Request(prompt=p, max_new_tokens=4, sampling=s)
                for p, s in zip(_prompts([4, 6], seed=71), (
                    SamplingParams(),
                    SamplingParams(temperature=0.9, top_k=8, seed=5)))]
        with ShardedEngine(model, params,
                           EngineConfig(max_slots=2, max_len=16)) as eng:
            mlp = eng._param_spec()["transformer"]["layers"][0]["mlp"]
            assert mlp["dense_h_to_4h"]["weight"] == P(None, "tensor", None)
            assert mlp["dense_4h_to_h"]["weight"] == P(None, "tensor")
            assert eng._params["transformer"]["layers"][0]["mlp"][
                "dense_h_to_4h"]["weight"].shape == (2, 128, 32)
            out = eng.serve(reqs)
            assert eng.decode_compiles == 1 and eng.decode_retraces == 0
        for req, res in zip(reqs, out):
            assert res.tokens == reference_stream(model, params, req, 16)
        assert out[0].tokens != out[1].tokens

    @pytest.mark.slow  # TP model parity: the slow-tier class (ROADMAP)
    def test_tp2_token_exact_vs_unsharded(self, small, tp2_mesh):
        """Acceptance: ShardedEngine decode on a tp=2 CPU mesh is
        token-exact vs the unsharded engine — greedy AND sampled — with
        zero decode retraces and bucket-bounded prefill compiles."""
        model, params = small
        rng = np.random.RandomState(61)
        specs = [(4, 6, SamplingParams()),
                 (7, 5, SamplingParams(temperature=0.8, top_k=8, seed=3)),
                 (3, 8, SamplingParams()),
                 (5, 4, SamplingParams(temperature=1.1, seed=9))]
        prompts = [rng.randint(0, 64, size=n).tolist()
                   for n, _, _ in specs]

        def requests():
            return [Request(prompt=p, max_new_tokens=m, sampling=s)
                    for p, (_, m, s) in zip(prompts, specs)]

        ref_engine = InferenceEngine(
            model, params, EngineConfig(max_slots=4, max_len=32))
        with ref_engine:
            ref = ref_engine.serve(requests())

        sharded = ShardedEngine(
            model, params, EngineConfig(max_slots=4, max_len=32))
        with sharded:
            out = sharded.serve(requests())
            assert sharded.decode_retraces == 0
            assert sharded.prefill_compiles <= len(sharded.buckets)
            sharded.slots.check()
        for a, b in zip(ref, out):
            assert a.finish_reason == b.finish_reason
            assert a.tokens == b.tokens, (a.request_id, a.tokens, b.tokens)

    @pytest.mark.slow
    def test_sharded_engine_under_supervision(self, small, tp2_mesh):
        """The composition the fleet is for: a ShardedEngine replica
        under an EngineSupervisor recovers from an injected crash
        token-exact — the sharded program rebuilds like any engine."""
        model, params = small
        inj = ServingFaultInjector(decode_raise_calls={2})
        sup = EngineSupervisor(
            model, params, EngineConfig(max_slots=2, max_len=32),
            faults=inj,
            engine_factory=lambda m, p, c, **kw: ShardedEngine(m, p, c,
                                                               **kw))
        req = Request(prompt=_prompts([4], seed=67)[0], max_new_tokens=8)
        with sup:
            results = sup.serve([req])
        assert sup.restarts == 1
        assert results[0].tokens == reference_stream(model, params, req,
                                                     32)


# ---------------------------------------------------------------------------
# chaos: randomized arrivals x per-replica faults x draining restarts


@pytest.mark.slow
class TestFleetChaosSweep:
    def test_chaos_terminal_exactly_once_no_leaks(self, small):
        """Slow-tier acceptance: randomized arrivals, per-replica fault
        injection, cancellations, and draining restarts — every request
        reaches exactly one terminal state, no replica leaks slots, and
        structural capacity never drops below N-1 (at most one replica
        draining/probing at any point)."""
        model, params = small
        for seed in (0, 1, 2):
            rng = np.random.RandomState(100 + seed)
            faults = {
                0: ServingFaultInjector(
                    decode_raise_calls={int(rng.randint(2, 12))},
                    poison_decode={int(rng.randint(4, 16)):
                                   (int(rng.randint(0, 2)),
                                    "nonfinite")}),
                1: ServingFaultInjector(
                    decode_raise_calls={int(rng.randint(2, 12))}),
            }
            reg = MetricsRegistry([InMemorySink()])
            fleet = _fleet(
                model, params, faults=faults, metrics=reg,
                supervisor=SupervisorConfig(max_restarts_per_request=3,
                                            breaker_threshold=3,
                                            breaker_cooldown_s=0.05))
            submitted = []
            cancelled = set()
            drained = [False]
            with fleet:
                for step in range(40):
                    if rng.rand() < 0.6:
                        req = Request(
                            prompt=rng.randint(
                                0, 64,
                                size=int(rng.randint(2, 9))).tolist(),
                            max_new_tokens=int(rng.randint(1, 8)),
                            sampling=(
                                SamplingParams() if rng.rand() < 0.5
                                else SamplingParams(
                                    temperature=0.9,
                                    seed=int(rng.randint(0, 2**31)))))
                        try:
                            fleet.submit(req)
                            submitted.append(req)
                        except Exception:
                            submitted.append(req)  # recorded terminally
                    if submitted and rng.rand() < 0.1:
                        victim = submitted[int(rng.randint(
                            0, len(submitted)))]
                        if fleet.cancel(victim.request_id):
                            cancelled.add(victim.request_id)
                    if step == 15 and not drained[0]:
                        target = [r.replica_id for r in fleet.replicas
                                  if r.state == REPLICA_ACTIVE]
                        if target:
                            try:
                                fleet.drain_restart(target[0])
                                drained[0] = True
                            except RuntimeError:
                                pass
                    fleet.tick()
                    busy = sum(1 for r in fleet.replicas
                               if r.state in (REPLICA_DRAINING,
                                              REPLICA_PROBING))
                    assert busy <= 1, "capacity fell below N-1"
                guard = 0
                while fleet.inflight_count and guard < 400:
                    fleet.tick()
                    guard += 1
                assert not fleet.inflight_count, "requests stuck"
                for req in submitted:
                    assert req.request_id in fleet.completed, \
                        req.request_id
                    assert fleet.completed[req.request_id].finish_reason \
                        in FINISH_REASONS
                for r in fleet.replicas:
                    r.supervisor.engine.slots.check()
            # conservation: counted submits == terminal records, split
            # by reason (probe requests included on both sides)
            counters = reg.counters()
            terminal = sum(counters[f"requests_{r}"]
                           for r in FINISH_REASONS)
            assert counters["requests_submitted"] == terminal
