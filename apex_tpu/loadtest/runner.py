"""Scenario execution: generator -> supervised engine -> JSONL -> SLOs.

:func:`run_scenario` is the harness's engine room. It materializes the
scenario's arrival schedule (:mod:`~apex_tpu.loadtest.generator`),
builds the model under test, wraps an
:class:`~apex_tpu.serving.InferenceEngine` in an
:class:`~apex_tpu.serving.EngineSupervisor` (with the scenario's fault
schedule driving a :class:`~apex_tpu.testing_faults.\
ServingFaultInjector`), and replays the schedule **open-loop** against
wall clock: a request is submitted the moment its arrival time passes,
whether or not the engine kept up — queue growth, shedding, and
deadline misses are the signal, not an error.

Everything observable flows through one
:class:`~apex_tpu.observability.MetricsRegistry`: the scenario record
(name, seed, declared SLOs — so the log scores itself in
``python -m apex_tpu.monitor``), every ``kind="request"`` row and
incident event the serving tier already emits, and the final counter
snapshot. The returned :class:`ScenarioRun` carries the in-memory
record stream plus the scored :class:`~apex_tpu.observability.slo.\
SLOReport`, and the same records land in ``log_path`` when given.
"""

from __future__ import annotations

import tempfile
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from apex_tpu.loadtest.generator import ScheduledRequest, TrafficGenerator
from apex_tpu.loadtest.scenario import ModelSpec, Scenario
from apex_tpu.observability import (
    FleetMetrics,
    InMemorySink,
    JsonlSink,
    MetricsRegistry,
)
from apex_tpu.observability.slo import (
    SLOReport,
    SLOSpec,
    evaluate_slos,
    measure_slo_metrics,
)
from apex_tpu.serving import (
    DeadlineExpiredError,
    EngineConfig,
    EngineSupervisor,
    EngineUnavailableError,
    QueueFullError,
    RequestResult,
    SchedulerConfig,
    SupervisorConfig,
    UnknownAdapterError,
)
from apex_tpu.serving import clock
from apex_tpu.utils.logging import get_logger, log_event

__all__ = ["ScenarioRun", "build_model", "run_scenario"]

_LOG = get_logger(__name__)

#: while no arrival is due and nothing is in flight, sleep at most this
#: long per wait slice (keeps the loop responsive to the next arrival
#: without busy-spinning)
_IDLE_SLEEP_S = 0.005


def build_model(spec: ModelSpec):
    """Build the (seeded) model under test from its scenario spec —
    same construction the serving tests use, so a scenario's weights are
    reproducible across runs and machines."""
    import jax  # deferred: scenario loading/scoring stays jax-free

    from apex_tpu.models import GPTModel, TransformerConfig

    model = GPTModel(TransformerConfig(
        num_layers=spec.num_layers, hidden_size=spec.hidden_size,
        num_attention_heads=spec.num_attention_heads,
        vocab_size=spec.vocab_size,
        max_position_embeddings=spec.max_position_embeddings,
        hidden_dropout=0.0, attention_dropout=0.0))
    params = model.init(jax.random.PRNGKey(spec.param_seed))
    return model, params


@dataclass
class ScenarioRun:
    """Everything one scenario execution produced."""

    scenario: Scenario
    schedule: List[ScheduledRequest]
    results: Dict[int, RequestResult]     # request_id -> terminal result
    records: List[dict]                   # the full JSONL record stream
    counters: Dict[str, int]
    wall_s: float
    aborted: bool = False                 # hit the max_wall_s guard
    slo: Optional[SLOReport] = None
    log_path: Optional[str] = None
    ticks: int = 0
    engine_restarts: int = 0
    submitted: int = 0                    # arrivals actually offered
    metrics_by_name: Dict[str, Optional[float]] = field(
        default_factory=dict)
    #: per-tenant SLO attribution (adapter_id -> metrics dict); kept
    #: apart from metrics_by_name — never part of the baseline payload
    slo_by_adapter: Dict[str, Dict[str, Optional[float]]] = field(
        default_factory=dict)
    #: the final FleetMetrics.signals() poll (fleet scenarios only) —
    #: also stamped into the log as the kind="signals" record
    signals: Optional[dict] = None
    #: recompilations beyond the engines' expected warmup compiles, from
    #: the RetraceWatchdogs every engine wraps its step programs in —
    #: must be 0; a storm fails the run even when every SLO passes
    retraces: int = 0
    #: postmortem bundles the scenario's FlightRecorder dumped (empty
    #: when no ``recorder`` block, or when nothing incident-class fired)
    bundles: List[dict] = field(default_factory=list)
    #: where those bundles landed on disk — next to the run log (empty
    #: for in-memory runs with no ``log_path``)
    bundle_paths: List[str] = field(default_factory=list)

    @property
    def ok(self) -> bool:
        """SLO verdict (vacuously true when no SLOs are declared) —
        AND'd with the retrace watchdogs: a recompilation storm is a
        perf bug even when the SLOs it hasn't yet sunk still pass."""
        slo_ok = self.slo.ok if self.slo is not None else True
        return slo_ok and self.retraces == 0


def _build_serving(scenario: Scenario, model, params,
                   metrics: MetricsRegistry):
    """The serving tier under test: a single
    :class:`~apex_tpu.serving.EngineSupervisor`, or — when the scenario
    declares a ``fleet`` block — a
    :class:`~apex_tpu.serving.fleet.ReplicaFleet` (the fault schedule
    then drives replica 0's injector). Both expose the same driving
    surface, so the replay loop below is tier-agnostic."""
    from apex_tpu.testing_faults import ServingFaultInjector

    knobs = scenario.engine
    adapters = None
    if knobs.lora_adapters:
        # seeded adapter store: ids "0".."n-1", each a random rank-r
        # adapter keyed by the scenario seed — reproducible per-tenant
        # weights, the same way build_model seeds the base model
        import jax

        from apex_tpu.lora import AdapterStore, random_adapter

        adapters = AdapterStore(model.config, knobs.lora_rank,
                                max_adapters=knobs.lora_adapters)
        keys = jax.random.split(jax.random.PRNGKey(scenario.seed),
                                knobs.lora_adapters)
        for ix in range(knobs.lora_adapters):
            adapters.load(str(ix), random_adapter(
                model.config, knobs.lora_rank, keys[ix]))
    engine_cfg = EngineConfig(
        max_slots=knobs.max_slots, max_len=knobs.max_len,
        page_size=knobs.page_size, n_pages=knobs.n_pages,
        prefix_cache=knobs.prefix_cache,
        prefix_lru_capacity=knobs.prefix_lru_capacity,
        kv_dtype=knobs.kv_dtype,
        speculation=knobs.speculation,
        prefill_token_budget=knobs.prefill_token_budget,
        scheduler=SchedulerConfig(
            max_queue=knobs.max_queue,
            max_prefills_per_tick=knobs.max_prefills_per_tick))
    sup_cfg = SupervisorConfig(**scenario.supervisor)
    faults = None
    if not scenario.faults.empty:
        faults = ServingFaultInjector(**scenario.faults.injector_kwargs())
    if scenario.fleet is not None:
        from apex_tpu.serving.fleet import (
            AutoscaleConfig,
            FleetConfig,
            ReplicaFleet,
        )

        fl = scenario.fleet
        autoscale = AutoscaleConfig(**scenario.autoscale.config_kwargs()) \
            if scenario.autoscale is not None else None
        sentinel = None
        if scenario.sentinel is not None:
            from apex_tpu.observability.sentinel import SentinelConfig

            sentinel = SentinelConfig(
                **scenario.sentinel.config_kwargs())
        quotas = None
        if scenario.quotas is not None:
            from apex_tpu.serving.fleet import QuotaConfig, TenantQuota

            quotas = QuotaConfig(
                tenants={k: TenantQuota(**v)
                         for k, v in scenario.quotas.tenants.items()},
                default=(TenantQuota(**scenario.quotas.default)
                         if scenario.quotas.default is not None else None))
        brownout = None
        if scenario.brownout is not None:
            from apex_tpu.serving.fleet import BrownoutConfig

            brownout = BrownoutConfig(
                **scenario.brownout.config_kwargs())
        return ReplicaFleet(
            model, params, engine_cfg, supervisor=sup_cfg,
            fleet=FleetConfig(n_replicas=fl.n_replicas,
                              migrate_on_drain=fl.migrate_on_drain,
                              probe_on_rebuild=fl.probe_on_rebuild),
            metrics=metrics, faults=faults, adapters=adapters,
            autoscale=autoscale, sentinel=sentinel,
            quotas=quotas, brownout=brownout)
    return EngineSupervisor(model, params, engine_cfg,
                            supervisor=sup_cfg, metrics=metrics,
                            faults=faults, adapters=adapters)


def _prepare_deploy(scenario: Scenario, model, params,
                    scratch: str) -> Dict[str, Any]:
    """Materialize the scenario's ``deploy`` artifact and return the
    kwargs for :meth:`~apex_tpu.serving.fleet.ReplicaFleet.deploy`.

    ``kind="checkpoint"`` saves the scenario's own seeded parameters at
    step 1 through a :class:`~apex_tpu.checkpoint.\
ShardedCheckpointManager` (a happy-path deploy is weight-identical, so
    it must be token-exact); ``poison=true`` then value-corrupts the
    committed step with :func:`~apex_tpu.testing_faults.\
corrupt_checkpoint_weights` — fsck stays green, the live canary score
    is the only detector. ``kind="adapter"`` builds a seeded LoRA
    canary tenant (NaN factors when poisoned)."""
    from apex_tpu.serving.fleet import CanaryConfig

    spec = scenario.deploy
    canary = CanaryConfig(**{
        k: (int(v) if k == "min_requests" else float(v))
        for k, v in spec.canary.items()})
    if spec.kind == "adapter":
        import jax

        from apex_tpu.lora import random_adapter

        factors = random_adapter(
            model.config, scenario.engine.lora_rank,
            # offset keeps the canary tenant's weights distinct from
            # the runner-preloaded ids "0".."n-1" (same seed stream)
            jax.random.PRNGKey(scenario.seed + 7919))
        if spec.poison:
            factors = jax.tree_util.tree_map(
                lambda a: a * float("nan"), factors)
        return {"adapter": (spec.adapter_id, factors), "canary": canary}
    from apex_tpu.checkpoint import ShardedCheckpointManager

    ShardedCheckpointManager(scratch, max_to_keep=1).save(1, params)
    if spec.poison:
        from apex_tpu.testing_faults import corrupt_checkpoint_weights

        corrupt_checkpoint_weights(scratch, 1)
    return {"checkpoint_dir": scratch, "step": 1, "canary": canary}


def run_scenario(scenario: Scenario, *, model=None, params=None,
                 metrics: Optional[MetricsRegistry] = None,
                 log_path: Optional[str] = None) -> ScenarioRun:
    """Execute ``scenario`` and score it against its declared SLOs.

    ``model``/``params`` default to :func:`build_model` of the
    scenario's model spec (pass them to reuse an already-built model,
    e.g. a test fixture). ``metrics`` defaults to a fresh registry;
    ``log_path`` attaches a JSONL sink so the run is
    ``python -m apex_tpu.monitor``-able afterwards. An
    :class:`~apex_tpu.observability.InMemorySink` is always attached:
    the SLO verdict is computed from the very records the sinks saw.
    """
    if (model is None) != (params is None):
        raise ValueError("pass both model and params, or neither")
    if model is None:
        model, params = build_model(scenario.model)
    registry = metrics if metrics is not None else MetricsRegistry()
    mem = InMemorySink()
    registry.add_sink(mem)
    if log_path is not None:
        registry.add_sink(JsonlSink(log_path))
    recorder = None
    if scenario.recorder is not None:
        import os

        from apex_tpu.observability.recorder import FlightRecorder

        # bundles land next to the run log, named after it; a run with
        # no log keeps them in memory (ScenarioRun.bundles)
        bundle_dir = bundle_prefix = None
        if log_path is not None:
            bundle_dir = os.path.dirname(os.path.abspath(log_path))
            bundle_prefix = os.path.splitext(
                os.path.basename(log_path))[0]
        recorder = FlightRecorder(
            bundle_dir=bundle_dir,
            bundle_prefix=bundle_prefix or scenario.name,
            **scenario.recorder.recorder_kwargs())
        # attached before the scenario record so the rings hold the
        # run's self-description too
        registry.add_sink(recorder)
    # the log's self-description: name + seed for provenance, the SLO
    # spec so the monitor (and --from-log re-scoring) can render a
    # verdict without the scenario file at hand
    registry.emit_record({
        "kind": "scenario", "name": scenario.name, "seed": scenario.seed,
        "total_requests": scenario.total_requests,
        "slo": dict(scenario.slo), "wall": clock.wall()})

    schedule = TrafficGenerator(scenario).schedule()
    sup = _build_serving(scenario, model, params, registry)
    if recorder is not None:
        recorder.attach(sup, registry)
    run = ScenarioRun(scenario=scenario, schedule=schedule, results={},
                      records=mem.records, counters={}, wall_s=0.0,
                      log_path=log_path)
    # the fleet-level fault schedule: draining restarts at fixed offsets
    drains = sorted(scenario.fleet.drain_restarts) \
        if scenario.fleet is not None else []
    d = 0
    # the continuous-deployment schedule: one rollout at a fixed offset
    # (artifact materialized up front — a poisoned checkpoint must be
    # committed and fsck-green BEFORE the first drain)
    deploy_fired = scenario.deploy is None
    deploy_kwargs: Optional[Dict[str, Any]] = None
    scratch = None
    if scenario.deploy is not None:
        scratch = tempfile.TemporaryDirectory(prefix="apex-deploy-")
        deploy_kwargs = _prepare_deploy(scenario, model, params,
                                        scratch.name)

    def _deploy_active() -> bool:
        dep = getattr(sup, "deployment", None)
        return dep is not None and not dep.done

    def _autoscale_settling() -> bool:
        # after traffic drains, keep polling until the autoscaler has
        # retired back to min_replicas — an idle fleet always meets the
        # scale-down bands, so this converges (max_wall_s still guards)
        scaler = getattr(sup, "autoscaler", None)
        return (scaler is not None
                and len(sup.replicas) > scaler.config.min_replicas)

    autoscaling = getattr(sup, "autoscaler", None) is not None
    t0 = clock.now()
    i = 0
    try:
        while (i < len(schedule) or sup.inflight_count or d < len(drains)
               or not deploy_fired or _deploy_active()
               or _autoscale_settling()):
            now = clock.now() - t0
            if now > scenario.max_wall_s:
                run.aborted = True
                _abort(sup, scenario, registry, now)
                break
            while d < len(drains) and drains[d][0] <= now:
                at_s, replica = drains[d]
                d += 1
                try:
                    sup.drain_restart(replica)
                except RuntimeError as exc:
                    # another drain still in progress (or replica not
                    # active): skip rather than stack — N-1 capacity is
                    # the invariant; the skip is stamped into the log
                    log_event(_LOG, "drain_restart_skipped",
                              replica_id=replica, at_s=at_s,
                              reason=str(exc))
                    registry.event("drain_restart_skipped",
                                   replica_id=replica, at_s=at_s,
                                   reason=str(exc))
            if not deploy_fired and scenario.deploy.at_s <= now:
                deploy_fired = True
                try:
                    sup.deploy(**deploy_kwargs)
                except Exception as exc:
                    # pre-flight rejection (fsck failure) or a topology
                    # race: the fleet already stamped deploy_rejected
                    # when it could; the skip itself is logged too
                    log_event(_LOG, "deploy_skipped",
                              at_s=scenario.deploy.at_s, reason=str(exc))
                    registry.event("deploy_skipped",
                                   at_s=scenario.deploy.at_s,
                                   reason=str(exc))
            while i < len(schedule) and schedule[i].at_s <= now:
                req = schedule[i].request
                # open-loop contract: the deadline clock starts at the
                # SCHEDULED arrival, not whenever the loop got to it
                req.arrival_ts = t0 + schedule[i].at_s
                i += 1
                run.submitted += 1
                try:
                    sup.submit(req)
                except (EngineUnavailableError, QueueFullError,
                        DeadlineExpiredError, UnknownAdapterError):
                    pass        # recorded terminally by the supervisor
            if sup.inflight_count or _deploy_active():
                sup.tick()
                run.ticks += 1
            elif i < len(schedule):
                gap = (t0 + schedule[i].at_s) - clock.now()
                if gap > 0:
                    clock.sleep(min(gap, _IDLE_SLEEP_S))
                if autoscaling:
                    # idle ticks keep the autoscaler's poll clock alive
                    # through traffic gaps (scale-down happens here)
                    sup.tick()
                    run.ticks += 1
            elif d < len(drains) or not deploy_fired \
                    or _autoscale_settling():
                # waiting on a scheduled drain/deploy, or for the
                # autoscaler to retire back to min_replicas
                clock.sleep(_IDLE_SLEEP_S)
                if autoscaling:
                    sup.tick()
                    run.ticks += 1
    finally:
        run.wall_s = clock.now() - t0
        if hasattr(sup, "replica_metrics"):
            # final autoscaler poll, stamped into the log before the
            # close-time snapshots so signals precede the counters they
            # must reconcile with
            run.signals = FleetMetrics(sup).signals()
            registry.emit_record({"kind": "signals", "wall": clock.wall(),
                                  "values": run.signals})
        sup.close()             # flushes the final counter snapshot
        if scratch is not None:
            scratch.cleanup()   # the deployed weights live in the fleet
    run.results = dict(sup.completed)
    run.counters = registry.counters()
    if recorder is not None:
        run.bundles = list(recorder.bundles)
        run.bundle_paths = list(recorder.bundle_paths)
    run.engine_restarts = sup.restarts
    # the engines' RetraceWatchdogs mirror every counted recompile into
    # the shared registry; surface the total and fail loudly — a storm
    # that the resilience layer papered over (restart + re-warm) must
    # not pass a load test silently
    run.retraces = int(run.counters.get("retraces", 0))
    if run.retraces:
        log_event(_LOG, "scenario_retraces", scenario=scenario.name,
                  retraces=run.retraces, level="error")
        registry.event("scenario_retraces", scenario=scenario.name,
                       retraces=run.retraces)
    if scenario.slo:
        run.slo = evaluate_slos(mem.records,
                                SLOSpec.from_dict(scenario.slo))
        run.metrics_by_name = dict(run.slo.metrics)
    else:
        run.metrics_by_name = measure_slo_metrics(mem.records)
    run.slo_by_adapter = measure_slo_metrics(mem.records,
                                             by_adapter=True)
    return run


def _abort(sup: EngineSupervisor, scenario: Scenario,
           registry: MetricsRegistry, now_s: float) -> None:
    """Wall-budget breach: cancel every non-terminal request (each still
    reaches exactly one terminal record — conservation holds even for an
    aborted run) and stamp the abort into the log."""
    log_event(_LOG, "loadtest_aborted", scenario=scenario.name,
              wall_s=now_s, budget_s=scenario.max_wall_s,
              inflight=sup.inflight_count)
    registry.event("loadtest_aborted", scenario=scenario.name,
                   wall_s=now_s, budget_s=scenario.max_wall_s,
                   inflight=sup.inflight_count)
    for rid in sup.inflight_ids:
        sup.cancel(rid)
    # in-flight cancellations retire at the start of the next tick
    guard = 0
    while sup.inflight_count and guard < scenario.engine.max_slots + 2:
        sup.tick()
        guard += 1
