"""Load-test harness + SLO regression gate tests.

Three contracts, layered:

- **Determinism**: same seed + same scenario => byte-identical arrival
  schedule and per-request sampling draws (what makes a committed SLO
  baseline meaningful at all).
- **Reconciliation**: the tier-1 smoke scenario drives the full
  generator -> supervisor -> JSONL -> SLO-verdict pipeline and its
  monitor SLO section must reconcile exactly with the registry counters
  and request records — every offered arrival reaches exactly one
  terminal record.
- **The gate fails red**: the regression gate is only worth committing
  if it FAILS on a violation — synthetic bad-latency logs and synthetic
  tightened baselines must exit nonzero (1 and 2 respectively), not
  just the green path exit 0.

The full overload and crash-recovery scenarios are slow-tier; a scaled-
down crash scenario keeps the finite-recovery-time acceptance in
tier-1.
"""

import glob
import json
import os
import subprocess
import sys

import jax
import pytest

from apex_tpu.loadtest import (
    FaultSchedule,
    Scenario,
    TrafficGenerator,
    compare_to_baseline,
    load_baseline,
    run_scenario,
    update_baseline,
)
from apex_tpu.loadtest.__main__ import (
    EXIT_NO_BASELINE,
    EXIT_OK,
    EXIT_REGRESSION,
    EXIT_SLO_VIOLATION,
    main as loadtest_main,
)
from apex_tpu.models import GPTModel, TransformerConfig
from apex_tpu.observability import build_report, render_report
from apex_tpu.observability.slo import (
    SLOSpec,
    evaluate_slos,
    measure_slo_metrics,
)
from apex_tpu.serving import FINISH_REASONS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCENARIO_DIR = os.path.join(REPO, "benchmarks", "scenarios")


@pytest.fixture(scope="module")
def small():
    """The tier-1 serving model — SAME dims as the committed scenarios'
    model spec, so tests can run them without a second model build."""
    model = GPTModel(TransformerConfig(
        num_layers=2, hidden_size=32, num_attention_heads=4, vocab_size=64,
        max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0))
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _scenario_dict(**over):
    base = {
        "name": "t", "seed": 3,
        "model": {"num_layers": 2, "hidden_size": 32,
                  "num_attention_heads": 4, "vocab_size": 64,
                  "max_position_embeddings": 64},
        "engine": {"max_slots": 4, "max_len": 32, "max_queue": 16},
        "phases": [{"name": "p", "n_requests": 8, "rate_rps": 200.0,
                    "prompt_lens": {"4": 2, "8": 1},
                    "max_new_tokens": {"3": 1, "5": 1}}],
    }
    base.update(over)
    return base


# ---------------------------------------------------------------------------
# scenario schema


class TestScenarioSchema:
    def test_committed_scenarios_load_and_round_trip(self):
        paths = sorted(glob.glob(os.path.join(SCENARIO_DIR, "*.json")))
        assert len(paths) >= 3, f"missing committed scenarios: {paths}"
        for path in paths:
            scn = Scenario.load(path)
            # to_dict -> from_dict is a fixed point (the schema is
            # self-describing, no silent field loss)
            again = Scenario.from_dict(scn.to_dict())
            assert again.to_dict() == scn.to_dict(), path
            assert scn.total_requests >= 1

    def test_unknown_scenario_key_rejected(self):
        with pytest.raises(ValueError, match="unknown scenario keys"):
            Scenario.from_dict(_scenario_dict(bogus=1))

    def test_unknown_phase_key_rejected(self):
        d = _scenario_dict()
        d["phases"][0]["surprise"] = True
        with pytest.raises(ValueError, match="unknown keys"):
            Scenario.from_dict(d)

    def test_unknown_supervisor_knob_rejected(self):
        with pytest.raises(ValueError, match="supervisor knobs"):
            Scenario.from_dict(_scenario_dict(supervisor={"not_a_knob": 1}))

    def test_unknown_slo_metric_rejected(self):
        with pytest.raises(ValueError, match="unknown SLO metric"):
            Scenario.from_dict(_scenario_dict(slo={"p99_vibes": 1.0}))

    def test_phase_budget_must_fit_engine(self):
        d = _scenario_dict()
        d["phases"][0]["max_new_tokens"] = {"40": 1}   # 8 + 40 > 32
        with pytest.raises(ValueError, match="exceeds engine max_len"):
            Scenario.from_dict(d)

    def test_bad_mix_weight_rejected(self):
        d = _scenario_dict()
        d["phases"][0]["prompt_lens"] = {"4": 0}
        with pytest.raises(ValueError, match="weight"):
            Scenario.from_dict(d)

    def test_page_knobs_round_trip(self):
        d = _scenario_dict(engine={
            "max_slots": 4, "max_len": 32, "max_queue": 16,
            "page_size": 8, "n_pages": 12})
        scn = Scenario.from_dict(d)
        assert scn.engine.page_size == 8
        assert scn.engine.n_pages == 12
        again = Scenario.from_dict(scn.to_dict())
        assert again.to_dict() == scn.to_dict()
        # n_pages=None stays absent
        plain = Scenario.from_dict(_scenario_dict(engine={
            "max_slots": 4, "max_len": 32}))
        assert "n_pages" not in plain.to_dict()["engine"]

    def test_bad_kv_layout_rejected(self):
        """The engine has one KV layout: a scenario file that still
        names the key is refused by name, as any unknown key is."""
        with pytest.raises(ValueError,
                           match=r"unknown engine keys \['kv_layout'\]"):
            Scenario.from_dict(_scenario_dict(engine={
                "max_slots": 4, "max_len": 32, "kv_layout": "paged"}))

    def test_kv_dtype_and_speculation_round_trip(self):
        scn = Scenario.from_dict(_scenario_dict(engine={
            "max_slots": 4, "max_len": 32, "max_queue": 16,
            "kv_dtype": "int8", "speculation": 3}))
        assert scn.engine.kv_dtype == "int8"
        assert scn.engine.speculation == 3
        again = Scenario.from_dict(scn.to_dict())
        assert again.to_dict() == scn.to_dict()
        # defaults stay absent: a pre-existing scenario file's dict form
        # is unchanged by the new knobs
        plain = Scenario.from_dict(_scenario_dict())
        assert "kv_dtype" not in plain.to_dict()["engine"]
        assert "speculation" not in plain.to_dict()["engine"]

    def test_bad_kv_dtype_and_speculation_rejected(self):
        with pytest.raises(ValueError, match="kv_dtype"):
            Scenario.from_dict(_scenario_dict(engine={
                "max_slots": 4, "max_len": 32, "kv_dtype": "fp4"}))
        with pytest.raises(ValueError, match="speculation"):
            Scenario.from_dict(_scenario_dict(engine={
                "max_slots": 4, "max_len": 32, "speculation": 1}))

    def test_prompt_period_round_trip_and_validation(self):
        d = _scenario_dict()
        d["phases"][0]["prompt_period"] = 4
        scn = Scenario.from_dict(d)
        assert scn.phases[0].prompt_period == 4
        assert Scenario.from_dict(scn.to_dict()).to_dict() == scn.to_dict()
        assert "prompt_period" not in \
            Scenario.from_dict(_scenario_dict()).to_dict()["phases"][0]
        d["phases"][0]["prompt_period"] = -1
        with pytest.raises(ValueError, match="prompt_period"):
            Scenario.from_dict(d)

    def test_prompt_period_tiles_prompts(self):
        d = _scenario_dict()
        d["phases"][0]["prompt_period"] = 2
        for s in TrafficGenerator(Scenario.from_dict(d)).schedule():
            p = s.request.prompt
            assert p == (p[:2] * len(p))[:len(p)]

    def test_lora_knobs_and_adapter_mix_round_trip(self):
        d = _scenario_dict(engine={
            "max_slots": 4, "max_len": 32, "max_queue": 16,
            "lora_rank": 4, "lora_adapters": 2})
        d["phases"][0]["adapter_mix"] = {"0": 3, "1": 1, "base": 2}
        scn = Scenario.from_dict(d)
        assert scn.engine.lora_rank == 4
        assert scn.engine.lora_adapters == 2
        assert scn.phases[0].adapter_mix == {"0": 3.0, "1": 1.0,
                                             "base": 2.0}
        again = Scenario.from_dict(scn.to_dict())
        assert again.to_dict() == scn.to_dict()
        # defaults stay absent: a pre-LoRA scenario's dict form is
        # unchanged by the new knobs
        plain = Scenario.from_dict(_scenario_dict())
        assert "lora_rank" not in plain.to_dict()["engine"]
        assert "lora_adapters" not in plain.to_dict()["engine"]
        assert "adapter_mix" not in plain.to_dict()["phases"][0]

    def test_bad_lora_knobs_and_adapter_mix_rejected(self):
        # rank and bank size come together or not at all
        with pytest.raises(ValueError, match="lora_rank"):
            Scenario.from_dict(_scenario_dict(engine={
                "max_slots": 4, "max_len": 32, "lora_rank": 4}))
        with pytest.raises(ValueError, match="lora_rank"):
            Scenario.from_dict(_scenario_dict(engine={
                "max_slots": 4, "max_len": 32, "lora_adapters": 2}))
        # an adapter_mix needs a store, and its ids must fit the bank
        d = _scenario_dict()
        d["phases"][0]["adapter_mix"] = {"0": 1}
        with pytest.raises(ValueError, match="lora_adapters"):
            Scenario.from_dict(d)
        d = _scenario_dict(engine={
            "max_slots": 4, "max_len": 32, "lora_rank": 4,
            "lora_adapters": 2})
        d["phases"][0]["adapter_mix"] = {"2": 1}
        with pytest.raises(ValueError, match="adapter_mix"):
            Scenario.from_dict(d)
        d["phases"][0]["adapter_mix"] = {"tenant-a": 1}
        with pytest.raises(ValueError, match="adapter_mix"):
            Scenario.from_dict(d)
        d["phases"][0]["adapter_mix"] = {"0": 0}
        with pytest.raises(ValueError, match="weight"):
            Scenario.from_dict(d)

    def test_fault_schedule_round_trip(self):
        fs = FaultSchedule.from_dict({
            "decode_raise_calls": [3], "decode_hang": {"5": 1.5},
            "poison_decode": {"7": [1, "nonfinite"]}})
        assert fs.poison_decode == {7: (1, "nonfinite")}
        assert FaultSchedule.from_dict(fs.to_dict()) == fs
        kw = fs.injector_kwargs()
        assert kw["decode_hang"] == {5: 1.5}


class TestAutoscaleDeploySchema:
    """The PR 16 scenario blocks: strict parse-time validation, so a
    typo'd autoscale/deploy scenario fails at load, not mid-run."""

    def test_autoscale_round_trip(self):
        d = _scenario_dict(
            fleet={"n_replicas": 2},
            autoscale={"min_replicas": 1, "max_replicas": 3,
                       "poll_interval_s": 0.1, "cooldown_s": 1.0,
                       "scale_up_queue_per_replica": 3.0})
        scn = Scenario.from_dict(d)
        assert scn.autoscale.max_replicas == 3
        assert Scenario.from_dict(scn.to_dict()).to_dict() == scn.to_dict()
        # the runner builds AutoscaleConfig from exactly these kwargs
        kw = scn.autoscale.config_kwargs()
        assert len(kw) == 11 and kw["scale_up_queue_per_replica"] == 3.0

    def test_autoscale_unknown_key_rejected(self):
        with pytest.raises(ValueError, match="unknown autoscale keys"):
            Scenario.from_dict(_scenario_dict(
                fleet={"n_replicas": 1},
                autoscale={"max_replicas": 2, "vibes": 1}))

    def test_autoscale_needs_fleet_block(self):
        with pytest.raises(ValueError, match="needs a 'fleet' block"):
            Scenario.from_dict(_scenario_dict(
                autoscale={"max_replicas": 2}))

    def test_autoscale_band_must_cover_n_replicas(self):
        with pytest.raises(ValueError, match="autoscale band"):
            Scenario.from_dict(_scenario_dict(
                fleet={"n_replicas": 4},
                autoscale={"min_replicas": 1, "max_replicas": 2}))

    def test_autoscale_bad_band_rejected_at_parse(self):
        with pytest.raises(ValueError, match="max_replicas"):
            Scenario.from_dict(_scenario_dict(
                fleet={"n_replicas": 2},
                autoscale={"min_replicas": 3, "max_replicas": 2}))

    def test_deploy_round_trip(self):
        d = _scenario_dict(
            fleet={"n_replicas": 2},
            deploy={"at_s": 2.0, "kind": "checkpoint", "poison": True,
                    "canary": {"window_s": 0.5, "min_requests": 3}})
        scn = Scenario.from_dict(d)
        assert scn.deploy.poison is True
        assert Scenario.from_dict(scn.to_dict()).to_dict() == scn.to_dict()

    def test_deploy_unknown_keys_rejected(self):
        with pytest.raises(ValueError, match="unknown deploy keys"):
            Scenario.from_dict(_scenario_dict(
                fleet={"n_replicas": 2}, deploy={"at_s": 1.0, "when": 2}))
        with pytest.raises(ValueError,
                           match="unknown deploy canary keys"):
            Scenario.from_dict(_scenario_dict(
                fleet={"n_replicas": 2},
                deploy={"at_s": 1.0, "canary": {"vibe_check": 1}}))

    def test_deploy_needs_fleet_block(self):
        with pytest.raises(ValueError, match="needs a 'fleet' block"):
            Scenario.from_dict(_scenario_dict(deploy={"at_s": 1.0}))

    def test_deploy_bad_kind_rejected(self):
        with pytest.raises(ValueError, match="deploy kind"):
            Scenario.from_dict(_scenario_dict(
                fleet={"n_replicas": 2},
                deploy={"at_s": 1.0, "kind": "yolo"}))

    def test_adapter_deploy_needs_lora_and_fresh_id(self):
        with pytest.raises(ValueError, match="adapter store"):
            Scenario.from_dict(_scenario_dict(
                fleet={"n_replicas": 2},
                deploy={"at_s": 1.0, "kind": "adapter"}))
        # digit ids below lora_adapters are the runner's preloaded
        # tenants — the canary must be a NEW tenant
        d = _scenario_dict(fleet={"n_replicas": 2},
                           deploy={"at_s": 1.0, "kind": "adapter",
                                   "adapter_id": "0"})
        d["engine"].update({"lora_adapters": 2, "lora_rank": 2})
        with pytest.raises(ValueError, match="collides"):
            Scenario.from_dict(d)
        d["deploy"]["adapter_id"] = "canary"
        scn = Scenario.from_dict(d)
        assert scn.deploy.adapter_id == "canary"


class TestSentinelRecorderSchema:
    """The PR 18 scenario blocks: the drift sentinel and the flight
    recorder, validated at parse time like every other block."""

    def test_sentinel_round_trip(self):
        d = _scenario_dict(
            fleet={"n_replicas": 2},
            sentinel={"poll_interval_s": 0.1, "warmup_polls": 4,
                      "z_threshold": 5.0, "min_abs_dev": 2.0,
                      "signals": ["queue_depth", "ttft_p99_s"]})
        scn = Scenario.from_dict(d)
        assert scn.sentinel.z_threshold == 5.0
        assert scn.sentinel.signals == ("queue_depth", "ttft_p99_s")
        assert Scenario.from_dict(scn.to_dict()).to_dict() == scn.to_dict()
        # the runner builds SentinelConfig from exactly these kwargs
        from apex_tpu.observability.sentinel import SentinelConfig
        cfg = SentinelConfig(**scn.sentinel.config_kwargs())
        assert cfg.min_abs_dev == 2.0

    def test_recorder_round_trip(self):
        d = _scenario_dict(recorder={"events_capacity": 32,
                                     "max_bundles": 2})
        scn = Scenario.from_dict(d)
        assert scn.recorder.max_bundles == 2
        assert Scenario.from_dict(scn.to_dict()).to_dict() == scn.to_dict()
        from apex_tpu.observability import FlightRecorder
        rec = FlightRecorder(**scn.recorder.recorder_kwargs())
        assert rec.events.maxlen == 32 and rec.max_bundles == 2

    def test_sentinel_unknown_key_rejected(self):
        with pytest.raises(ValueError, match="unknown sentinel keys"):
            Scenario.from_dict(_scenario_dict(
                fleet={"n_replicas": 1}, sentinel={"vibes": 1}))

    def test_recorder_unknown_key_rejected(self):
        with pytest.raises(ValueError, match="unknown recorder keys"):
            Scenario.from_dict(_scenario_dict(recorder={"vibes": 1}))

    def test_sentinel_needs_fleet_block(self):
        with pytest.raises(ValueError, match="needs a 'fleet' block"):
            Scenario.from_dict(_scenario_dict(
                sentinel={"z_threshold": 4.0}))

    def test_sentinel_validation_mirrors_runtime_config(self):
        with pytest.raises(ValueError, match="ewma_alpha"):
            Scenario.from_dict(_scenario_dict(
                fleet={"n_replicas": 1}, sentinel={"ewma_alpha": 0.0}))
        with pytest.raises(ValueError, match="signals"):
            Scenario.from_dict(_scenario_dict(
                fleet={"n_replicas": 1}, sentinel={"signals": []}))
        with pytest.raises(ValueError, match="events_capacity"):
            Scenario.from_dict(_scenario_dict(
                recorder={"events_capacity": 0}))


# ---------------------------------------------------------------------------
# generator determinism (satellite: asserted across two runs)


class TestGeneratorDeterminism:
    def test_same_seed_same_schedule(self):
        d = _scenario_dict(phases=[
            {"name": "a", "n_requests": 10, "rate_rps": 100.0,
             "prompt_lens": {"4": 1, "8": 1}, "max_new_tokens": {"3": 1},
             "deadline_fraction": 0.5, "deadline_min_s": 1.0,
             "deadline_max_s": 2.0, "greedy_fraction": 0.4,
             "temperatures": [0.7, 1.1], "top_ks": [0, 8]},
            {"name": "b", "n_requests": 6, "rate_rps": 500.0,
             "prompt_lens": {"6": 1}, "max_new_tokens": {"2": 1, "4": 3}}])
        s1 = TrafficGenerator(Scenario.from_dict(d)).schedule()
        s2 = TrafficGenerator(Scenario.from_dict(d)).schedule()
        sig1 = [s.signature() for s in s1]
        sig2 = [s.signature() for s in s2]
        # identical arrival times AND per-request sampling draws —
        # prompts, budgets, deadlines, temperature/top-k/seed
        assert sig1 == sig2
        # ... while request_ids are fresh (process-global by design)
        assert [a.request.request_id for a in s1] != \
            [a.request.request_id for a in s2]

    def test_different_seed_differs(self):
        s1 = TrafficGenerator(
            Scenario.from_dict(_scenario_dict(seed=1))).schedule()
        s2 = TrafficGenerator(
            Scenario.from_dict(_scenario_dict(seed=2))).schedule()
        assert [a.signature() for a in s1] != [a.signature() for a in s2]

    def test_schedule_is_time_ordered_and_phased(self):
        d = _scenario_dict(phases=[
            {"name": "a", "n_requests": 5, "rate_rps": 100.0,
             "prompt_lens": {"4": 1}, "max_new_tokens": {"3": 1}},
            {"name": "b", "n_requests": 7, "rate_rps": 100.0,
             "prompt_lens": {"8": 1}, "max_new_tokens": {"2": 1}}])
        sched = TrafficGenerator(Scenario.from_dict(d)).schedule()
        assert len(sched) == 12
        times = [s.at_s for s in sched]
        assert times == sorted(times)
        assert all(t > 0 for t in times)
        assert [s.phase for s in sched] == ["a"] * 5 + ["b"] * 7
        assert all(len(s.request.prompt) == 4 for s in sched[:5])
        assert all(len(s.request.prompt) == 8 for s in sched[5:])

    def test_adapter_mix_deterministic_and_isolated(self):
        """The adapter draw rides LAST in the per-request draw chain:
        same seed -> same adapter assignment (part of signature()), and
        an empty mix leaves the pre-LoRA schedule byte-identical."""
        lora_engine = {"max_slots": 4, "max_len": 32, "max_queue": 16,
                       "lora_rank": 4, "lora_adapters": 2}
        with_mix = _scenario_dict(engine=dict(lora_engine))
        with_mix["phases"][0]["n_requests"] = 30
        with_mix["phases"][0]["adapter_mix"] = {"0": 2, "1": 1, "base": 1}
        s1 = TrafficGenerator(Scenario.from_dict(with_mix)).schedule()
        s2 = TrafficGenerator(Scenario.from_dict(with_mix)).schedule()
        assert [s.signature() for s in s1] == [s.signature() for s in s2]
        aids = {s.request.sampling.adapter_id for s in s1}
        assert aids == {"0", "1", None}   # 30 draws at 2/1/1 hit all
        # enabling the store WITHOUT a mix changes nothing: the adapter
        # draw only exists when the phase declares one
        plain = TrafficGenerator(
            Scenario.from_dict(_scenario_dict())).schedule()
        stored = TrafficGenerator(Scenario.from_dict(
            _scenario_dict(engine=dict(lora_engine)))).schedule()
        assert [s.signature() for s in plain] == \
            [s.signature() for s in stored]
        assert all(s.request.sampling.adapter_id is None for s in stored)

    def test_mixes_are_honored(self):
        d = _scenario_dict(phases=[
            {"name": "p", "n_requests": 40, "rate_rps": 100.0,
             "prompt_lens": {"4": 1, "8": 1},
             "max_new_tokens": {"3": 1, "5": 1},
             "deadline_fraction": 1.0, "deadline_min_s": 2.0,
             "deadline_max_s": 3.0, "greedy_fraction": 0.0,
             "temperatures": [0.9], "top_ks": [8]}])
        reqs = TrafficGenerator(Scenario.from_dict(d)).requests()
        assert {len(r.prompt) for r in reqs} == {4, 8}
        assert {r.max_new_tokens for r in reqs} == {3, 5}
        assert all(2.0 <= r.deadline_s <= 3.0 for r in reqs)
        assert all(r.sampling.temperature == 0.9 for r in reqs)
        assert all(r.sampling.top_k == 8 for r in reqs)
        assert all(0 <= t < 64 for r in reqs for t in r.prompt)


# ---------------------------------------------------------------------------
# SLO scoring (synthetic records — no engine, no jit)


def _req(reason="length", ttft=None, tpot=None, total=None, wall=0.0):
    r = {"kind": "request", "request_id": 0, "finish_reason": reason,
         "prompt_len": 4, "new_tokens": 3, "wall": wall}
    if ttft is not None:
        r["ttft_s"] = ttft
    if tpot is not None:
        r["tpot_s"] = tpot
    if total is not None:
        r["total_s"] = total
    return r


class TestSLOScoring:
    def test_hand_computed_metrics(self):
        records = [
            _req(ttft=0.1, tpot=0.01, total=0.5),
            _req(ttft=0.2, tpot=0.02, total=1.0),
            _req(ttft=0.4, tpot=0.04, total=2.0),
            _req(reason="error"),
            _req(reason="rejected"),
        ]
        m = measure_slo_metrics(records)
        assert m["ttft_p50_s"] == 0.2         # nearest-rank over 3 values
        assert m["ttft_p99_s"] == 0.4
        assert m["latency_p99_s"] == 2.0
        assert m["goodput"] == pytest.approx(3 / 5)
        assert m["error_budget"] == pytest.approx(1 / 5)
        assert m["recovery_s"] is None        # no disruption events

    def test_recovery_finite_then_infinite(self):
        ev = {"kind": "event", "event": "engine_restart", "wall": 10.0}
        done = _req(total=0.5, wall=12.5)
        m = measure_slo_metrics([ev, done])
        assert m["recovery_s"] == pytest.approx(2.5)
        # breaker_open counts as a disruption too
        m = measure_slo_metrics([
            {"kind": "event", "event": "breaker_open", "wall": 11.0}, done])
        assert m["recovery_s"] == pytest.approx(1.5)
        # no completion after the disruption: never recovered
        m = measure_slo_metrics([ev, _req(total=0.5, wall=9.0)])
        assert m["recovery_s"] == float("inf")

    def test_directions_and_verdict(self):
        records = [_req(ttft=0.2, total=1.0), _req(reason="error")]
        rep = evaluate_slos(records, SLOSpec.from_dict(
            {"ttft_p99_s": 0.5, "goodput": 0.9, "error_budget": 0.0}))
        by = {o.name: o for o in rep.objectives}
        assert by["ttft_p99_s"].ok            # 0.2 <= 0.5
        assert not by["goodput"].ok           # 0.5 < 0.9
        assert not by["error_budget"].ok      # 0.5 > 0.0
        assert not rep.ok and len(rep.failures) == 2

    def test_declared_objective_without_data_fails(self):
        # a pre-TTFT log cannot pass a TTFT objective — no data is a
        # failure, not a silent green
        rep = evaluate_slos([_req()], SLOSpec.from_dict(
            {"ttft_p99_s": 1.0}))
        assert not rep.ok
        assert rep.objectives[0].measured is None

    def test_unknown_metric_rejected(self):
        with pytest.raises(ValueError, match="unknown SLO metric"):
            SLOSpec.from_dict({"vibes": 1.0})


# ---------------------------------------------------------------------------
# the regression gate


class TestGate:
    def test_direction_aware_comparison(self):
        baseline = {"ttft_p99_s": 1.0, "goodput": 0.9}
        # within tolerance both ways
        assert not compare_to_baseline(
            {"ttft_p99_s": 1.2, "goodput": 0.8}, baseline, tolerance=0.25)
        # latency regression: grew past 1.25x
        regs = compare_to_baseline(
            {"ttft_p99_s": 1.3, "goodput": 0.9}, baseline, tolerance=0.25)
        assert [r.metric for r in regs] == ["ttft_p99_s"]
        # goodput regression: shrank past 0.75x
        regs = compare_to_baseline(
            {"ttft_p99_s": 1.0, "goodput": 0.6}, baseline, tolerance=0.25)
        assert [r.metric for r in regs] == ["goodput"]
        # improvements never fail
        assert not compare_to_baseline(
            {"ttft_p99_s": 0.1, "goodput": 1.0}, baseline, tolerance=0.25)

    def test_unmeasurable_baselined_metric_is_regression(self):
        regs = compare_to_baseline({"recovery_s": None},
                                   {"recovery_s": 2.0}, tolerance=0.5)
        assert regs and regs[0].measured is None
        assert "measured nothing" in regs[0].describe()

    def test_update_baseline_drops_unmeasured(self, tmp_path):
        path = str(tmp_path / "base.json")
        entry = update_baseline(path, "s", {
            "ttft_p99_s": 1.0, "recovery_s": None,
            "latency_p99_s": float("inf")})
        assert entry == {"ttft_p99_s": 1.0}
        assert load_baseline(path) == {"s": {"ttft_p99_s": 1.0}}
        # merge keeps other scenarios
        update_baseline(path, "t", {"goodput": 1.0})
        assert set(load_baseline(path)) == {"s", "t"}

    def test_malformed_baseline_rejected(self, tmp_path):
        path = tmp_path / "bad.json"
        path.write_text('{"s": 3}')
        with pytest.raises(ValueError, match="metric dicts"):
            load_baseline(str(path))


# ---------------------------------------------------------------------------
# gate CLI on synthetic fixtures (red paths FIRST-CLASS: the gate must
# fail on a violation, not only pass on the green path)


def _write_gate_fixture(tmp_path, *, ttft=0.01, slo_ttft=1.0):
    """A scenario file + a synthetic run log measuring ttft_p99_s=ttft."""
    scn = tmp_path / "scn.json"
    scn.write_text(json.dumps(_scenario_dict(
        name="gatecase", slo={"ttft_p99_s": slo_ttft, "goodput": 0.9},
        tolerance=0.25)))
    log = tmp_path / "run.jsonl"
    rows = [{"kind": "scenario", "name": "gatecase", "seed": 3,
             "slo": {"ttft_p99_s": slo_ttft, "goodput": 0.9},
             "wall": 1.0}]
    rows += [_req(ttft=ttft, tpot=0.001, total=ttft + 0.05,
                  wall=2.0 + i) for i in range(4)]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    return str(scn), str(log)


class TestGateCLI:
    def test_green_path_exit_zero(self, tmp_path):
        scn, log = _write_gate_fixture(tmp_path)
        base = str(tmp_path / "base.json")
        assert loadtest_main([scn, "--from-log", log, "--baseline", base,
                              "--update-baseline"]) == EXIT_OK
        assert loadtest_main([scn, "--from-log", log, "--check",
                              "--baseline", base]) == EXIT_OK

    def test_gate_fails_on_slo_violation(self, tmp_path):
        # measured ttft 5.0 >> objective 1.0 -> exit 1
        scn, log = _write_gate_fixture(tmp_path, ttft=5.0, slo_ttft=1.0)
        rc = loadtest_main([scn, "--from-log", log, "--check",
                            "--baseline", str(tmp_path / "none.json")])
        assert rc == EXIT_SLO_VIOLATION

    def test_gate_fails_on_synthetic_regression(self, tmp_path):
        # SLOs pass (0.5 <= 1.0) but the committed baseline says 0.01:
        # a 50x latency growth must trip the tolerance gate -> exit 2
        scn, log = _write_gate_fixture(tmp_path, ttft=0.5, slo_ttft=1.0)
        base = tmp_path / "base.json"
        base.write_text(json.dumps(
            {"gatecase": {"ttft_p99_s": 0.01, "goodput": 1.0}}))
        rc = loadtest_main([scn, "--from-log", log, "--check",
                            "--baseline", str(base)])
        assert rc == EXIT_REGRESSION

    def test_missing_baseline_entry_exit_three(self, tmp_path):
        scn, log = _write_gate_fixture(tmp_path)
        base = tmp_path / "base.json"
        base.write_text(json.dumps({"other_scenario": {"goodput": 1.0}}))
        rc = loadtest_main([scn, "--from-log", log, "--check",
                            "--baseline", str(base)])
        assert rc == EXIT_NO_BASELINE

    @pytest.mark.slow
    def test_real_cli_red_path(self, tmp_path):
        """The actual ``python -m apex_tpu.loadtest --check`` process
        exits nonzero on the synthetic regression fixture (subprocess —
        slow tier; the in-process tests above cover the same exit codes
        through the same main())."""
        scn, log = _write_gate_fixture(tmp_path, ttft=0.5)
        base = tmp_path / "base.json"
        base.write_text(json.dumps({"gatecase": {"ttft_p99_s": 0.01}}))
        proc = subprocess.run(
            [sys.executable, "-m", "apex_tpu.loadtest", scn,
             "--from-log", log, "--check", "--baseline", str(base)],
            capture_output=True, text=True, timeout=180,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == EXIT_REGRESSION, proc.stderr
        assert "regressions" in proc.stdout


# ---------------------------------------------------------------------------
# the tier-1 smoke scenario: full pipeline + exact reconciliation


def _assert_reconciles(report):
    """Counter/record conservation: one offered arrival == one counted
    submit == one terminal kind="request" record, split by reason."""
    counters = report["counters"]
    req = report["requests"]
    by_reason = req["by_finish_reason"]
    assert set(by_reason) <= set(FINISH_REASONS)
    assert req["count"] == sum(by_reason.values())
    assert counters["requests_submitted"] == req["count"]
    for reason in FINISH_REASONS:
        assert counters[f"requests_{reason}"] == \
            by_reason.get(reason, 0), reason


class TestSmokeScenario:
    def test_smoke_pipeline_reconciles_and_scores(self, small, tmp_path,
                                                  capsys):
        """Acceptance: the committed smoke scenario runs the generator ->
        supervisor -> JSONL -> SLO-verdict pipeline in tier-1; the
        monitor's SLO section (human and --json) reconciles exactly with
        the registry counters and request records."""
        model, params = small
        scn = Scenario.load(os.path.join(SCENARIO_DIR, "smoke.json"))
        log = str(tmp_path / "smoke.jsonl")
        run = run_scenario(scn, model=model, params=params, log_path=log)
        assert not run.aborted
        assert run.submitted == scn.total_requests
        assert run.slo is not None and run.ok, run.slo.as_dict()
        assert run.metrics_by_name["ttft_p99_s"] is not None
        assert run.metrics_by_name["tpot_p99_s"] is not None

        report = build_report(log)
        _assert_reconciles(report)
        # every terminal result the runner returned is one log record
        assert report["requests"]["count"] == len(run.results)
        # the embedded scenario record scored the log by itself
        assert report["scenario"]["name"] == "smoke"
        assert report["slo"] is not None and report["slo"]["ok"]
        slo_names = [o["name"] for o in report["slo"]["objectives"]]
        assert slo_names == list(scn.slo)
        text = render_report(report)
        assert "slo verdict: PASS" in text
        assert "ttft" in text and "tpot" in text

        # the monitor CLI agrees byte-for-byte on the verdict (in-process
        # main() — the ``python -m apex_tpu.monitor`` subprocess shim is
        # covered by the serving/observability tier-1 tests)
        from apex_tpu.observability.report import main as monitor_main

        assert monitor_main([log, "--json"]) == 0
        cli = json.loads(capsys.readouterr().out)
        assert cli["slo"] == json.loads(json.dumps(report["slo"]))
        assert cli["counters"] == report["counters"]

        # and the loadtest gate goes green against a just-written
        # baseline (CLI plumbing on a real run log)
        scn_path = os.path.join(SCENARIO_DIR, "smoke.json")
        base = str(tmp_path / "base.json")
        assert loadtest_main([scn_path, "--from-log", log,
                              "--baseline", base,
                              "--update-baseline"]) == EXIT_OK
        assert loadtest_main([scn_path, "--from-log", log, "--check",
                              "--baseline", base]) == EXIT_OK

    @pytest.mark.slow  # full scenario rerun: slow tier (ROADMAP)

    def test_crash_recovery_reports_finite_recovery(self, small, tmp_path):
        """Acceptance: a ServingFaultInjector-scheduled engine crash
        yields a finite measured recovery-time SLO (scaled-down tier-1
        variant of the slow-tier crash_recovery scenario)."""
        model, params = small
        scn = Scenario.from_dict(_scenario_dict(
            name="mini-crash", seed=5,
            supervisor={"max_restarts_per_request": 4},
            # one prompt bucket: each engine incarnation compiles a
            # single prefill shape — keeps the restart cheap in tier-1
            phases=[{"name": "steady", "n_requests": 8,
                     "rate_rps": 100.0, "prompt_lens": {"4": 1},
                     "max_new_tokens": {"5": 1}}],
            faults={"decode_raise_calls": [5]},
            slo={"goodput": 0.99, "error_budget": 0.0,
                 "recovery_s": 120.0}))
        log = str(tmp_path / "crash.jsonl")
        run = run_scenario(scn, model=model, params=params, log_path=log)
        assert run.engine_restarts >= 1
        assert run.counters["requests_recovered"] >= 1
        recovery = run.metrics_by_name["recovery_s"]
        assert recovery is not None and 0 < recovery < float("inf")
        assert run.ok, run.slo.as_dict()
        report = build_report(log)
        _assert_reconciles(report)
        assert report["slo"]["ok"]
        by = {o["name"]: o for o in report["slo"]["objectives"]}
        assert by["recovery_s"]["measured"] == pytest.approx(recovery)


class TestRecorderInRunner:
    def test_clean_run_arms_recorder_dumps_nothing(self, small,
                                                   tmp_path):
        """A recorder-armed clean run ends with ZERO bundles and the
        bundles counter declared at zero — arming the recorder is free
        on a healthy run (ring boundedness itself is asserted in
        test_observability's TestFlightRecorder)."""
        model, params = small
        scn = Scenario.from_dict(_scenario_dict(
            name="mini-clean", recorder={"events_capacity": 8,
                                         "records_capacity": 8,
                                         "gauges_capacity": 4}))
        log = str(tmp_path / "clean.jsonl")
        run = run_scenario(scn, model=model, params=params,
                           log_path=log)
        assert not run.aborted
        assert run.bundles == [] and run.bundle_paths == []
        assert run.counters["bundles_dumped"] == 0
        # no bundle file appeared next to the log
        import glob as _glob
        assert _glob.glob(str(tmp_path / "*-bundle-*.json")) == []
        # the report's bundle section says armed-but-quiet
        report = build_report(log)
        assert report["bundles"] is not None
        assert report["bundles"]["count"] == 0
        assert "nothing fired" in render_report(report)


# ---------------------------------------------------------------------------
# full scenarios: slow tier


@pytest.mark.slow
class TestFullScenarios:
    def test_overload_sheds_and_holds_goodput(self, small, tmp_path):
        model, params = small
        scn = Scenario.load(os.path.join(SCENARIO_DIR, "overload.json"))
        log = str(tmp_path / "overload.jsonl")
        run = run_scenario(scn, model=model, params=params, log_path=log)
        assert not run.aborted
        report = build_report(log)
        _assert_reconciles(report)
        counters = run.counters
        # the burst actually overloaded: rejected work exists, errors do
        # not — overload becomes fast rejections, not failures
        assert counters["requests_rejected"] > 0
        assert counters["requests_error"] == 0
        assert run.metrics_by_name["goodput"] < 1.0
        assert run.ok, run.slo.as_dict()

    def test_crash_recovery_scenario(self, small, tmp_path):
        model, params = small
        scn = Scenario.load(
            os.path.join(SCENARIO_DIR, "crash_recovery.json"))
        log = str(tmp_path / "crash.jsonl")
        run = run_scenario(scn, model=model, params=params, log_path=log)
        assert not run.aborted
        # decode crash + hung tick: two disruptions, both recovered
        assert run.engine_restarts >= 2
        recovery = run.metrics_by_name["recovery_s"]
        assert recovery is not None and recovery < float("inf")
        assert run.ok, run.slo.as_dict()
        report = build_report(log)
        _assert_reconciles(report)
        inc = report["serving_incidents"]
        assert inc["counts"]["engine_restart"] == \
            report["counters"]["engine_restarts"]

    def test_bimodal_burst_scenario(self, small, tmp_path):
        model, params = small
        scn = Scenario.load(
            os.path.join(SCENARIO_DIR, "bimodal_burst.json"))
        log = str(tmp_path / "bimodal.jsonl")
        run = run_scenario(scn, model=model, params=params, log_path=log)
        assert not run.aborted
        assert run.counters["requests_error"] == 0
        assert run.ok, run.slo.as_dict()
        # the burst's long prompts actually chunked (48 and 56 tokens at
        # budget 16 => 3-4 page-aligned chunks), short traffic did not
        done = list(run.results.values())
        chunks = [r.prefill_chunks or 1 for r in done
                  if r.finish_reason in ("eos", "length")]
        assert max(chunks) >= 3
        assert min(chunks) == 1
        # chunk audit reconciles: the counter equals the per-request sum
        report = build_report(log)
        _assert_reconciles(report)
        assert report["counters"]["prefill_chunks"] == \
            sum(r.prefill_chunks or 0 for r in done)

    def test_latency_drift_fires_sentinel_and_dumps_one_bundle(
            self, small, tmp_path, capsys):
        """Acceptance (PR 18): the committed latency_drift scenario —
        decode hangs degrade the fleet mid-surge with no hard failure —
        makes the sentinel fire ``kind="anomaly"`` with counters
        reconciling key-for-key, dumps EXACTLY ONE bundle next to the
        run log, and ``monitor bundle`` renders it (human and --json)
        with the trigger inside the frozen ring window."""
        model, params = small
        scn = Scenario.load(
            os.path.join(SCENARIO_DIR, "latency_drift.json"))
        log = str(tmp_path / "drift.jsonl")
        run = run_scenario(scn, model=model, params=params,
                           log_path=log)
        assert not run.aborted
        # the drift never hard-failed anything...
        assert run.engine_restarts == 0
        assert run.counters["requests_error"] == 0
        assert run.ok, run.slo.as_dict()
        # ...yet the sentinel caught it, reconciling key-for-key
        counters = run.counters
        assert counters["anomalies_total"] >= 1
        assert counters["anomalies_queue_depth"] == \
            counters["anomalies_total"]
        report = build_report(log)
        _assert_reconciles(report)
        anomalies = report["anomalies"]
        assert anomalies is not None
        assert anomalies["count"] == counters["anomalies_total"]
        assert anomalies["counters"]["anomalies_total"] == \
            counters["anomalies_total"]
        assert anomalies["by_signal"] == {
            "queue_depth": counters["anomalies_total"]}
        # exactly one bundle, dumped next to the run log
        assert counters["bundles_dumped"] == 1
        assert len(run.bundles) == 1
        expected = str(tmp_path / "drift-bundle-1.json")
        assert run.bundle_paths == [expected]
        assert report["bundles"]["count"] == 1
        assert report["bundles"]["dumps"][0]["trigger"] == "anomaly"
        text = render_report(report)
        assert "drift anomalies" in text
        assert "postmortem bundles (1 dumped" in text
        # the gauge trajectory fed the report
        assert len(report["gauge_trajectory"]) >= 3
        assert "signal trajectory" in text

        # the bundle is self-contained and renders in both modes with
        # the trigger inside the ring window it froze
        bundle = json.loads(open(expected).read())
        assert bundle["trigger"]["event"] == "anomaly"
        assert bundle["trigger"]["signal"] == "queue_depth"
        assert any(e.get("event") == "anomaly"
                   for e in bundle["events"])
        assert len(bundle["replicas"]) == 2
        from apex_tpu.observability.report import main as monitor_main

        assert monitor_main(["bundle", expected]) == 0
        human = capsys.readouterr().out
        assert "trigger: anomaly" in human and ">>" in human
        assert monitor_main(["bundle", expected, "--json"]) == 0
        assert json.loads(capsys.readouterr().out)["kind"] == \
            "flight_bundle"
