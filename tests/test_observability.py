"""Observability-subsystem suite (ISSUE 3): registry semantics and
thread-safety, bounded histograms, sink round-trips, MFU math, span
tracing, profiler-capture scheduling, retrace-watchdog metric emission —
and the acceptance path: a fault-injected CPU ``run_training`` with a
JSONL sink whose ``python -m apex_tpu.monitor`` report reconciles
exactly with ``TrainingResult.telemetry``.
"""

import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.amp.scaler import LossScaler
from apex_tpu.analysis.retrace import RetraceWatchdog
from apex_tpu.observability import (
    TRIGGER_EVENTS,
    DriftSentinel,
    FlightRecorder,
    InMemorySink,
    JsonlSink,
    MetricsRegistry,
    PrometheusTextfileSink,
    ProfilerCapture,
    SentinelConfig,
    StepMetrics,
    StepTimer,
    build_report,
    percentile,
    render_report,
    span,
)
from apex_tpu.optimizers import FusedSGD
from apex_tpu.resilience import (
    ResilienceConfig,
    make_resilient_train_step,
    make_train_state,
    run_training,
)
from apex_tpu.testing_faults import FaultInjector
from apex_tpu.utils.flops import (
    peak_flops_per_chip,
    resnet50_train_flops,
    transformer_train_flops,
)


class TestRegistry:
    def test_counters_gauges_histograms(self):
        reg = MetricsRegistry()
        assert reg.inc("steps") == 1
        assert reg.inc("steps", 4) == 5
        reg.declare_counters("skips", "steps")
        assert reg.counters() == {"steps": 5, "skips": 0}
        reg.set_gauge("loss", 0.25)
        assert reg.gauges()["loss"] == 0.25
        for v in (1.0, 2.0, 3.0, 4.0):
            reg.observe("h", v)
        snap = reg.histogram("h")
        assert snap.count == 4 and snap.sum == 10.0
        assert snap.min == 1.0 and snap.max == 4.0 and snap.mean == 2.5
        assert reg.histogram("missing") is None

    def test_percentile_nearest_rank(self):
        values = list(range(1, 101))  # 1..100
        assert percentile(values, 50) == 50
        assert percentile(values, 95) == 95
        assert percentile(values, 0) == 1
        assert percentile(values, 100) == 100
        assert percentile([7.0], 50) == 7.0
        with pytest.raises(ValueError):
            percentile([], 50)

    def test_events_are_seq_ordered_and_stamped(self):
        mem = InMemorySink()
        reg = MetricsRegistry([mem])
        reg.event("skip", step=3)
        reg.event("rollback", to_step=1)
        events = mem.of_kind("event")
        assert [e["event"] for e in events] == ["skip", "rollback"]
        assert events[0]["seq"] < events[1]["seq"]
        assert events[0]["ts"] <= events[1]["ts"]
        assert all("wall" in e for e in events)
        assert events[0]["step"] == 3

    def test_thread_safety_under_concurrent_emitters(self):
        # the real topology: watchdog thread + step loop both emit
        mem = InMemorySink()
        reg = MetricsRegistry([mem])
        workers, per = 8, 500

        def emit(worker):
            for i in range(per):
                reg.inc("c")
                reg.observe("h", float(i))
                reg.event("tick", worker=worker)

        threads = [threading.Thread(target=emit, args=(w,))
                   for w in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert reg.counters()["c"] == workers * per
        assert reg.histogram("h").count == workers * per
        events = mem.of_kind("event")
        assert len(events) == workers * per
        # seq never duplicated or skipped despite contention
        seqs = sorted(e["seq"] for e in events)
        assert seqs == list(range(1, workers * per + 1))

    def test_histogram_memory_bounded_over_1000_steps(self):
        # acceptance: ring memory does not grow with step count
        reg = MetricsRegistry(histogram_bound=64)
        for i in range(1200):
            reg.observe("step_time_s", float(i))
        snap = reg.histogram("step_time_s")
        assert snap.count == 1200          # exact aggregates kept
        assert snap.max == 1199.0 and snap.min == 0.0
        assert len(snap._recent) == 64     # percentile window stays bounded
        # percentiles reflect the recent window (values 1136..1199)
        assert snap.percentile(50) >= 1136


class TestSinks:
    def test_jsonl_round_trip(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        sink = JsonlSink(path)
        reg = MetricsRegistry([sink])
        reg.inc("steps", 3)
        reg.event("skip", step=1)
        reg.emit_step({"kind": "step", "step": 1, "step_time_s": 0.5})
        reg.flush()
        sink.close()
        kinds = [json.loads(line)["kind"]
                 for line in open(path, encoding="utf-8")]
        assert kinds == ["event", "step", "counters", "gauges",
                         "histograms"]
        counters = [json.loads(line) for line in open(path, encoding="utf-8")
                    if json.loads(line)["kind"] == "counters"]
        assert counters[-1]["values"] == {"steps": 3}

    def test_jsonl_degrades_unserializable_fields(self, tmp_path):
        path = str(tmp_path / "run.jsonl")
        sink = JsonlSink(path)
        sink.write({"kind": "event", "event": "odd", "obj": object()})
        sink.close()
        rec = json.loads(open(path, encoding="utf-8").read())
        assert rec["event"] == "odd" and "object" in rec["obj"]

    def test_prometheus_textfile_round_trip(self, tmp_path):
        path = str(tmp_path / "metrics.prom")
        reg = MetricsRegistry([PrometheusTextfileSink(path)])
        reg.inc("steps", 7)
        reg.set_gauge("mfu", 0.4)
        for v in (1.0, 2.0, 3.0):
            reg.observe("step_time_s", v)
        reg.flush()
        text = open(path, encoding="utf-8").read()
        assert "apex_tpu_steps_total 7" in text
        assert "apex_tpu_mfu 0.4" in text
        assert "apex_tpu_step_time_s_count 3" in text
        assert "apex_tpu_step_time_s_sum 6.0" in text
        assert 'quantile="0.50"' in text
        # no torn files: the render is atomic (temp + rename)
        assert not os.path.exists(path + ".tmp")

    def test_prometheus_gauges_round_trip(self, tmp_path):
        """Plain gauges survive the sink round-trip with exact values
        and one TYPE declaration each."""
        path = str(tmp_path / "metrics.prom")
        reg = MetricsRegistry([PrometheusTextfileSink(path)])
        reg.set_gauge("mfu", 0.4)
        reg.set_gauge("kv_pages_free", 12)
        reg.set_gauge("loss_scale", 256.0)
        reg.flush()
        lines = open(path, encoding="utf-8").read().splitlines()
        assert "apex_tpu_mfu 0.4" in lines
        assert "apex_tpu_kv_pages_free 12.0" in lines
        assert "apex_tpu_loss_scale 256.0" in lines
        assert lines.count("# TYPE apex_tpu_mfu gauge") == 1

    def test_prometheus_labeled_gauges_round_trip(self, tmp_path):
        """Fleet-labeled gauges (``name{replica="i"}`` flat keys, what
        FleetMetrics.write_prometheus emits) render as one metric family
        per base name — a single TYPE line followed by every label set —
        and the label block survives name sanitization untouched."""
        sink = PrometheusTextfileSink(str(tmp_path / "metrics.prom"))
        sink.write({"kind": "gauges", "wall": 0.0, "values": {
            "kv_pages_free": 5.0,
            'kv_pages_free{replica="0"}': 2.0,
            'kv_pages_free{replica="1"}': 3.0,
        }})
        sink.flush()
        lines = open(sink.path, encoding="utf-8").read().splitlines()
        assert "apex_tpu_kv_pages_free 5.0" in lines
        assert 'apex_tpu_kv_pages_free{replica="0"} 2.0' in lines
        assert 'apex_tpu_kv_pages_free{replica="1"} 3.0' in lines
        # one TYPE line per family, not per label set
        assert lines.count("# TYPE apex_tpu_kv_pages_free gauge") == 1
        # labeled series sit under their family's TYPE line
        t = lines.index("# TYPE apex_tpu_kv_pages_free gauge")
        assert lines[t + 1].startswith("apex_tpu_kv_pages_free")


class TestFlops:
    def test_transformer_train_flops_hand_computed(self):
        # 6N term: 6 * 1e6 params; attention: 12 * L2 * s8 * d16 = 1536/tok
        got = transformer_train_flops(n_params=1_000_000, tokens=100,
                                      num_layers=2, hidden=16, seq=8,
                                      causal=False)
        assert got == 100 * (6.0 * 1_000_000 + 12 * 2 * 8 * 16)
        causal = transformer_train_flops(n_params=1_000_000, tokens=100,
                                         num_layers=2, hidden=16, seq=8,
                                         causal=True)
        assert causal == 100 * (6.0 * 1_000_000 + 6 * 2 * 8 * 16)

    def test_resnet50_train_flops_hand_computed(self):
        assert resnet50_train_flops(10, 224) == 10 * 3.0 * 4.09e9
        # area scaling: 112px is a quarter of the pixels
        assert resnet50_train_flops(1, 112) == pytest.approx(
            3.0 * 4.09e9 * 0.25)

    def test_peak_flops_unknown_on_cpu(self):
        assert peak_flops_per_chip() is None  # tier-1 runs on CPU


class TestStepMetrics:
    def _clock(self, dt):
        """Deterministic clock advancing dt per reading."""
        state = {"t": 0.0}

        def clock():
            state["t"] += dt / 2  # begin+end = one dt per step
            return state["t"]

        return clock

    def test_mfu_and_throughput_hand_computed(self):
        mem = InMemorySink()
        reg = MetricsRegistry([mem])
        sm = StepMetrics(reg, tokens_per_step=1000,
                         model_flops_per_step=2e12, peak_flops=8e12,
                         memory_interval_steps=0, clock=self._clock(0.5))
        sm.begin_step()
        sm.end_step(1)
        rec = sm.record_polled(1, loss=0.5, grad_norm=2.0, skipped=False)
        assert rec["step_time_s"] == pytest.approx(0.25)
        assert rec["tokens_per_s"] == pytest.approx(1000 / 0.25)
        # mfu = model_flops / dt / peak = 2e12 / 0.25 / 8e12 = 1.0
        assert rec["mfu"] == pytest.approx(1.0)
        assert rec["model_tflops"] == pytest.approx(8.0)
        assert reg.gauges()["mfu"] == pytest.approx(1.0)
        assert reg.histogram("loss").count == 1
        steps = mem.of_kind("step")
        assert len(steps) == 1 and steps[0]["loss"] == 0.5

    def test_peak_defaults_to_chip_table(self):
        reg = MetricsRegistry()
        sm = StepMetrics(reg, model_flops_per_step=1e12)
        assert sm.peak_flops is None  # CPU: unknown chip, MFU stays unset
        sm.begin_step()
        sm.end_step(1)
        rec = sm.record_polled(1, loss=1.0)
        assert "mfu" not in rec and "model_tflops" in rec

    def test_skipped_steps_stay_out_of_loss_histogram(self):
        reg = MetricsRegistry()
        sm = StepMetrics(reg, memory_interval_steps=0)
        sm.begin_step()
        sm.end_step(1)
        rec = sm.record_polled(1, loss=float("nan"), skipped=True)
        assert rec["skipped"] is True
        assert reg.histogram("loss") is None  # never polluted by NaN

    def test_pending_map_stays_bounded(self):
        # 1200 steps, polled each step: buffered timings never accumulate
        reg = MetricsRegistry(histogram_bound=32)
        sm = StepMetrics(reg, tokens_per_step=10, memory_interval_steps=0,
                         clock=self._clock(0.1))
        for step in range(1, 1201):
            sm.begin_step()
            sm.end_step(step)
            sm.record_polled(step, loss=1.0)
        assert sm._pending == {}
        snap = reg.histogram("step_time_s")
        assert snap.count == 1200 and len(snap._recent) == 32

    def test_step_timer_context(self):
        reg = MetricsRegistry()
        with StepTimer(reg, "data_wait_s") as t:
            pass
        assert t.elapsed >= 0
        assert reg.histogram("data_wait_s").count == 1


class TestTracing:
    def test_nvtx_range_without_registry_is_bare_scope(self):
        from apex_tpu.utils.profiling import nvtx_range

        with nvtx_range("legacy"):  # original call shape still works
            pass

    def test_span_without_registry_is_a_bare_trace_annotation(self):
        s = span("bwd", queued=2)
        assert isinstance(s, jax.profiler.TraceAnnotation)
        with s as inner:
            inner.set_metadata(retired=1)   # attributes known at the end

    def test_profiler_capture_schedule(self, tmp_path):
        calls = []
        prof = ProfilerCapture(
            str(tmp_path), every_n_steps=5, capture_steps=2,
            max_captures=2, registry=None,
            start_fn=lambda d: calls.append(("start", d)),
            stop_fn=lambda: calls.append(("stop",)))
        for step in range(1, 21):
            prof.on_step(step)
        # windows [5,7) and [10,12); then the capture budget is spent
        assert [c[0] for c in calls] == ["start", "stop", "start", "stop"]
        assert calls[0][1].endswith("step5_interval")
        assert calls[2][1].endswith("step10_interval")
        assert prof.captures == 2 and not prof.active

    def test_profiler_capture_on_incident(self, tmp_path):
        calls = []
        reg = MetricsRegistry()
        prof = ProfilerCapture(
            str(tmp_path), capture_steps=1, registry=reg,
            start_fn=lambda d: calls.append(d),
            stop_fn=lambda: None)
        prof.on_incident("loss_spike", step=42)
        assert prof.active and calls[0].endswith("step42_loss_spike")
        prof.on_incident("grad_spike", step=43)  # already active: no-op
        assert len(calls) == 1
        prof.on_step(43)  # past the window: auto-stop
        assert not prof.active
        assert reg.counters()["profiler_captures"] == 1


class TestRetraceWatchdogMetrics:
    def test_retraces_emit_counter_and_events(self):
        mem = InMemorySink()
        reg = MetricsRegistry([mem])
        f = jax.jit(lambda x: x * 2)
        wd = RetraceWatchdog(f, budget=None, metrics=reg)
        for n in range(2, 8):  # every call a new shape
            wd(jnp.ones((n,)))
        assert wd.retraces == 5
        assert reg.counters()["retraces"] == 5
        events = [e for e in mem.of_kind("event")
                  if e["event"] == "retrace"]
        assert len(events) == 5
        assert events[-1]["retraces"] == 5

    def test_no_registry_no_emission(self):
        f = jax.jit(lambda x: x + 1)
        wd = RetraceWatchdog(f, budget=None)
        for n in range(2, 5):
            wd(jnp.ones((n,)))
        assert wd.metrics is None and wd.retraces == 2


# ---------------------------------------------------------------------------
# acceptance: fault-injected run -> JSONL -> monitor report reconciliation
# ---------------------------------------------------------------------------

TARGET = jnp.full((4, 4), 0.3)


def _loss_fn(p, batch, rng):
    pred = batch["x"] @ p["w"] + p["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def _batch_fn(step):
    x = jax.random.normal(jax.random.PRNGKey(step), (8, 4))
    return {"x": x, "y": x @ TARGET}


@pytest.fixture(scope="module")
def fault_run(tmp_path_factory):
    """One fault-injected CPU run with the full sink stack attached;
    shared by the reconciliation/report/CLI assertions below."""
    tmp = tmp_path_factory.mktemp("obsrun")
    jsonl = str(tmp / "run.jsonl")
    prom = str(tmp / "metrics.prom")
    reg = MetricsRegistry([JsonlSink(jsonl), PrometheusTextfileSink(prom)])
    scaler = LossScaler("dynamic", init_scale=2.0 ** 8, scale_window=100)
    opt = FusedSGD(lr=0.05)
    params = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    step_fn = make_resilient_train_step(_loss_fn, opt, scaler)
    state = make_train_state(params, opt.init(params), scaler.init())
    cfg = ResilienceConfig(
        poll_interval_steps=2, save_interval_steps=4,
        max_consecutive_skips=3, min_history=4, save_backoff_base=0.0,
        handle_sigterm=False, metrics=reg,
        tokens_per_step=32, model_flops_per_step=1e9,
        peak_flops=1e12,  # CPU has no table entry: override for MFU
        memory_stats_interval_steps=5)
    inj = FaultInjector(nan_grad_calls=range(6, 10))
    result = run_training(step_fn, state, _batch_fn, 20,
                          checkpoint_dir=str(tmp / "ckpts"),
                          config=cfg, fault_injector=inj)
    reg.close()
    return {"result": result, "jsonl": jsonl, "prom": prom}


class TestMonitorReconciliation:
    def test_counters_reconcile_exactly_with_telemetry(self, fault_run):
        report = build_report(fault_run["jsonl"])
        assert report["counters"] == fault_run["result"].telemetry
        # the run actually exercised the incident paths
        assert report["counters"]["rollbacks"] == 1
        assert report["counters"]["skips"] >= 3

    def test_step_stats_nonzero(self, fault_run):
        report = build_report(fault_run["jsonl"])
        for key in ("step_time_s", "tokens_per_s", "mfu"):
            stats = report[key]
            assert stats is not None, key
            assert stats["p50"] > 0 and stats["p95"] > 0, key
            assert stats["count"] == fault_run["result"].telemetry["steps"]
        assert report["loss"]["last"] < report["loss"]["first"]

    def test_incident_timeline_orders_skips_and_rollback(self, fault_run):
        report = build_report(fault_run["jsonl"])
        names = [e["event"] for e in report["timeline"]]
        assert "skip" in names and "rollback" in names
        assert "watchdog_verdict" in names
        # verdict precedes its rollback in seq order
        assert names.index("watchdog_verdict") < names.index("rollback")

    def test_rendered_report_mentions_everything(self, fault_run):
        report = build_report(fault_run["jsonl"])
        text = render_report(report)
        for token in ("counters:", "step time", "tokens/s", "mfu",
                      "incident timeline", "rollback"):
            assert token in text, token

    def test_monitor_cli_reconciles(self, fault_run):
        """The acceptance criterion through the real CLI:
        ``python -m apex_tpu.monitor run.jsonl --json``."""
        proc = subprocess.run(
            [sys.executable, "-m", "apex_tpu.monitor",
             fault_run["jsonl"], "--json"],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stderr
        report = json.loads(proc.stdout)
        telemetry = fault_run["result"].telemetry
        assert report["counters"] == {k: int(v) for k, v in
                                      telemetry.items()}
        assert report["mfu"]["p50"] > 0

    def test_monitor_cli_text_mode_and_missing_file(self, fault_run):
        from apex_tpu.monitor import main

        assert main([fault_run["jsonl"]]) == 0
        assert main([fault_run["jsonl"] + ".nope"]) == 2

    def test_prometheus_file_written(self, fault_run):
        text = open(fault_run["prom"], encoding="utf-8").read()
        assert "apex_tpu_steps_total" in text
        assert "apex_tpu_rollbacks_total 1" in text

    def test_report_survives_torn_last_line(self, fault_run, tmp_path):
        torn = tmp_path / "torn.jsonl"
        data = open(fault_run["jsonl"], encoding="utf-8").read()
        torn.write_text(data + '{"kind": "step", "ste')  # killed mid-write
        report = build_report(str(torn))
        assert report["counters"] == fault_run["result"].telemetry


class TestReportBackCompat:
    """Run logs outlive the writers that produced them: the reader must
    fold records missing newer fields into "no data", never raise."""

    FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "pre_pr6_run.jsonl")

    def test_pre_pr6_log_still_renders(self):
        """A committed pre-TTFT-era log (request rows without
        ``ttft_s``/``tpot_s``, a step row without ``step``, a torn last
        line) builds and renders without KeyError."""
        report = build_report(self.FIXTURE)
        req = report["requests"]
        assert req["count"] == 3
        assert req["by_finish_reason"] == {
            "length": 1, "eos": 1, "rejected": 1}
        # the newer stats degrade to no-data instead of raising
        assert req["ttft_s"] is None and req["tpot_s"] is None
        assert req["total_s"]["count"] == 3
        assert report["slo"] is None          # nothing declared, no verdict
        text = render_report(report)
        assert "serving requests" in text
        assert "ttft" in text and "(no data)" in text

    def test_pre_pr6_log_scores_against_external_spec(self, tmp_path,
                                                      capsys):
        """``--slo`` can score an old log — and a TTFT objective FAILS
        on it (no data is never a pass), while reason-based objectives
        still evaluate."""
        report = build_report(self.FIXTURE, slo_spec={
            "ttft_p99_s": 1.0, "goodput": 0.5})
        slo = report["slo"]
        assert slo is not None and not slo["ok"]
        by = {o["name"]: o for o in slo["objectives"]}
        assert by["ttft_p99_s"]["measured"] is None
        assert not by["ttft_p99_s"]["ok"]
        assert by["goodput"]["ok"]            # 2/3 >= 0.5
        # the monitor CLI takes the same spec via --slo (in-process —
        # the monitor's subprocess plumbing is covered elsewhere)
        from apex_tpu.observability.report import main as monitor_main

        spec = tmp_path / "spec.json"
        spec.write_text(json.dumps({"goodput": 0.5}))
        assert monitor_main(
            [self.FIXTURE, "--json", "--slo", str(spec)]) == 0
        cli = json.loads(capsys.readouterr().out)
        assert cli["slo"]["ok"] is True

    PRE_PR7 = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "pre_pr7_run.jsonl")

    def test_pre_pr7_log_without_replica_id_still_renders(self):
        """A committed pre-fleet-era log (PR-6 vintage: ttft/tpot
        present, ``replica_id`` absent, no fleet counters) builds,
        renders with NO fleet section, and scores its embedded SLO —
        the readers tolerate the field's absence end-to-end."""
        report = build_report(self.PRE_PR7)
        req = report["requests"]
        assert req["count"] == 3
        assert req["ttft_s"]["count"] == 2     # newer fields still fold
        # no replica_id on any row, no fleet counters: no fleet section
        assert report["fleet"] is None
        # the embedded scenario SLO scores the old log (goodput 2/3)
        assert report["slo"]["ok"]
        text = render_report(report)
        assert "serving requests" in text
        assert "fleet:" not in text

    def test_mixed_replica_id_rows_fold_by_replica(self, tmp_path):
        """Rows with and without ``replica_id`` coexist (a fleet log
        whose fleet-level sheds carry no replica): the fleet section
        groups the tagged ones and never raises on the untagged."""
        log = tmp_path / "mixed.jsonl"
        rows = [
            {"kind": "request", "request_id": 0, "finish_reason": "length",
             "prompt_len": 4, "new_tokens": 2, "total_s": 0.1, "wall": 1.0,
             "replica_id": 0},
            {"kind": "request", "request_id": 1, "finish_reason": "length",
             "prompt_len": 4, "new_tokens": 2, "total_s": 0.1, "wall": 2.0,
             "replica_id": 1},
            {"kind": "request", "request_id": 2, "finish_reason":
             "rejected", "prompt_len": 4, "new_tokens": 0, "wall": 3.0},
            {"kind": "counters", "wall": 4.0, "values":
             {"fleet_dispatches": 2, "replica0_dispatches": 1,
              "replica1_dispatches": 1}},
        ]
        log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
        report = build_report(str(log))
        fleet = report["fleet"]
        assert fleet["requests_by_replica"] == {"0": 1, "1": 1}
        assert fleet["dispatches"]["fleet_dispatches"] == 2
        text = render_report(report)
        assert "dispatches: 2" in text
        assert "replica0=1 replica1=1" in text

    PRE_PR14 = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "data", "pre_pr14_run.jsonl")

    def test_pre_pr14_log_without_spans_still_renders(self):
        """A committed pre-tracing-era log (PR-13 vintage: adapter
        ledger present, NO ``trace_id`` on requests, NO span rows, no
        ``spans_*`` counters, torn last line) builds, renders without a
        tracing section, and still yields per-tenant attribution from
        the ``adapter_id`` request fields alone."""
        report = build_report(self.PRE_PR14)
        assert report["requests"]["count"] == 4
        # no span rows anywhere: the tracing section degrades to absent
        assert report["spans"] is None
        assert report["signals"] is None
        # per-tenant attribution needs only adapter_id on request rows
        by_adapter = report["slo_by_adapter"]
        assert set(by_adapter) == {"0", "1", "base"}
        assert by_adapter["0"]["requests"] == 1
        assert by_adapter["1"]["requests"] == 1
        assert by_adapter["base"]["requests"] == 2
        text = render_report(report)
        assert "per-tenant slo" in text
        assert "request tracing" not in text
        assert "fleet signals" not in text

    def test_pre_pr14_log_span_check_is_vacuous(self):
        """``check_span_conservation`` only examines requests that carry
        a ``trace_id`` — a trace-less log passes vacuously, so the
        loadtest ``--check`` gate cannot fail old logs."""
        from apex_tpu.observability.report import read_records
        from apex_tpu.observability.trace import check_span_conservation

        records = read_records(self.PRE_PR14)
        assert check_span_conservation(records) == []

    def test_pre_pr14_trace_lookup_reports_not_found(self, capsys):
        """``--trace`` on a trace-less log exits 2 with a clear message
        instead of raising."""
        from apex_tpu.observability.report import main as monitor_main

        assert monitor_main([self.PRE_PR14, "--trace", "0"]) == 2
        out = capsys.readouterr()
        assert "no spans" in (out.out + out.err).lower() or \
            "not found" in (out.out + out.err).lower()

    PRE_PR15 = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "data", "pre_pr15_run.jsonl")

    def test_pre_pr15_log_without_chunk_fields_still_renders(self):
        """A committed pre-chunked-prefill log (PR-14 vintage: tracing
        present, single-segment prefill spans, NO ``prefill_chunks``
        request fields, no ``prefill_tokens_per_tick`` histogram, torn
        last line) builds and renders with no chunked-prefill section —
        the new audit line only appears when the counter is non-zero."""
        report = build_report(self.PRE_PR15)
        assert report["requests"]["count"] == 4
        # rows without the field fold to a zero sum, not a KeyError
        assert report["requests"]["prefill_chunks"] == 0
        text = render_report(report)
        assert "chunked prefill" not in text

    def test_pre_pr15_log_span_check_still_conserves(self):
        """Single-segment prefill spans from a pre-chunking engine pass
        the SAME conservation checker the multi-segment timelines do —
        the gate cannot fail old logs."""
        from apex_tpu.observability.report import read_records
        from apex_tpu.observability.trace import check_span_conservation

        records = read_records(self.PRE_PR15)
        assert check_span_conservation(records) == []

    def test_pre_pr15_trace_renders_single_segment(self, capsys):
        """``--trace`` on a pre-chunking timeline renders the familiar
        queued/prefill/decode trio with no chunk annotations."""
        from apex_tpu.observability.report import main as monitor_main

        assert monitor_main([self.PRE_PR15, "--trace", "0"]) == 0
        out = capsys.readouterr().out
        assert "prefill" in out and "chunk=" not in out

    PRE_PR16 = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "data", "pre_pr16_run.jsonl")

    def test_pre_pr16_log_without_autoscale_deploy_still_renders(self):
        """A committed pre-autoscaling log (PR-15 vintage: fleet +
        signals present, NO ``queued_tokens``/``window_s`` signal keys,
        ``goodput_window`` still null-on-idle, no ``kind="autoscale"``
        / ``kind="deploy"`` rows, no ``replica_scale_*``/``deploys_*``
        counters, torn last line) builds and renders with no autoscale
        or deployment section."""
        report = build_report(self.PRE_PR16)
        assert report["requests"]["count"] == 4
        assert report["autoscale"] is None
        assert report["deploys"] is None
        # the old signals snapshot still renders: the new keys are
        # guarded, not assumed
        signals = report["signals"]
        assert signals is not None
        assert "queued_tokens" not in signals
        assert signals["goodput_window"] is None
        text = render_report(report)
        assert "fleet signals" in text
        assert "autoscale decisions" not in text
        assert "deployments (" not in text
        assert "queued_tokens=" not in text

    def test_pre_pr16_fleet_section_still_reconciles(self):
        """The fleet incident reconciliation (drain/rebuild events vs
        their counters) is unchanged by the PR 16 counter additions —
        absent deploy/scale counters read as zero, not as a mismatch."""
        report = build_report(self.PRE_PR16)
        fleet = report["fleet"]
        assert fleet["counts"]["replica_drain"] == 1
        assert fleet["counts"]["replica_rebuild"] == 1
        assert fleet["requests_by_replica"] == {"0": 2, "1": 1}

    def test_pre_pr16_log_span_check_still_conserves(self):
        from apex_tpu.observability.report import read_records
        from apex_tpu.observability.trace import check_span_conservation

        records = read_records(self.PRE_PR16)
        assert check_span_conservation(records) == []

    PRE_PR18 = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "data", "pre_pr18_run.jsonl")

    def test_pre_pr18_log_without_anomaly_bundle_still_renders(self):
        """A committed pre-flight-recorder log (PR-17 vintage: fleet +
        autoscale rows present, NO ``kind="anomaly"`` /
        ``kind="bundle"`` / ``kind="gauge_snapshot"`` rows, no
        ``anomalies_*`` / ``bundles_dumped`` / ``gauge_snapshots``
        counters, torn last line) builds and renders with no drift or
        bundle section — the new sections only appear when their rows
        or counters exist."""
        report = build_report(self.PRE_PR18)
        assert report["requests"]["count"] == 3
        assert report["anomalies"] is None
        assert report["bundles"] is None
        assert report["gauge_trajectory"] == []
        text = render_report(report)
        assert "drift anomalies" not in text
        assert "postmortem bundles" not in text
        assert "signal trajectory" not in text
        # the era's own sections are untouched by the new readers
        assert "autoscale decisions" in text

    def test_pre_pr18_log_span_check_still_conserves(self):
        from apex_tpu.observability.report import read_records
        from apex_tpu.observability.trace import check_span_conservation

        records = read_records(self.PRE_PR18)
        assert check_span_conservation(records) == []


class TestFlightRecorder:
    """The bounded-ring recorder + incident bundle dumper."""

    def _registry_with_recorder(self, **kwargs):
        rec = FlightRecorder(**kwargs)
        reg = MetricsRegistry([rec])
        rec.attach(None, reg)
        return reg, rec

    def test_rings_bounded_o_capacity(self):
        """Memory stays O(capacity) no matter how long the run is —
        the ring length never exceeds maxlen and keeps the NEWEST
        records."""
        reg, rec = self._registry_with_recorder(
            events_capacity=4, records_capacity=3, gauges_capacity=2,
            triggers=frozenset())
        for i in range(50):
            reg.event("tick", i=i)
            reg.emit_record({"kind": "request", "request_id": i})
            reg.emit_record({"kind": "gauge_snapshot", "signals": {},
                             "i": i})
        assert len(rec.events) == 4 and rec.events.maxlen == 4
        assert [e["i"] for e in rec.events] == [46, 47, 48, 49]
        assert len(rec.records) == 3
        assert [r["request_id"] for r in rec.records] == [47, 48, 49]
        assert len(rec.gauge_snapshots) == 2

    def test_incident_event_triggers_exactly_one_dump(self):
        """Any TRIGGER_EVENTS member flowing through the sink dumps a
        bundle; the max_bundles=1 latch makes later incidents no-ops."""
        reg, rec = self._registry_with_recorder(max_bundles=1)
        reg.event("heartbeat")            # not incident-class
        assert rec.bundles == []
        reg.event("engine_restart", replica_id=0)
        assert len(rec.bundles) == 1
        reg.event("engine_restart", replica_id=1)
        reg.event("replica_quarantine", replica_id=1)
        assert len(rec.bundles) == 1      # latched
        assert reg.counters()["bundles_dumped"] == 1
        bundle = rec.bundles[0]
        assert bundle["schema"] == 1
        assert bundle["trigger"]["event"] == "engine_restart"
        # the trigger itself sits inside the ring window it froze
        assert any(e.get("event") == "engine_restart"
                   for e in bundle["events"])

    def test_bundle_dumped_is_not_a_trigger(self):
        """The dump's own co-sited event must never re-trigger a dump
        (and is statically excluded from the trigger table)."""
        assert "bundle_dumped" not in TRIGGER_EVENTS
        reg, rec = self._registry_with_recorder(max_bundles=5)
        reg.event("engine_restart")
        assert len(rec.bundles) == 1      # one incident, one bundle

    def test_bundle_counters_snapshot_precedes_own_increment(self):
        """The bundle freezes the counters as they were AT the incident
        — its own ``bundles_dumped`` increment lands after the
        snapshot."""
        reg, rec = self._registry_with_recorder()
        reg.inc("engine_restarts")
        reg.event("engine_restart")
        bundle = rec.bundles[0]
        assert bundle["counters"]["engine_restarts"] == 1
        assert bundle["counters"]["bundles_dumped"] == 0
        assert reg.counters()["bundles_dumped"] == 1

    def test_bundle_reconciles_key_for_key(self):
        """Dumping follows the reconcile contract: one counter inc
        co-sited with one ``bundle_dumped`` event and one
        ``kind="bundle"`` record."""
        mem = InMemorySink()
        rec = FlightRecorder()
        reg = MetricsRegistry([mem, rec])
        rec.attach(None, reg)
        reg.event("tick_failure")
        events = [e for e in mem.of_kind("event")
                  if e["event"] == "bundle_dumped"]
        records = mem.of_kind("bundle")
        assert len(events) == 1 == len(records)
        assert reg.counters()["bundles_dumped"] == 1
        assert records[0]["trigger"] == "tick_failure"

    def test_bundle_file_is_self_contained_json(self, tmp_path):
        """With a bundle_dir the dump lands as one deterministic-named
        JSON file, loadable with nothing but the stdlib."""
        rec = FlightRecorder(bundle_dir=str(tmp_path),
                             bundle_prefix="myrun")
        reg = MetricsRegistry([rec])
        rec.attach(None, reg)
        reg.emit_record({"kind": "signals",
                         "values": {"queue_depth": 7}})
        reg.event("deploy_rollback")
        path = tmp_path / "myrun-bundle-1.json"
        assert rec.bundle_paths == [str(path)]
        bundle = json.loads(path.read_text())
        assert bundle["kind"] == "flight_bundle"
        assert bundle["trigger"]["event"] == "deploy_rollback"
        assert bundle["signals"] == {"queue_depth": 7}

    def test_dump_never_raises_on_torn_target(self):
        """Postmortem evidence is best-effort: a digest target that
        explodes mid-incident degrades the digest, not the serving
        path."""
        class Torn:
            @property
            def replicas(self):
                raise RuntimeError("mid-rebuild")

        rec = FlightRecorder()
        reg = MetricsRegistry([rec])
        rec.attach(Torn(), reg)
        reg.event("engine_restart")       # must not raise
        assert len(rec.bundles) == 1
        assert rec.bundles[0]["replicas"] == []

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            FlightRecorder(events_capacity=0)
        with pytest.raises(ValueError):
            FlightRecorder(max_bundles=-1)

    def test_retrace_watchdog_event_triggers_dump(self):
        """Satellite: a real RetraceWatchdog recompile is an
        incident-class trigger — the shape-drift postmortem survives
        even though retrace counters batch."""
        rec = FlightRecorder()
        reg = MetricsRegistry([rec])
        rec.attach(None, reg)
        f = jax.jit(lambda x: x * 2)
        wd = RetraceWatchdog(f, budget=None, metrics=reg)
        wd(jnp.ones((2,)))
        wd(jnp.ones((3,)))       # retrace -> trigger
        assert len(rec.bundles) == 1
        assert rec.bundles[0]["trigger"]["event"] == "retrace"

    def test_trigger_table_covers_every_incident_map(self):
        """LOCK: TRIGGER_EVENTS must be a superset of every key of
        every ``*_INCIDENT_COUNTERS`` map the monitor reconciles —
        the inclusion APX013 re-checks tree-wide."""
        from apex_tpu.observability import report as report_mod

        for name in dir(report_mod):
            if not name.endswith("_INCIDENT_COUNTERS"):
                continue
            for event in getattr(report_mod, name):
                assert event in TRIGGER_EVENTS, (
                    f"{name} key {event!r} missing from TRIGGER_EVENTS")
        assert "retrace" in TRIGGER_EVENTS   # recorder-only extra


class TestDriftSentinel:
    """The pure EWMA/robust-z detector core, then the fleet seam."""

    def _drive(self, sentinel, values, start=0.0, dt=1.0):
        fired = []
        for i, v in enumerate(values):
            fired.extend(sentinel.observe({"queue_depth": v},
                                          start + i * dt))
        return fired

    def test_warmup_gate_holds_fire(self):
        s = DriftSentinel(SentinelConfig(
            warmup_polls=5, hysteresis_polls=1, min_abs_dev=0.5,
            signals=("queue_depth",)))
        # a huge excursion during warmup is baseline-learning, not news
        assert self._drive(s, [0, 0, 100, 0]) == []

    def test_spike_fires_after_hysteresis(self):
        s = DriftSentinel(SentinelConfig(
            warmup_polls=3, hysteresis_polls=2, z_threshold=4.0,
            min_abs_dev=0.5, cooldown_s=100.0,
            signals=("queue_depth",)))
        fired = self._drive(s, [1, 1, 1, 1, 30, 30, 30])
        assert len(fired) == 1            # breach #2 arms it, once
        a = fired[0]
        assert a["signal"] == "queue_depth" and a["value"] == 30.0
        assert a["z"] >= 4.0 and a["baseline"] < 2.0

    def test_single_breach_is_not_an_anomaly(self):
        """hysteresis_polls=2: one outlier poll (a scheduling blip)
        stays quiet."""
        s = DriftSentinel(SentinelConfig(
            warmup_polls=3, hysteresis_polls=2, min_abs_dev=0.5,
            signals=("queue_depth",)))
        assert self._drive(s, [1, 1, 1, 1, 30, 1, 1, 30, 1]) == []

    def test_breaches_do_not_corrupt_baseline(self):
        """Breach values are evidence about the incident, not the
        baseline: after the excursion the baseline still reflects the
        healthy level."""
        s = DriftSentinel(SentinelConfig(
            warmup_polls=3, hysteresis_polls=2, min_abs_dev=0.5,
            cooldown_s=0.0, signals=("queue_depth",)))
        self._drive(s, [1, 1, 1, 1, 30, 30])
        assert s._trackers["queue_depth"].mean < 2.0

    def test_direction_a_good_day_never_fires(self):
        """goodput_window degrades DOWN: a jump above baseline is an
        improvement, not an anomaly."""
        s = DriftSentinel(SentinelConfig(
            warmup_polls=3, hysteresis_polls=1, min_abs_dev=0.01,
            signals=("goodput_window",)))
        fired = []
        for i, v in enumerate([0.5, 0.5, 0.5, 0.5, 1.0, 1.0]):
            fired.extend(s.observe({"goodput_window": v}, float(i)))
        assert fired == []
        # ...while the same magnitude downward fires
        s2 = DriftSentinel(SentinelConfig(
            warmup_polls=3, hysteresis_polls=1, min_abs_dev=0.01,
            signals=("goodput_window",)))
        fired2 = []
        for i, v in enumerate([0.5, 0.5, 0.5, 0.5, 0.0]):
            fired2.extend(s2.observe({"goodput_window": v}, float(i)))
        assert len(fired2) == 1

    def test_cooldown_suppresses_refire(self):
        s = DriftSentinel(SentinelConfig(
            warmup_polls=3, hysteresis_polls=1, z_threshold=4.0,
            min_abs_dev=0.5, cooldown_s=100.0,
            signals=("queue_depth",)))
        fired = self._drive(s, [1, 1, 1, 1, 30, 35, 40, 45])
        assert len(fired) == 1            # one excursion, one anomaly

    def test_none_and_missing_signals_are_skipped(self):
        s = DriftSentinel(SentinelConfig(
            warmup_polls=2, hysteresis_polls=1, min_abs_dev=0.5,
            signals=("queue_depth", "ttft_p99_s")))
        # None (idle window) and absent keys never touch the tracker
        for i in range(6):
            s.observe({"queue_depth": 1.0, "ttft_p99_s": None},
                      float(i))
        assert s._trackers["ttft_p99_s"].samples == 0
        assert s._trackers["queue_depth"].samples == 6

    def test_min_abs_dev_floors_flat_baselines(self):
        """A perfectly flat baseline has dev=0 — without the floor the
        first real wiggle would divide by ~zero and fire on noise."""
        s = DriftSentinel(SentinelConfig(
            warmup_polls=3, hysteresis_polls=1, z_threshold=4.0,
            min_abs_dev=2.0, signals=("queue_depth",)))
        # wiggles of |x - 0| < 2*4 stay under threshold
        assert self._drive(s, [0, 0, 0, 0, 3, 4, 3, 5, 0]) == []

    def test_config_validation(self):
        with pytest.raises(ValueError):
            SentinelConfig(ewma_alpha=0.0)
        with pytest.raises(ValueError):
            SentinelConfig(warmup_polls=0)
        with pytest.raises(ValueError):
            SentinelConfig(signals=())

    class _FakeSupervisor:
        queued_count = 0
        active_count = 0
        queued_prompt_tokens = 0

    class _FakeReplica:
        def __init__(self):
            self.supervisor = TestDriftSentinel._FakeSupervisor()

    class _FakeConfig:
        max_slots = 4

    class _FakeFleet:
        """Duck-typed just far enough for FleetMetrics: the sentinel's
        fleet seam is the interface, not the ReplicaFleet class."""

        def __init__(self, registry):
            self.metrics = registry
            self.replica_metrics = {0: MetricsRegistry()}
            self.replicas = [TestDriftSentinel._FakeReplica()]
            self.config = TestDriftSentinel._FakeConfig()
            self.inflight_count = 0

        def dispatch_set(self):
            return list(self.replicas)

    def test_maybe_poll_declares_and_reconciles_counters(self):
        """The fleet seam: counters declared up front (snapshots carry
        the keys at zero), poll gating by interval, anomaly emission
        co-sited counter+event+record, periodic gauge_snapshot."""
        mem = InMemorySink()
        reg = MetricsRegistry([mem])
        fleet = self._FakeFleet(reg)
        s = DriftSentinel(SentinelConfig(
            poll_interval_s=1.0, warmup_polls=2, hysteresis_polls=1,
            z_threshold=4.0, min_abs_dev=0.5, snapshot_every_polls=2,
            signals=("queue_depth",)))
        assert s.maybe_poll(fleet, 0.0) == []
        counters = reg.counters()
        assert counters["anomalies_total"] == 0
        assert counters["anomalies_queue_depth"] == 0
        assert counters["gauge_snapshots"] == 0
        # inside the interval: gated, no poll consumed
        assert s.maybe_poll(fleet, 0.5) == [] and s.polls == 1
        s.maybe_poll(fleet, 1.0)          # poll 2 -> gauge_snapshot
        assert reg.counters()["gauge_snapshots"] == 1
        snaps = mem.of_kind("gauge_snapshot")
        assert len(snaps) == 1
        assert "queue_depth" in snaps[0]["signals"]
        # now degrade: queue_depth jumps fleet-wide
        self._FakeSupervisor.queued_count = 40
        try:
            fired = s.maybe_poll(fleet, 2.0)
        finally:
            self._FakeSupervisor.queued_count = 0
        assert len(fired) == 1
        counters = reg.counters()
        assert counters["anomalies_total"] == 1
        assert counters["anomalies_queue_depth"] == 1
        events = [e for e in mem.of_kind("event")
                  if e["event"] == "anomaly"]
        records = mem.of_kind("anomaly")
        assert len(events) == 1 == len(records)
        assert records[0]["signal"] == "queue_depth"


class TestBundleRendering:
    """``python -m apex_tpu.monitor bundle <path>`` — the postmortem
    reader."""

    def _dump_bundle(self, tmp_path):
        rec = FlightRecorder(bundle_dir=str(tmp_path),
                             bundle_prefix="t")
        reg = MetricsRegistry([rec])
        rec.attach(None, reg)
        reg.emit_record({"kind": "gauge_snapshot", "wall": 1.0,
                         "signals": {"queue_depth": 0,
                                     "ttft_p99_s": 0.1}})
        reg.emit_record({"kind": "gauge_snapshot", "wall": 2.0,
                         "signals": {"queue_depth": 9,
                                     "ttft_p99_s": 0.4}})
        reg.emit_record({"kind": "request", "request_id": 0,
                         "wall": 2.5})
        reg.event("anomaly", signal="queue_depth", value=9.0, z=5.0)
        return rec.bundle_paths[0]

    def test_render_marks_trigger_inside_timeline(self, tmp_path):
        from apex_tpu.observability.report import render_bundle

        path = self._dump_bundle(tmp_path)
        text = render_bundle(json.loads(open(path).read()))
        assert "postmortem bundle" in text
        assert "trigger: anomaly" in text
        # the trigger row is matched in the merged ring timeline
        assert ">>" in text
        assert "queue_depth" in text and "0 -> 9" in text

    def test_monitor_bundle_cli_human_and_json(self, tmp_path, capsys):
        from apex_tpu.observability.report import main as monitor_main

        path = self._dump_bundle(tmp_path)
        assert monitor_main(["bundle", path]) == 0
        human = capsys.readouterr().out
        assert "trigger: anomaly" in human
        assert monitor_main(["bundle", path, "--json"]) == 0
        bundle = json.loads(capsys.readouterr().out)
        assert bundle["kind"] == "flight_bundle"
        assert bundle["trigger"]["event"] == "anomaly"

    def test_monitor_bundle_cli_bad_path_exits_2(self, tmp_path):
        from apex_tpu.observability.report import main as monitor_main

        assert monitor_main(["bundle",
                             str(tmp_path / "missing.json")]) == 2
        torn = tmp_path / "torn.json"
        torn.write_text("{not json")
        assert monitor_main(["bundle", str(torn)]) == 2


class TestLabeledHistogramExport:
    """FleetMetrics' labeled histograms through the Prometheus sink:
    one TYPE line per family, per-replica label splits, quantiles
    folded into the label block."""

    def test_one_type_line_per_family_with_label_splits(self, tmp_path):
        path = tmp_path / "prom.txt"
        sink = PrometheusTextfileSink(str(path))
        summ = {"count": 3, "sum": 0.6, "p50": 0.2, "p95": 0.3}
        sink.write({"kind": "histograms", "values": {
            "request_ttft_s": dict(summ),
            'request_ttft_s{replica="0"}': dict(summ),
            'request_ttft_s{replica="1"}': dict(summ)}})
        sink.flush()
        text = path.read_text()
        assert text.count("# TYPE apex_tpu_request_ttft_s summary") == 1
        assert "apex_tpu_request_ttft_s_count 3" in text
        assert 'apex_tpu_request_ttft_s_count{replica="0"} 3' in text
        assert 'apex_tpu_request_ttft_s_sum{replica="1"} 0.6' in text
        # quantile merged into the replica label block, not appended
        assert ('apex_tpu_request_ttft_s{replica="0",quantile="0.50"} '
                "0.2") in text
        assert 'apex_tpu_request_ttft_s{quantile="0.95"} 0.3' in text
