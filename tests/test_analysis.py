"""apex_tpu.analysis suite: one positive + one negative fixture per APX
rule, suppression/baseline/config behavior, CLI exit codes, and the
retrace watchdog (fires on a forced recompile storm, stays silent on
stable shapes — standalone and wired through ``resilience.run_training``).
"""

import json
import logging
import os
import textwrap

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.analysis import (
    Baseline,
    RetraceBudgetExceeded,
    RetraceWatchdog,
    analyze_source,
    load_config,
)
from apex_tpu.analysis.engine import main as cli_main
from apex_tpu.analysis.rules import all_rules


def codes(src, only=None):
    """Run the pack (or one rule) over a snippet, return finding codes."""
    rules = all_rules()
    if only is not None:
        rules = [r for r in rules if r.code == only]
    return [f.code for f in analyze_source(textwrap.dedent(src),
                                           "snippet.py", rules)]


# ---------------------------------------------------------------------------
# rule fixtures: positive (must fire) + negative (must stay silent)
# ---------------------------------------------------------------------------

class TestAPX001PrngReuse:
    def test_positive_sequential_reuse(self):
        src = """
            import jax
            def sample(key):
                a = jax.random.normal(key, (3,))
                b = jax.random.uniform(key, (3,))
                return a + b
        """
        assert codes(src, "APX001") == ["APX001"]

    def test_positive_loop_reuse(self):
        src = """
            import jax
            def sample(key, n):
                out = []
                for _ in range(n):
                    out.append(jax.random.normal(key, (3,)))
                return out
        """
        assert codes(src, "APX001") == ["APX001"]

    def test_positive_comprehension_reuse(self):
        src = """
            import jax
            def sample(key):
                return [jax.random.normal(key, (3,)) for _ in range(4)]
        """
        assert codes(src, "APX001") == ["APX001"]

    def test_negative_split_between(self):
        src = """
            import jax
            def sample(key):
                a = jax.random.normal(key, (3,))
                key, sub = jax.random.split(key)
                b = jax.random.uniform(key, (3,))
                c = {k: jax.random.normal(k, (2,))
                     for k in jax.random.split(sub, 3)}
                return a + b, c
        """
        assert codes(src, "APX001") == []

    def test_negative_fold_in_loop(self):
        src = """
            import jax
            def sample(key, n):
                out = []
                for i in range(n):
                    k = jax.random.fold_in(key, i)
                    out.append(jax.random.normal(k, (3,)))
                return out
        """
        assert codes(src, "APX001") == []

    def test_import_alias_resolved(self):
        src = """
            from jax import random as jr
            def sample(key):
                return jr.normal(key, (3,)) + jr.uniform(key, (3,))
        """
        assert codes(src, "APX001") == ["APX001"]


class TestAPX002Concretization:
    def test_positive_float_and_if(self):
        src = """
            import jax
            @jax.jit
            def f(x):
                if x > 0:
                    return float(x)
                return x
        """
        got = codes(src, "APX002")
        assert got == ["APX002", "APX002"]

    def test_positive_call_form_jit(self):
        src = """
            import jax
            def f(x):
                return x.item()
            g = jax.jit(f)
        """
        assert codes(src, "APX002") == ["APX002"]

    def test_negative_static_and_shape_reads(self):
        src = """
            import jax
            from functools import partial
            @partial(jax.jit, static_argnames=("n",))
            def f(x, n):
                if n > 2:               # static: fine
                    pass
                if x is not None:       # structure check: fine
                    pass
                if x.ndim == 2:         # shape read: fine
                    pass
                m = int(x.shape[0])     # static shape: fine
                return x * m
        """
        assert codes(src, "APX002") == []


class TestAPX003HostSync:
    def test_positive_step_body(self):
        src = """
            import jax
            def train_step(state, batch):
                loss = state + batch
                jax.device_get(loss)
                return loss
        """
        assert codes(src, "APX003") == ["APX003"]

    def test_positive_block_until_ready(self):
        src = """
            import jax
            def _step(x):
                x.block_until_ready()
                return x
        """
        assert codes(src, "APX003") == ["APX003"]

    def test_negative_poll_helper_and_tests(self):
        src = """
            import jax
            def poll_metrics(pending):
                return jax.device_get(pending)   # off the hot loop: fine
            def test_step_values(x):
                return jax.device_get(x)         # test body: fine
        """
        assert codes(src, "APX003") == []


class TestAPX004Recompile:
    def test_positive_mutable_default_and_shape(self):
        src = """
            import jax
            @jax.jit
            def f(x, opts={}, shape=None):
                return x
        """
        got = codes(src, "APX004")
        assert got == ["APX004", "APX004"]

    def test_negative_static_shape(self):
        src = """
            import jax
            from functools import partial
            @partial(jax.jit, static_argnames=("shape",))
            def f(x, shape=None, opts=()):
                return x
        """
        assert codes(src, "APX004") == []


class TestAPX005Collectives:
    def test_positive_unbound_axis(self):
        src = """
            from jax import lax
            def f(x):
                return lax.psum(x, "tp")
        """
        assert codes(src, "APX005") == ["APX005"]

    def test_negative_bound_by_spec_or_mesh(self):
        src = """
            from jax import lax
            from jax.sharding import Mesh, PartitionSpec
            def make(devs):
                return Mesh(devs, ("data",))
            SPEC = PartitionSpec("tp")
            def f(x, axis):
                return lax.psum(x, "tp") + lax.pmean(x, "data") \\
                    + lax.psum(x, axis)   # variable axis: resolved elsewhere
        """
        assert codes(src, "APX005") == []


class TestAPX006Dtype:
    def test_positive_chained_roundtrip(self):
        src = """
            import jax.numpy as jnp
            def f(x):
                return x.astype(jnp.float32).astype(jnp.bfloat16)
        """
        assert codes(src, "APX006") == ["APX006"]

    def test_positive_fp32_in_bf16_function(self):
        src = """
            import jax.numpy as jnp
            def f(x):
                h = x.astype(jnp.bfloat16)
                acc = jnp.zeros((4,), dtype=jnp.float32)
                return h, acc
        """
        assert codes(src, "APX006") == ["APX006"]

    def test_negative_single_policy(self):
        src = """
            import jax.numpy as jnp
            def f(x):
                return x.astype(jnp.bfloat16)
            def g(x):
                return jnp.zeros((4,), dtype=jnp.float32)
        """
        assert codes(src, "APX006") == []


class TestAPX007PallasScan:
    def test_positive_interpret_in_scan_body(self):
        src = """
            from jax import lax
            from jax.experimental import pallas as pl
            def body(c, x):
                y = pl.pallas_call(lambda r: None, interpret=True)(x)
                return c, y
            def run(xs):
                return lax.scan(body, 0, xs)
        """
        assert codes(src, "APX007") == ["APX007"]

    def test_positive_one_call_hop(self):
        src = """
            from jax import lax
            from jax.experimental import pallas as pl
            def kernel(x, interpret):
                return pl.pallas_call(lambda r: None,
                                      interpret=interpret)(x)
            def body(c, x):
                return c, kernel(x, True)
            def run(xs):
                return lax.scan(body, 0, xs)
        """
        assert codes(src, "APX007") == ["APX007"]

    def test_negative_interpret_false_or_no_scan(self):
        src = """
            from jax import lax
            from jax.experimental import pallas as pl
            def body(c, x):
                y = pl.pallas_call(lambda r: None, interpret=False)(x)
                return c, y
            def run(xs):
                return lax.scan(body, 0, xs)
            def standalone(x):
                return pl.pallas_call(lambda r: None, interpret=True)(x)
        """
        assert codes(src, "APX007") == []


class TestAPX008MutableState:
    def test_positive_store_and_method(self):
        src = """
            import jax
            _CACHE = {}
            _LOG = []
            @jax.jit
            def f(x):
                _CACHE["last"] = x
                _LOG.append(1)
                return x
        """
        got = codes(src, "APX008")
        assert got == ["APX008", "APX008"]

    def test_negative_outside_jit_or_immutable(self):
        src = """
            import jax
            _CACHE = {}
            _LIMIT = 3
            def warm(x):
                _CACHE["x"] = x     # host-side registry: fine
                return x
            @jax.jit
            def f(x):
                return x * _LIMIT   # read-only: fine
        """
        assert codes(src, "APX008") == []


def codes_at(src, path, only):
    """Like :func:`codes` but with an explicit module path — the
    path-scoped rules (APX011/APX012) key off where the file lives."""
    rules = [r for r in all_rules() if r.code == only]
    return [f.code for f in analyze_source(textwrap.dedent(src), path,
                                           rules)]


class TestAPX009RecordContract:
    def test_positive_emit_without_counter(self):
        src = """
            def emit(metrics):
                metrics.emit_record({"kind": "widget", "n": 1})
        """
        assert codes(src, "APX009") == ["APX009"]

    def test_positive_dict_via_variable(self):
        src = """
            def emit(metrics):
                rec = {"kind": "widget"}
                rec.update(n=1)
                metrics.emit_record(rec)
        """
        assert codes(src, "APX009") == ["APX009"]

    def test_negative_counter_in_module(self):
        src = """
            def emit(metrics):
                metrics.inc("widgets")
                metrics.emit_record({"kind": "widget", "n": 1})
        """
        assert codes(src, "APX009") == []

    def test_negative_typed_result_record_skipped(self):
        # result.record() is the typed RequestResult path — reconciled
        # by construction, not a dict-literal contract site
        src = """
            def emit(metrics, result):
                metrics.emit_record(result.record(wall=0.0))
        """
        assert codes(src, "APX009") == []

    def _tree(self, tmp_path, report_src):
        from apex_tpu.analysis.engine import AnalysisConfig, analyze_paths
        from apex_tpu.analysis.rules.apx009_record_contract import (
            APX009RecordContract,
        )
        pkg = tmp_path / "pkg"
        obs = tmp_path / "observability"
        pkg.mkdir()
        obs.mkdir()
        (pkg / "emitter.py").write_text(textwrap.dedent("""
            def emit(metrics):
                metrics.inc("widgets")
                metrics.emit_record({"kind": "widget"})
        """))
        (obs / "report.py").write_text(report_src)
        cfg = AnalysisConfig(root=str(tmp_path))
        return analyze_paths([str(pkg), str(obs)], cfg,
                             [APX009RecordContract()])

    def test_cross_file_kind_unknown_to_report(self, tmp_path):
        found = self._tree(tmp_path, 'KINDS = ("request", "scenario")\n')
        assert [f.code for f in found] == ["APX009"]
        assert "unknown to observability/report.py" in found[0].message

    def test_cross_file_kind_reconciled(self, tmp_path):
        found = self._tree(tmp_path, 'KINDS = ("request", "widget")\n')
        assert found == []


class TestAPX010ScenarioSchema:
    def _tree(self, tmp_path, scenario_src, runner_src):
        from apex_tpu.analysis.engine import AnalysisConfig, analyze_paths
        from apex_tpu.analysis.rules.apx010_scenario_schema import (
            APX010ScenarioSchema,
        )
        lt = tmp_path / "loadtest"
        lt.mkdir()
        (lt / "scenario.py").write_text(textwrap.dedent(scenario_src))
        (lt / "runner.py").write_text(textwrap.dedent(runner_src))
        cfg = AnalysisConfig(root=str(tmp_path))
        return analyze_paths([str(lt)], cfg, [APX010ScenarioSchema()])

    _DRIFTED = """
        class Scenario:
            name: str
            seed: int = 0
            extra: int = 0

            @property
            def total_requests(self):
                return 0

            @classmethod
            def from_dict(cls, data):
                known = {"name", "seed", "ghost"}
                return cls()
    """

    _ALIGNED = """
        class Scenario:
            name: str
            seed: int = 0

            @property
            def total_requests(self):
                return 0

            @classmethod
            def from_dict(cls, data):
                known = {"name", "seed"}
                return cls()
    """

    def test_positive_schema_drift_both_directions(self, tmp_path):
        found = self._tree(tmp_path, self._DRIFTED,
                           "def run(scenario):\n    return scenario.name\n")
        msgs = [f.message for f in found]
        assert len(found) == 2
        assert any("'ghost'" in m for m in msgs)
        assert any("'extra'" in m for m in msgs)

    def test_positive_runner_reads_missing_attr(self, tmp_path):
        found = self._tree(
            tmp_path, self._ALIGNED,
            "def run(scenario):\n"
            "    n = scenario.total_requests\n"
            "    return scenario.bogus\n")
        assert [f.code for f in found] == ["APX010"]
        assert "scenario.bogus" in found[0].message

    def test_negative_aligned_surfaces(self, tmp_path):
        found = self._tree(
            tmp_path, self._ALIGNED,
            "def run(scenario):\n"
            "    return scenario.name, scenario.seed, "
            "scenario.total_requests\n")
        assert found == []

    def test_real_tree_is_clean(self):
        # the live scenario/runner pair must satisfy its own contract
        import apex_tpu

        from apex_tpu.analysis.engine import analyze_paths
        from apex_tpu.analysis.rules.apx010_scenario_schema import (
            APX010ScenarioSchema,
        )
        lt = os.path.join(os.path.dirname(apex_tpu.__file__), "loadtest")
        assert analyze_paths([lt], rules=[APX010ScenarioSchema()]) == []


class TestAPX011WallClock:
    def test_positive_direct_reads_in_serving(self):
        src = """
            import time
            def poll():
                t0 = time.monotonic()
                time.sleep(0.1)
                return time.time() - t0
        """
        got = codes_at(src, "apex_tpu/serving/foo.py", "APX011")
        assert got == ["APX011"] * 3

    def test_positive_alias_resolved_in_loadtest(self):
        src = """
            import time as _t
            def stamp():
                return _t.perf_counter()
        """
        assert codes_at(src, "apex_tpu/loadtest/foo.py",
                        "APX011") == ["APX011"]

    def test_negative_clock_module_is_exempt(self):
        src = """
            import time
            def now():
                return time.monotonic()
        """
        assert codes_at(src, "apex_tpu/serving/clock.py", "APX011") == []

    def test_negative_outside_scoped_trees(self):
        src = """
            import time
            def now():
                return time.monotonic()
        """
        assert codes_at(src, "apex_tpu/checkpoint/retry.py",
                        "APX011") == []

    def test_negative_clock_seam_usage(self):
        src = """
            from apex_tpu.serving import clock
            def poll():
                clock.sleep(0.1)
                return clock.now()
        """
        assert codes_at(src, "apex_tpu/serving/foo.py", "APX011") == []


class TestAPX012CounterBypass:
    def test_positive_bare_paired_counter(self):
        src = """
            def retire(self, rid):
                self.metrics.inc("replica_scale_downs")
        """
        got = codes_at(src, "apex_tpu/serving/fleet/foo.py", "APX012")
        assert got == ["APX012"]

    def test_negative_event_co_sited(self):
        src = """
            def retire(self, rid):
                self.metrics.inc("replica_scale_downs")
                self.metrics.event("replica_scale_down", replica_id=rid)
        """
        assert codes_at(src, "apex_tpu/serving/fleet/foo.py",
                        "APX012") == []

    def test_negative_unpaired_counter_is_fine(self):
        # dispatch counters are deliberately high-frequency/unpaired
        src = """
            def dispatch(self):
                self.metrics.inc("fleet_dispatches")
        """
        assert codes_at(src, "apex_tpu/serving/fleet/foo.py",
                        "APX012") == []

    def test_negative_outside_serving(self):
        src = """
            def retire(self):
                self.metrics.inc("replica_scale_downs")
        """
        assert codes_at(src, "apex_tpu/loadtest/foo.py", "APX012") == []

    def test_rule_set_matches_mc_invariants(self):
        # the lint rule and the runtime invariant must police the same
        # counter<->event pairs
        from apex_tpu.analysis.rules.apx012_counter_bypass import (
            _PAIRED_COUNTERS,
        )
        inv = pytest.importorskip("apex_tpu.analysis.mc.invariants")
        assert _PAIRED_COUNTERS == frozenset(inv.COUNTER_EVENTS)


class TestAPX013TriggerTable:
    """Every ``*_INCIDENT_COUNTERS`` key in the monitor must be a
    flight-recorder trigger — an incident the monitor reconciles but
    the recorder sleeps through leaves no postmortem."""

    def test_positive_ghost_incident_event(self):
        src = """
            GHOST_INCIDENT_COUNTERS = {
                "foo_melted": "foo_meltdowns",
            }
        """
        got = codes_at(src, "apex_tpu/observability/report.py",
                       "APX013")
        assert got == ["APX013"]

    def test_negative_real_trigger_events_pass(self):
        src = """
            SERVING_INCIDENT_COUNTERS = {
                "engine_restart": "engine_restarts",
                "tick_failure": "tick_failures",
            }
        """
        assert codes_at(src, "apex_tpu/observability/report.py",
                        "APX013") == []

    def test_negative_non_incident_maps_ignored(self):
        # only *_INCIDENT_COUNTERS assignments are the contract; other
        # dicts (shed reasons, render tables) may name non-triggers
        src = """
            SERVING_SHED_COUNTERS = {
                "queue_full": "requests_shed_queue_full",
            }
        """
        assert codes_at(src, "apex_tpu/observability/report.py",
                        "APX013") == []

    def test_negative_scoped_to_monitor_module(self):
        src = """
            MY_INCIDENT_COUNTERS = {"foo_melted": "x"}
        """
        assert codes_at(src, "apex_tpu/serving/foo.py", "APX013") == []

    def test_real_tree_is_clean(self):
        """The committed monitor module passes its own lint — the
        recorder builds TRIGGER_EVENTS from these maps by
        construction."""
        import apex_tpu.observability.report as report_mod
        with open(report_mod.__file__, encoding="utf-8") as f:
            src = f.read()
        rules = [r for r in all_rules() if r.code == "APX013"]
        found = analyze_source(
            src, "apex_tpu/observability/report.py", rules)
        assert [f.code for f in found] == []


# ---------------------------------------------------------------------------
# suppression, baseline, config, CLI
# ---------------------------------------------------------------------------

REUSE_SRC = """
import jax
def sample(key):
    a = jax.random.normal(key, (3,))
    b = jax.random.uniform(key, (3,))%s
    return a + b
"""


class TestSuppression:
    def test_noqa_specific_code(self):
        assert codes(REUSE_SRC % "  # noqa: APX001") == []

    def test_noqa_bare(self):
        assert codes(REUSE_SRC % "  # noqa") == []

    def test_noqa_other_code_does_not_suppress(self):
        assert codes(REUSE_SRC % "  # noqa: APX005") == ["APX001"]

    def test_noqa_multiple_codes(self):
        assert codes(REUSE_SRC % "  # noqa: APX005, APX001") == []


class TestBaseline:
    def _findings(self):
        from apex_tpu.analysis.engine import analyze_source
        return analyze_source(REUSE_SRC % "", "pkg/mod.py")

    def test_partition_matches_and_news(self):
        found = self._findings()
        bl = Baseline([{"path": "pkg/mod.py", "code": "APX001",
                        "snippet": found[0].snippet,
                        "justification": "known"}])
        new, matched, stale = bl.partition(found)
        assert new == [] and len(matched) == 1 and stale == []

    def test_unmatched_finding_is_new(self):
        found = self._findings()
        bl = Baseline([{"path": "other.py", "code": "APX001",
                        "snippet": found[0].snippet,
                        "justification": "known"}])
        new, matched, stale = bl.partition(found)
        assert len(new) == 1 and matched == [] and len(stale) == 1

    def test_snippet_keying_survives_line_drift(self):
        found = self._findings()
        bl = Baseline([{"path": "pkg/mod.py", "code": "APX001",
                        "line": 9999,  # wrong line: snippet still matches
                        "snippet": found[0].snippet,
                        "justification": "known"}])
        new, _, _ = bl.partition(found)
        assert new == []

    def test_roundtrip_save_load(self, tmp_path):
        found = self._findings()
        bl = Baseline.from_findings(found)
        p = tmp_path / "bl.json"
        bl.save(str(p))
        loaded = Baseline.load(str(p))
        # fresh from --write-baseline: placeholder justification, so the
        # entry does NOT yet suppress (the gate stays red until edited)
        new, _, _ = loaded.partition(found)
        assert len(new) == 1
        assert loaded.unjustified_entries() == loaded.entries
        for e in loaded.entries:    # ...the human step
            e["justification"] = "deliberate in this fixture"
        loaded.save(str(p))
        loaded = Baseline.load(str(p))
        new, matched, stale = loaded.partition(found)
        assert new == [] and len(matched) == 1 and stale == []
        assert all("justification" in e for e in loaded.entries)


class TestConfigAndCLI:
    def _project(self, tmp_path, extra=""):
        (tmp_path / "pyproject.toml").write_text(textwrap.dedent(f"""
            [project]
            name = "demo"

            [tool.apex_tpu.analysis]
            paths = ["pkg"]
            baseline = "bl.json"
            exclude = ["skipme"]
            {extra}
        """))
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "mod.py").write_text(REUSE_SRC % "")
        (pkg / "skipme.py").write_text(REUSE_SRC % "")
        return tmp_path

    def test_load_config_walks_up(self, tmp_path):
        root = self._project(tmp_path)
        cfg = load_config(str(root / "pkg" / "mod.py"))
        assert cfg.paths == ["pkg"]
        assert cfg.baseline == "bl.json"
        assert cfg.exclude == ["skipme"]
        assert cfg.root == str(root)

    def test_cli_reports_and_exits_nonzero(self, tmp_path, capsys):
        root = self._project(tmp_path)
        rc = cli_main([str(root / "pkg")])
        out = capsys.readouterr().out
        assert rc == 1
        assert "APX001" in out and "skipme" not in out

    @staticmethod
    def _justify(root):
        """The human step after ``--write-baseline``: replace the
        placeholder justifications with a real reason."""
        p = root / "bl.json"
        data = json.loads(p.read_text())
        for e in data["entries"]:
            e["justification"] = "deliberate in this fixture"
        p.write_text(json.dumps(data))

    def test_cli_write_baseline_then_clean(self, tmp_path, capsys):
        root = self._project(tmp_path)
        rc = cli_main([str(root / "pkg"), "--write-baseline"])
        assert rc == 0
        assert json.loads((root / "bl.json").read_text())["entries"]
        # placeholder justifications do not suppress: still red, with
        # the unjustified entry called out on stderr
        rc = cli_main([str(root / "pkg")])
        captured = capsys.readouterr()
        assert rc == 1
        assert "justification" in captured.err
        self._justify(root)
        rc = cli_main([str(root / "pkg")])
        out = capsys.readouterr().out
        assert rc == 0
        assert "1 baselined" in out

    def test_cli_stale_entry_reported(self, tmp_path, capsys):
        root = self._project(tmp_path)
        cli_main([str(root / "pkg"), "--write-baseline"])
        self._justify(root)
        (root / "pkg" / "mod.py").write_text("x = 1\n")
        rc = cli_main([str(root / "pkg")])
        err = capsys.readouterr().err
        assert rc == 0
        assert "stale" in err

    def test_cli_select_disable(self, tmp_path, capsys):
        root = self._project(tmp_path)
        assert cli_main([str(root / "pkg"), "--disable", "APX001"]) == 0
        assert cli_main([str(root / "pkg"), "--select", "APX005"]) == 0
        assert cli_main([str(root / "pkg"), "--select", "APX001"]) == 1
        capsys.readouterr()

    def test_syntax_error_is_finding_not_crash(self, tmp_path, capsys):
        root = self._project(tmp_path)
        (root / "pkg" / "broken.py").write_text("def f(:\n")
        rc = cli_main([str(root / "pkg")])
        out = capsys.readouterr().out
        assert rc == 1 and "APX000" in out

    def test_cli_prune_baseline_drops_dead_entries(self, tmp_path, capsys):
        root = self._project(tmp_path)
        cli_main([str(root / "pkg"), "--write-baseline"])
        self._justify(root)
        # fix the offending code: the baseline entry is now dead weight
        (root / "pkg" / "mod.py").write_text("x = 1\n")
        rc = cli_main([str(root / "pkg"), "--prune-baseline"])
        captured = capsys.readouterr()
        assert rc == 0
        assert "pruned 1 stale baseline entry (0 kept)" in captured.out
        assert json.loads((root / "bl.json").read_text())["entries"] == []
        # and the stale warning is gone on the next normal run
        rc = cli_main([str(root / "pkg")])
        assert rc == 0 and "stale" not in capsys.readouterr().err

    def test_cli_prune_keeps_live_entries(self, tmp_path, capsys):
        root = self._project(tmp_path)
        cli_main([str(root / "pkg"), "--write-baseline"])
        self._justify(root)
        rc = cli_main([str(root / "pkg"), "--prune-baseline"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "pruned 0 stale" in out and "(1 kept)" in out
        assert len(json.loads(
            (root / "bl.json").read_text())["entries"]) == 1

    def test_cli_prune_without_baseline_file_is_usage_error(
            self, tmp_path, capsys):
        root = self._project(tmp_path)   # bl.json configured, not written
        rc = cli_main([str(root / "pkg"), "--prune-baseline"])
        captured = capsys.readouterr()
        assert rc == 2
        assert "no baseline file to prune" in captured.err


class TestTomlReader:
    """``_read_toml_table`` prefers stdlib tomllib (py3.11+) and falls
    back to the mini reader on 3.10 — whose documented gap is backslash
    escapes in basic strings (returned verbatim, not decoded)."""

    def _table(self, tmp_path, body):
        from apex_tpu.analysis.engine import _read_toml_table
        p = tmp_path / "pyproject.toml"
        p.write_text("[tool.apex_tpu.analysis]\n" + textwrap.dedent(body))
        return _read_toml_table(str(p), "tool.apex_tpu.analysis")

    def test_plain_values_agree_across_readers(self, tmp_path):
        table = self._table(tmp_path, """\
            paths = ["pkg", "tools"]
            baseline = "bl.json"
            exclude = []
        """)
        assert table == {"paths": ["pkg", "tools"],
                         "baseline": "bl.json", "exclude": []}

    def test_escaped_string_values(self, tmp_path):
        # TOML basic strings decode \\t to a TAB; the mini reader does
        # not decode escapes — this test pins the divergence down so
        # config values stay escape-free until the gap matters
        table = self._table(tmp_path, 'baseline = "bl\\tname.json"\n')
        try:
            import tomllib  # noqa: F401  (py3.11+: the real parser)
            assert table["baseline"] == "bl\tname.json"
        except ImportError:
            assert table["baseline"] == "bl\\tname.json"

    def test_missing_file_and_table_are_empty(self, tmp_path):
        from apex_tpu.analysis.engine import _read_toml_table
        assert _read_toml_table(str(tmp_path / "nope.toml"),
                                "tool.apex_tpu.analysis") == {}
        assert self._table(tmp_path, "") == {}


# ---------------------------------------------------------------------------
# log_event ordering stamps (satellite: seq + monotonic ts)
# ---------------------------------------------------------------------------

class TestLogEventStamps:
    def test_seq_and_ts_present_and_monotonic(self):
        from apex_tpu.utils.logging import get_logger, log_event
        log = get_logger("apex_tpu.test_stamps")
        log.setLevel(logging.CRITICAL)  # keep output quiet
        lines = [log_event(log, "retrace", fn="step", call=i)
                 for i in range(3)]
        seqs, tss = [], []
        for line in lines:
            fields = dict(kv.split("=", 1) for kv in line.split()
                          if "=" in kv)
            assert fields["event"] == "retrace"
            seqs.append(int(fields["seq"]))
            tss.append(float(fields["ts"]))
        assert seqs == sorted(seqs) and len(set(seqs)) == 3
        assert tss == sorted(tss)

    def test_wall_stamp_is_epoch_time(self):
        # wall= (time.time()) rides next to the monotonic ts= so events
        # from different processes/hosts can be correlated; ts stays the
        # rate-measurement stamp (immune to clock steps)
        import time as _time

        from apex_tpu.utils.logging import get_logger, log_event
        log = get_logger("apex_tpu.test_stamps")
        log.setLevel(logging.CRITICAL)
        before = _time.time()
        line = log_event(log, "retrace", fn="step", call=0)
        after = _time.time()
        fields = dict(kv.split("=", 1) for kv in line.split() if "=" in kv)
        assert before <= float(fields["wall"]) <= after
        assert "ts" in fields  # monotonic stamp kept alongside


# ---------------------------------------------------------------------------
# retrace watchdog
# ---------------------------------------------------------------------------

class TestRetraceWatchdog:
    def test_stable_shapes_stay_silent(self):
        f = jax.jit(lambda x: x * 2)
        wd = RetraceWatchdog(f, budget=0)
        for _ in range(5):
            wd(jnp.ones((4,)))
        assert wd.retraces == 0 and wd.compiles == 1 and wd.calls == 5

    def test_budget_fires_on_forced_recompiles(self):
        f = jax.jit(lambda x: x * 2)
        wd = RetraceWatchdog(f, budget=2)
        with pytest.raises(RetraceBudgetExceeded) as exc:
            for n in range(2, 10):
                wd(jnp.ones((n,)))  # every call a new shape = a retrace
        assert exc.value.retraces == 3 and exc.value.budget == 2

    def test_log_only_when_budget_none(self):
        f = jax.jit(lambda x: x + 1)
        wd = RetraceWatchdog(f, budget=None)
        for n in range(2, 8):
            wd(jnp.ones((n,)))
        assert wd.retraces == 5  # counted, never raised

    def test_prewarmed_cache_is_baselined(self):
        f = jax.jit(lambda x: x - 1)
        f(jnp.ones((3,)))  # compile before the watchdog watches
        wd = RetraceWatchdog(f, budget=0)
        wd(jnp.ones((3,)))
        assert wd.compiles == 0 and wd.retraces == 0

    def test_signature_fallback_for_plain_callables(self):
        calls = []

        def plain(x):
            calls.append(x.shape)
            return x

        wd = RetraceWatchdog(plain, budget=2)
        wd(jnp.ones((2,)))
        wd(jnp.ones((2,)))
        assert wd.compiles == 1  # same signature, one "trace"
        with pytest.raises(RetraceBudgetExceeded):
            for n in range(3, 10):
                wd(jnp.ones((n,)))

    def test_dtype_change_counts_as_retrace(self):
        f = jax.jit(lambda x: x * 1)
        wd = RetraceWatchdog(f, budget=None)
        wd(jnp.ones((4,), jnp.float32))
        wd(jnp.ones((4,), jnp.bfloat16))
        assert wd.retraces == 1

    def test_calls_that_may_trace_get_stack_room(self):
        """While compiles are expected the callable runs under the
        roomy frame; once they are all in, the call is direct."""
        import sys

        callers = []

        def plain(x, scale=1):
            callers.append(sys._getframe(1).f_code.co_name)
            return x * scale

        wd = RetraceWatchdog(plain, budget=None, expected_compiles=2)
        assert float(wd(jnp.ones(()), scale=3)) == 3.0
        wd(jnp.ones((2,)))
        wd(jnp.ones((2,)))
        assert callers == ["_call_with_stack_room"] * 2 + ["__call__"]
        assert wd.compiles == 2 and wd.calls == 3 and wd.retraces == 0

    def test_stack_room_takes_the_page_faults_out_of_deep_loops(self):
        """The fault the frame is there for: at some depth a loop's
        callees fall beyond the end of a block of the interpreter's frame
        stack and every call faults a page in. From the roomy frame no
        depth does."""
        import resource

        from apex_tpu.analysis.retrace import _call_with_stack_room

        def leaf():
            return 0

        def loop():
            before = resource.getrusage(resource.RUSAGE_THREAD).ru_minflt
            for _ in range(2000):
                leaf()
            return resource.getrusage(resource.RUSAGE_THREAD).ru_minflt \
                - before

        def at_depth(n):
            return loop() if n == 0 else at_depth(n - 1)

        def worst():
            return max(at_depth(d) for d in range(300))

        if worst() < 1000:
            pytest.skip("this interpreter's frame stack has no such edge")
        assert _call_with_stack_room(worst, (), {}) < 200


class TestRunTrainingRetraceIntegration:
    def _step(self):
        @jax.jit
        def step(state, batch, rng):
            new = {"params": state["params"] - 0.1 * batch.mean(),
                   "step": state["step"] + 1}
            return new, {"loss": batch.mean(), "skipped": jnp.asarray(False)}
        return step

    def test_ragged_batches_trip_budget(self):
        from apex_tpu.resilience import ResilienceConfig, run_training
        state = {"params": jnp.zeros(()), "step": jnp.asarray(0, jnp.int32)}
        cfg = ResilienceConfig(retrace_budget=2, handle_sigterm=False,
                               poll_interval_steps=100)
        with pytest.raises(RetraceBudgetExceeded):
            # a ragged data pipeline: every step a new batch shape
            run_training(self._step(), state,
                         lambda step: jnp.ones((step + 2,)),
                         num_steps=10, config=cfg)

    def test_stable_run_reports_zero_retraces(self):
        from apex_tpu.resilience import ResilienceConfig, run_training
        state = {"params": jnp.zeros(()), "step": jnp.asarray(0, jnp.int32)}
        cfg = ResilienceConfig(retrace_budget=2, handle_sigterm=False,
                               poll_interval_steps=4)
        res = run_training(self._step(), state,
                           lambda step: jnp.ones((8,)),
                           num_steps=6, config=cfg)
        assert res.status == "completed"
        assert res.telemetry["retraces"] == 0

    def test_watchdog_disabled_with_none(self):
        from apex_tpu.resilience import ResilienceConfig, run_training
        state = {"params": jnp.zeros(()), "step": jnp.asarray(0, jnp.int32)}
        cfg = ResilienceConfig(retrace_budget=None, handle_sigterm=False,
                               poll_interval_steps=4)
        res = run_training(self._step(), state,
                           lambda step: jnp.ones((step + 2,)),
                           num_steps=5, config=cfg)
        assert res.status == "completed"  # slow, but allowed when opted out
