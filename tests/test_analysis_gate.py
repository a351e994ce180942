"""Tier-1 hazard gate: the lint engine runs over the whole configured
tree and fails on any finding that is neither ``# noqa``-suppressed nor
recorded (with a justification) in the committed baseline — so JAX
hazards are caught by the same ``pytest -m 'not slow'`` invocation that
runs everything else, with no new CI infrastructure.

Also enforces the slow-tier marker discipline that PR 1's budget
regression motivated: test modules importing the compile-heavy
interpret-mode pallas models must carry ``slow`` markers (or sit on the
reviewed cheap-usage allowlist below), so the tier-1 wall clock cannot
quietly re-absorb the multi-layer parity suites.
"""

import ast
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from apex_tpu.analysis import (
    Baseline,
    Finding,
    analyze_paths,
    load_config,
)
from apex_tpu.analysis.engine import PLACEHOLDER_JUSTIFICATION

REPO_ROOT = Path(__file__).resolve().parents[1]
PYPROJECT = REPO_ROOT / "pyproject.toml"

#: model-importing test modules reviewed as tier-1-cheap (small configs /
#: single layers; measured ~2 min combined on CPU, inside the 870 s
#: budget). A NEW module importing apex_tpu.models with no slow markers
#: must either be added here after review or mark its heavy tests.
CHEAP_MODEL_TEST_MODULES = {
    "test_context_parallel.py",
    "test_data_pipeline.py",
    "test_gqa.py",
    "test_imports.py",
    "test_moe.py",
    # 2-layer hidden-32 engine and train step, one compile each, reused
    # by every test of the module: 24 s for the whole file (PR 26)
    "test_tick_spans.py",
    "test_trace_fleet.py",
    # PR 30: a 4-layer hidden-64 mixed model against its plain reference,
    # every forward jitted once (55 s), and the routed layer at hidden 32
    # (45 s); ISSUE 30 asks for them in tier-1
    "test_afmoe.py",
    "test_routed_experts.py",
    # PR 33: the in-flight decode scenarios on a 2-layer hidden-32 engine,
    # tick by tick (18 tests, 57 s for the file); ISSUE 33 asks for them
    # in tier-1
    "test_decode_in_flight.py",
    # PR 34: a 3-layer hidden-64 latent-attention model against its plain
    # reference, every path jitted once (41 tests, 70 s for the file);
    # ISSUE 34 asks for them in tier-1
    "test_dots_vlm.py",
    # not a test module: the per-request reference the serving tests
    # import (one jitted prefill and one decode step a request)
    "serving_reference.py",
}


def _config():
    cfg = load_config(pyproject=str(PYPROJECT))
    assert cfg.baseline, "pyproject [tool.apex_tpu.analysis] lost baseline"
    return cfg


class TestHazardGate:
    # full-tree scans + a subprocess entrypoint run: the three heavy
    # gate tests are slow-tier per the ROADMAP tier policy (they still
    # gate nightly; the targeted unit tests below stay tier-1)
    @pytest.mark.slow
    def test_tree_has_no_unbaselined_findings(self):
        cfg = _config()
        findings = analyze_paths(
            [str(REPO_ROOT / p) for p in cfg.paths], cfg)
        bl = Baseline.load(str(REPO_ROOT / cfg.baseline))
        new, _, _ = bl.partition(findings)
        assert not new, (
            "new JAX-hazard findings (fix them, add `# noqa: APX###` "
            "with cause, or baseline with a justification — see "
            "docs/analysis.md):\n" + "\n".join(f.render() for f in new))

    @pytest.mark.slow
    def test_baseline_is_fresh_and_justified(self):
        cfg = _config()
        findings = analyze_paths(
            [str(REPO_ROOT / p) for p in cfg.paths], cfg)
        bl = Baseline.load(str(REPO_ROOT / cfg.baseline))
        _, _, stale = bl.partition(findings)
        assert not stale, (
            "stale baseline entries (the hazard was fixed — drop its "
            "ledger line):\n" + "\n".join(str(e) for e in stale))
        unjustified = [e for e in bl.entries
                       if not str(e.get("justification", "")).strip()
                       or "TODO" in str(e.get("justification", ""))]
        assert not unjustified, (
            "baseline entries need a real one-line justification:\n"
            + "\n".join(str(e) for e in unjustified))

    def test_placeholder_justification_does_not_suppress(self):
        """A baseline entry still carrying the ``--write-baseline``
        placeholder (or a blank justification) must NOT suppress its
        finding — the gate stays red until a human writes the reason."""
        finding = Finding(code="APX001", message="m",
                          path="pkg/mod.py", line=3, col=0,
                          snippet="jax.random.normal(key)")
        entry = {"path": "pkg/mod.py", "code": "APX001", "line": 3,
                 "snippet": "jax.random.normal(key)"}
        for bad in (PLACEHOLDER_JUSTIFICATION,
                    f"{PLACEHOLDER_JUSTIFICATION} later", "", "   ", None):
            bl = Baseline([{**entry, "justification": bad}])
            new, matched, stale = bl.partition([finding])
            assert new == [finding] and not matched and not stale, (
                f"justification {bad!r} suppressed the finding")
            assert bl.unjustified_entries() == bl.entries
        # the same entry with a real justification does suppress it
        bl = Baseline([{**entry,
                        "justification": "deliberate: test fixture"}])
        new, matched, stale = bl.partition([finding])
        assert not new and matched == [finding] and not stale
        assert bl.unjustified_entries() == []

    def test_write_baseline_output_is_rejected_until_edited(self):
        """``Baseline.from_findings`` (what ``--write-baseline`` saves)
        stamps the placeholder, so a freshly written baseline cannot
        silently green the gate."""
        finding = Finding(code="APX002", message="m", path="a.py",
                          line=1, col=0, snippet="x")
        bl = Baseline.from_findings([finding])
        assert bl.unjustified_entries() == bl.entries
        new, _, _ = bl.partition([finding])
        assert new == [finding]

    @pytest.mark.slow
    def test_module_entrypoint_runs_clean(self):
        """``python -m apex_tpu.analysis`` exits 0 on the committed tree
        (the acceptance criterion, exercised through the real CLI)."""
        proc = subprocess.run(
            [sys.executable, "-m", "apex_tpu.analysis"],
            cwd=str(REPO_ROOT), capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
        assert proc.returncode == 0, (
            f"linter found new hazards:\n{proc.stdout}\n{proc.stderr}")

    def test_console_script_registered(self):
        text = PYPROJECT.read_text()
        assert "apex-tpu-analysis" in text and \
            "apex_tpu.analysis.engine:main" in text


class TestSlowTierDiscipline:
    @staticmethod
    def _imports_models(tree: ast.AST) -> bool:
        for n in ast.walk(tree):
            if isinstance(n, ast.ImportFrom) and n.module and \
                    n.module.startswith("apex_tpu.models"):
                return True
            if isinstance(n, ast.Import) and any(
                    a.name.startswith("apex_tpu.models")
                    for a in n.names):
                return True
        return False

    @staticmethod
    def _has_any_slow_marker(tree: ast.AST) -> bool:
        return any(
            isinstance(n, (ast.Attribute, ast.Name))
            and getattr(n, "attr", getattr(n, "id", "")) == "slow"
            for n in ast.walk(tree))

    def test_model_importing_modules_carry_slow_markers(self):
        violations = []
        for path in sorted((REPO_ROOT / "tests").glob("*.py")):
            tree = ast.parse(path.read_text())
            if not self._imports_models(tree):
                continue
            if path.name in CHEAP_MODEL_TEST_MODULES:
                continue
            if not self._has_any_slow_marker(tree):
                violations.append(path.name)
        assert not violations, (
            f"test modules importing apex_tpu.models (interpret-mode "
            f"pallas multi-layer fixtures) without any @pytest.mark.slow: "
            f"{violations} — mark the compile-bound tests slow, or review "
            f"and add to CHEAP_MODEL_TEST_MODULES")

    def test_parity_and_convergence_tests_are_slow(self):
        """The specific shape of the PR 1 regression: multi-layer
        model-parity / convergence sweeps in the quick tier."""
        pat = re.compile(r"parity|convergence")
        violations = []
        for path in sorted((REPO_ROOT / "tests").glob("*.py")):
            if path.name == "test_analysis_gate.py":
                continue
            tree = ast.parse(path.read_text())
            if not self._imports_models(tree):
                continue
            module_slow = any(
                isinstance(n, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "pytestmark"
                    for t in n.targets)
                and "slow" in ast.dump(n.value)
                for n in tree.body)

            def deco_slow(deco_list):
                return any("slow" in ast.dump(d) for d in deco_list)

            def check(body, inherited):
                for n in body:
                    if isinstance(n, ast.ClassDef):
                        check(n.body,
                              inherited or deco_slow(n.decorator_list))
                    elif isinstance(n, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)) and \
                            n.name.startswith("test") and \
                            pat.search(n.name):
                        if not (module_slow or inherited
                                or deco_slow(n.decorator_list)):
                            violations.append(
                                f"{path.name}::{n.name}")
            check(tree.body, False)
        assert not violations, (
            f"parity/convergence tests over apex_tpu.models outside the "
            f"slow tier: {violations}")
