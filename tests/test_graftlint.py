"""graftlint (lightgbm_tpu/analysis/) test suite.

Fixture-based: every rule's known-bad/known-good snippet pairs replay
through the full engine in throwaway tmp-dir projects (no repo
mutation), plus the contracts the linter itself rests on — the live
tree is clean modulo the committed baseline, pragmas beat baselines,
the baseline demands justifications, the journal-schema extraction
matches the runtime SCHEMA, and the prometheus-naming rule really is
the runtime ``lint_family_name`` (one implementation, satellite of
ISSUE 15).
"""

import json
import os
import subprocess
import sys

import pytest

from lightgbm_tpu.analysis import (REGISTRY, Baseline, Severity,
                                   lint_project, load_rules)
from lightgbm_tpu.analysis.baseline import BaselineError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

load_rules()


def write_project(tmp_path, files):
    for rel, text in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)
    return str(tmp_path)


def rule_fixture_params():
    params = []
    for name in sorted(REGISTRY):
        for fx in REGISTRY[name].fixtures():
            params.append(pytest.param(name, fx, id=f"{name}-{fx.name}"))
    return params


# ------------------------------------------------------ fixture corpus

@pytest.mark.parametrize("rule_name,fx", rule_fixture_params())
def test_rule_fixture(tmp_path, rule_name, fx):
    root = write_project(tmp_path, fx.files)
    result = lint_project(root, rule_names=[rule_name],
                          use_baseline=False)
    got = [v for v in result.violations if v.rule == rule_name]
    assert len(got) == fx.expect, \
        f"{rule_name}/{fx.name}: {[v.format() for v in got]}"


def test_every_rule_ships_bad_and_good_fixtures():
    """A rule without a known-bad fixture can silently stop firing; one
    without a known-good fixture can silently flag everything."""
    for name, rule in REGISTRY.items():
        fixtures = rule.fixtures()
        assert any(fx.expect > 0 for fx in fixtures), \
            f"{name} has no known-bad fixture"
        assert any(fx.expect == 0 for fx in fixtures), \
            f"{name} has no known-good fixture"


def test_issue_rule_set_complete():
    expected = {"unguarded-collective", "non-atomic-shared-write",
                "precision-contract", "nondeterminism", "journal-schema",
                "prometheus-naming", "config-doc-drift"}
    assert expected <= set(REGISTRY)


# ------------------------------------------------------------ live tree

def test_live_tree_clean_modulo_baseline():
    result = lint_project(REPO)
    assert not result.parse_errors, result.parse_errors
    msgs = [v.format() for v in result.violations
            if v.severity == Severity.ERROR]
    assert msgs == [], "\n".join(msgs)
    # and the committed baseline carries no dead entries
    assert result.baseline_unused == [], result.baseline_unused


def test_live_tree_runs_fast():
    result = lint_project(REPO)
    assert result.elapsed_s < 10.0, \
        f"lint took {result.elapsed_s:.1f}s (bar: 10s)"
    assert result.files > 100   # really walked the tree


# ------------------------------------------- pragma/baseline precedence

_BAD_SYNC = (
    "import jax\n"
    "def fetch(out):\n"
    "    return jax.device_get(out)\n"
)


def test_pragma_suppresses_same_and_previous_line(tmp_path):
    inline = _BAD_SYNC.replace(
        "return jax.device_get(out)",
        "return jax.device_get(out)  "
        "# graftlint: disable=unguarded-collective")
    above = _BAD_SYNC.replace(
        "    return jax.device_get(out)",
        "    # graftlint: disable=unguarded-collective\n"
        "    return jax.device_get(out)")
    for src in (inline, above):
        root = write_project(tmp_path, {
            "lightgbm_tpu/parallel/x.py": src})
        result = lint_project(root, use_baseline=False)
        assert [v.rule for v in result.violations] == []
        sup = [v for v in result.suppressed
               if v.rule == "unguarded-collective"]
        assert len(sup) == 1 and sup[0].suppressed_by == "pragma"


def test_pragma_for_other_rule_does_not_suppress(tmp_path):
    src = _BAD_SYNC.replace(
        "return jax.device_get(out)",
        "return jax.device_get(out)  # graftlint: disable=nondeterminism")
    root = write_project(tmp_path, {"lightgbm_tpu/parallel/x.py": src})
    result = lint_project(root, use_baseline=False)
    assert [v.rule for v in result.violations] == ["unguarded-collective"]


def test_baseline_suppresses_by_line_content(tmp_path):
    root = write_project(tmp_path, {
        "lightgbm_tpu/parallel/x.py": _BAD_SYNC,
        "tools/lint_baseline.json": json.dumps({
            "version": 1,
            "entries": [{"rule": "unguarded-collective",
                         "file": "lightgbm_tpu/parallel/x.py",
                         "line_text": "return jax.device_get(out)",
                         "justification": "test entry"}]})})
    result = lint_project(root)
    assert result.violations == []
    assert [v.suppressed_by for v in result.suppressed] == ["baseline"]
    assert result.baseline_unused == []


def test_pragma_wins_over_baseline_and_entry_reports_unused(tmp_path):
    """Precedence: pragma first — the baseline entry then shows up as
    unused instead of silently double-covering."""
    src = _BAD_SYNC.replace(
        "return jax.device_get(out)",
        "return jax.device_get(out)  "
        "# graftlint: disable=unguarded-collective")
    root = write_project(tmp_path, {
        "lightgbm_tpu/parallel/x.py": src,
        "tools/lint_baseline.json": json.dumps({
            "version": 1,
            "entries": [{"rule": "unguarded-collective",
                         "file": "lightgbm_tpu/parallel/x.py",
                         "line_text": ("return jax.device_get(out)  "
                                       "# graftlint: disable="
                                       "unguarded-collective"),
                         "justification": "now redundant"}]})})
    result = lint_project(root)
    assert result.violations == []
    assert [v.suppressed_by for v in result.suppressed] == ["pragma"]
    assert len(result.baseline_unused) == 1


def test_baseline_without_justification_is_fatal(tmp_path):
    root = write_project(tmp_path, {
        "lightgbm_tpu/parallel/x.py": _BAD_SYNC,
        "tools/lint_baseline.json": json.dumps({
            "version": 1,
            "entries": [{"rule": "unguarded-collective",
                         "file": "lightgbm_tpu/parallel/x.py",
                         "line_text": "return jax.device_get(out)",
                         "justification": "   "}]})})
    with pytest.raises(BaselineError):
        lint_project(root)


def test_baseline_placeholder_justification_is_fatal(tmp_path):
    root = write_project(tmp_path, {
        "lightgbm_tpu/parallel/x.py": _BAD_SYNC,
        "tools/lint_baseline.json": json.dumps({
            "version": 1,
            "entries": [{"rule": "unguarded-collective",
                         "file": "lightgbm_tpu/parallel/x.py",
                         "line_text": "return jax.device_get(out)",
                         "justification": "FIXME: justify or fix"}]})})
    with pytest.raises(BaselineError):
        lint_project(root)


def test_baseline_render_preserves_justifications(tmp_path):
    root = write_project(tmp_path, {"lightgbm_tpu/parallel/x.py":
                                    _BAD_SYNC})
    result = lint_project(root, use_baseline=False)
    old = Baseline([{"rule": "unguarded-collective",
                     "file": "lightgbm_tpu/parallel/x.py",
                     "line_text": "return jax.device_get(out)",
                     "justification": "kept on purpose"}])
    text = Baseline.render(result.violations, old)
    data = json.loads(text)
    assert data["entries"][0]["justification"] == "kept on purpose"


# --------------------------------------------- single-source contracts

def test_journal_schema_extraction_matches_runtime():
    """The static rule reads SCHEMA by AST; the runtime lint imports
    it. Both must see the same record types or one of them lies."""
    from lightgbm_tpu.analysis.core import Project
    from lightgbm_tpu.analysis.rules.journal_schema import (
        JOURNAL_REL, extract_schema_keys)
    from lightgbm_tpu.telemetry import journal
    proj = Project(REPO, scope_dirs=("lightgbm_tpu/telemetry",),
                   scope_files=())
    pf = proj.get(JOURNAL_REL)
    assert pf is not None
    assert extract_schema_keys(pf) == set(journal.SCHEMA)


def test_prometheus_rule_uses_runtime_lint_implementation():
    """Satellite: telemetry/prometheus.py lint_family_name is THE
    single naming-contract implementation — the static rule's loaded
    copy must behave identically on both sides of the contract, and
    lint_names must delegate to it."""
    from lightgbm_tpu.analysis.rules import prom_naming
    from lightgbm_tpu.telemetry import prometheus
    loaded = prom_naming._prometheus()
    for name, kind in [("lightgbm_tpu_sync_wait_s", "gauge"),
                       ("lightgbm_tpu_request_millis", "summary"),
                       ("lightgbm_tpu_swap", "counter"),
                       ("lightgbm_tpu_ok_total", "counter"),
                       ("bad_prefix_total", "counter"),
                       ("lightgbm_tpu_ok_ratio", "gauge")]:
        assert loaded.lint_family_name(name, kind) == \
            prometheus.lint_family_name(name, kind)
    # and the page-level audit really delegates per family
    page = "# TYPE lightgbm_tpu_x_ms gauge\nlightgbm_tpu_x_ms 1\n"
    assert prometheus.lint_names(page) == [
        "line 2: " + v
        for v in prometheus.lint_family_name("lightgbm_tpu_x_ms",
                                             "gauge")]


# ---------------------------------------------------------------- CLI

def test_cli_json_and_exit_codes(tmp_path):
    bad_root = write_project(tmp_path / "bad",
                             {"lightgbm_tpu/parallel/x.py": _BAD_SYNC})
    clean_root = write_project(tmp_path / "clean", {
        "lightgbm_tpu/parallel/x.py": "def ok():\n    return 1\n"})
    tool = os.path.join(REPO, "tools", "graftlint.py")
    out_json = tmp_path / "report.json"

    r = subprocess.run([sys.executable, tool, bad_root,
                        "--json", str(out_json)],
                       capture_output=True, text=True)
    assert r.returncode == 1, r.stdout + r.stderr
    assert "unguarded-collective" in r.stdout
    data = json.loads(out_json.read_text())
    assert data["error_count"] == 1
    assert data["violations"][0]["rule"] == "unguarded-collective"
    assert data["violations"][0]["file"] == "lightgbm_tpu/parallel/x.py"

    r = subprocess.run([sys.executable, tool, clean_root],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_self_check():
    tool = os.path.join(REPO, "tools", "graftlint.py")
    r = subprocess.run([sys.executable, tool, "--self-check"],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK" in r.stdout


def test_cli_shim_never_imports_jax():
    """tools/graftlint.py exists so the CI gate doesn't pay (or depend
    on) the accelerator runtime."""
    tool = os.path.join(REPO, "tools", "graftlint.py")
    code = ("import sys, runpy\n"
            f"sys.argv = ['graftlint', '--list-rules']\n"
            f"runpy.run_path({tool!r}, run_name='__main__')\n")
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    check = ("import sys, runpy\n"
             f"sys.argv = ['graftlint', '--list-rules']\n"
             "try:\n"
             f"    runpy.run_path({tool!r}, run_name='__main__')\n"
             "except SystemExit:\n"
             "    pass\n"
             "assert 'jax' not in sys.modules, 'shim imported jax'\n"
             "print('nojax-ok')\n")
    r = subprocess.run([sys.executable, "-c", check],
                       capture_output=True, text=True)
    assert "nojax-ok" in r.stdout, r.stdout + r.stderr


def test_update_baseline_with_rule_keeps_other_rules_entries(tmp_path):
    """--rule + --update-baseline must not drop entries (and their
    justifications) belonging to rules that didn't run."""
    root = write_project(tmp_path, {
        "lightgbm_tpu/parallel/x.py": _BAD_SYNC,
        "tools/lint_baseline.json": json.dumps({
            "version": 1,
            "entries": [{"rule": "nondeterminism",
                         "file": "lightgbm_tpu/models/y.py",
                         "line_text": "rng = np.random.default_rng()",
                         "justification": "kept on purpose"}]})})
    tool = os.path.join(REPO, "tools", "graftlint.py")
    r = subprocess.run([sys.executable, tool, root,
                        "--rule", "unguarded-collective",
                        "--update-baseline"],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    data = json.loads((tmp_path / "tools" /
                       "lint_baseline.json").read_text())
    by_rule = {e["rule"]: e for e in data["entries"]}
    assert by_rule["nondeterminism"]["justification"] == "kept on purpose"
    assert "unguarded-collective" in by_rule


def test_prom_naming_uses_linted_trees_contract(tmp_path):
    """Linting another checkout applies THAT tree's naming contract
    (like journal-schema reads the linted tree's SCHEMA), not this
    checkout's."""
    strict_prom = (
        "import re\n"
        "def sanitize_name(name, prefix='lightgbm_tpu'):\n"
        "    return f'{prefix}_{name}'\n"
        "def canonical_name(name, kind='gauge'):\n"
        "    return name.lower(), 1.0\n"
        "def lint_family_name(base, kind=None):\n"
        "    if base.endswith('_weird'):\n"
        "        return [f'{base!r} ends _weird']\n"
        "    return []\n"
    )
    root = write_project(tmp_path, {
        "lightgbm_tpu/telemetry/prometheus.py": strict_prom,
        "lightgbm_tpu/telemetry/consumers.py":
            "def account(m):\n"
            "    m.inc('swap_weird')\n"
            "    m.inc('request_millis')\n"})
    result = lint_project(root, rule_names=["prometheus-naming"],
                          use_baseline=False)
    msgs = [v.message for v in result.violations]
    # the target tree's contract flags _weird and (unlike this
    # checkout's) accepts _millis
    assert len(msgs) == 1 and "_weird" in msgs[0], msgs


def test_update_baseline_rewrites_rotten_baseline(tmp_path):
    """--update-baseline exists to rewrite a rotten baseline: FIXME
    placeholders must not make it exit 2, and well-formed entries'
    justifications must survive the rewrite."""
    root = write_project(tmp_path, {
        "lightgbm_tpu/parallel/x.py": _BAD_SYNC,
        "tools/lint_baseline.json": json.dumps({
            "version": 1,
            "entries": [
                {"rule": "unguarded-collective",
                 "file": "lightgbm_tpu/parallel/x.py",
                 "line_text": "return jax.device_get(out)",
                 "justification": "kept on purpose"},
                {"rule": "nondeterminism",
                 "file": "lightgbm_tpu/models/gone.py",
                 "line_text": "rng = np.random.default_rng()",
                 "justification": "FIXME: justify or fix"}]})})
    tool = os.path.join(REPO, "tools", "graftlint.py")
    r = subprocess.run([sys.executable, tool, root, "--update-baseline"],
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    data = json.loads((tmp_path / "tools" /
                       "lint_baseline.json").read_text())
    assert len(data["entries"]) == 1
    assert data["entries"][0]["justification"] == "kept on purpose"


def test_partial_rule_run_does_not_report_other_rules_unused(tmp_path):
    """`--rule X` cannot judge rule Y's baseline entries — they are
    untested, not unused (reporting them as droppable would talk a
    developer into breaking the full run)."""
    root = write_project(tmp_path, {
        "lightgbm_tpu/parallel/x.py": _BAD_SYNC,
        "tools/lint_baseline.json": json.dumps({
            "version": 1,
            "entries": [
                {"rule": "unguarded-collective",
                 "file": "lightgbm_tpu/parallel/x.py",
                 "line_text": "return jax.device_get(out)",
                 "justification": "kept"},
                {"rule": "nondeterminism",
                 "file": "lightgbm_tpu/models/other.py",
                 "line_text": "rng = np.random.default_rng()",
                 "justification": "kept"}]})})
    result = lint_project(root, rule_names=["unguarded-collective"])
    assert result.violations == []
    assert result.baseline_unused == []   # nondeterminism didn't run
    # the full run DOES judge the stale nondeterminism entry
    result = lint_project(root)
    assert [e["rule"] for e in result.baseline_unused] == \
        ["nondeterminism"]


def test_cli_unknown_rule_is_usage_error(tmp_path):
    tool = os.path.join(REPO, "tools", "graftlint.py")
    r = subprocess.run([sys.executable, tool, "--rule", "no-such-rule"],
                       capture_output=True, text=True)
    assert r.returncode == 2
    assert "unknown rule" in r.stderr
