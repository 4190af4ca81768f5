"""graftlint framework tests: the tier-1 whole-registry gate, the CLI,
and the suppression grammar.

The gate test is THE static-analysis entry in tier-1: every registered
checker runs over the real package + test tree and must come back with
zero unsuppressed findings — the same invariant ``python -m
dryad_tpu.tools.lint`` enforces with its exit status.
"""

import json

import pytest

from dryad_tpu.analysis import engine
from dryad_tpu.analysis.core import Project, all_checkers, known_rules, run
from dryad_tpu.tools import lint as lint_cli


@pytest.mark.lint
def test_whole_registry_clean_over_repo():
    report = engine.run_repo()
    assert set(report.rules_run) == set(all_checkers())
    assert report.ok, "\n".join(f.render() for f in report.unsuppressed())
    # the framework rules double-check this, but the contract is
    # important enough to assert directly: every suppression in the
    # tree carries a reason and pulled its weight
    for s in report.suppressions:
        assert s.reason, f"{s.path}:{s.line}: suppression without reason"
        assert s.used_rules, f"{s.path}:{s.line}: unused suppression"


def test_registry_has_every_expected_rule():
    expected = {
        "operand-registry", "fuse-classification", "host-transfer",
        "layer-imports", "placement-snapshot", "coded-linearity",
        "event-schema", "kernel-determinism", "recompile-hazard",
        "span-discipline", "config-key", "collective-order",
        "sync-in-dispatch-loop", "serve-layering", "rewrite-layering",
        "metric-key", "mailbox-discipline", "trace-context",
        "routing-hash", "view-state-discipline",
    }
    assert expected == set(all_checkers())
    assert {"bad-suppression", "unused-suppression"} <= set(known_rules())


# -- CLI ---------------------------------------------------------------------


def test_cli_exits_zero_on_clean_tree(capsys):
    assert lint_cli.main([]) == 0
    out = capsys.readouterr().out
    assert "0 finding(s)" in out


def test_cli_json_report(capsys):
    assert lint_cli.main(["--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["ok"] is True
    assert doc["counts"] == {}
    assert doc["suppressions"], "expected the tree's suppressions listed"
    assert all(s["reason"] for s in doc["suppressions"])


def test_cli_rule_filter_and_list(capsys):
    assert lint_cli.main(["--rule", "event-schema"]) == 0
    assert lint_cli.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "event-schema" in out and "kernel-determinism" in out


def test_cli_unknown_rule_is_usage_error(capsys):
    assert lint_cli.main(["--rule", "no-such-rule"]) == 2


# -- suppression grammar -----------------------------------------------------

_HAZARD = "import time\n\n\ndef f():\n    return time.time()\n"


def _sup(rules: str, reason: str = "") -> str:
    """Build a suppression comment at runtime — written literally, the
    project-wide scan would parse THIS test file's fixture strings as
    real suppressions."""
    txt = "# graftlint" + ": disable=" + rules
    if reason:
        txt += " -- " + reason
    return txt


def _proj(body: str) -> Project:
    return Project.from_sources({"dryad_tpu/ops/fixture.py": body})


def test_finding_fires_without_suppression():
    report = run(_proj(_HAZARD), rules=["kernel-determinism"])
    assert [f.rule for f in report.unsuppressed()] == ["kernel-determinism"]


def test_trailing_suppression_with_reason():
    body = _HAZARD.replace(
        "return time.time()",
        "return time.time()  " + _sup("kernel-determinism", "test fixture"),
    )
    report = run(_proj(body), rules=["kernel-determinism"])
    assert report.ok
    assert len(report.suppressed()) == 1
    assert report.suppressed()[0].reason == "test fixture"


def test_suppression_on_line_above_covers_next_line():
    body = _HAZARD.replace(
        "    return time.time()",
        "    " + _sup("kernel-determinism", "test fixture") + "\n"
        "    return time.time()",
    )
    report = run(_proj(body), rules=["kernel-determinism"])
    assert report.ok and len(report.suppressed()) == 1


def test_suppression_without_reason_is_rejected():
    body = _HAZARD.replace(
        "return time.time()",
        "return time.time()  " + _sup("kernel-determinism"),
    )
    report = run(_proj(body), rules=["kernel-determinism"])
    rules = sorted(f.rule for f in report.unsuppressed())
    # the original finding stays live AND the bare suppression is flagged
    assert rules == ["bad-suppression", "kernel-determinism"]


def test_unused_suppression_is_reported():
    body = "X = 1  " + _sup("kernel-determinism", "nothing here") + "\n"
    report = run(_proj(body), rules=["kernel-determinism"])
    assert [f.rule for f in report.unsuppressed()] == ["unused-suppression"]


def test_unknown_rule_in_suppression_is_rejected():
    body = "X = 1  " + _sup("not-a-rule", "whatever") + "\n"
    report = run(_proj(body), rules=["kernel-determinism"])
    assert [f.rule for f in report.unsuppressed()] == ["bad-suppression"]


def test_filtered_run_does_not_flag_foreign_suppressions():
    # a suppression for a rule OUTSIDE the filtered set must not be
    # reported unused — the filtered run cannot know it is stale
    body = "X = 1  " + _sup("host-transfer", "covered elsewhere") + "\n"
    report = run(_proj(body), rules=["kernel-determinism"])
    assert report.ok


def test_suppression_only_covers_its_named_rule():
    body = _HAZARD.replace(
        "return time.time()",
        "return time.time()  " + _sup("host-transfer", "wrong rule"),
    )
    report = run(_proj(body), rules=["kernel-determinism", "host-transfer"])
    rules = sorted(f.rule for f in report.unsuppressed())
    assert rules == ["kernel-determinism", "unused-suppression"]
