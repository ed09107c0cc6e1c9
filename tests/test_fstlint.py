"""fstlint: each rule fires on its known-bad fixture (incl. the
reconstructed PR 7 donation-aliasing and PR 8 falsy-zero bugs) and
stays quiet on the corrected twin; the baseline machinery enforces
reasons and staleness; and the repo itself lints clean — the same
contract scripts/run_static_analysis.py gates in the tier-1 lane."""

import os

import pytest

from flink_siddhi_tpu.analysis.baseline import (
    BaselineError,
    apply_baseline,
    parse_baseline,
)
from flink_siddhi_tpu.analysis.findings import RULES, Finding
from flink_siddhi_tpu.analysis.fstlint import REPO_ROOT, lint_paths, main
from flink_siddhi_tpu.analysis.rules import lint_module
from flink_siddhi_tpu.analysis.threads import analyze_sources

FIXTURES = os.path.join(os.path.dirname(__file__), "analysis_fixtures")


def _lint_fixture(name):
    """BOTH passes — the per-module FST1xx rules and the fstrace
    FST2xx thread pass — over one fixture, so every bad fixture is
    checked quiet against EVERY other rule, not just its own
    family's."""
    path = os.path.join(FIXTURES, name)
    with open(path) as fh:
        src = fh.read()
    return sorted(
        set(lint_module(src, name) + analyze_sources({name: src}))
    )


# rule -> (bad fixture, expected finding count on it)
CASES = {
    "FST101": ("fst101_donation", 2),  # PR 7 reconstruction
    "FST102": ("fst102_hostsync", 4),
    "FST103": ("fst103_falsy_zero", 2),  # PR 8 reconstruction
    "FST104": ("fst104_tracer_leak", 2),
    "FST105": ("fst105_retrace", 2),
    "FST106": ("fst106_checkpoint", 2),  # PR 10 reconstruction
    # fstrace (analysis/threads.py): thread ownership & lock discipline
    "FST201": ("fst201_offthread", 2),  # PR 12 contract, enforced
    "FST202": ("fst202_shared", 2),
    "FST203": ("fst203_lock_sleep", 2),  # PR 7 backoff-under-lock
    "FST204": ("fst204_checkact", 1),
}


@pytest.mark.parametrize("rule", sorted(CASES))
def test_rule_fires_on_bad_fixture(rule):
    stem, expected = CASES[rule]
    findings = _lint_fixture(f"{stem}_bad.py")
    hits = [f for f in findings if f.rule == rule]
    assert len(hits) == expected, findings
    # and ONLY that rule fires: a bad fixture for one hazard must not
    # trip another rule's false positive
    assert {f.rule for f in findings} == {rule}, findings


@pytest.mark.parametrize("rule", sorted(CASES))
def test_rule_quiet_on_corrected_twin(rule):
    stem, _ = CASES[rule]
    assert _lint_fixture(f"{stem}_good.py") == []


def test_pr7_donation_alias_is_the_alias_read():
    """The PR 7 shape specifically: the flagged read is the alias
    captured BEFORE the donating call, not the rebound binding."""
    findings = _lint_fixture("fst101_donation_bad.py")
    assert any("snap" in f.message for f in findings), findings


def test_pr8_reconstruction_names_the_config():
    findings = _lint_fixture("fst103_falsy_zero_bad.py")
    assert any("drain_interval_ms" in f.message for f in findings)


def test_every_rule_has_a_fixture_and_registry_entry():
    assert set(CASES) == set(RULES)


def test_fst101_same_line_read_after_donating_call():
    """`step(x) + x.sum()` reads x AFTER the donating call on one line
    (left-to-right evaluation) — must flag; the mirrored spelling
    evaluates x.sum() BEFORE the call and must not."""
    src = (
        "import jax\n"
        "step = jax.jit(lambda s: s, donate_argnums=(0,))\n"
        "def bad(x):\n"
        "    return step(x) + x.sum()\n"
        "def ok(x):\n"
        "    return x.sum() + step(x)\n"
    )
    findings = lint_module(src, "t.py")
    assert [(f.rule, f.line) for f in findings] == [("FST101", 4)]


def test_fst101_mutually_exclusive_branches_do_not_flag():
    """A donation in one if-branch must not flag a read in the OTHER
    branch (only one executes); a read AFTER the if still flags."""
    src = (
        "import jax\n"
        "step = jax.jit(lambda s: s, donate_argnums=(0,))\n"
        "def ok(x, cond):\n"
        "    if cond:\n"
        "        y = step(x)\n"
        "    else:\n"
        "        z = x.sum()\n"
        "def bad(x, cond):\n"
        "    if cond:\n"
        "        y = step(x)\n"
        "    return x.sum()\n"
    )
    findings = lint_module(src, "t.py")
    assert [(f.rule, f.line) for f in findings] == [("FST101", 11)]


def test_fst106_ephemeral_requires_reason():
    """A bare `# fst:ephemeral` is itself a finding — like baseline
    suppressions, the reason is mandatory."""
    src = (
        "class S:\n"
        "    def __init__(self):\n"
        "        # fst:ephemeral\n"
        "        self._clock = 0\n"
        "    def tick(self):\n"
        "        self._clock += 1\n"
        "    def state_dict(self):\n"
        "        return {}\n"
    )
    findings = lint_module(src, "t.py")
    # the bare mark is flagged AND the attr stays uncovered until the
    # reason lands — both surface
    assert [(f.rule, f.line) for f in findings] == [
        ("FST106", 4), ("FST106", 6),
    ]
    assert "without a reason" in findings[0].message


def test_fst106_uncovered_class_is_out_of_scope():
    """Classes with no checkpoint story (no state_dict, no
    fst:checkpointed mark) are not linted — the rule polices snapshot
    COMPLETENESS, not snapshot existence."""
    src = (
        "class Scratch:\n"
        "    def tick(self):\n"
        "        self._n = 1\n"
    )
    assert lint_module(src, "t.py") == []


def test_fst106_external_by_coverage_resolves_snapshot_job():
    """The `# fst:checkpointed by=` annotation pulls coverage from
    runtime/checkpoint.py: an attr snapshot_job reads is covered, a
    made-up one is flagged."""
    src = (
        "# fst:checkpointed by=flink_siddhi_tpu/runtime/checkpoint.py:snapshot_job\n"
        "class J:\n"
        "    def run(self):\n"
        "        self._epoch_ms = 5\n"      # snapshot_job reads job._epoch_ms
        "        self._never_saved = 1\n"
    )
    findings = lint_module(src, "t.py")
    assert [(f.rule, f.line) for f in findings] == [("FST106", 5)]
    assert "_never_saved" in findings[0].message


def test_rule_filter_cli(tmp_path):
    """`fstlint --rule` restricts output to one rule so it can be
    iterated without a full-repo sweep."""
    bad = tmp_path / "planted.py"
    bad.write_text(
        "def f(j):\n"
        "    return j.drain_interval_ms or 500\n"
    )
    # the planted file has an FST103 finding; filtered to FST106 it
    # reads clean, filtered to FST103 it fails
    assert main([str(bad), "--no-baseline", "--rule", "FST106"]) == 0
    assert main([str(bad), "--no-baseline", "--rule", "FST103"]) == 1
    with pytest.raises(SystemExit):
        main([str(bad), "--rule", "FST999"])
    # a baseline regenerated from a filtered sweep would drop other
    # rules' suppressions — the combination is refused
    with pytest.raises(SystemExit):
        main([
            str(bad), "--rule", "FST103",
            "--write-baseline", str(tmp_path / "gen.toml"),
        ])


def test_repo_lints_clean_with_checked_in_baseline():
    """The tier-1 contract: zero unsuppressed findings over the repo
    surface. If this fails, either fix the finding or baseline it WITH
    a reason (docs/static_analysis.md)."""
    assert main([]) == 0


def test_hotpath_allowlist_still_annotated():
    """The FST102 rule only sees functions carrying the fst:hotpath
    marker; a refactor that drops the annotations silently disables
    the rule. Pin the allowlist floor."""
    marked = {}
    for rel in (
        "flink_siddhi_tpu/runtime/executor.py",
        "flink_siddhi_tpu/runtime/replay.py",
        "flink_siddhi_tpu/compiler/plan.py",
        "flink_siddhi_tpu/compiler/nfa.py",
        "flink_siddhi_tpu/compiler/window.py",
        "flink_siddhi_tpu/compiler/scan_windows.py",
        "flink_siddhi_tpu/compiler/select.py",
        "flink_siddhi_tpu/compiler/join.py",
    ):
        with open(os.path.join(REPO_ROOT, rel)) as fh:
            marked[rel] = fh.read().count("fst:hotpath")
    # seg_scan and the drain's pack
    assert marked["flink_siddhi_tpu/runtime/executor.py"] >= 2
    assert marked["flink_siddhi_tpu/runtime/replay.py"] >= 1
    assert marked["flink_siddhi_tpu/compiler/plan.py"] >= 4
    assert marked["flink_siddhi_tpu/compiler/nfa.py"] >= 5
    assert sum(marked.values()) >= 20


# -- baseline machinery ----------------------------------------------------


def test_baseline_requires_reason():
    with pytest.raises(BaselineError, match="reason"):
        parse_baseline(
            '[[suppress]]\nrule = "FST103"\npath = "a.py"\nline = 3\n'
        )
    with pytest.raises(BaselineError, match="reason"):
        parse_baseline(
            '[[suppress]]\nrule = "FST103"\npath = "a.py"\n'
            'reason = "  "\n'
        )


def test_baseline_rejects_unknown_syntax():
    with pytest.raises(BaselineError, match="unsupported"):
        parse_baseline("[suppress]\nrule = 'x'\n")


def test_baseline_reason_may_contain_hash():
    """Issue references are the most natural reasons; '#' inside a
    quoted string is content, not a comment."""
    sups = parse_baseline(
        '[[suppress]]  # trailing comment\nrule = "FST103"\n'
        'path = "a.py"\nreason = "tracked in #42"\n'
    )
    assert sups[0].reason == "tracked in #42"


def test_baseline_suppression_and_staleness():
    sups = parse_baseline(
        '[[suppress]]\nrule = "FST103"\npath = "a.py"\nline = 3\n'
        'reason = "explained"\n\n'
        '[[suppress]]\nrule = "FST101"\npath = "gone.py"\n'
        'reason = "also explained"\n'
    )
    f_hit = Finding("a.py", 3, "FST103", "x or 5")
    f_open = Finding("b.py", 9, "FST103", "y or 5")
    open_findings, stale = apply_baseline([f_hit, f_open], sups)
    assert open_findings == [f_open]
    assert [s.path for s in stale] == ["gone.py"]


def test_stale_and_reviewme_baseline_fail_the_run(tmp_path):
    bl = tmp_path / "baseline.toml"
    bl.write_text(
        '[[suppress]]\nrule = "FST103"\npath = "nowhere.py"\n'
        'reason = "stale on purpose"\n'
    )
    assert main(["--baseline", str(bl)]) == 2
    bl.write_text(
        '[[suppress]]\nrule = "FST103"\npath = "nowhere.py"\n'
        'reason = "REVIEWME: fill me in"\n'
    )
    assert main(["--baseline", str(bl)]) == 2


def test_write_baseline_roundtrip(tmp_path):
    out = tmp_path / "gen.toml"
    bad = os.path.join(FIXTURES, "fst103_falsy_zero_bad.py")
    assert main([bad, "--write-baseline", str(out)]) == 0
    sups = parse_baseline(out.read_text())
    assert len(sups) == 2
    findings = lint_paths([bad])
    open_findings, stale = apply_baseline(findings, sups)
    assert open_findings == [] and stale == []


def test_write_baseline_preserves_existing_reasons(tmp_path):
    """Regenerating a live baseline keeps human-written reasons for
    findings that still exist; only NEW findings get REVIEWME."""
    out = tmp_path / "gen.toml"
    bad = os.path.join(FIXTURES, "fst103_falsy_zero_bad.py")
    assert main([bad, "--write-baseline", str(out)]) == 0
    text = out.read_text().replace(
        "REVIEWME", "explained: tracked in #42", 1
    )
    out.write_text(text)
    assert main([bad, "--write-baseline", str(out)]) == 0
    sups = parse_baseline(out.read_text())
    reasons = sorted(s.reason for s in sups)
    assert any(r.startswith("explained: tracked in #42") for r in reasons)
    assert sum(r.startswith("REVIEWME") for r in reasons) == 1


def test_targeted_run_does_not_report_out_of_scope_stale(tmp_path):
    """`fstlint <one file>` with a baseline whose entries cover OTHER
    files must not call them stale (staleness is a full-sweep
    concept) — and suppressions for the targeted file still apply."""
    bl = tmp_path / "baseline.toml"
    bl.write_text(
        '[[suppress]]\nrule = "FST103"\npath = "chip_smoke.py"\n'
        'reason = "covers a file outside this targeted run"\n'
    )
    clean = os.path.join(FIXTURES, "fst103_falsy_zero_good.py")
    assert main([clean, "--baseline", str(bl)]) == 0
