"""scripts/run_static_analysis.py in the tier-1 lane: the combined
lint + plancheck gate must exit 0 on the repo as committed. ``--fast``
skips only the deep inert-tape zoo executions (run in full by CI /
direct invocation; tests/test_plancheck.py keeps deep coverage on the
padded-stack shapes in tier-1)."""

import importlib.util
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load():
    spec = importlib.util.spec_from_file_location(
        "run_static_analysis",
        os.path.join(REPO, "scripts", "run_static_analysis.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_static_analysis_gate_passes():
    assert _load().main(["--fast"]) == 0


def test_gate_fails_on_unsuppressed_finding(tmp_path, monkeypatch):
    """The gate actually gates: a planted finding flips fstlint's
    exit, and run_static_analysis propagates a lint failure to its
    own exit code (the tier-1 lane reads only the latter)."""
    mod = _load()
    bad = tmp_path / "planted.py"
    bad.write_text("def f(j):\n    return j.drain_interval_ms or 500\n")
    from flink_siddhi_tpu.analysis import fstlint

    assert fstlint.main([str(bad), "--no-baseline"]) == 1
    assert mod.main(["--skip-plancheck"]) == 0  # repo itself is clean
    # combined-runner propagation: a failing lint half must flip the
    # runner's exit even when plancheck is skipped
    monkeypatch.setattr(fstlint, "main", lambda argv: 1)
    assert mod.main(["--skip-plancheck"]) == 1
