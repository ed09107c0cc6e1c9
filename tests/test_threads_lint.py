"""fstrace (analysis/threads.py) machinery: annotations with mandatory
reasons, the runloop-only walk boundary, cross-module ownership, the
receiver-hint conservatism, and the mtime-keyed sweep cache behind
`fstlint --changed`. The per-rule fire/quiet contracts live in
tests/test_fstlint.py next to the other fixture cases."""

import os

import pytest

from flink_siddhi_tpu.analysis import fstlint
from flink_siddhi_tpu.analysis.threads import analyze_sources


def _rules(findings):
    return [(f.rule, f.line) for f in findings]


def test_bare_threadsafe_mark_is_a_finding():
    src = (
        "class C:\n"
        "    def __init__(self):\n"
        "        # fst:threadsafe\n"
        "        self.stats = {}\n"
    )
    findings = analyze_sources({"t.py": src})
    assert [(f.rule) for f in findings] == ["FST202"]
    assert "without a reason" in findings[0].message


def test_bare_blocking_ok_mark_is_a_finding():
    src = (
        "import time\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        import threading\n"
        "        self._lock = threading.Lock()\n"
        "    def wait(self):\n"
        "        with self._lock:\n"
        "            # fst:blocking-ok\n"
        "            time.sleep(1)\n"
    )
    findings = analyze_sources({"t.py": src})
    assert [f.rule for f in findings] == ["FST203"]
    assert "without a reason" in findings[0].message


def test_threadsafe_with_reason_silences_fst202():
    src = (
        "class C:\n"
        "    def __init__(self):\n"
        "        # fst:threadsafe single writer; reader snapshots\n"
        "        self.stats = {}\n"
        "    # fst:thread-root name=a\n"
        "    def wa(self):\n"
        "        self.stats['x'] = 1\n"
        "    # fst:thread-root name=b\n"
        "    def rb(self):\n"
        "        return dict(self.stats)\n"
    )
    assert analyze_sources({"t.py": src}) == []


def test_runloop_only_bounds_the_offthread_walk():
    """A `# fst:runloop-only` def is the run loop's private surface:
    the service walk stops there, so its mutations are not attributed
    to the service thread. Without the mark, the same shape flags."""
    tpl = (
        "class Job:\n"
        "    def __init__(self):\n"
        "        self._acc = {}\n"
        "    # fst:thread-root name=run-loop\n"
        "    def run_cycle(self):\n"
        "        self._acc['n'] = 1\n"
        "        self.drain()\n"
        "{mark}"
        "    def drain(self):\n"
        "        self._acc['n'] = 0\n"
        "class Service:\n"
        "    def __init__(self, job):\n"
        "        self.job = job\n"
        "    # fst:thread-root name=service\n"
        "    def do_GET(self):\n"
        "        self.job.drain()\n"
    )
    flagged = analyze_sources({"t.py": tpl.replace("{mark}", "")})
    assert any(f.rule == "FST201" for f in flagged)
    quiet = analyze_sources(
        {"t.py": tpl.replace("{mark}", "    # fst:runloop-only\n")}
    )
    assert quiet == []


def test_cross_module_ownership_resolves_by_receiver_hint():
    """service code in one module mutating Job state defined in
    another is still caught — resolution joins on the method name
    gated by the receiver<->class hint (`self.job.retire()` -> Job)."""
    job_mod = (
        "class Job:\n"
        "    def __init__(self):\n"
        "        self._plans = {}\n"
        "    # fst:thread-root name=run-loop\n"
        "    def run_cycle(self):\n"
        "        self._plans['p'] = 1\n"
        "    def retire(self, pid):\n"
        "        self._plans.pop(pid, None)\n"
    )
    svc_mod = (
        "class Service:\n"
        "    def __init__(self, job):\n"
        "        self.job = job\n"
        "    # fst:thread-root name=service\n"
        "    def do_DELETE(self, pid):\n"
        "        self.job.retire(pid)\n"
    )
    findings = analyze_sources({"job.py": job_mod, "svc.py": svc_mod})
    assert [(f.rule, f.path) for f in findings] == [
        ("FST201", "job.py")
    ]
    # an implausible receiver drops the edge instead of guessing
    svc2 = svc_mod.replace("self.job = job", "self.widget = job"
                           ).replace("self.job.retire", "self.widget.retire")
    assert analyze_sources({"job.py": job_mod, "svc.py": svc2}) == []


def test_locked_writes_are_not_ownership_violations():
    """State the run loop itself only mutates under a lock has a
    synchronization story; FST201 polices the lock-free single-writer
    state only."""
    src = (
        "class Job:\n"
        "    def __init__(self):\n"
        "        import threading\n"
        "        self._lock = threading.Lock()\n"
        "        self.ring = {}\n"
        "    # fst:thread-root name=run-loop\n"
        "    def run_cycle(self):\n"
        "        with self._lock:\n"
        "            self.ring['a'] = 1\n"
        "    def record(self):\n"
        "        with self._lock:\n"
        "            self.ring['b'] = 2\n"
        "class Service:\n"
        "    def __init__(self, job):\n"
        "        self.job = job\n"
        "    # fst:thread-root name=service\n"
        "    def do_POST(self):\n"
        "        self.job.record()\n"
    )
    assert analyze_sources({"t.py": src}) == []


def test_lock_context_inherited_by_locked_only_helpers():
    """A helper whose every call site holds the lock inherits lock
    context — blocking inside it is still blocking under the lock
    (the kafka _read_frame shape)."""
    src = (
        "class C:\n"
        "    def __init__(self, sock):\n"
        "        import threading\n"
        "        self._lock = threading.Lock()\n"
        "        self._sock = sock\n"
        "    def call(self):\n"
        "        with self._lock:\n"
        "            return self._read()\n"
        "    def _read(self):\n"
        "        return self._sock.recv(4)\n"
    )
    findings = analyze_sources({"t.py": src})
    assert [f.rule for f in findings] == ["FST203"]


# -- the sweep cache behind `fstlint --changed` ----------------------------


def test_sweep_cache_reuses_unchanged_files(tmp_path, monkeypatch):
    cache = tmp_path / "cache.json"
    monkeypatch.setattr(fstlint, "CACHE_PATH", str(cache))
    calls = []
    real = fstlint.lint_module

    def counting(source, path):
        calls.append(path)
        return real(source, path)

    monkeypatch.setattr(fstlint, "lint_module", counting)
    assert fstlint.main([]) == 0
    assert cache.exists()
    first = len(calls)
    assert first > 50  # the full default surface was linted
    assert fstlint.main([]) == 0
    assert len(calls) == first  # warm run re-linted NOTHING
    # touching one file re-lints exactly that file; restore the real
    # stamp afterwards or the repo's LIVE sweep cache (the tier-1
    # repo-lints-clean gate's) sees a stale whole-set key and pays a
    # full FST2xx re-run on the next real fstlint invocation
    target = os.path.join(
        fstlint.REPO_ROOT, "scripts", "run_static_analysis.py")
    st = os.stat(target)
    try:
        os.utime(target)
        assert fstlint.main([]) == 0
        assert calls[first:] == ["scripts/run_static_analysis.py"]
    finally:
        os.utime(target, ns=(st.st_atime_ns, st.st_mtime_ns))


def test_changed_reports_only_stale_files(tmp_path, monkeypatch):
    cache = tmp_path / "cache.json"
    monkeypatch.setattr(fstlint, "CACHE_PATH", str(cache))
    assert fstlint.main([]) == 0  # builds the cache
    # an up-to-date cache: --changed has nothing to report even if a
    # (hypothetical) finding existed elsewhere
    assert fstlint.main(["--changed"]) == 0
    with pytest.raises(SystemExit):
        fstlint.main(["--changed", "some/path.py"])
    with pytest.raises(SystemExit):
        # a baseline regenerated from the stale-files subset would
        # drop unchanged files' suppressions
        fstlint.main(
            ["--changed", "--write-baseline", str(tmp_path / "b.toml")]
        )
