"""fstlint: the JAX-hazard + thread-discipline linter CLI.

Usage::

    fstlint [paths...] [--baseline FILE | --no-baseline]
            [--rule FSTnnn[,FSTnnn...]] [--changed] [--no-cache]
            [--write-baseline FILE] [--list-rules] [--json]

With no paths, lints the default surface: the ``flink_siddhi_tpu``
package and ``scripts/``. The default sweep runs the
per-module FST1xx rules (rules.py) AND the cross-module FST2xx
thread-ownership pass (threads.py). ``--rule`` restricts output
to the named rule id(s) — iterate on ONE rule without wading through
a full-repo sweep (staleness is not enforced on a filtered run, like
a targeted-paths run).

The default sweep is cached (``.fstlint_cache.json`` at the repo
root, keyed by per-file mtime+size plus a fingerprint of the analysis
package itself), so the tier-1 repo-lints-clean gate does not
re-parse ~100 unchanged files every run — the suite runs ~833s of an
870s budget and every second counts. ``--no-cache`` bypasses it;
``--changed`` additionally restricts REPORTING to files whose cache
entry was stale (a quick pre-commit loop; staleness is not enforced,
like a targeted run). Targeted-path runs never use the cache.

Exit codes: 0 clean; 1 unsuppressed findings; 2 baseline problems
(stale entries, missing or REVIEWME reasons, parse errors).
``scripts/run_static_analysis.py`` runs this (plus plancheck and
admission over the query zoo) in the tier-1 lane.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .baseline import (
    BaselineError,
    apply_baseline,
    parse_baseline,
    render_baseline,
)
from .findings import RULES, Finding
from .rules import lint_module
from .threads import analyze_sources

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(_PKG_DIR)
DEFAULT_BASELINE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "baseline.toml"
)

# generated / vendored files the default sweep skips
_SKIP_PARTS = {".jax_cache", "__pycache__", ".git", "analysis_fixtures"}


def _default_targets() -> List[str]:
    out = [_PKG_DIR]
    scripts = os.path.join(REPO_ROOT, "scripts")
    if os.path.exists(scripts):
        out.append(scripts)
    return out


def _iter_py_files(paths: Iterable[str]) -> Iterable[str]:
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                yield p
            continue
        for root, dirs, files in os.walk(p):
            dirs[:] = [d for d in dirs if d not in _SKIP_PARTS]
            for f in sorted(files):
                if f.endswith(".py"):
                    yield os.path.join(root, f)


def _rel(path: str, root: str) -> str:
    try:
        rel = os.path.relpath(os.path.abspath(path), root)
    except ValueError:
        rel = path
    return rel.replace(os.sep, "/")


CACHE_PATH = os.path.join(REPO_ROOT, ".fstlint_cache.json")
_CACHE_VERSION = 1


def _rules_fingerprint() -> List:
    """mtime+size of every analysis-package module: editing a rule (or
    adding one) invalidates the whole cache — stale findings from an
    old rule set must never satisfy the tier-1 gate."""
    d = os.path.dirname(os.path.abspath(__file__))
    out = []
    for f in sorted(os.listdir(d)):
        if f.endswith(".py"):
            st = os.stat(os.path.join(d, f))
            out.append([f, st.st_mtime_ns, st.st_size])
    return out


def _load_cache() -> Dict:
    try:
        with open(CACHE_PATH, "r", encoding="utf-8") as fh:
            cache = json.load(fh)
    except (OSError, ValueError):
        return {}
    if (
        cache.get("version") != _CACHE_VERSION
        or cache.get("rules") != _rules_fingerprint()
    ):
        return {}
    return cache


def _store_cache(cache: Dict) -> None:
    cache["version"] = _CACHE_VERSION
    cache["rules"] = _rules_fingerprint()
    tmp = CACHE_PATH + ".tmp"
    try:
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(cache, fh)
        os.replace(tmp, CACHE_PATH)
    except OSError:
        pass  # a read-only checkout just pays the full sweep


def _decode_findings(raw) -> List[Finding]:
    return [Finding(p, int(ln), r, m) for p, ln, r, m in raw]


def _encode_findings(findings: Iterable[Finding]) -> List:
    return [[f.path, f.line, f.rule, f.message] for f in findings]


def lint_paths(
    paths: Optional[Sequence[str]] = None,
    root: Optional[str] = None,
    cache: bool = False,
    changed_out: Optional[Set[str]] = None,
) -> List[Finding]:
    """Lint files/directories; findings carry root-relative paths.

    Runs the per-module FST1xx rules over every file plus the
    cross-module FST2xx thread pass over the whole set. ``cache=True``
    (the default sweep) reuses per-file results keyed by mtime+size
    and the whole-set thread-pass result keyed by every file's stamp;
    ``changed_out`` (a set) receives the rel-paths that were actually
    re-linted."""
    root = root or REPO_ROOT
    targets = list(paths) if paths else _default_targets()
    stored = _load_cache() if cache else {}
    file_cache: Dict = stored.get("files", {}) if cache else {}
    new_files: Dict = {}
    findings: List[Finding] = []
    sources: Dict[str, str] = {}
    stamps: List = []
    for fp in _iter_py_files(targets):
        rel = _rel(fp, root)
        st = os.stat(fp)
        key = [st.st_mtime_ns, st.st_size]
        stamps.append([rel, key])
        with open(fp, "r", encoding="utf-8") as fh:
            source = fh.read()
        sources[rel] = source
        entry = file_cache.get(rel)
        if cache and entry is not None and entry.get("key") == key:
            per_file = _decode_findings(entry["findings"])
        else:
            if changed_out is not None:
                changed_out.add(rel)
            try:
                per_file = lint_module(source, rel)
            except SyntaxError as e:
                per_file = [
                    Finding(
                        rel,
                        e.lineno or 0,
                        "FST000",
                        f"file does not parse: {e.msg}",
                    )
                ]
        new_files[rel] = {
            "key": key, "findings": _encode_findings(per_file)
        }
        findings.extend(per_file)
    # cross-module thread pass (FST2xx): cached on the WHOLE file-set
    # stamp — one changed file re-runs it (ownership is a cross-module
    # property), an unchanged set reuses the stored result
    sweep_key = sorted(stamps)
    threads_entry = stored.get("threads", {}) if cache else {}
    if cache and threads_entry.get("key") == sweep_key:
        thread_findings = _decode_findings(threads_entry["findings"])
    else:
        thread_findings = analyze_sources(sources)
    findings.extend(thread_findings)
    if cache:
        _store_cache(
            {
                "files": new_files,
                "threads": {
                    "key": sweep_key,
                    "findings": _encode_findings(thread_findings),
                },
            }
        )
    return sorted(set(findings))


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="fstlint", description=__doc__.splitlines()[0]
    )
    ap.add_argument("paths", nargs="*", help="files/dirs (default: repo)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--no-baseline", action="store_true")
    ap.add_argument(
        "--write-baseline",
        metavar="FILE",
        help="emit a baseline covering current findings (reasons left "
        "REVIEWME; the linter rejects them until a human explains)",
    )
    ap.add_argument(
        "--rule",
        action="append",
        default=[],
        metavar="FSTnnn",
        help="only report these rule id(s) (repeatable / comma-"
        "separated); staleness is not enforced on a filtered run",
    )
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--json", action="store_true")
    ap.add_argument(
        "--changed",
        action="store_true",
        help="report only findings in files whose sweep-cache entry "
        "was stale (quick pre-commit loop; staleness not enforced, "
        "like a targeted run)",
    )
    ap.add_argument(
        "--no-cache",
        action="store_true",
        help="bypass the mtime-keyed sweep cache (.fstlint_cache.json)",
    )
    args = ap.parse_args(argv)
    if args.changed and args.paths:
        ap.error("--changed applies to the default sweep only")
    if args.changed and args.no_cache:
        ap.error("--changed needs the cache to know what changed")
    if args.changed and args.write_baseline:
        # same hole as --rule below: a baseline regenerated from the
        # stale-files subset would silently DROP every unchanged
        # file's suppressions (and their human-written reasons)
        ap.error(
            "--changed cannot be combined with --write-baseline (the "
            "regenerated baseline would drop unchanged files' entries)"
        )

    rule_filter = {
        r.strip().upper()
        for chunk in args.rule
        for r in chunk.split(",")
        if r.strip()
    }
    unknown = rule_filter - set(RULES)
    if unknown:
        ap.error(
            f"unknown rule id(s) {sorted(unknown)}; --list-rules "
            "prints the registry"
        )
    if rule_filter and args.write_baseline:
        # a baseline regenerated from a filtered sweep would silently
        # DROP every other rule's suppressions (and their human-written
        # reasons) — refuse the combination
        ap.error(
            "--rule cannot be combined with --write-baseline (the "
            "regenerated baseline would drop other rules' entries)"
        )

    if args.list_rules:
        for rid, desc in sorted(RULES.items()):
            if rule_filter and rid not in rule_filter:
                continue
            print(f"{rid}  {desc}")
        return 0

    changed: Set[str] = set()
    findings = lint_paths(
        args.paths or None,
        # cache the default sweep only: targeted paths (tests, tmp
        # files) are cheap and their churn would thrash the cache
        cache=not args.paths and not args.no_cache,
        changed_out=changed,
    )
    if rule_filter:
        findings = [f for f in findings if f.rule in rule_filter]
    if args.changed:
        findings = [f for f in findings if f.path in changed]

    if args.write_baseline:
        # regenerating a live baseline must PRESERVE human-written
        # reasons for findings that still exist; only new findings get
        # REVIEWME placeholders
        prior = []
        if os.path.exists(args.write_baseline):
            try:
                with open(
                    args.write_baseline, "r", encoding="utf-8"
                ) as fh:
                    prior = parse_baseline(
                        fh.read(), _rel(args.write_baseline, REPO_ROOT)
                    )
            except BaselineError as e:
                print(f"warning: existing baseline unparseable ({e}); "
                      "reasons cannot be carried over")
        with open(args.write_baseline, "w", encoding="utf-8") as fh:
            fh.write(render_baseline(findings, prior))
        print(
            f"wrote {len(findings)} suppression(s) to "
            f"{args.write_baseline}; fill in any REVIEWME reasons"
        )
        return 0

    stale = []
    baseline_errors: List[str] = []
    if not args.no_baseline and os.path.exists(args.baseline):
        try:
            with open(args.baseline, "r", encoding="utf-8") as fh:
                sups = parse_baseline(
                    fh.read(), _rel(args.baseline, REPO_ROOT)
                )
        except BaselineError as e:
            baseline_errors.append(str(e))
            sups = []
        for s in sups:
            if s.reason.strip().upper().startswith("REVIEWME"):
                baseline_errors.append(
                    f"{_rel(args.baseline, REPO_ROOT)}:{s.src_line}: "
                    f"suppression for {s.rule} at {s.path} still has a "
                    "REVIEWME reason — explain it or fix the finding"
                )
        findings, stale = apply_baseline(findings, sups)
        if args.paths or rule_filter or args.changed:
            # a targeted run lints a SUBSET of the surface (by path or
            # by rule), so a suppression for an out-of-scope finding
            # matching nothing is expected, not stale — staleness is
            # only meaningful (and only enforced) against the full
            # default sweep
            stale = []

    if args.json:
        print(
            json.dumps(
                {
                    "findings": [f.__dict__ for f in findings],
                    "stale_suppressions": [
                        {"rule": s.rule, "path": s.path, "line": s.line}
                        for s in stale
                    ],
                    "baseline_errors": baseline_errors,
                },
                indent=2,
            )
        )
    else:
        for f in findings:
            print(f.render())
        for s in stale:
            print(
                f"{_rel(args.baseline, REPO_ROOT)}:{s.src_line}: STALE "
                f"suppression ({s.rule} at {s.path}"
                + (f":{s.line}" if s.line is not None else "")
                + ") matches no current finding — delete it"
            )
        for msg in baseline_errors:
            print(msg)
        if findings:
            print(f"{len(findings)} finding(s)")

    if stale or baseline_errors:
        return 2
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
