#!/usr/bin/env python3
"""Tier-1 static analysis gate: fstlint + plancheck + admission.

Runs in the tier-1 lane (tests/test_static_analysis.py imports and
invokes this; CI can also call it directly). Exits nonzero on:

* any unsuppressed fstlint finding over the repo surface
  (flink_siddhi_tpu/, scripts/),
* any stale / reason-less / REVIEWME baseline.toml suppression,
* any plancheck issue over the window/pattern/join/multiquery zoo
  (full tier: static NFA/stack checks + eval_shape schema/donation
  checks + the deep inert-tape execution; ``--fast`` skips deep),
* any admission failure (analysis/admit.py): a legitimate zoo entry
  NOT admitted with finite bounds under the default budgets, or a
  HOSTILE zoo entry not rejected with its exact ADM rule id.

docs/static_analysis.md is the rule and invariant reference.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Optional, Sequence

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--skip-lint", action="store_true")
    ap.add_argument("--skip-plancheck", action="store_true")
    ap.add_argument("--skip-admission", action="store_true")
    ap.add_argument(
        "--fast",
        action="store_true",
        help="skip the deep inert-tape zoo execution (trace checks "
        "still run; the tier-1 lane uses this to protect its wall-"
        "clock budget — CI outside the lane runs full deep)",
    )
    args = ap.parse_args(argv)
    failed = False

    if not args.skip_lint:
        from flink_siddhi_tpu.analysis import fstlint

        print("== fstlint ==", flush=True)
        rc = fstlint.main([])
        if rc != 0:
            failed = True
            print(f"fstlint: FAILED (exit {rc})")
        else:
            print("fstlint: clean")

    plans = None  # zoo compiled once, shared by plancheck + admission

    def _zoo():
        nonlocal plans
        if plans is None:
            from flink_siddhi_tpu.analysis.zoo import compile_zoo

            plans = compile_zoo()
        return plans

    if not args.skip_plancheck:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from flink_siddhi_tpu.analysis.plancheck import (
            PlanCheckError,
            verify_plan,
        )

        print("== plancheck (query zoo) ==", flush=True)
        try:
            plans = _zoo()
        except Exception as e:  # noqa: BLE001 — a zoo compile failure IS the finding
            print(f"zoo compile FAILED: {type(e).__name__}: {e}")
            return 1
        for name, plan in plans:
            try:
                verify_plan(plan, trace=True, deep=not args.fast)
                print(f"  {name}: ok")
            except PlanCheckError as e:
                failed = True
                print(f"  {name}: FAILED")
                for issue in e.issues:
                    print(f"    {issue.render()}")

    if not args.skip_admission:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from flink_siddhi_tpu.analysis.admit import (
            DEFAULT_BUDGETS,
            analyze_plan,
        )

        # --fast (the tier-1 lane): static tier only — every zoo
        # entry's cost_info() hooks must collect clean, no eval_shape,
        # no hostile compiles (tests/test_admit.py carries the full
        # budget/signature/hostile contract in tier-1 already). Direct
        # runs add the deep tier + the full hostile zoo.
        tier = "static tier" if args.fast else "full, default budgets"
        print(f"== admission (query zoo, {tier}) ==", flush=True)
        try:
            plans = _zoo()
        except Exception as e:  # noqa: BLE001
            print(f"zoo compile FAILED: {type(e).__name__}: {e}")
            return 1
        for name, plan in plans:
            rep = analyze_plan(
                plan,
                budgets=None if args.fast else DEFAULT_BUDGETS,
                deep=not args.fast,
            )
            if not rep.admitted:
                failed = True
                print(f"  {name}: NOT ADMITTED")
                for issue in rep.findings:
                    print(f"    {issue.render()}")
            elif args.fast:
                print(f"  {name}: ok (amp={rep.amplification})")
            else:
                print(
                    f"  {name}: admitted (state={rep.state_bytes}B "
                    f"acc={rep.acc_bytes}B amp={rep.amplification} "
                    f"sig={rep.signature[:12]})"
                )

        if not args.fast:
            from flink_siddhi_tpu.analysis.zoo import (
                compile_hostile,
                hostile_budgets,
            )

            print("== admission (hostile zoo) ==", flush=True)
            try:
                hostile = compile_hostile()
            except Exception as e:  # noqa: BLE001
                print(
                    f"hostile zoo compile FAILED: "
                    f"{type(e).__name__}: {e}"
                )
                return 1
            for name, plan, rule, profile in hostile:
                rep = analyze_plan(
                    plan, budgets=hostile_budgets(profile)
                )
                got = [i.rule for i in rep.findings]
                if not rep.admitted and rule in got:
                    print(f"  {name}: rejected by {rule} ({profile})")
                else:
                    failed = True
                    print(
                        f"  {name}: FAILED — expected rejection by "
                        f"{rule} under {profile} budgets, got "
                        f"{got or 'ADMITTED'}"
                    )

    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
