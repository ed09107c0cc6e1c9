"""The plancheck query zoo: one representative plan per artifact class.

scripts/run_static_analysis.py (tier-1) and tests/test_plancheck.py
both compile and deep-verify every entry — window zoo, patterns
(chain, slot-NFA quantifiers, absence), sequences, joins, group-by,
chained multi-query composition, and a stacked multi-query group. A new
artifact class earns a zoo row in the same PR that adds it, or
plancheck silently stops covering the compiler's output surface.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

# name -> CQL (all over the S / Trades streams of zoo_schemas())
PLAN_ZOO: Dict[str, str] = {
    "filter_select": (
        "from S[id == 2] select id, name, price insert into out"
    ),
    "length_window_agg": (
        "from S#window.length(16) select sum(price) as total, "
        "count() as c insert into out"
    ),
    "time_window_groupby": (
        "from S#window.time(3 sec) select id, avg(price) as a "
        "group by id insert into out"
    ),
    "timebatch_window": (
        "from S#window.timeBatch(2 sec) select sum(price) as s "
        "insert into out"
    ),
    "hop_window_max": (
        "from S[id != 0]#window.hop(timestamp, 10 sec, 2 sec) "
        "select id, count() as num group by id "
        "having num >= windowMax(num) insert into out"
    ),
    "keyed_session_window": (
        "from S[id != 0]#window.session(timestamp, 10 sec, id) "
        "select id, count() as n, min(timestamp) as t0, sum(price) as s "
        "group by id insert into out"
    ),
    "unique_window": (
        "from S#window.unique(id) select id, price insert into out"
    ),
    "sort_window": (
        "from S#window.sort(8, price) select id, price insert into out"
    ),
    "expired_events": (
        "from S#window.length(4) select id, price "
        "insert expired events into out"
    ),
    "chain_pattern": (
        "from every s1 = S[id == 1] -> s2 = S[id == 2] -> "
        "s3 = S[id == 3] "
        "select s1.price as p1, s3.price as p3 insert into out"
    ),
    "chain_pattern_within": (
        "from every s1 = S[id == 1] -> s2 = S[price > 50.0] "
        "within 5 sec "
        "select s1.id as a, s2.price as p insert into out"
    ),
    "pattern_absence": (
        "from every s1 = S[id == 1] -> not S[id == 9] -> "
        "s2 = S[id == 2] "
        "select s1.price as p1, s2.price as p2 insert into out"
    ),
    "slot_nfa_quantified": (
        "from every s1 = S[id == 1] -> s2 = S[id == 2]<2:4> -> "
        "s3 = S[id == 3] "
        "select s1.price as a, s3.price as b insert into out"
    ),
    "sequence": (
        "from every s1 = S[id == 1], s2 = S[id == 2] "
        "select s1.price as p1, s2.price as p2 insert into out"
    ),
    "window_join": (
        "from S#window.length(8) as a join Trades#window.length(8) "
        "as b on a.id == b.vol "
        "select a.id, b.price insert into out"
    ),
    "tumbling_window_join": (
        "from S[id > 0]#window.hop(timestamp, 10 sec, 10 sec) as a join "
        "Trades#window.hop(timestamp, 10 sec, 10 sec) as b "
        "on a.id == b.vol "
        "select a.id as id, count() as n group by a.id insert into out"
    ),
    "join_groupby_rewrite": (
        "from S#window.length(8) as a join Trades#window.length(8) "
        "as b on a.id == b.vol "
        "select a.id, sum(b.price) as total group by a.id "
        "insert into out"
    ),
    "chained_composition": (
        "from S[price > 10.0] select id, price insert into mid; "
        "from mid#window.length(8) select sum(price) as s "
        "insert into out"
    ),
}

# a stacked multi-query group: structurally-identical chains fold onto
# one query axis (StackedChainArtifact) — the padded-stack PLC3xx rows
MULTIQUERY_STACK = "; ".join(
    f"from every s1 = S[id == {i}] -> s2 = S[id == {i + 1}] "
    f"select s1.price as p1, s2.price as p2 insert into out{i}"
    for i in range(6)
)
PLAN_ZOO["multiquery_stack6"] = MULTIQUERY_STACK

# -- the hostile zoo (analysis/admit.py) ------------------------------------
#
# Syntactically perfect, plancheck-clean tenant queries a production
# admission gate must REJECT: each entry names the exact ADM rule it
# must trip and the budget profile it is judged under ("default" =
# AdmissionBudgets(); "strict" = STRICT_BUDGETS, the multi-tenant
# profile that demands bounded residency). scripts/run_static_analysis
# and tests/test_admit.py both enforce rejection BY RULE ID — a hostile
# entry slipping through (or tripping the wrong rule) fails the gate.
HOSTILE_ZOO: Dict[str, Tuple[str, str, str]] = {
    # a 2^20-row window: ~13 MB of ring state for ONE tenant query —
    # over the default per-plan state budget
    "hostile_length_window_1m": (
        "from S#window.length(1048576) select sum(price) as s "
        "insert into out",
        "ADM101",
        "default",
    ),
    # 128k-row join rings: each arriving event demands up to 131072
    # output rows — over the default amplification budget (the
    # emission buffer would truncate with counted overflow, i.e.
    # silently degraded answers at the tenant's chosen scale)
    "hostile_join_amplification": (
        "from S#window.length(131072) as a join "
        "Trades#window.length(131072) as b on a.id == b.vol "
        "select a.id, b.price insert into out",
        "ADM120",
        "default",
    ),
    # 'every' with no 'within': armed partials never expire — the
    # unbounded-slot-residency class the strict profile rejects
    "hostile_pattern_no_within": (
        "from every s1 = S[id == 1] -> s2 = S[id == 2] "
        "select s1.price as p1, s2.price as p2 insert into out",
        "ADM110",
        "strict",
    ),
    # a declared-but-absurd residency: one-hour partial matches under
    # a 60 s tenant budget
    "hostile_eternal_within": (
        "from every s1 = S[id == 1] -> s2 = S[id == 2] "
        "within 3600 sec "
        "select s1.price as p1, s2.price as p2 insert into out",
        "ADM111",
        "strict",
    ),
    # window-less join: semantically retains ALL history, truncated at
    # ring capacity with counted overflow — unbounded retention under
    # the strict profile
    "hostile_unbounded_join": (
        "from S as a join Trades as b on a.id == b.vol "
        "select a.id, b.price insert into out",
        "ADM112",
        "strict",
    ),
}


def hostile_budgets(profile: str):
    """Budget profile for a HOSTILE_ZOO entry."""
    from .admit import DEFAULT_BUDGETS, STRICT_BUDGETS

    return {"default": DEFAULT_BUDGETS, "strict": STRICT_BUDGETS}[profile]


def zoo_schemas():
    """Fresh schema objects per call (schemas carry shared string
    tables; zoo entries must not cross-contaminate interning)."""
    from ..schema.stream_schema import StreamSchema
    from ..schema.types import AttributeType

    return {
        "S": StreamSchema(
            [
                ("id", AttributeType.INT),
                ("name", AttributeType.STRING),
                ("price", AttributeType.DOUBLE),
                ("timestamp", AttributeType.LONG),
            ]
        ),
        "Trades": StreamSchema(
            [
                ("sym", AttributeType.STRING),
                ("price", AttributeType.DOUBLE),
                ("vol", AttributeType.INT),
                ("timestamp", AttributeType.LONG),
            ]
        ),
    }


def compile_zoo(
    verify: bool = False,
) -> List[Tuple[str, object]]:
    """Compile every zoo plan; returns [(name, CompiledPlan)].
    ``verify=False`` so callers decide when plancheck runs (the tier-1
    conftest exports FST_VERIFY_PLANS=1, which applies regardless)."""
    from ..compiler.config import EngineConfig
    from ..compiler.plan import compile_plan

    out = []
    cfg = EngineConfig(verify_plans=verify)
    for name, cql in PLAN_ZOO.items():
        out.append(
            (
                name,
                compile_plan(
                    cql, zoo_schemas(), plan_id=f"zoo:{name}", config=cfg
                ),
            )
        )
    return out


def compile_hostile() -> List[Tuple[str, object, str, str]]:
    """Compile every hostile zoo plan; returns
    [(name, CompiledPlan, expected ADM rule, budget profile)]. These
    are well-formed (plancheck passes) — only ADMISSION must reject
    them, so the caller runs analysis/admit.py explicitly with the
    entry's profile."""
    from ..compiler.config import EngineConfig
    from ..compiler.plan import compile_plan

    out = []
    cfg = EngineConfig()
    for name, (cql, rule, profile) in HOSTILE_ZOO.items():
        out.append(
            (
                name,
                compile_plan(
                    cql, zoo_schemas(), plan_id=f"zoo:{name}", config=cfg
                ),
                rule,
                profile,
            )
        )
    return out
