"""Shape-keyed AOT executable cache for the dynamic control plane.

The ~3.4 s first-compile (or multi-second cache-deserialize) cost of a
plan's jitted step executables is the dominant cost of admitting a query
into a running job. PR 11 built the cache KEY — ``analysis/admit.py
plan_signature``, a process-stable hash of the step's shape/dtype fixed
point with constants masked (property-tested collide/split contract) —
this module is the cache itself: compiled-executable bundles held under
that key so the first-compile cost is paid once per *shape class*, not
once per query.

What is actually cached: the ``jax.jit`` wrapper set a ``_PlanRuntime``
holds (step, step_acc, seg_scan, init_acc, flush). A jit wrapper owns
its compiled-executable cache keyed by input shapes, so reusing the
wrapper across two plans of the same shape class reuses every XLA
executable already compiled for it — zero lowering, zero
backend_compile (the retrace-budget monitoring hook in the tests pins
this).

Soundness contract (why a hit cannot compute the wrong answer): the
cached wrappers close over the plan they were FIRST built for, so a hit
is only taken when the closed-over step function is trace-equivalent to
the candidate's:

* a plan whose single artifact is a ``DynamicChainGroup`` traces from
  the group's *template* only — member filter literals, comparison
  operators, and ``within`` values are device STATE (compiler/nfa.py).
  Two signature-equal group hosts are therefore interchangeable
  programs, and the cache key is the bare signature: constants-only
  tenant variants share one executable set.
* every other plan bakes its constants into the traced program as
  literal operands, so the key additionally pins the exact source text
  — a hit then means "the same query re-admitted" (the retire/re-admit
  churn case), which is still the common control-plane cycle.

Eviction is bounded-size LRU; ``control.cache_hit`` /
``control.cache_miss`` / ``control.cache_evict`` counters land in the
bound job's telemetry registry (surfaced by ``Job.metrics()`` and
``GET /api/v1/health``). docs/control_plane.md has the full contract.

This cache is in-process. ``fleet/warmstore.py`` adds the persistent
tier UNDER it: the same ``cache_key`` names an on-disk directory of
AOT-serialized executables, so a fresh replica process warm-starts the
whole shape class with zero lowerings (cross-process property tests in
tests/test_fleet.py pin that the two tiers agree on keys — and that the
soundness split above carries over verbatim: the disk tier shares
bare-signature entries only for dyn-group hosts, and pins source text
otherwise, because it inherits ``cache_key`` unchanged).
"""

from __future__ import annotations

import hashlib
import logging
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

_LOG = logging.getLogger(__name__)

# default bound: executable bundles are host-memory-cheap (the XLA
# executables dominate, one set per shape x tape-bucket), but unbounded
# growth across a long-lived multi-tenant job is exactly the class of
# leak the engine refuses elsewhere
DEFAULT_MAX_ENTRIES = 32


@dataclass
class CachedExecutables:
    """One shape class's jit wrapper set (the ``_PlanRuntime`` slots
    ``Job._create_runtime`` fills). ``traces`` is the shared
    trace-counter cell the retrace tests read — reuse means the counter
    does NOT advance."""

    jitted_seg: Callable
    jitted_init_acc: Callable
    jitted_flush: Callable
    traces: Dict = field(default_factory=lambda: {"n": 0})
    # bucketed drain pack programs (Job._pack_data): width -> jit —
    # shared so a cache-hit admit's first drain re-slices with the
    # already-compiled pack executables instead of recompiling them
    pack_jits: Dict = field(default_factory=dict)
    # provenance for status/debugging: the plan id the bundle was first
    # compiled for, and how many plans have since shared it
    first_plan_id: str = ""
    reuses: int = 0


def cache_key(plan, capacity: int = 128) -> Optional[Tuple[str, str]]:
    """The cache key for ``plan``, or None when the plan is not safely
    cacheable (signature computation failed — conservative miss).

    ``("dyn", signature)`` for dynamic-group hosts (constants are device
    data); ``("exact", signature + source-text digest)`` otherwise."""
    try:
        sig = plan.signature(capacity)
    except Exception as e:  # noqa: BLE001 — uncacheable, never wrong
        _LOG.debug(
            "plan %s is not AOT-cacheable (%s: %s)",
            getattr(plan, "plan_id", "?"), type(e).__name__, e,
        )
        return None
    from ..compiler.nfa import DynamicChainGroup

    arts = plan.artifacts
    if len(arts) == 1 and isinstance(arts[0], DynamicChainGroup):
        return ("dyn", sig)
    text = plan.source_text or ""
    if not text:
        # the signature masks constants by design, so the "exact" key's
        # soundness rests entirely on the source text: a plan without
        # it (hand-built, dataclasses.replace()d) could collide with a
        # constants-only variant and reuse the wrong baked-in program.
        # Uncacheable, never wrong.
        return None
    digest = hashlib.sha256(text.encode("utf-8")).hexdigest()
    return ("exact", f"{sig}:{digest}")


def sig_label(key: Optional[Tuple[str, str]]) -> Optional[str]:
    """Human/metrics label for a cache key: kind-prefixed, signature
    truncated. ONE definition — the executor's compile-attribution
    labels (``metrics()["compiles"].by_signature``) and the flight
    recorder's aotcache.* event signatures are cross-correlated by
    exact string match, so they must be minted by the same code."""
    if key is None:
        return None
    return f"{key[0]}:{key[1][:32]}"


class AOTExecutableCache:
    """Bounded LRU of :class:`CachedExecutables` keyed by
    :func:`cache_key`. Thread-compat: control-plane admits run on the
    job's run-loop thread only (the epoch-boundary contract), so no
    locking is needed — documented, not accidental."""

    def __init__(
        self, max_entries: int = DEFAULT_MAX_ENTRIES, telemetry=None
    ) -> None:
        self.max_entries = max(1, int(max_entries))
        self._entries: "OrderedDict[Tuple[str, str], CachedExecutables]" = (
            OrderedDict()
        )
        self._telemetry = telemetry
        self._flightrec = None
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def bind_telemetry(self, registry) -> None:
        self._telemetry = registry

    def bind_flightrec(self, recorder) -> None:
        """Journal hit/miss/evict into the bound job's flight recorder
        (telemetry/flightrec.py) alongside the counters."""
        self._flightrec = recorder

    def _inc(self, name: str) -> None:
        if self._telemetry is not None:
            self._telemetry.inc(name)

    def _rec(self, kind: str, key, **kw) -> None:
        if self._flightrec is not None:
            self._flightrec.record(kind, signature=sig_label(key), **kw)

    def lookup(self, key) -> Optional[CachedExecutables]:
        """Counted lookup: a None key (uncacheable plan) is a miss."""
        if key is None:
            self.misses += 1
            self._inc("control.cache_miss")
            self._rec("aotcache.miss", key, uncacheable=True)
            return None
        entry = self._entries.get(key)
        if entry is None:
            self.misses += 1
            self._inc("control.cache_miss")
            self._rec("aotcache.miss", key)
            return None
        self._entries.move_to_end(key)
        entry.reuses += 1
        self.hits += 1
        self._inc("control.cache_hit")
        self._rec(
            "aotcache.hit", key, first_plan_id=entry.first_plan_id
        )
        return entry

    def insert(self, key, entry: CachedExecutables) -> None:
        if key is None:
            return
        self._entries[key] = entry
        self._entries.move_to_end(key)
        while len(self._entries) > self.max_entries:
            old_key, old = self._entries.popitem(last=False)
            self.evictions += 1
            self._inc("control.cache_evict")
            self._rec(
                "aotcache.evict", old_key,
                first_plan_id=old.first_plan_id, reuses=old.reuses,
            )
            _LOG.debug(
                "AOT cache evicted %s (first compiled for %s, "
                "%d reuses)", old_key[0], old.first_plan_id, old.reuses,
            )

    def __len__(self) -> int:
        return len(self._entries)

    def stats(self) -> Dict[str, int]:
        return {
            "entries": len(self._entries),
            "max_entries": self.max_entries,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
        }
