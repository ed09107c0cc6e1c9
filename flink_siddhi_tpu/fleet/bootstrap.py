"""Replica bootstrap: supervisor checkpoint + warm store → serving.

The bootstrap sequence (docs/fleet.md) a replica process runs before
accepting load:

1. **restore** — ``Supervisor._build_restored`` walks the checkpoint
   generations; the factory below builds each candidate job with the
   warm store and the commit-log sink already bound, so the restore's
   dynamic replay (``Job._replay_dynamic`` → ``_create_runtime``)
   consults the store for every live plan: admitted tenants, enabled
   flags, tenant attribution, and the transactional-sink pending block
   all come back from the snapshot, executables from disk;
2. **warm** — every store-held executable for the restored shape
   classes is deserialized during that same replay (fleet.warm_hit
   events); nothing is lowered for a shape class the store has seen —
   ``metrics()["compiles"]`` stays at zero, cross-process-pinned by
   tests/test_fleet.py;
3. **serve** — the run loop starts; ``cold_start_to_first_row``
   (process start → first emitted row, measured by the first-row clock
   sink) is what the store is for. No cell reads it yet (ROADMAP
   Queue 3, ``fleet/``); what a CPU can pin, zero lowerings on a warm
   boot, is tests/test_fleet.py's.

:class:`ReplicaSupervisor` extends the supervisor's checkpoint
boundary: the commit-log epoch about to be stamped rides the snapshot's
fleet block, and after every committed checkpoint the warm store is
brought up to date (``Job.persist_warm``) — so the store is current
whenever a successor might boot from it (the rolling-restart handoff
drains at exactly such a boundary).
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from ..runtime.supervisor import Supervisor
from .commitlog import CommitLogSink


class FirstRowClock:
    """Sink recording when the first output row surfaced, relative to
    the process-start clock — the serving half of
    cold-start-to-first-row. Stateless across checkpoints by design
    (no state_dict): a successor replica measures its OWN first row."""

    def __init__(self, t0: float, boot: Dict[str, object]) -> None:
        self._t0 = t0
        self._boot = boot

    def __call__(self, abs_ts, row) -> None:
        if "first_row_s" not in self._boot:
            self._boot["first_row_s"] = round(
                time.monotonic() - self._t0, 6
            )


class ReplicaSupervisor(Supervisor):
    """Supervisor with the fleet account folded into its checkpoint
    boundary (see module docstring). ``commit_sinks`` are the
    transactional file sinks the factory attached — the supervisor's
    inherited two-phase protocol already drives their prepare/commit;
    this subclass only mirrors their epoch into the job's fleet block
    and persists the warm store once the epoch is durable."""

    def __init__(
        self, factory, checkpoint_path: str, *,
        commit_sinks: Optional[List[CommitLogSink]] = None,
        **kw,
    ) -> None:
        super().__init__(factory, checkpoint_path, **kw)
        self.commit_sinks = list(commit_sinks or [])

    def _checkpoint(self, job) -> None:
        if self.commit_sinks:
            # the epoch the log will commit for THIS checkpoint — set
            # before the save so the snapshot's fleet block carries it
            job._fleet_epoch = max(
                s.next_epoch() for s in self.commit_sinks
            )
        super()._checkpoint(job)
        # the snapshot and the commit-log epoch are durable: bring the
        # store up to date so a successor booting from this boundary
        # finds every executable (off the hot path, unattributed)
        job.persist_warm()
