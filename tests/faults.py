"""Deterministic fault-injection harness.

Two injection axes, both seeded, both replayable:

* **wire faults** — :class:`FaultSchedule` plugs into
  ``FakeBroker.fault_hook`` and decides, per request (in the broker's
  deterministic arrival order), whether to drop the connection, close
  it mid-frame, answer with a transient broker error code, serve a
  corrupt batch, or delay. Consecutive faults are capped below the
  client's retry budget, so a bounded RetryPolicy always eventually
  gets through — the schedule injects pain, not livelock.
* **process faults** — :class:`CrashPlan` + :func:`wrap_job`
  (re-exported from ``flink_siddhi_tpu.runtime.faultinject``, the one
  implementation) inject
  crashes into a SUPERVISED job at scheduled source-pull boundaries
  and killed-mid-checkpoint; see that module's docstring.

No wall-clock sleeps anywhere (the only sleep is the broker's bounded
2 ms ``delay`` action and the client's own milliseconds-scale test
backoff); every decision is a function of (seed, sequence number).
"""

from __future__ import annotations

import random
import threading
from typing import Optional, Sequence

from flink_siddhi_tpu.runtime.faultinject import (  # noqa: F401
    CrashPlan,
    InjectedCrash,
    wrap_job,
)


class FaultSchedule:
    """Seeded per-request wire-fault decisions for FakeBroker.

    ``p_fault`` is the per-request fault probability; ``actions`` the
    pool drawn from. ``max_consecutive`` caps the run of consecutive
    faulted requests (default 2 — safely below the client's default
    5-attempt budget)."""

    ACTIONS = ("drop", "drop_mid_frame", "error", "corrupt", "delay")

    def __init__(
        self,
        seed: int,
        p_fault: float = 0.2,
        actions: Sequence[str] = ACTIONS,
        max_consecutive: int = 2,
    ) -> None:
        self._rng = random.Random(seed)
        self.p_fault = float(p_fault)
        self.actions = tuple(actions)
        self.max_consecutive = int(max_consecutive)
        self._consecutive = 0
        self.injected = []  # [(seq, api, action)] — the audit trail
        # the broker serves connections from multiple threads; the
        # schedule must stay an ordered, race-free decision sequence
        self._lock = threading.Lock()

    def __call__(self, api: int, seq: int) -> Optional[str]:
        with self._lock:
            fault = (
                self._consecutive < self.max_consecutive
                and self._rng.random() < self.p_fault
            )
            if not fault:
                self._consecutive = 0
                return None
            action = self.actions[
                self._rng.randrange(len(self.actions))
            ]
            self._consecutive += 1
            self.injected.append((seq, api, action))
            return action


class HeldFetchThread:
    """A stalled drain fetch thread: holds a job's one fetch thread so
    that requested drains stay pending (swapped out and queued, not
    fetched), until the ``with`` block ends. ``release_after`` opens it
    from a timer instead, for a caller that is about to block on a
    pending drain; leaving the block opens it in any case, so a failed
    assertion cannot leave the pool's thread waiting at exit."""

    def __init__(self, job) -> None:
        self._gate = threading.Event()
        self._timer: Optional[threading.Timer] = None
        job._fetch_pool.submit(self._gate.wait)

    def release_after(self, seconds: float) -> None:
        self._timer = threading.Timer(seconds, self._gate.set)
        self._timer.start()

    def __enter__(self) -> "HeldFetchThread":
        return self

    def __exit__(self, *exc) -> None:
        self._gate.set()
        if self._timer is not None:
            self._timer.cancel()
