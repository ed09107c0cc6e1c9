"""``Job._poll`` (runtime/executor.py): a source that brings a batch at
every poll and takes its time over each is polled one batch ahead on the
poll thread. The rows are those of the run loop's own polls, the source
sees one poll at a time and in order, a source between events, a fast
source and a source whose position a checkpoint records are polled by
the run loop alone. Nothing here is a rate."""

import threading
import time

import numpy as np
import pytest

from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.schema.batch import EventBatch
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType

SCHEMA = StreamSchema([("k", AttributeType.INT), ("v", AttributeType.INT)])
CQL = "from S[v >= 0] select k, v insert into o"
BATCH = 64


class _Source:
    """``n`` batches of ``BATCH`` events, ``cost_s`` seconds a poll;
    ``gaps``: polls (by number) that bring nothing. Notes the thread of
    every poll and fails a poll made while another runs."""

    stream_id, schema = "S", SCHEMA

    def __init__(self, n, cost_s=0.0, gaps=()):
        self.n, self.cost_s, self.gaps = n, cost_s, set(gaps)
        self.served, self.polls, self.threads = 0, 0, []
        self._busy = threading.Lock()

    def poll(self, max_events):
        assert self._busy.acquire(blocking=False), "two polls at once"
        try:
            self.threads.append(threading.get_ident())
            self.polls += 1
            if self.served == self.n:
                return None, np.iinfo(np.int64).max, True
            if self.polls in self.gaps:
                return None, None, False
            time.sleep(self.cost_s)
            j, self.served = self.served, self.served + 1
            at = j * BATCH + np.arange(BATCH)
            cols = {"k": (at % 7).astype(np.int32),
                    "v": (at % 11 - 1).astype(np.int32)}
            ts = 1_000 + at.astype(np.int64)
            return EventBatch("S", SCHEMA, cols, ts), int(ts[-1]), False
        finally:
            self._busy.release()


class _Recorded(_Source):
    def state_dict(self):
        return {"served": self.served}


def _run(src, after=4, min_s=0.001):
    job = Job([compile_plan(CQL, {"S": SCHEMA}, plan_id="p")], [src],
              batch_size=BATCH, time_mode="processing",
              retain_results=False)
    job.POLL_AHEAD_AFTER, job.POLL_AHEAD_MIN_S = after, min_s
    rows = []
    job.add_sink("o", lambda ts, row: rows.append((ts, *row)))
    main = threading.get_ident()
    ahead_seen = 0
    while not job.finished:
        job.run_cycle()
        ahead_seen += bool(job._polled_ahead)
    job.flush()
    off = [t for t in src.threads if t != main]
    return rows, off, ahead_seen, job


def _expected(n):
    at = np.arange(n * BATCH)
    keep = at % 11 - 1 >= 0
    return [(1_000 + int(i), int(i % 7), int(i % 11 - 1)) for i in at[keep]]


def test_a_slow_source_is_polled_ahead_and_the_rows_are_the_same():
    src = _Source(24, cost_s=0.003)
    rows, off, ahead_seen, job = _run(src)
    assert rows == _expected(24)
    # four polls by the run loop, the rest (and the one that ends the
    # stream) on the poll thread, one thread's
    assert src.threads[:4] == [threading.get_ident()] * 4
    assert len(off) == src.polls - 4 == 21 and len(set(off)) == 1
    assert ahead_seen >= 19 and not job._polled_ahead
    stages = job.telemetry.snapshot()["stages"]
    assert stages["source_poll_ahead"]["count"] == 21
    assert stages["nested.source_pull"]["count"] == src.polls


@pytest.mark.parametrize("make, min_s", [
    # (a poll that makes 64 events takes microseconds, a loaded
    # machine's perhaps a millisecond: the threshold is far above both)
    (lambda: _Source(24), 0.25),
    (lambda: _Recorded(24, cost_s=0.003), 0.001),
], ids=["fast", "checkpointed"])
def test_the_run_loop_alone_polls(make, min_s):
    src = make()
    rows, off, ahead_seen, job = _run(src, min_s=min_s)
    assert rows == _expected(24)
    assert not off and not ahead_seen
    assert "source_poll_ahead" not in job.telemetry.snapshot()["stages"]


def test_a_poll_that_brings_nothing_ends_the_run_of_polls_ahead():
    """Polls 9 and 10 bring nothing (a live source between events): the
    ninth was made ahead, the tenth and the four batches after it are
    the run loop's, then the poll thread takes over again."""
    src = _Source(24, cost_s=0.003, gaps=(9, 10))
    rows, off, _seen, _job = _run(src)
    assert rows == _expected(24)
    main = threading.get_ident()
    by = ["main" if t == main else "ahead" for t in src.threads]
    assert by[:4] == ["main"] * 4 and by[4:9] == ["ahead"] * 5
    assert by[9:14] == ["main"] * 5 and by[14:] == ["ahead"] * (len(by) - 14)


def test_a_replaced_sources_poll_in_flight_is_dropped():
    src = _Source(24, cost_s=0.003)
    job = Job([compile_plan(CQL, {"S": SCHEMA}, plan_id="p")], [src],
              batch_size=BATCH, time_mode="processing",
              retain_results=False)
    job.POLL_AHEAD_AFTER, job.POLL_AHEAD_MIN_S = 4, 0.001
    rows = []
    job.add_sink("o", lambda ts, row: rows.append((ts, *row)))
    while not job._polled_ahead:
        job.run_cycle()
    other = _Source(3)
    job._sources = [other]
    while not job.finished:
        job.run_cycle()
    job.flush()
    assert other.served == 3 and threading.get_ident() in other.threads
    # the first source's rows up to the swap, then the second's
    # (which start over), and none of the batch that was in flight
    assert len(rows) == len(_expected(src.served - 1)) + len(_expected(3))
