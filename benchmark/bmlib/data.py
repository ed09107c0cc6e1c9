"""The stream's schema and the two sources (closed loop, open loop).

Copied in shape from ``chip_smoke.py`` (``make_schema``) and ``bench.py``
(``_CyclingSource``, ``_PacedSource``) so that the program's own copies
can change without moving the yardstick.

The stream is the configuration's: its ``fields`` are the schema,
``stream`` its name, and ``generator`` names the file under
``generators/`` whose pool makes the events from ``--seed`` and keeps
the event clock (the contract is ``generators/__init__.py``'s
docstring). The sources cut that pool's stream into batches; they know
events by their index, never by their timestamp.
"""

from __future__ import annotations

import time

import numpy as np

# A job cannot be fed for ever. Lazy projection numbers events in int32
# and resets that space at 2**30 events with a synchronous drain, after
# which rows still in flight can decode as None (seen on the chip in 3 of
# 7 runs, PERF.md); timestamps travel as int32 ms since the job's first
# event and wrap at 2**31. The sources stop short of the nearer one and
# the window closes there.
EVENT_HORIZON = (1 << 30) - (1 << 22)


def stream_name(cfg) -> str:
    return cfg.get("stream", "inputStream")


def make_schema(cfg):
    from flink_siddhi_tpu.schema.stream_schema import StreamSchema
    from flink_siddhi_tpu.schema.types import AttributeType

    return StreamSchema(
        [(name, AttributeType(kind)) for name, kind in cfg["fields"]]
    )


class _PoolSource:
    """Shared part: cuts the pool into batches and stamps them."""

    def __init__(self, pool, schema, stream_id, batch: int) -> None:
        from flink_siddhi_tpu.schema.batch import EventBatch

        if pool.n % batch:
            raise ValueError(f"pool {pool.n} is not a multiple of {batch}")
        self.stream_id = stream_id
        self.schema = schema
        self.batch = batch
        self.served = 0  # batches handed to the job
        self.stopped = False
        self.exhausted = False  # ran into EVENT_HORIZON
        self._EventBatch = EventBatch
        self._serve = pool.server(
            batch, lambda field, s: schema.string_tables[field].intern(s)
        )

    def _next(self):
        j = self.served
        cols, ts = self._serve(j)
        self.served = j + 1
        return self._EventBatch(self.stream_id, self.schema, cols, ts)

    def _room(self) -> bool:
        if (self.served + 1) * self.batch > EVENT_HORIZON:
            self.exhausted = True
        return not (self.stopped or self.exhausted)

    def stop(self) -> None:
        self.stopped = True


class CyclingSource(_PoolSource):
    """Closed loop: one batch per poll, for as long as the job polls."""

    def poll(self, max_events: int):
        if not self._room():
            return None, np.iinfo(np.int64).max, True
        b = self._next()
        return b, int(b.timestamps[-1]), False


class PacedSource(_PoolSource):
    """Open loop. Off the schedule (warm-up) it feeds like the closed
    loop, ``max_release`` batches as one to a poll, so the tape capacity
    is at its sticky maximum, and the one segment shape compiled, before
    anything is timed. From ``begin_schedule(t0)`` on, event ``i``
    (counted from the first scheduled event) is due at ``t0 + i / rate``,
    and a micro-batch is released once its last event is due, whatever
    the job is doing. A poll that finds several batches due hands over
    up to ``max_release`` of them as one batch: a stall must not throttle
    the offered load to one batch per cycle (``bench.py:_PacedSource``)."""

    def __init__(self, pool, schema, stream_id, batch, rate,
                 max_release=3) -> None:
        super().__init__(pool, schema, stream_id, batch)
        self.rate = float(rate)
        self.period = batch / self.rate
        self.max_release = max_release
        self.t0 = None
        self.first = 0  # index of the first scheduled batch
        self.released_at = []  # perf_counter per scheduled batch

    def begin_schedule(self, t0: float) -> None:
        self.first = self.served
        self.t0 = t0

    def due_s(self, event_index):
        """Due time(s) of stream event(s) ``event_index`` (global)."""
        return self.t0 + (event_index - self.first * self.batch) / self.rate

    def poll(self, max_events: int):
        if not self._room():
            return None, np.iinfo(np.int64).max, True
        if self.t0 is None:
            out = [self._next() for _ in range(self.max_release)]
        else:
            now = time.perf_counter()
            out = []
            while (
                len(out) < self.max_release
                and self._room()
                and now
                >= self.t0 + (self.served + 1 - self.first) * self.period
            ):
                out.append(self._next())
                self.released_at.append(now)
            if not out:
                return None, None, False
        b = out[0] if len(out) == 1 else self._EventBatch.concat(out)
        return b, int(b.timestamps[-1]), False
