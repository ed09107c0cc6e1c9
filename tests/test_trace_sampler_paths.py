"""The trace sampler's narrow mask against the full mask it replaced.

``TraceSampler`` decides membership (``abs_ts % sample_every == 0``) in
the stamps' own width, with the epoch folded into a scalar residue and
``&`` for a power-of-two ``sample_every`` (``telemetry/tracing.py``).
Here the full mask it had before (every stamp cast to int64, the epoch
added, an int64 modulo), kept verbatim as ``_FullMaskSampler``, is the
oracle: driven through the same calls, both leave the same pending map,
FIFO, counts, ring and histogram count after every call. Nothing here
is a time.
"""

import threading
import time
from collections import deque

import numpy as np
import pytest

from flink_siddhi_tpu.telemetry import MetricsRegistry, TraceSampler
from flink_siddhi_tpu.telemetry.histogram import LatencyHistogram

N = 4096
EVERY = (1, 3, 8, 1024)
DTYPES = (np.int32, np.int64)
SHAPES = (
    "ordered", "shuffled", "repeats", "negative", "epoch_off", "empty",
    "no_multiple",
)
PENDING = ("empty", "sparse", "dense", "at_max")


class _FullMaskSampler:
    """The sampler as it was before: every stamp cast to
    int64, the epoch added, an int64 modulo and ``nonzero`` per call."""

    def __init__(self, registry, sample_every=1024, max_pending=1 << 16,
                 ring_capacity=256):
        self.registry = registry
        self.sample_every = int(sample_every)
        self.max_pending = int(max_pending)
        self._lock = threading.Lock()
        self._pending = {}
        self._order = deque()
        self._ring = deque(maxlen=ring_capacity)
        self.sampled = 0
        self.completed = 0
        self.evicted = 0

    def _mask(self, abs_ts):
        return (abs_ts % self.sample_every) == 0

    def stamp_ingest(self, timestamps):
        ts = np.asarray(timestamps)
        if ts.size == 0:
            return
        hits = ts[self._mask(ts)]
        if hits.size == 0:
            return
        now = time.monotonic()
        with self._lock:
            for t in np.unique(hits).tolist():
                t = int(t)
                if t in self._pending:
                    continue
                self._pending[t] = now
                self._order.append(t)
                self.sampled += 1
            while len(self._pending) > self.max_pending:
                old = self._order.popleft()
                if self._pending.pop(old, None) is not None:
                    self.evicted += 1
            if len(self._order) > max(
                2 * len(self._pending), 2 * self.max_pending
            ):
                self._order = deque(
                    k for k in self._order if k in self._pending
                )

    def complete_rows(self, epoch_ms, rows, hist=None):
        if not rows:
            return
        with self._lock:
            if not self._pending:
                return
        rel = np.fromiter(
            (r[0] for r in rows), dtype=np.int64, count=len(rows)
        )
        self.complete_ts(epoch_ms, rel, hist=hist)

    def complete_ts(self, epoch_ms, rel_ts, hist=None):
        rel = np.asarray(rel_ts)
        if rel.size == 0:
            return
        with self._lock:
            if not self._pending:
                return
        abs_ts = rel.astype(np.int64) + int(epoch_ms)
        idx = np.nonzero(self._mask(abs_ts))[0]
        if idx.size == 0:
            return
        now = time.monotonic()
        samples = []
        with self._lock:
            for i in idx.tolist():
                t = int(abs_ts[i])
                t0 = self._pending.pop(t, None)
                if t0 is None:
                    continue
                dt = now - t0
                samples.append(dt)
                self.completed += 1
                self._ring.append({"ts": t, "e2e_ms": round(dt * 1e3, 3)})
        if samples:
            if hist is None:
                hist = self.registry.histogram("trace.e2e")
            hist.record_many_seconds(samples)


def _state(tr):
    return {
        "pending": list(tr._pending),
        "order": list(tr._order),
        "sampled": tr.sampled,
        "completed": tr.completed,
        "evicted": tr.evicted,
        "recent": [r["ts"] for r in tr._ring],
        "e2e": tr.registry.histogram("trace.e2e").count,
    }


class _Pair:
    """The sampler and its oracle behind one set of calls; every call
    ends with the two states compared."""

    def __init__(self, every, **kw):
        self.new = TraceSampler(MetricsRegistry(), sample_every=every, **kw)
        self.old = _FullMaskSampler(
            MetricsRegistry(), sample_every=every, **kw
        )
        self.calls = 0

    def __getattr__(self, name):
        def call(*args, **kw):
            getattr(self.old, name)(*args, **kw)
            getattr(self.new, name)(*args, **kw)
            self.calls += 1
            assert _state(self.new) == _state(self.old), (name, self.calls)
        return call


def _stamps(shape, every, dtype, rng):
    """(epoch, relative stamps) of a case. The int32 cases keep the
    epoch small: ingest sees ``rel + epoch`` in the same width."""
    big = dtype is np.int64
    epoch = every * (1_403_240_625 if big else 977)  # a multiple
    ordered = np.sort(rng.integers(0, 8 * N, N))
    if shape == "ordered":
        rel = ordered
    elif shape == "shuffled":
        rel = rng.permutation(ordered)
    elif shape == "repeats":
        rel = np.repeat(np.arange(N // 64), 64)
    elif shape == "negative":
        epoch, rel = 0, ordered - 5 * N  # before an epoch is set
    elif shape == "epoch_off":
        epoch, rel = epoch + every // 2 + 1, ordered
    elif shape == "empty":
        rel = ordered[:0]
    elif shape == "no_multiple":
        lo, hi = 5 * every + 1, 6 * every - 1
        if hi < lo:  # sample_every 1: every stamp is a multiple
            lo = hi = 5
        rel = np.sort(rng.integers(lo, hi + 1, N))
    return epoch, rel.astype(dtype)


@pytest.mark.parametrize("pending", PENDING)
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("every", EVERY)
def test_same_samples_as_the_full_mask(every, dtype, shape, pending):
    rng = np.random.default_rng(
        [every, DTYPES.index(dtype), SHAPES.index(shape),
         PENDING.index(pending)]
    )
    epoch, rel = _stamps(shape, every, dtype, rng)
    if shape == "epoch_off" and every > 1:
        assert epoch % every
    pair = _Pair(every, max_pending=64 if pending == "at_max" else 1 << 16)
    ahead = (int(rel[-1]) if rel.size else 0) + epoch
    if pending == "empty":
        pair.complete_ts(epoch, rel)
    elif pending == "sparse":  # a few keys the delivery never carries
        pair.stamp_ingest(
            (ahead // every + 10 + np.arange(5)) * every
        )
    elif pending == "dense":  # more keys pending than rows delivered
        first = (int(rel[0]) if rel.size else 0) + epoch
        pair.stamp_ingest(
            (first // every - N + np.arange(2 * N + 7)) * every
        )
        assert len(pair.new._pending) > rel.size
    elif pending == "at_max":
        pair.stamp_ingest((ahead // every + np.arange(200)) * every)
        assert pair.new.evicted == 200 - 64
    # the later half arrives first: the pending map's own order is not
    # the delivery's
    half = rel.size // 2
    pair.stamp_ingest(rel[half:] + dtype(epoch))
    pair.stamp_ingest(rel[:half] + dtype(epoch))
    pair.complete_ts(epoch, rel)
    pair.stamp_ingest(rel + dtype(epoch))
    pair.complete_ts(epoch, rel[:half])
    pair.complete_rows(epoch, [(int(t), ()) for t in rel[half:]])
    # a second delivery of the same stamps completes nothing again
    pair.complete_ts(epoch, rel)
    # and one into a histogram of the caller's (the per-shard lane)
    pair.stamp_ingest(rel + dtype(epoch))
    hists = [LatencyHistogram(), LatencyHistogram()]
    pair.old.complete_ts(epoch, rel, hist=hists[0])
    pair.new.complete_ts(epoch, rel, hist=hists[1])
    assert _state(pair.new) == _state(pair.old)
    assert hists[0].count == hists[1].count


def test_sample_every_changed_between_stamp_and_completion():
    """A key stamped under another ``sample_every`` does not
    complete: the rule is the present one's."""
    pair = _Pair(8)
    ts = np.arange(N, dtype=np.int64)
    pair.stamp_ingest(ts[:64])
    pair.new.sample_every = pair.old.sample_every = 1024
    pair.stamp_ingest(ts)
    pair.complete_ts(0, ts)
    assert pair.new.completed == N // 1024
    assert len(pair.new._pending) == 64 // 8 - 1


@pytest.mark.parametrize("case", ["uint32", "float64", "every_past_int32"])
def test_widths_the_narrow_form_cannot_hold(case):
    """Stamps that are not signed integers, and a ``sample_every`` the
    stamps' width cannot hold, go through int64 as before."""
    every = 1 << 33 if case == "every_past_int32" else 8
    dtype = {"uint32": np.uint32, "float64": np.float64}.get(case, np.int32)
    pair = _Pair(every)
    rel = np.arange(N).astype(dtype)
    epoch = every - 5  # stamp 5 is sampled
    pair.stamp_ingest(np.arange(N, dtype=np.int64) + epoch)
    pair.complete_ts(epoch, rel)
    if case != "every_past_int32":  # the full mask raised there too
        pair.stamp_ingest(rel)
        pair.complete_ts(0, rel)
    assert pair.new.completed > 0
