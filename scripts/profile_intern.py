"""``GroupEncoder`` alone on ``linear_road_rows4``'s key stream, on the
host, no device (schema/encoders.py: the array mode for keys that do
not lie close together). The generator's ``vid``, selection
(``type == 0``) and time columns at the cell's scale (544,000 events a
batch, 538,560 distinct vehicles in each, 3.6M live, 13,000-15,000 born
and purged a batch) go through one ``GroupEncoder(retain_ticks=4,
mark_new=True)``, as ``runtime/tape.py`` calls it for the per-key
window, a tick 30 s. PERF.md's reading of the cell's
``group_intern_ms_per_batch`` (PR 51) rests on these numbers: the forms
of the table are ranked here before a chip is asked for.

Prints, after ``warm`` batches that fill the table (30: five rounds, as
the cell warms), over ``batches`` more (48):

* ``intern_rows`` ms a batch: median, mean, and the median of the
  batches with and without a sweep;
* ms a batch by part, each the time inside one method of the encoder
  less what it spent in another listed one: ``sort`` (``_sorted_runs``:
  the batch's one sort, its distinct keys and runs), ``table search``
  (``_find``: the main table, then the side table for the misses),
  ``new keys`` (``_intern_unique``: slots for the misses, the side
  table's insert and its merge; before PR 51 the search and the two
  ``np.insert`` over the whole table were there), ``sweep``
  (``_sweep``: the dead keys out of both tables), ``rows``
  (``intern_rows``' own: the selection's gather, the scatter back to
  stream order, the stamps). A method the encoder lacks is left out, so
  the script reads an older encoder too;
* the primitives alone on the last batch at the table's size: the
  argsort in each kind, ``np.unique``, a search of the sorted and of the
  unsorted batch in the sorted keys, the stamps, and the encoder's own
  ``_search`` at several sizes of its block;
* ``checksum``: a CRC of every batch's codes, the warm ones too, with
  ``len()``, ``live`` and ``stats``: two encoders that print the same
  line handed out the same codes, batch for batch.

Usage: python scripts/profile_intern.py [batches [warm [seed [first]]]]
(``tiny`` as the first argument: 4 expressways, for a rehearsal).
``first`` is the stream's batch the replay starts at (0): the trips'
numbers spread as the stream goes on (three distinct in a batch at
round 14, seven at round 100, where the cell's window ends: batch 600),
so a batch's ``vid``s interleave more and a merge sort of them costs
more; the table is full six batches after any start. On the
chip tool's machine it reads that host's CPU (about twice this
sandbox's); it touches no device.
"""

from __future__ import annotations

import collections
import json
import os
import statistics
import sys
import time
import zlib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "benchmark"))

import numpy as np  # noqa: E402

from flink_siddhi_tpu.runtime.executor import _keep_heap_warm  # noqa: E402
from flink_siddhi_tpu.schema.encoders import GroupEncoder  # noqa: E402

TICK_MS, RETAIN = 30_000, 4  # @purge(interval 30 s, idle.period 90 s)
PARTS = (
    ("_sorted_runs", "sort"),
    ("_find", "table search"),
    ("_intern_unique", "new keys"),
    ("_sweep", "sweep"),
    ("intern_rows", "rows"),
)


class PartClock:
    """Self time of the wrapped methods: a call's time less that of the
    wrapped calls inside it."""

    def __init__(self) -> None:
        self.ms = collections.defaultdict(float)
        self._inner = []

    def wrap(self, cls, name, part) -> None:
        static = isinstance(cls.__dict__[name], staticmethod)
        fn = getattr(cls, name)

        def timed(*args, **kw):
            t0 = time.perf_counter()
            self._inner.append(0.0)
            try:
                return fn(*args, **kw)
            finally:
                dt = time.perf_counter() - t0
                self.ms[part] += (dt - self._inner.pop()) * 1e3
                if self._inner:
                    self._inner[-1] += dt

        setattr(cls, name, staticmethod(timed) if static else timed)


def key_stream(seed, tiny):
    from bmlib.cell import load_cell, make_pool

    _, cfg, params = load_cell("linear_road_rows4.replay")
    if tiny:
        cfg = dict(cfg, expressways=4, batch=cfg["batch"] // 16)
    batch = int(cfg["batch"])
    pool = make_pool(cfg, seed, params["pool_batches"] * batch)

    def batch_of(j):
        cols = pool.columns(j * batch, (j + 1) * batch,
                            ("type", "vid", "time"))
        return (cols["vid"], cols["type"] == 0,
                cols["time"] - cfg["base_time_ms"])

    return batch_of


def best_ms(fn, repeats=5):
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e3)
    return min(times)


def primitives(enc, vid, select, codes):
    vals = vid[select]
    # the live keys, sorted, as array mode keeps them (through the
    # snapshot: a few seconds, and no private name)
    table = np.sort(np.asarray(
        [v[0] for v in enc.state_dict()["values"] if v is not None],
        dtype=vals.dtype))
    sv = np.sort(vals)
    slots = np.where(codes[select] < 0, ~codes[select], codes[select])
    stamps = np.zeros(len(enc), dtype=np.int64)
    out = np.zeros(len(select), dtype=np.int32)

    def stamp():
        stamps[slots] = 7

    def scatter():
        out[select] = slots

    for name, fn in (
        ("argsort", lambda: np.argsort(vals)),
        ("argsort[stable]", lambda: np.argsort(vals, kind="stable")),
        ("sort", lambda: np.sort(vals)),
        ("unique", lambda: np.unique(vals)),
        ("search[sorted batch in table]",
         lambda: np.searchsorted(table, sv)),
        ("search[batch in sorted batch]",
         lambda: np.searchsorted(sv, vals)),
        ("select[gather]", lambda: vid[select]),
        ("scatter[out[select] = slots]", scatter),
        ("stamps", stamp),
    ):
        print(f"  {name} {best_ms(fn):.2f} ms", flush=True)
    if hasattr(GroupEncoder, "SEARCH_BLOCK"):
        # the table search as the encoder makes it, by the block's size
        shipped, places = GroupEncoder.SEARCH_BLOCK, np.arange(
            len(table), dtype=np.int32)
        for block in (1 << 10, 1 << 12, 1 << 14, 1 << 30):
            GroupEncoder.SEARCH_BLOCK = block
            ms = best_ms(lambda: GroupEncoder._search(table, places, sv))
            print(f"  search[blocks of {block}"
                  f"{', shipped' if block == shipped else ''}] {ms:.2f} ms",
                  flush=True)
        GroupEncoder.SEARCH_BLOCK = shipped


def main(argv):
    tiny = bool(argv) and argv[0] == "tiny"
    nums = [int(a) for a in argv if a != "tiny"]
    batches, warm, seed, first = (
        nums + [48, 30, 2_345_678_901, 0][len(nums):])[:4]
    _keep_heap_warm()  # as in a job: numpy's temporaries from a kept heap
    batch_of = key_stream(seed, tiny)
    clock = PartClock()
    for name, part in PARTS:
        if hasattr(GroupEncoder, name):
            clock.wrap(GroupEncoder, name, part)
    enc = GroupEncoder(retain_ticks=RETAIN, mark_new=True)
    crc, took, swept = 0, [], []
    for j in range(warm + batches):
        vid, select, ticks = batch_of(first + j)
        if j == warm:
            clock.ms.clear()
        before = enc.stats["expired"]
        t0 = time.perf_counter()
        codes = enc.intern_rows([vid], select, ticks, TICK_MS)
        dt = (time.perf_counter() - t0) * 1e3
        crc = zlib.crc32(codes.tobytes(), crc)
        if j >= warm:
            took.append(dt)
            swept.append(enc.stats["expired"] != before)
    with_sweep = [t for t, s in zip(took, swept) if s]
    without = [t for t, s in zip(took, swept) if not s]
    print(f"batches {batches} after {warm}, {int(select.sum())} rows "
          f"selected a batch, {enc.live} keys live of {len(enc)} slots")
    print(f"intern_rows {statistics.median(took):.2f} ms a batch (median; "
          f"mean {statistics.fmean(took):.2f}; "
          f"{statistics.median(with_sweep) if with_sweep else 0:.2f} with "
          f"a sweep, {len(with_sweep)} batches; "
          f"{statistics.median(without) if without else 0:.2f} without)")
    print("by part, mean ms a batch:")
    for part, ms in sorted(clock.ms.items(), key=lambda kv: -kv[1]):
        print(f"  {part} {ms / batches:.2f} ms", flush=True)
    print("primitives alone, best of 5:")
    primitives(enc, vid, select, codes)
    print(json.dumps({
        "checksum": f"{crc:08x}", "len": len(enc), "live": enc.live,
        "stats": enc.stats, "batches": warm + batches, "seed": seed,
        "first": first,
    }))


if __name__ == "__main__":
    main(sys.argv[1:])
