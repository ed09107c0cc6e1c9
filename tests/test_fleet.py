"""Serving fleet (flink_siddhi_tpu/fleet/, docs/fleet.md): the
persistent warm-start compile store, the commit-log exactly-once
account, the key-hash router, and the rolling-restart protocol.

The two headline properties pinned here:

* **cross-process zero-lowering warm start** — a store written by
  process A lets process B restore a 20-tenant fleet and serve rows
  with ``metrics()["compiles"]["total_lowerings"] == 0``, and the two
  processes agree byte-for-byte on every store key (the PR 11
  fresh-subprocess signature property extended to the disk tier);
* **rolling restart exactness** — replacing a replica under sustained
  load keeps every admitted tenant live and keeps the committed output
  row-exact against an unfaulted in-process oracle (0 duplicated,
  0 lost), with the handoff journaled.
"""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

from flink_siddhi_tpu.app.service import ControlQueueSource
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.control import AdmissionGate, ControlPlane
from flink_siddhi_tpu.fleet.commitlog import (
    CommitLogSink,
    read_committed,
)
from flink_siddhi_tpu.fleet.router import (
    FleetRouter,
    hash_route,
    label_prometheus,
)
from flink_siddhi_tpu.fleet.warmstore import (
    WarmStartStore,
    aval_signature,
    store_key_dir,
    store_namespace,
)
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.sources import CallbackSource
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# XLA's CPU backend cannot serialize again an executable that it loaded
# from the persistent compilation cache: the copy loads and then fails
# at its first result ("Function wrapped_iota not found"). The suite's
# cache (tests/conftest.py) keeps what took two seconds to compile, so
# under a loaded run a program of this file would cross that line, be
# kept, and break the store's copy of it in the next process or test.
# The store is the tier under test here: it gets compiled executables.
_NO_XLA_CACHE = {"JAX_ENABLE_COMPILATION_CACHE": "0"}


@pytest.fixture(autouse=True)
def _no_persistent_compile_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


SCHEMA = StreamSchema([
    ("id", AttributeType.INT),
    ("price", AttributeType.DOUBLE),
    ("timestamp", AttributeType.LONG),
])


def compiler(cql, pid):
    return compile_plan(cql, {"S": SCHEMA}, plan_id=pid)


def chain_cql(a, b):
    return (
        f"from every s1 = S[id == {a}] -> s2 = S[id == {b}] "
        "within 60 sec "
        "select s1.timestamp as t1, s2.timestamp as t2 "
        "insert into out"
    )


class Rec:
    def __init__(self, id, price, timestamp):
        self.id, self.price, self.timestamp = id, price, timestamp


# -- the warm store: keys, signatures, fallback ------------------------------


def test_store_key_dir_is_deterministic_and_fs_safe():
    plan = compiler(chain_cql(0, 1), "q0")
    from flink_siddhi_tpu.control.aotcache import cache_key

    key = cache_key(plan)
    assert key is not None
    d1, d2 = store_key_dir(key), store_key_dir(key)
    assert d1 == d2
    assert "/" not in d1 and d1.startswith(key[0] + "-")
    ns = store_namespace()
    assert "/" not in ns and " " not in ns
    # the namespace pins platform + device population + jax version:
    # an executable serialized for another world must not be offered
    import jax

    assert str(jax.device_count()) in ns or f"n{jax.device_count()}" \
        in ns


def test_aval_signature_splits_on_shape_and_dtype():
    import numpy as np

    a = {"x": np.zeros((4, 2), np.float32)}
    b = {"x": np.zeros((4, 2), np.float32)}
    c = {"x": np.zeros((4, 3), np.float32)}
    d = {"x": np.zeros((4, 2), np.int32)}
    assert aval_signature((a,)) == aval_signature((b,))
    assert aval_signature((a,)) != aval_signature((c,))
    assert aval_signature((a,)) != aval_signature((d,))


def test_warm_slot_falls_back_to_wrapper_on_broken_executable(
    tmp_path,
):
    """A deserialized executable that rejects its inputs must degrade
    to the live jit wrapper (counted as a store error), never poison
    results."""
    from flink_siddhi_tpu.fleet.warmstore import WarmSlot

    store = WarmStartStore(str(tmp_path))
    calls = []

    def wrapper(x):
        calls.append(x)
        return x + 1

    class Broken:
        def __call__(self, *a):
            raise TypeError("wrong aval")

    slot = WarmSlot(wrapper, store, ("dyn", "sig"), "jitted_seg")
    sig = aval_signature((3,))
    slot.adopt(sig, Broken())
    assert slot(3) == 4
    assert calls == [3]
    assert store.stats()["errors"] == 1


# -- store eviction: size-bounded LRU + corrupt-entry sweep ------------------


def _seed_store_entry(store, name, sig, nbytes, age_s):
    """Fabricate an on-disk store entry (valid pickled triple) whose
    newest-file mtime is ``age_s`` seconds in the past."""
    import pickle

    kd = os.path.join(store._dir, name)
    os.makedirs(kd, exist_ok=True)
    path = os.path.join(kd, f"jitted_seg@{sig}.exe")
    with open(path, "wb") as f:
        pickle.dump((b"x" * nbytes, None, None), f)
    t = time.time() - age_s
    os.utime(path, (t, t))
    return path


def test_warm_store_gc_evicts_lru_under_byte_budget(tmp_path):
    store = WarmStartStore(str(tmp_path))
    _seed_store_entry(store, "k-old", "s1", 1000, 300)
    _seed_store_entry(store, "k-mid", "s1", 1000, 200)
    _seed_store_entry(store, "k-new", "s1", 1000, 100)
    out = store.gc(max_bytes=2500)
    assert out["evicted"] == 1 and out["corrupt_removed"] == 0
    assert sorted(os.listdir(store._dir)) == ["k-mid", "k-new"]
    assert out["bytes"] <= 2500
    assert store.stats()["evictions"] == 1
    # idempotent: already under budget → nothing further
    assert store.gc(max_bytes=2500)["evicted"] == 0


def test_warm_store_gc_entry_count_bound(tmp_path):
    store = WarmStartStore(str(tmp_path))
    for i, age in enumerate((400, 300, 200, 100)):
        _seed_store_entry(store, f"k-{i}", "s1", 10, age)
    out = store.gc(max_entries=2)
    assert out["evicted"] == 2 and out["kept"] == 2
    assert sorted(os.listdir(store._dir)) == ["k-2", "k-3"]


def test_warm_store_gc_sweeps_corrupt_and_torn_entries(tmp_path):
    """Unreadable ``.exe`` payloads and leftover ``.tmp-<pid>`` files
    are removed regardless of budget; an emptied key dir disappears;
    every removal is counted and journaled with a reason."""
    from flink_siddhi_tpu.telemetry.flightrec import FlightRecorder

    store = WarmStartStore(str(tmp_path))
    frec = FlightRecorder()
    store.bind_flightrec(frec)
    keep = _seed_store_entry(store, "k-good", "s1", 100, 100)
    bad = os.path.join(store._dir, "k-bad")
    os.makedirs(bad)
    with open(os.path.join(bad, "jitted_seg@sX.exe"), "wb") as f:
        f.write(b"\x00not-a-pickle")
    with open(keep + ".tmp-99999", "wb") as f:
        f.write(b"torn write")
    out = store.gc()  # no budget: sweep only
    assert out["evicted"] == 0 and out["corrupt_removed"] == 2
    assert sorted(os.listdir(store._dir)) == ["k-good"]
    assert store.stats()["evictions"] == 2
    evs = [e for e in frec.events() if e["kind"] == "fleet.warm_evict"]
    assert len(evs) == 2
    assert {e["reason"] for e in evs} == {"corrupt"}


def test_warm_store_gc_lru_eviction_is_journaled(tmp_path):
    from flink_siddhi_tpu.telemetry.flightrec import FlightRecorder

    store = WarmStartStore(str(tmp_path))
    frec = FlightRecorder()
    store.bind_flightrec(frec)
    _seed_store_entry(store, "k-a", "s1", 500, 200)
    _seed_store_entry(store, "k-b", "s1", 500, 100)
    store.gc(max_entries=1)
    evs = [e for e in frec.events() if e["kind"] == "fleet.warm_evict"]
    assert len(evs) == 1
    assert evs[0]["reason"] == "lru" and evs[0]["entry"] == "k-a"
    assert evs[0]["bytes"] > 0


def test_warm_store_gc_evicted_key_recompiles_as_cold_miss(tmp_path):
    """The never-wrong contract: after eviction a lookup is an ordinary
    miss — the slot compiles live and re-persists, results unchanged."""
    import jax

    from flink_siddhi_tpu.fleet.warmstore import WarmSlot

    store = WarmStartStore(str(tmp_path))
    wrapper = jax.jit(lambda x: x + 1)
    slot = WarmSlot(wrapper, store, ("dyn", "sig-gc"), "jitted_seg")
    assert slot(3) == 4  # cold miss, compiles via wrapper
    out = store.gc(max_entries=0)
    assert store.stats()["evictions"] == out["evicted"]
    slot2 = WarmSlot(wrapper, store, ("dyn", "sig-gc"), "jitted_seg")
    assert slot2(3) == 4
    assert store.stats()["misses"] >= 2  # second cold miss, not a hit


# -- the commit log: two-phase exactness across handoffs ---------------------


def test_commitlog_two_phase_commit_and_read_back(tmp_path):
    path = str(tmp_path / "commit.log")
    sink = CommitLogSink(path, "out")
    sink(1000, (1, 2))
    sink(1001, (3, 4))
    assert sink.next_epoch() == 0
    sink.prepare_commit()
    assert sink.next_epoch() == 0  # pending epoch, not yet advanced
    sink.commit_transaction()
    assert sink.next_epoch() == 1
    sink(1002, (5, 6))
    sink.prepare_commit()
    sink.commit_transaction()
    rows = read_committed(path, "out")
    assert rows == [(1000, (1, 2)), (1001, (3, 4)), (1002, (5, 6))]
    st = sink.txn_stats()
    assert st["commits"] == 2 and st["committed_rows"] == 3


def test_commitlog_resume_is_exactly_once_both_crash_windows(
    tmp_path,
):
    """Crash between snapshot and append → the successor appends the
    promised epoch (zero lost). Crash after the append → the successor
    finds the epoch present and skips (zero duplicated). Either way
    the lineage row counter includes the epoch."""
    path = str(tmp_path / "commit.log")
    sink = CommitLogSink(path, "out")
    sink(1000, (1, 2))
    sink.prepare_commit()
    snap = sink.state_dict()  # the snapshot that rode the checkpoint
    # window 1: crash BEFORE the append — log is empty
    successor = CommitLogSink(path, "out")
    successor.load_state_dict(snap)
    assert read_committed(path, "out") == [(1000, (1, 2))]
    assert successor.committed_rows == 1
    assert successor.resumed == 1
    assert successor.next_epoch() == 1
    # window 2: crash AFTER the append — same snapshot, epoch now in
    # the log: the resume must NOT append again
    successor2 = CommitLogSink(path, "out")
    successor2.load_state_dict(snap)
    assert read_committed(path, "out") == [(1000, (1, 2))]
    assert successor2.committed_rows == 1
    assert successor2.next_epoch() == 1


def test_commitlog_abort_discards_uncommitted_only(tmp_path):
    path = str(tmp_path / "commit.log")
    sink = CommitLogSink(path, "out")
    sink(1000, (1, 2))
    sink.prepare_commit()
    sink.commit_transaction()
    sink(2000, (9, 9))
    sink.abort_transaction()
    assert read_committed(path, "out") == [(1000, (1, 2))]


def test_read_committed_skips_torn_tail_line(tmp_path):
    path = str(tmp_path / "commit.log")
    sink = CommitLogSink(path, "out")
    sink(1000, (1, 2))
    sink.prepare_commit()
    sink.commit_transaction()
    with open(path, "a") as f:
        f.write('{"epoch": 1, "streams": {"out": [[2, [')  # torn
    assert read_committed(path, "out") == [(1000, (1, 2))]


# -- the router: hashing, label injection ------------------------------------


def test_hash_route_is_deterministic_and_covers_slots():
    assert hash_route("k", 4) == hash_route("k", 4)
    assert hash_route(b"k", 4) == hash_route("k", 4)
    hits = {hash_route(str(i), 4) for i in range(64)}
    assert hits == {0, 1, 2, 3}
    assert all(0 <= hash_route(str(i), 3) < 3 for i in range(32))


def test_hash_route_matches_sha256_spec():
    import hashlib

    want = int.from_bytes(
        hashlib.sha256(b"42").digest()[:8], "big"
    ) % 5
    assert hash_route("42", 5) == want


def test_label_prometheus_injects_replica_label():
    text = (
        "# HELP fst_x c\n"
        "# TYPE fst_x counter\n"
        "fst_x_total 3\n"
        'fst_y{a="b"} 1 17\n'
        "other_metric 9\n"
    )
    out = label_prometheus(text, "r0")
    assert 'fst_x_total{replica="r0"} 3' in out
    assert 'fst_y{a="b",replica="r0"} 1 17' in out
    assert "other_metric 9" in out  # non-fst lines pass through
    assert "# HELP fst_x c" in out


# -- fleet status surfaces ---------------------------------------------------


def _make_job(src, ctrl, store=None):
    job = Job(
        [], [src], batch_size=64, time_mode="processing",
        control_sources=[ctrl], plan_compiler=compiler,
    )
    if store is not None:
        job.bind_warm_store(store)
    return job


def test_first_row_clock_stamps_the_boot_account_once():
    """The serving half of cold-start-to-first-row (the old harness's
    fleet driver was its one reader): the first row stamps
    ``first_row_s`` into the replica's boot account, later rows leave
    it alone."""
    from flink_siddhi_tpu.fleet.bootstrap import FirstRowClock

    boot = {"warm_store": True}
    clock = FirstRowClock(time.monotonic(), boot)
    assert "first_row_s" not in boot
    clock(1_000, (1, 2.0))
    first = boot["first_row_s"]
    assert first >= 0
    clock(2_000, (3, 4.0))
    assert boot == {"warm_store": True, "first_row_s": first}


def test_fleet_block_absent_outside_a_fleet():
    """Single-process jobs keep their payloads unchanged: no store, no
    replica identity → fleet is None everywhere it is surfaced."""
    src, ctrl = CallbackSource("S", SCHEMA), ControlQueueSource()
    job = _make_job(src, ctrl)
    assert job.fleet_status() is None
    assert job.metrics()["fleet"] is None
    assert "fst_fleet_" not in job.openmetrics()


def test_fleet_status_and_openmetrics_inside_a_fleet(tmp_path):
    src, ctrl = CallbackSource("S", SCHEMA), ControlQueueSource()
    store = WarmStartStore(str(tmp_path / "store"))
    job = _make_job(src, ctrl, store)
    job.set_replica_info("r7", boot={"warm_store": True})
    plane = ControlPlane(job, ctrl, gate=AdmissionGate(compiler))
    plane.admit(chain_cql(0, 1), plan_id="q0", tenant="t0")
    for i in range(6):
        src.emit(Rec(i % 2, float(i), 1000 + i), 1000 + i)
    job.run_cycle()
    job.persist_warm()
    st = job.fleet_status()
    assert st["replica"] == "r7" and st["role"] == "replica"
    assert st["warm_store"]["persists"] >= 1
    assert st["boot"]["warm_store"] is True
    text = job.openmetrics()
    assert 'fst_fleet_replica_info{replica="r7"' in text
    assert "fst_fleet_warm_store_persists_total" in text
    # the store events were journaled with plan scope
    kinds = {e["kind"] for e in job.flightrec.events()}
    assert "fleet.persist" in kinds
    assert "fleet.warm_miss" in kinds
    job.record_handoff(reason="test")
    assert any(
        e["kind"] == "fleet.handoff" for e in job.flightrec.events()
    )
    assert job.fleet_status()["last_handoff"]["reason"] == "test"


def test_fleet_epoch_and_handoff_ride_the_checkpoint(tmp_path):
    src, ctrl = CallbackSource("S", SCHEMA), ControlQueueSource()
    job = _make_job(src, ctrl)
    job.set_replica_info("r1")
    job._fleet_epoch = 7
    job.record_handoff(reason="drain")
    ckpt = str(tmp_path / "ckpt")
    job.save_checkpoint(ckpt)
    src2, ctrl2 = CallbackSource("S", SCHEMA), ControlQueueSource()
    job2 = _make_job(src2, ctrl2)
    job2.restore(ckpt)
    assert job2._fleet_epoch == 7
    assert job2._last_handoff["reason"] == "drain"


@pytest.mark.parametrize("cql", [
    "from S[id == 0] select id, price insert into out",
    "from S select id, price insert into out",
    "partition with (id of S) begin from S#window.length(2) "
    "select id, sum(price) as total insert into out; end",
], ids=["filtered_select", "select", "perkey_window"])
def test_standalone_dynamic_plan_restores_warm_from_store(tmp_path, cql):
    """Regression: a NON-chain dynamic tenant (filter/select — no
    DynamicChainGroup wrap, so it replays through _replay_dynamic's
    standalone branch, not the group loop) must stay cacheable across
    restore: the original admit created it cacheable, and a replica
    bootstrap can only warm it from the persistent store if the replay
    does too. Before the fix the standalone branch replayed via plain
    add_plan (cacheable=False) and the warm store was silently skipped
    for every non-chain tenant.

    The filtered select's append front-compacts through a sort
    (compiler/compact.py), the per-key window's fold sorts by key, and
    both have run by the time of persist_warm; XLA's CPU backend cannot
    serialize an executable whose sort has run, so the store serializes
    each as it is compiled (warmstore.py ``WarmSlot._compile``) and
    nothing here is missed."""
    _persist_and_resume_warm(tmp_path, cql)


def _persist_and_resume_warm(tmp_path, cql, between=lambda store: None):
    """Admit ``cql``, persist its executables and a checkpoint, let
    ``between`` at the store as process A left it, then restore a
    replica from both: no miss, no error, the cold replica's rows.
    Returns the replica's job and store."""
    store_dir = str(tmp_path / "store")
    src, ctrl = CallbackSource("S", SCHEMA), ControlQueueSource()
    job = _make_job(src, ctrl, WarmStartStore(store_dir))
    plane = ControlPlane(job, ctrl, gate=AdmissionGate(compiler))
    plane.admit(cql, plan_id="flt0", tenant="t0")
    for i in range(8):
        src.emit(Rec(i % 2, float(i), 1000 + i), 1000 + i)
    job.run_cycle()
    job.persist_warm()
    assert job.warm_store.stats()["persists"] >= 1
    assert job.warm_store.stats()["errors"] == 0
    ckpt = str(tmp_path / "ckpt")
    job.save_checkpoint(ckpt)

    def resume(store):
        src2, ctrl2 = CallbackSource("S", SCHEMA), ControlQueueSource()
        job2 = _make_job(src2, ctrl2, store)
        job2.restore(ckpt)
        return src2, job2

    # a replica with no store compiles cold: the rows to expect
    src_cold, job_cold = resume(None)
    for i in range(8):
        src_cold.emit(Rec(i % 2, float(i), 2000 + i), 2000 + i)
    job_cold.run_cycle()
    job_cold.drain_outputs()
    want = [tuple(r) for r in job_cold.results("out")]
    assert want

    between(job.warm_store)
    store2 = WarmStartStore(store_dir)
    src2, job2 = resume(store2)
    rt = job2._plans["flt0"]
    assert rt.warm_key is not None  # replayed cacheable → store-wrapped
    # the preload walked the executables process A persisted
    assert store2.stats()["hits"] >= 1
    for i in range(8):
        src2.emit(Rec(i % 2, float(i), 2000 + i), 2000 + i)
    job2.run_cycle()
    job2.drain_outputs()
    assert store2.stats()["misses"] == 0
    # a loaded executable that rejected its inputs would have been
    # counted here and served by the jit wrapper instead
    assert store2.stats()["errors"] == 0
    assert [tuple(r) for r in job2.results("out")] == want
    return job2, store2


def test_a_store_with_the_retired_slots_files_loads_the_rest(tmp_path):
    """A store written before ``jitted`` and ``jitted_acc`` left
    ``SLOT_NAMES`` holds files of those names beside the rest. Nothing
    opens them again (these two could not be unpickled: an error would
    be counted) and the slots that stay load as before."""
    from flink_siddhi_tpu.fleet.warmstore import SLOT_NAMES

    strays = []

    def parent_left_these(store):
        (key_dir,) = [
            os.path.join(store._dir, d) for d in os.listdir(store._dir)
        ]
        for slot in ("jitted", "jitted_acc"):
            strays.append(os.path.join(key_dir, f"{slot}@0123abcd.exe"))
            with open(strays[-1], "wb") as f:
                f.write(b"not an executable")

    job2, store2 = _persist_and_resume_warm(
        tmp_path, "from S[id == 0] select id, price insert into out",
        between=parent_left_these,
    )
    assert strays and all(os.path.exists(p) for p in strays)
    assert SLOT_NAMES == ("jitted_seg", "jitted_init_acc", "jitted_flush")
    # no miss (checked above), so what ran was loaded: the slots the
    # replica's one cycle and drain call
    entry = job2._plans["flt0"].warm_entry
    loaded = {n for n in SLOT_NAMES if getattr(entry, n)._exes}
    assert {"jitted_seg", "jitted_init_acc"} <= loaded
    assert store2.stats()["hits"] >= len(loaded)
    assert not hasattr(entry, "jitted") and not hasattr(entry, "jitted_acc")


# -- the headline: cross-process zero-lowering warm start --------------------


_AB_SCRIPT = r"""
import json, os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {repo!r})

from flink_siddhi_tpu.app.service import ControlQueueSource
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.control import AdmissionGate, ControlPlane
from flink_siddhi_tpu.control.aotcache import cache_key
from flink_siddhi_tpu.fleet.warmstore import (
    WarmStartStore, store_key_dir, store_namespace,
)
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.sources import CallbackSource
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType

SCHEMA = StreamSchema([
    ("id", AttributeType.INT),
    ("price", AttributeType.DOUBLE),
    ("timestamp", AttributeType.LONG),
])

def compiler(cql, pid):
    return compile_plan(cql, {{"S": SCHEMA}}, plan_id=pid)

def chain_cql(a, b):
    return (
        f"from every s1 = S[id == {{a}}] -> s2 = S[id == {{b}}] "
        "within 60 sec select s1.timestamp as t1, s2.timestamp as t2 "
        "insert into out"
    )

class Rec:
    def __init__(self, id, price, timestamp):
        self.id, self.price, self.timestamp = id, price, timestamp

store_dir, ckpt, mode = sys.argv[1], sys.argv[2], sys.argv[3]
src = CallbackSource("S", SCHEMA)
ctrl = ControlQueueSource()
job = Job(
    [], [src], batch_size=64, time_mode="processing",
    control_sources=[ctrl], plan_compiler=compiler,
)
job.bind_warm_store(WarmStartStore(store_dir))
job.set_replica_info("r-" + mode)

if mode == "cold":
    plane = ControlPlane(job, ctrl, gate=AdmissionGate(compiler))
    for t in range(20):
        plane.admit(chain_cql(t % 4, (t + 1) % 4), plan_id=f"q{{t}}",
                    tenant=f"t{{t}}")
    base = 1000
else:
    job.restore(ckpt)
    base = 2000
for i in range(16):
    src.emit(Rec(i % 4, float(i), base + i), base + i)
job.run_cycle()
job.run_cycle()
job.drain_outputs()
if mode == "cold":
    job.persist_warm()
    job.save_checkpoint(ckpt)
m = job.metrics()
keydirs = sorted({{
    store_key_dir(rt.warm_key)
    for rt in job._plans.values()
    if getattr(rt, "warm_key", None) is not None
}})
print(json.dumps({{
    "mode": mode,
    "rows": len(job.results("out")),
    "plans": len(job._plans) + len(job._folded),
    "namespace": store_namespace(),
    "keydirs": keydirs,
    "store": job.warm_store.stats(),
    "compiles": m["compiles"]["total_lowerings"],
    "fleet": m["fleet"],
}}))
"""


def _run_ab(tmp_path, mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **_NO_XLA_CACHE)
    out = subprocess.run(
        [sys.executable, "-c", _AB_SCRIPT.format(repo=REPO),
         str(tmp_path / "store"), str(tmp_path / "ckpt"), mode],
        capture_output=True, text=True, cwd=REPO, timeout=240,
        env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_warm_store_cross_process_zero_lowerings_20_tenants(
    tmp_path,
):
    """THE fleet acceptance pin: process A admits a 20-tenant fleet
    cold (populating the store + checkpoint), then an independent
    process B restores all 20 to live and serves fresh rows with ZERO
    new XLA lowerings — every executable deserialized from the store —
    and the two processes agree on every disk-tier cache key."""
    a = _run_ab(tmp_path, "cold")
    assert a["plans"] >= 20
    assert a["rows"] > 0
    assert a["store"]["persists"] >= 1
    assert a["store"]["errors"] == 0
    assert a["keydirs"], "cold process computed no store keys"

    b = _run_ab(tmp_path, "warm")
    assert b["plans"] == a["plans"]  # every tenant restored to live
    assert b["rows"] > 0  # ... and actually serving
    # the disk tier agreed on keys across independent processes
    assert b["namespace"] == a["namespace"]
    assert b["keydirs"] == a["keydirs"]
    # zero new lowerings, pinned via the attributed compile account
    assert b["compiles"] == 0, b
    assert b["store"]["hits"] >= 1
    assert b["store"]["misses"] == 0
    assert b["store"]["errors"] == 0
    assert b["fleet"]["replica"] == "r-warm"


# -- rolling restart: the dryrun-scale 2-replica tier-1 gate -----------------


def _spawn_replica(root, slot, rid):
    spec = {
        "replica_id": rid,
        "schema": [["id", "int"], ["price", "double"],
                   ["timestamp", "long"]],
        "checkpoint_path": os.path.join(root, f"slot{slot}", "ckpt"),
        "commit_log": os.path.join(root, f"slot{slot}", "commit.log"),
        "store_dir": os.path.join(root, "store"),
        "checkpoint_every_cycles": 1_000_000,
        "checkpoint_interval_s": 0.3,
        "batch_size": 64,
    }
    path = os.path.join(root, f"spec-{rid}.json")
    with open(path, "w") as f:
        json.dump(spec, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", **_NO_XLA_CACHE)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "flink_siddhi_tpu.fleet.replica",
         path],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        text=True, cwd=REPO,
    )
    line = proc.stdout.readline()
    try:
        ready = json.loads(line)
    except ValueError:
        proc.kill()
        raise AssertionError(
            f"replica {rid} did not boot: {line!r} "
            f"{proc.stderr.read()[-2000:]}"
        )
    return proc, ready


def _drain_and_exit(router, slot, proc):
    router.pause(slot)
    router.drain(slot)
    proc.wait(timeout=180)
    return json.loads(proc.stdout.readline() or "{}")


def _http_json(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=15
    ) as resp:
        return json.loads(resp.read())


def test_rolling_restart_two_replicas_row_exact_no_tenant_dropped(
    tmp_path,
):
    """Dryrun-scale 2-replica fleet under sustained feed: slot 0 is
    rolling-restarted mid-stream. Afterwards every admitted tenant is
    live on the successor, and each slot's committed output is
    row-exact (multiset) against an unfaulted single-process oracle
    fed the same partition — 0 duplicated, 0 lost."""
    root = str(tmp_path)
    tenants = 6
    pairs = [(t % 3, (t + 1) % 3) for t in range(tenants)]

    p0, r0 = _spawn_replica(root, 0, "r0")
    p1, r1 = _spawn_replica(root, 1, "r1")
    router = FleetRouter([r0, r1], key_field="id")
    try:
        for t, (a, b) in enumerate(pairs):
            ack = router.admit(
                chain_cql(a, b), plan_id=f"q{t}", tenant=f"t{t}"
            )
            assert ack["id"] == f"q{t}"
            assert set(ack["replicas"]) == {"r0", "r1"}

        def feed(rows):
            conn = socket.create_connection(
                ("127.0.0.1", router.ingest_port), timeout=10
            )
            try:
                conn.sendall(b"".join(
                    json.dumps(r).encode() + b"\n" for r in rows
                ))
            finally:
                conn.close()

        rows_a = [
            {"id": i % 3, "price": float(i), "timestamp": 1000 + i}
            for i in range(48)
        ]
        rows_b = [
            {"id": i % 3, "price": float(i), "timestamp": 2000 + i}
            for i in range(48, 96)
        ]
        feed(rows_a)
        time.sleep(1.5)  # sustained load in flight before the handoff

        # -- rolling restart of slot 0 mid-stream ----------------
        exit0 = _drain_and_exit(router, 0, p0)
        assert exit0["compiles"] >= 0  # clean exit account parsed
        p0b, r0b = _spawn_replica(root, 0, "r0b")
        router.set_replica(0, r0b)
        feed(rows_b)
        time.sleep(1.5)

        # every admitted tenant is live on the successor (poll: the
        # listing reads empty until the restore completes and the
        # supervisor publishes the restored job)
        want = {f"q{t}" for t in range(tenants)}
        deadline = time.monotonic() + 60
        live = {}
        while time.monotonic() < deadline:
            listing = _http_json(r0b["api_port"], "/api/v1/queries")
            live = {q["id"]: q for q in listing["queries"]}
            if want <= set(live):
                break
            time.sleep(0.2)
        assert want <= set(live), sorted(live)
        assert all(
            live[f"q{t}"].get("enabled", True)
            for t in range(tenants)
        )
        # the handoff is journaled on the successor
        health = _http_json(r0b["api_port"], "/api/v1/health")
        assert health["fleet"]["replica"] == "r0b"

        exit1 = _drain_and_exit(router, 1, p1)
        exit0b = _drain_and_exit(router, 0, p0b)
    finally:
        router.close()
        for p in (p0, p1):
            if p.poll() is None:
                p.kill()
        if "p0b" in dir() and p0b.poll() is None:
            p0b.kill()

    # -- row-exactness vs the unfaulted oracle, per partition --------
    all_rows = rows_a + rows_b
    for slot, final_exit in ((0, exit0b), (1, exit1)):
        part = [
            r for r in all_rows
            if hash_route(r["id"], 2) == slot
        ]
        oracle = _oracle_rows(pairs, part)
        log = read_committed(
            os.path.join(root, f"slot{slot}", "commit.log"), "out"
        )
        got = sorted(tuple(row) for _, row in log)
        assert got == sorted(oracle), (
            f"slot {slot}: committed log diverged from the unfaulted "
            f"oracle ({len(got)} vs {len(oracle)} rows)"
        )
        # the lineage counter (rides the checkpoint across the
        # handoff) must equal the log exactly: 0 lost
        lineage = sum(
            s.get("committed_rows", 0)
            for s in final_exit.get("commit", [])
        )
        assert lineage == len(got)


def _oracle_rows(pairs, partition_rows):
    """The unfaulted single-process oracle: one fresh Job fed the
    identical partition, same tenants — its output multiset is the
    ground truth for the commit log."""
    src, ctrl = CallbackSource("S", SCHEMA), ControlQueueSource()
    job = _make_job(src, ctrl)
    plane = ControlPlane(job, ctrl, gate=AdmissionGate(compiler))
    for t, (a, b) in enumerate(pairs):
        plane.admit(chain_cql(a, b), plan_id=f"q{t}", tenant=f"t{t}")
    for r in partition_rows:
        src.emit(
            Rec(r["id"], r["price"], r["timestamp"]), r["timestamp"]
        )
    job.run_cycle()
    job.run_cycle()
    job.drain_outputs()
    return [tuple(row) for row in job.results("out")]
