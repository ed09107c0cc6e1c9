"""Real-TPU smoke lane: result-ASSERTING runs on the actual chip.

Everything else in tests/ runs on the virtual CPU mesh, so
f32/Pallas-lowering divergence on hardware would go unseen there
(chip_smoke.py checks the served path at deployment size; the
benchmark's cells decide ``correct``). This 5-minute lane runs the
headline pattern, a sliding window aggregation, and a join at small N
against the same Python oracles the CPU tests use, with Pallas COMPILED
(not interpreted).

Invocation (one process per chip — see .claude/skills/verify):

    FST_TPU_SMOKE=1 python -m pytest -m tpu tests/ -q

Without FST_TPU_SMOKE=1 the lane is deselected (tests/conftest.py); with
it, a missing accelerator FAILS every test — a smoke lane that skips
its way to exit 0 has checked nothing.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.tpu

from flink_siddhi_tpu.compiler.config import EngineConfig  # noqa: E402
from flink_siddhi_tpu.compiler.plan import compile_plan  # noqa: E402
from flink_siddhi_tpu.runtime.executor import Job  # noqa: E402
from flink_siddhi_tpu.runtime.sources import BatchSource  # noqa: E402
from flink_siddhi_tpu.schema.batch import EventBatch  # noqa: E402
from flink_siddhi_tpu.schema.stream_schema import StreamSchema  # noqa: E402
from flink_siddhi_tpu.schema.types import AttributeType  # noqa: E402

SCHEMA = StreamSchema(
    [("id", AttributeType.INT), ("price", AttributeType.DOUBLE),
     ("timestamp", AttributeType.LONG)]
)


@pytest.fixture(scope="module")
def on_tpu():
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        pytest.fail(
            "FST_TPU_SMOKE=1 but jax.devices()[0].platform == 'cpu': "
            "the smoke lane needs the accelerator"
        )
    return dev


def _batches(n, batch, seed=7, n_ids=6):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, n_ids, n).astype(np.int32)
    prices = np.round(rng.random(n) * 100, 3)
    ts = (1000 + np.arange(n)).astype(np.int64)
    return ids, prices, ts, [
        EventBatch(
            "S", SCHEMA,
            {"id": ids[s:s + batch], "price": prices[s:s + batch],
             "timestamp": ts[s:s + batch]},
            ts[s:s + batch],
        )
        for s in range(0, n, batch)
    ]


def _run(cql, batches, batch, config=None):
    plan = compile_plan(cql, {"S": SCHEMA}, config=config)
    job = Job(
        [plan], [BatchSource("S", SCHEMA, iter(batches))],
        batch_size=batch, time_mode="processing",
    )
    job.run()
    return job


def test_headline_pattern_matches_oracle_on_device(on_tpu):
    ids, prices, ts, batches = _batches(4096, 1024)
    cql = (
        "from every s1 = S[id == 1] -> s2 = S[id == 2] -> "
        "s3 = S[id == 3] within 5 sec "
        "select s1.timestamp as t1, s3.timestamp as t3, "
        "s3.price as price insert into m"
    )
    job = _run(
        cql, batches, 1024,
        EngineConfig(lazy_projection=True, pred_pushdown=True),
    )
    rows = sorted(job.results("m"))
    # per-event oracle (the JVM engine's partial-match walk)
    partials, exp = [], []
    for i in range(len(ids)):
        nxt = []
        for step, t1, _caps in partials:
            if ts[i] - t1 > 5000:
                continue
            want = (2, 3)[step - 1]
            if ids[i] == want:
                if step == 2:
                    exp.append((int(t1), int(ts[i]), float(prices[i])))
                    continue
                nxt.append((step + 1, t1, None))
            else:
                nxt.append((step, t1, _caps))
        partials = nxt
        if ids[i] == 1:
            partials.append((1, ts[i], None))
    exp.sort()
    assert len(rows) == len(exp) > 0
    for (t1, t3, p), (et1, et3, ep) in zip(rows, exp):
        assert (t1, t3) == (et1, et3)
        assert p == pytest.approx(ep, rel=1e-6)


def test_window_groupby_matches_oracle_on_device(on_tpu):
    ids, prices, ts, batches = _batches(3000, 1024)
    cql = (
        "from S#window.length(100) select id, sum(price) as s, "
        "count() as c group by id insert into o"
    )
    job = _run(cql, batches, 1024)
    rows = job.results("o")
    hist = []
    exp = []
    for i in range(len(ids)):
        hist.append((int(ids[i]), float(prices[i])))
        win = hist[-100:]
        mine = [p for k, p in win if k == ids[i]]
        exp.append((int(ids[i]), sum(mine), len(mine)))
    assert len(rows) == len(exp)
    for (k, s, c), (ek, es, ec) in zip(rows, exp):
        assert (k, c) == (ek, ec)
        assert s == pytest.approx(es, rel=1e-4)


def test_join_matches_oracle_on_device(on_tpu):
    t_schema = StreamSchema(
        [("id", AttributeType.INT), ("qty", AttributeType.INT),
         ("timestamp", AttributeType.LONG)]
    )
    rng = np.random.default_rng(5)
    n = 512
    ids_s = rng.integers(0, 4, n).astype(np.int32)
    prices = np.round(rng.random(n) * 10, 2)
    ts_s = (1000 + 2 * np.arange(n)).astype(np.int64)
    ids_t = rng.integers(0, 4, n).astype(np.int32)
    qty = rng.integers(1, 9, n).astype(np.int32)
    ts_t = (1001 + 2 * np.arange(n)).astype(np.int64)
    sb = [EventBatch("S", SCHEMA,
                     {"id": ids_s, "price": prices, "timestamp": ts_s},
                     ts_s)]
    tb = [EventBatch("T", t_schema,
                     {"id": ids_t, "qty": qty, "timestamp": ts_t},
                     ts_t)]
    cql = (
        "from S#window.length(8) join T#window.length(8) "
        "on S.id == T.id "
        "select S.timestamp as st, T.timestamp as tt insert into j"
    )
    plan = compile_plan(cql, {"S": SCHEMA, "T": t_schema})
    job = Job(
        [plan],
        [BatchSource("S", SCHEMA, iter(sb)),
         BatchSource("T", t_schema, iter(tb))],
        batch_size=2048, time_mode="processing",
    )
    job.run()
    got = sorted(job.results("j"))
    # oracle: merged arrival order; each arrival pairs against the
    # other side's last-8 ring
    events = sorted(
        [(int(t), "S", int(i)) for t, i in zip(ts_s, ids_s)]
        + [(int(t), "T", int(i)) for t, i in zip(ts_t, ids_t)]
    )
    ring = {"S": [], "T": []}
    exp = []
    for t, side, k in events:
        other = "T" if side == "S" else "S"
        for (ot, ok) in ring[other][-8:]:
            if ok == k:
                exp.append((t, ot) if side == "S" else (ot, t))
        ring[side].append((t, k))
    exp.sort()
    assert got == exp and len(got) > 0


def test_pallas_compiled_not_interpreted(on_tpu):
    # the chain core's Pallas reverse-cummin must COMPILE on hardware,
    # alone and under shard_map (both probes raise on a Mosaic failure
    # or an oracle mismatch; they return False only off the TPU)
    from flink_siddhi_tpu.compiler import pallas_ops

    assert pallas_ops.mode() == "compiled", pallas_ops.mode()
    assert pallas_ops.warmup()
    assert pallas_ops.warmup_shard()


def test_session_window_fold_and_close_on_device(on_tpu):
    # the session window's vectorised fold (scatters over slot codes)
    # and its close on the stream's clock (compiler/session_window.py),
    # on real hardware; a batch here holds several sessions of a key
    ids = np.array([0, 1, 0, 0, 1, 0, 1, 1], dtype=np.int32)
    ts = np.array(
        [1000, 1002, 1005, 1040, 1041, 1100, 1101, 1150],
        dtype=np.int64,
    )
    prices = np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0])
    batches = [
        EventBatch(
            "S", SCHEMA,
            {"id": ids[s:s + 4], "price": prices[s:s + 4],
             "timestamp": ts[s:s + 4]},
            ts[s:s + 4],
        )
        for s in range(0, 8, 4)
    ]
    job = _run(
        "from S#window.session(10 ms, id) "
        "select id, sum(price) as s, count() as c insert into o",
        batches, 4,
    )
    rows = sorted(job.results("o"))
    expect = sorted([
        (0, 4.0, 2), (0, 4.0, 1), (0, 6.0, 1),
        (1, 2.0, 1), (1, 5.0, 1), (1, 7.0, 1), (1, 8.0, 1),
    ])
    assert len(rows) == len(expect)
    for (k, s, c), (ek, es, ec) in zip(rows, expect):
        assert (k, c) == (ek, ec)
        assert s == pytest.approx(es, rel=1e-4)


def test_sharded_step_on_device(on_tpu):
    # the shard_map'd step (stacked state + collectives) compiled and
    # executed on the real chip — a 1-device mesh exercises the same
    # program the virtual 8-device CPU mesh runs
    from flink_siddhi_tpu.parallel import ShardedJob

    ids, prices, ts, batches = _batches(2048, 512)
    cql = (
        "from every s1 = S[id == 1] -> s2 = S[id == 2] "
        "select s1.timestamp as t1, s2.timestamp as t2 insert into o"
    )
    sj = ShardedJob(
        [compile_plan(cql, {"S": SCHEMA})],
        [BatchSource("S", SCHEMA, iter(batches))],
        n_shards=1, batch_size=512, time_mode="processing",
    )
    sj.run()
    got = sorted(sj.results("o"))
    # oracle: every-restart 2-step chain
    partials, exp = [], []
    for i in range(len(ids)):
        nxt = []
        for t1 in partials:
            if ids[i] == 2:
                exp.append((int(t1), int(ts[i])))
            else:
                nxt.append(t1)
        partials = nxt
        if ids[i] == 1:
            partials.append(ts[i])
    assert got == sorted(exp) and got


def test_checkpoint_roundtrip_on_device(on_tpu, tmp_path):
    # device state snapshot mid-stream -> fresh job -> identical tail
    ids, prices, ts, batches = _batches(4096, 512)
    cql = (
        "from S#window.length(64) select id, sum(price) as s "
        "group by id insert into o"
    )

    def build(bs):
        plan = compile_plan(cql, {"S": SCHEMA})
        return Job(
            [plan], [BatchSource("S", SCHEMA, iter(bs))],
            batch_size=512, time_mode="processing",
        )

    solo = build(batches)
    solo.run()
    expect = solo.results("o")

    job1 = build(batches)
    job1.run(max_cycles=4)
    assert not job1.finished
    ck = str(tmp_path / "ck")
    job1.save_checkpoint(ck)
    head = job1.results("o")
    job2 = build(batches[4:])
    job2.restore(ck)
    job2.run()
    got = head + job2.results("o")
    assert len(got) == len(expect) == 4096
    for (k, s), (ek, es) in zip(got, expect):
        assert k == ek
        assert s == pytest.approx(es, rel=1e-5)


def test_resident_replay_on_device(on_tpu):
    # the bounded-replay scan (the bench's execution mode) against the
    # streaming path ON HARDWARE — row-identical
    from flink_siddhi_tpu.runtime.replay import ResidentReplay

    ids, prices, ts, batches = _batches(4096, 1024)
    cql = (
        "from every s1 = S[id == 1] -> s2 = S[id == 2] -> "
        "s3 = S[id == 3] within 5 sec "
        "select s1.timestamp as t1, s3.timestamp as t3 insert into m"
    )
    cfg = EngineConfig(lazy_projection=True, pred_pushdown=True)
    a = _run(cql, list(batches), 1024, cfg)
    plan = compile_plan(cql, {"S": SCHEMA}, config=cfg)
    b = Job(
        [plan], [BatchSource("S", SCHEMA, iter(batches))],
        batch_size=1024, time_mode="processing",
    )
    ResidentReplay(b).execute()
    ra, rb = a.results_with_ts("m"), b.results_with_ts("m")
    assert sorted(ra) == sorted(rb) and ra
