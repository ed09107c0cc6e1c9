"""Front-compaction that moves nothing where the mask is a prefix and
sorts by the mask where it is not (compiler/compact.py): the helper
against numpy on both branches, alone and as the body of a scan, the
accumulator append around the ``fits`` boundary, the blocked length window
against the per-event interpreter with and without a filter, and the two
counters that say how often the identity engaged. Nothing here is a rate
or a time."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flink_siddhi_tpu.baseline import BaselineEngine
from flink_siddhi_tpu.compiler.compact import batch_rows, front_compact
from flink_siddhi_tpu.compiler.config import EngineConfig
from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.parallel import ShardedJob, make_cep_mesh
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.schema.batch import EventBatch
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType

E = 640
N_VALID = 500


def _masks():
    """name -> (mask, is it a prefix). ``disabled`` is what a plan whose
    ``enabled`` flag is off hands over: its mask is all false, and the
    empty mask is the prefix of length 0. From ``nine_rows`` on, what
    only the sort branch meets."""
    iota = np.arange(E)
    valid = iota < N_VALID
    hole = valid.copy()
    hole[123] = False
    nine = np.zeros(E, bool)
    nine[np.arange(9) * 67 + 17] = True
    draws = np.random.default_rng(2).random((3, 4_001))  # no power of two
    sparse, dense = draws[0] < 0.001, draws[2] < 0.999
    sparse[-1], dense[5] = True, False  # neither is empty nor a prefix
    return {
        "full": (np.ones(E, bool), True),
        "proper_prefix": (valid, True),
        "empty": (np.zeros(E, bool), True),
        "one_hole": (hole, False),
        "suffix": (iota >= E - N_VALID, False),
        "disabled": (valid & np.asarray(False), True),
        "nine_rows": (nine, False),
        "last_row_alone": (iota == E - 1, False),
        "all_but_the_first": (iota > 0, False),
        "alternating": (iota % 2 == 1, False),
        "random_0.001": (sparse, False),
        "random_0.5": (draws[1] < 0.5, False),
        "random_0.999": (dense, False),
    }


MASKS = _masks()


def _rows(rng, e=E):
    col = rng.standard_normal(e).astype(np.float32)
    col[::7] = -0.0  # a selected -0.0 keeps its sign bit on both branches
    bits = col.view(np.int32)  # and a NaN its sign and payload
    bits[1::5] = 0x7FC12345
    bits[2::11] = -0x3FFFB3  # 0xFFC0004D
    bits[3::13] = 0x7F800001  # signalling
    return {
        "block": rng.integers(-(1 << 31), 1 << 31, (4, e)).astype(np.int32),
        "words": rng.integers(-(1 << 31), 1 << 31, (12, e)).astype(np.int32),
        "col": col,
        "flag": rng.random(e) < 0.5,
    }


def _np_front_compact(mask, rows):
    n = int(mask.sum())
    out = {}
    for k, r in rows.items():
        o = np.zeros_like(r)
        o[..., :n] = r[..., mask]
        out[k] = o
    return n, out


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


def _in_a_scan(masks, rows):
    """Four front-compactions as the body of one scan, the fused
    segment's shape: a step's mask and rows are its slice of ``xs``."""
    return jax.lax.scan(
        lambda _c, x: (None, front_compact(*x)), None, (masks, rows)
    )[1]


@pytest.mark.parametrize("shape", ["jit", "scan_of_four"])
@pytest.mark.parametrize("name", list(MASKS))
def test_the_helper_equals_a_numpy_front_compaction(name, shape):
    mask, prefix = MASKS[name]
    e, rng = len(mask), np.random.default_rng(3)
    assert prefix is np.array_equal(mask, np.arange(e) < mask.sum())
    if shape == "jit":
        masks, steps = [mask], [_rows(rng, e)]
        outs = [jax.jit(front_compact)(jnp.asarray(mask), steps[0])]
    else:  # both branches in one scan where the mask's complement flips
        masks = [mask, ~mask, np.roll(mask, 1), mask]
        steps = [_rows(rng, e) for _ in masks]
        outs = jax.jit(_in_a_scan)(
            jnp.asarray(np.stack(masks)),
            {k: np.stack([r[k] for r in steps]) for k in steps[0]},
        )
        outs = [jax.tree.map(lambda x: x[t], outs) for t in range(4)]
    for t, (m, rows, out) in enumerate(zip(masks, steps, outs)):
        n, got, is_prefix = out
        want_n, want = _np_front_compact(m, rows)
        assert n.dtype == jnp.int32 and int(n) == want_n
        assert bool(is_prefix) is np.array_equal(m, np.arange(e) < want_n)
        for k in rows:
            assert got[k].dtype == rows[k].dtype
            np.testing.assert_array_equal(
                _bits(got[k]), _bits(want[k]), f"{k}, step {t}")


@pytest.mark.parametrize("name", [k for k, v in MASKS.items() if v[1]])
def test_a_prefix_gives_the_scatters_bits(name):
    """The branch the helper skips, computed here as the step computed
    it before: the same block, bit for bit."""
    mask, _ = MASKS[name]
    rows = _rows(np.random.default_rng(4))
    dest = np.where(mask, np.cumsum(mask) - 1, E)
    _n, got, is_prefix = jax.jit(front_compact)(jnp.asarray(mask), rows)
    assert bool(is_prefix)
    for k, r in rows.items():
        scattered = jnp.zeros_like(r).at[..., dest].set(r, mode="drop")
        np.testing.assert_array_equal(_bits(got[k]), _bits(scattered), k)


@pytest.mark.parametrize("name", list(MASKS))
def test_batch_rows_brings_each_selected_row_its_value(name):
    mask, prefix = MASKS[name]
    rng = np.random.default_rng(5)
    e, offset = len(mask), 17
    seqs = {
        "f": rng.standard_normal(offset + e).astype(np.float32),
        "i": rng.integers(0, 1 << 20, offset + e).astype(np.int32),
        "b": rng.random(offset + e) < 0.5,
    }
    got = jax.jit(batch_rows, static_argnums=3)(
        jnp.asarray(mask), jnp.asarray(prefix), seqs, offset
    )
    rank = np.cumsum(mask) - 1
    for k, v in seqs.items():
        assert got[k].dtype == v.dtype and got[k].shape == (e,)
        np.testing.assert_array_equal(
            _bits(got[k])[mask], _bits(v)[offset + rank[mask]], k
        )


# -- the accumulator append -------------------------------------------------

SCHEMA = StreamSchema(
    [("id", AttributeType.INT), ("price", AttributeType.DOUBLE)]
)
SELECT = "from s select id, price insert into out"
WINDOW = (
    "from s#window.length(100) select id, sum(price) as total, "
    "count() as cnt group by id insert into out"
)
WINDOW_FILTERED = (
    "from s[price > 10.0]#window.length(100) select id, sum(price) as "
    "total, count() as cnt group by id insert into out"
)


@pytest.mark.parametrize("prefix", [True, False], ids=["prefix", "holes"])
def test_two_appends_across_the_fits_boundary(prefix):
    """The first block fits, the second does not (it is counted as
    overflow and the buffer keeps the first): ``buf`` and ``meta[0:2]``
    as the append computed them before; rows 2 and 3 count the appends
    and, of them, the ones that scattered nothing."""
    plan = compile_plan(  # the smallest accumulator: 65,536 columns
        SELECT, {"s": SCHEMA}, plan_id="t",
        config=EngineConfig(acc_budget_bytes=1),
    )
    cap, v = plan.acc_capacity(), 40_000
    assert v < cap < 2 * v
    art = plan.artifacts[0]
    assert art.output_mode == "aligned"
    rng = np.random.default_rng(6)
    acc = jax.jit(plan.init_acc)()
    want_buf = np.zeros(acc["buf"].shape, np.int32)
    want_n = want_over = 0
    append = jax.jit(plan._append_outputs)
    for i in range(2):
        mask = np.arange(v) < v - 100 - i
        if not prefix:
            mask &= rng.random(v) < 0.7
        ts = (1_000 + i * v + np.arange(v)).astype(np.int32)
        ids = rng.integers(0, 50, v).astype(np.int32)
        price = rng.random(v).astype(np.float32)
        _s, acc = append({}, acc, {art.name: (mask, ts, (ids, price))})
        n = int(mask.sum())
        if want_n + v <= cap:
            block = np.stack([ts, ids, price.view(np.int32)])[:, mask]
            want_buf[:, want_n:want_n + n] = block
            want_n += n
        else:
            want_over += n
    assert want_over  # the second append straddled the boundary
    meta = np.asarray(acc["meta"])
    assert meta.shape == (6, 1)  # rows 4 and 5: an artifact's own counts
    assert meta[:2, 0].tolist() == [want_n, want_over]
    assert meta[2:, 0].tolist() == [2, 2 if prefix else 0, 0, 0]
    # beyond the count the buffer holds what the block's tail left there
    # (zeros): the whole of it is compared
    np.testing.assert_array_equal(np.asarray(acc["buf"]), want_buf)


# -- the blocked length window against the interpreter ----------------------


def _batches(n_events, batch, stream="s"):
    """Prices are small whole numbers, so that a float32 sum of a hundred
    of them is exact and the interpreter's float64 rows compare equal.
    Every batch holds, before its last row, a price the filter drops."""
    rng = np.random.default_rng(11)
    out = []
    for start in range(0, n_events, batch):
        m = min(batch, n_events - start)
        price = rng.integers(11, 90, m).astype(np.float64)
        price[rng.integers(0, m - 1, max(m // 9, 1))] = 5.0
        price[0] = 5.0
        cols = {"id": rng.integers(0, 10, m).astype(np.int32), "price": price}
        ts = 1_000 + start + np.arange(m, dtype=np.int64)
        out.append(EventBatch(stream, SCHEMA, cols, ts))
    return out


def _interpreter_rows(cql, batches):
    eng, rows = BaselineEngine(cql, ["id", "price"]), []
    eng._emit = lambda out, ts, row: rows.append((ts, row))
    for b in batches:
        eng.run_columns(
            {k: b.columns[k].tolist() for k in ("id", "price")},
            b.timestamps.tolist(),
        )
    return rows


def _counters(job):
    tel = job.telemetry
    return (tel.counter_value("acc.compactions"),
            tel.counter_value("acc.compactions_identity"))


# a window longer than a batch (C > E: the expiries of a batch are all
# the ring's), and int sums on digit planes beside min/max (PR 34: each
# takes the static merge, compiler/window_merge.py)
WINDOW_LONG = WINDOW.replace("length(100)", "length(700)")
WINDOW_INT_MINMAX = (
    "from s#window.length(100) select id, sum(id) as total, min(price) as "
    "lo, max(price) as hi group by id insert into out"
)


@pytest.mark.parametrize("fused", [0, 3], ids=["seg_of_1", "seg_of_3"])
@pytest.mark.parametrize(
    "cql", [WINDOW, WINDOW_FILTERED, WINDOW_LONG, WINDOW_INT_MINMAX],
    ids=["unfiltered", "filtered", "longer_than_a_batch", "int_sum_min_max"],
)
def test_the_blocked_length_window_equals_the_interpreter(cql, fused):
    n_events, batch = 12 * 512, 512  # twelve batches: four whole segments
    plan = compile_plan(cql, {"s": SCHEMA}, plan_id="t")
    assert plan.artifacts[0]._blocked()
    assert plan.artifacts[0].merge_form == "static"
    job = Job(
        [plan], [BatchSource("s", SCHEMA, iter(_batches(n_events, batch)))],
        batch_size=batch, time_mode="processing",
    )
    job.fused_segment_len = fused
    job.run()
    got = [(ts, tuple(row)) for ts, row in job.results_with_ts("out")]
    want = _interpreter_rows(cql, _batches(n_events, batch))
    assert len(want) > n_events // 2
    assert got == want  # row for row, in order
    appends, identity = _counters(job)
    assert appends == 12
    # unfiltered, the tape's valid prefix is the mask; the filter drops a
    # row from the middle of every batch
    assert identity == (0 if cql is WINDOW_FILTERED else 12)


# -- the counters, on one device and on a mesh ------------------------------


def _job(cql, sharded, n_events=8 * 2_048, batch=2_048):
    plan = compile_plan(cql, {"s": SCHEMA}, plan_id="t")
    source = BatchSource("s", SCHEMA, iter(_batches(n_events, batch)))
    if sharded:
        return ShardedJob([plan], [source], mesh=make_cep_mesh(4),
                          batch_size=batch, time_mode="processing")
    return Job([plan], [source], batch_size=batch, time_mode="processing")


CUMULATIVE = (
    "from s select id, sum(price) as total, count() as cnt group by id "
    "insert into out"
)
CUMULATIVE_FILTERED = CUMULATIVE.replace("from s", "from s[price > 10.0]")


@pytest.mark.parametrize("sharded", [False, True], ids=["Job", "ShardedJob"])
@pytest.mark.parametrize(
    "cql, identity_share",
    [(CUMULATIVE, 1), (CUMULATIVE_FILTERED, 0), (WINDOW, 1)],
    ids=["running", "running_filtered", "length_window"],
)
def test_the_jobs_book_how_often_the_identity_engaged(
    cql, identity_share, sharded
):
    """Rows 2 and 3 of the count prefix, booked at each drain (summed
    over shards on a mesh): every aligned append, and those whose mask
    was a prefix. A shard's tape is the events routed to it, a prefix;
    the filter drops rows from the middle of every shard's every tape."""
    job = _job(cql, sharded)
    job.run()
    passed = sum(
        int((b.columns["price"] > 10.0).sum()) if "[" in cql else len(b)
        for b in _batches(8 * 2_048, 2_048)
    )
    assert len(job.results("out")) == passed  # a row per selected event
    appends, identity = _counters(job)
    steps = 8 * (4 if sharded else 1)
    assert appends == steps
    assert identity == identity_share * steps
    counters = job.metrics()["telemetry"]["counters"]
    assert counters["acc.compactions"] == appends
