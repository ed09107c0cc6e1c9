"""The blocked sliding-window fold's merge of arrivals and expiries
(compiler/window_merge.py): a length window's order is a fact of C and
E, so its tiles are cut from the concat sequence with slices. Here: the
static tiles hold, row for row, each arrival beside the expiry that
leaves ahead of it; their windowed sums equal a numpy count over the
window; a processing-time window, in its ring since PR 50
(compiler/time_window.py), gives its rows; and the run loop books which
form each dispatched batch compiled. Nothing here is a rate or a
time."""

import jax.numpy as jnp
import numpy as np
import pytest

from flink_siddhi_tpu.compiler.plan import compile_plan
from flink_siddhi_tpu.compiler.window_merge import static_merge, tile_fold
from flink_siddhi_tpu.parallel import ShardedJob, make_cep_mesh
from flink_siddhi_tpu.runtime.executor import Job
from flink_siddhi_tpu.runtime.sources import BatchSource
from flink_siddhi_tpu.schema.batch import EventBatch
from flink_siddhi_tpu.schema.stream_schema import StreamSchema
from flink_siddhi_tpu.schema.types import AttributeType

G = 8


# (C, E, tile, chunk): a window shorter than, as long as and longer than
# the batch; the cell's proportions; a tile that does not divide 2N
SHAPES = [
    (1, 8, 4, 2),
    (5, 8, 4, 2),
    (8, 8, 8, 1),
    (13, 8, 4, 2),
    (1000, 4096, 512, 16),
    (37, 1000, 64, 3),
]
IDS = [f"C{c}-E{e}-tile{t}" for c, e, t, _ in SHAPES]


def _concat_sequence(C, E, seed, contiguous):
    """Codes, live flags and two value planes (a whole-numbered value and
    the count's ones) of a concat sequence. ``contiguous``: live as the
    step has it, a suffix of the ring and a prefix of the batch; else
    any rows dead, which the merge order must not depend on."""
    rng = np.random.default_rng(seed)
    N = C + E
    codes = rng.integers(0, G, N).astype(np.int32)
    if contiguous:
        pos = np.arange(N)
        live = (pos >= C - rng.integers(0, C + 1)) & (
            pos < C + rng.integers(0, E + 1))
    else:
        live = rng.random(N) < 0.8
    V = np.stack(
        [rng.integers(1, 90, N), np.ones(N)], axis=1).astype(np.float32)
    return codes, live, V


def _numpy_window_sums(codes, live, V, C):
    """Per concat row j: the sums over the live rows of j's group among
    rows j - C + 1 .. j."""
    N = len(codes)
    out = np.zeros_like(V)
    for j in range(N):
        lo = max(0, j - C + 1)
        sel = live[lo:j + 1] & (codes[lo:j + 1] == codes[j])
        out[j] = V[lo:j + 1][sel].sum(axis=0)
    return out


@pytest.mark.parametrize("C, E, tile, chunk", SHAPES, ids=IDS)
def test_the_static_tiles_hold_each_arrival_beside_its_expiry(
    C, E, tile, chunk
):
    """Element for element: pair i of the tiles is concat row i as an
    arrival (+v) and, ahead of it, row i - C as an expiry (-v): none
    beside the ring's own C rows, and the expiries nothing follows are
    left out. The halves of a tile are its expiries and its arrivals."""
    codes, live, V = _concat_sequence(C, E, seed=C * 31 + E, contiguous=False)
    N, h = C + E, tile // 2
    static = static_merge(
        jnp.asarray(codes), jnp.asarray(live), jnp.asarray(V), C, tile,
        chunk)
    s_code = np.asarray(static.codes_t)
    s_val = np.asarray(static.V_t)
    exp_code, arr_code = (s_code[:, :h].reshape(-1), s_code[:, h:].reshape(-1))
    exp_val, arr_val = (s_val[:, :h].reshape(-1, 2), s_val[:, h:].reshape(-1, 2))
    held = np.where(live[:, None], V, 0.0)
    # arrivals: pair i's arrival is concat row i
    np.testing.assert_array_equal(arr_val[:N], held)
    # (a dead row's code reaches no sum and is not compared)
    np.testing.assert_array_equal(arr_code[:N][live], codes[live])
    # expiries: pair i's is row i - C's, right ahead of arrival i
    np.testing.assert_array_equal(exp_val[:C], 0.0)
    np.testing.assert_array_equal(exp_val[C:N], -held[:E])
    np.testing.assert_array_equal(
        exp_code[C:N][live[:E]], codes[:E][live[:E]])
    # padding pairs are dead, and the arrival half is what is read back
    assert not exp_val[N:].any() and not arr_val[N:].any()
    assert static.rows == slice(h, tile)
    # an arrival comes after the expiries and arrivals of pairs <= its own
    tril = np.tril(np.ones((h, h), np.float32))
    np.testing.assert_array_equal(
        np.asarray(static.prec), np.concatenate([tril, tril], axis=1))


@pytest.mark.parametrize("contiguous", [True, False], ids=["fifo", "any_dead"])
@pytest.mark.parametrize("C, E, tile, chunk", SHAPES, ids=IDS)
def test_the_static_merge_gives_the_windows_count_and_sums(
    C, E, tile, chunk, contiguous
):
    codes, live, V = _concat_sequence(C, E, seed=C + E, contiguous=contiguous)
    got = tile_fold(
        static_merge(jnp.asarray(codes), jnp.asarray(live), jnp.asarray(V),
                     C, tile, chunk), G, (), chunk)
    # whole numbers under 2^24: float32 sums are exact in any order
    np.testing.assert_array_equal(
        np.stack(got, axis=1), _numpy_window_sums(codes, live, V, C))


def test_int_planes_carry_in_int32_through_the_static_merge():
    """Digit planes carry across tiles in modular int32 (window.py's
    base-2^11 decomposition): the static tiles feed the same fold."""
    C, E, tile, chunk = 5, 40, 8, 2
    codes, live, V = _concat_sequence(C, E, seed=3, contiguous=True)
    V[:, 0] = 2_047.0  # the largest digit, C of them a window at most
    digit, cnt = tile_fold(
        static_merge(jnp.asarray(codes), jnp.asarray(live), jnp.asarray(V),
                     C, tile, chunk), G, (0,), chunk)
    want = _numpy_window_sums(codes, live, V, C)
    assert digit.dtype == jnp.int32 and cnt.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(digit), want[:, 0])
    np.testing.assert_array_equal(np.asarray(cnt), want[:, 1])


# -- the jobs: rows, and which merge each dispatched batch compiled ---------

SCHEMA = StreamSchema(
    [("id", AttributeType.INT), ("price", AttributeType.DOUBLE)]
)
LENGTH = (
    "from s#window.length(100) select id, sum(price) as total, "
    "count() as cnt group by id insert into out"
)
TIME = (
    "from s#window.time(50) select id, sum(price) as total, "
    "count() as cnt group by id insert into out"
)
N_BATCHES, BATCH = 6, 512


def _batches():
    rng = np.random.default_rng(34)
    out = []
    for k in range(N_BATCHES):
        cols = {
            "id": rng.integers(0, 6, BATCH).astype(np.int32),
            "price": rng.integers(1, 90, BATCH).astype(np.float64),
        }
        # two events a millisecond: a 50 ms window holds a hundred
        ts = 1_000 + (k * BATCH + np.arange(BATCH, dtype=np.int64)) // 2
        out.append(EventBatch("s", SCHEMA, cols, ts))
    return out


def _job(cql, sharded, fused=None):
    plan = compile_plan(cql, {"s": SCHEMA}, plan_id="t")
    source = BatchSource("s", SCHEMA, iter(_batches()))
    if sharded:
        return plan, ShardedJob([plan], [source], mesh=make_cep_mesh(4),
                                batch_size=BATCH, time_mode="processing")
    job = Job([plan], [source], batch_size=BATCH, time_mode="processing")
    if fused is not None:
        job.fused_segment_len = fused
    return plan, job


def _merge_counters(job):
    tel = job.telemetry
    return (tel.counter_value("window.merge_steps"),
            tel.counter_value("window.merge_steps_static"))


@pytest.mark.parametrize("fused", [0, 3], ids=["seg_of_1", "seg_of_3"])
def test_a_processing_time_window_gives_its_rows_from_its_ring(fused):
    plan, job = _job(TIME, sharded=False, fused=fused)
    art = plan.artifacts[0]
    assert art._blocked() and art.merge_form == "ring"
    job.run()
    got = [(ts, tuple(row)) for ts, row in job.results_with_ts("out")]
    ids = np.concatenate([b.columns["id"] for b in _batches()])
    price = np.concatenate([b.columns["price"] for b in _batches()])
    ts = np.concatenate([b.timestamps for b in _batches()])
    want = []
    for j in range(len(ts)):
        lo = np.searchsorted(ts, ts[j] - 50, side="right")
        sel = ids[lo:j + 1] == ids[j]
        want.append((int(ts[j]), (int(ids[j]), float(price[lo:j + 1][sel].sum()),
                                  int(sel.sum()))))
    assert got == want  # row for row, in order
    assert _merge_counters(job) == (N_BATCHES, 0)


@pytest.mark.parametrize("sharded", [False, True], ids=["Job", "ShardedJob"])
def test_a_length_window_books_the_static_merge_every_batch(sharded):
    plan, job = _job(LENGTH, sharded)
    assert plan.artifacts[0].merge_form == "static"
    job.run()
    assert len(job.results("out")) == N_BATCHES * BATCH
    assert _merge_counters(job) == (N_BATCHES, N_BATCHES)
    counters = job.metrics()["telemetry"]["counters"]
    assert counters["window.merge_steps_static"] == N_BATCHES


def test_a_window_off_the_blocked_path_books_no_merge():
    cql = ("from s#window.length(100) select id, distinctCount(price) as n "
           "insert into out")
    plan, job = _job(cql, sharded=False)
    assert plan.artifacts[0].merge_form is None
    job.run()
    assert _merge_counters(job) == (0, 0)
