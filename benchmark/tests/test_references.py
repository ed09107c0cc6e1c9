"""Each configuration's plain reference against the repo's per-event
interpreter (``baseline.BaselineEngine``), on the same seeded events.
The benchmark never imports the interpreter; this test may."""

import numpy as np
import pytest

from bmlib.cell import load_json, load_module, make_pool
from bmlib.compare import bf16_round, compare_range


def _interpreter_rows(cfg, pool, n, names):
    from flink_siddhi_tpu.baseline import BaselineEngine

    cols = pool.columns(0, n)
    eng = BaselineEngine(cfg["cql"], [name for name, _ in cfg["fields"]])
    out_ts, rows = [], []
    eng._emit = lambda _o, t, row: (out_ts.append(t), rows.append(row))
    eng.run_columns(
        {k: v.tolist() for k, v in cols.items()},
        cols["timestamp"].tolist(),
    )
    table = {"@ts": np.asarray(out_ts, np.int64)}
    for name, col in zip(names, zip(*rows)):
        table[name] = np.asarray(col)
    return table


def _running_rows(_cfg, pool, n, _names):
    """The interpreter has no window-less group-by: a loop over events."""
    cols = pool.columns(0, n, ("id", "price", "timestamp"))
    total, cnt, rows = {}, {}, []
    for k, x in zip(cols["id"].tolist(), cols["price"].tolist()):
        total[k] = total.get(k, 0.0) + x
        cnt[k] = cnt.get(k, 0) + 1
        rows.append((k, total[k], cnt[k]))
    i, t, c = (np.asarray(col) for col in zip(*rows))
    return {"@ts": cols["timestamp"], "id": i, "total": t, "cnt": c}


@pytest.mark.parametrize("config, names, rows_of", [
    ("pattern3", ("t1", "t3", "price"), _interpreter_rows),
    ("window1k", ("id", "total", "cnt"), _interpreter_rows),
    ("keyed1k_x4", ("id", "total", "cnt"), _running_rows),
])
def test_reference_equals_interpreter(config, names, rows_of):
    cfg = load_json("configs", config)
    ref = load_module("configs", config)
    n = 20_000
    pool = make_pool(cfg, 11, 8_192)  # shorter than n: the pool cycles
    want = rows_of(cfg, pool, n, names)
    got = ref.expected(pool, 0, n)
    assert len(got["@idx"]) == len(want["@ts"]) > 0
    for k in ("@ts",) + names:
        assert np.allclose(
            got[k].astype(float), want[k].astype(float), rtol=1e-12, atol=1e-9
        ), k
    # a range in the middle needs only its own history
    a, b = 9_000, 9_700
    part = ref.expected(pool, a, b)
    keep = (got["@idx"] >= a) & (got["@idx"] < b)
    for k in part:
        assert np.allclose(part[k].astype(float), got[k][keep].astype(float))


@pytest.mark.parametrize("config", ["pattern3", "window1k", "keyed1k_x4"])
def test_the_lower_precision_control_fails_the_limits(config):
    """The control: the reference computed in bfloat16 and put in the
    program's place comes out as not correct."""
    cfg = load_json("configs", config)
    ref = load_module("configs", config)
    pool = make_pool(cfg, 12, 8_192)
    want = ref.expected(pool, 2_000, 6_000)
    sound = compare_range(want, want, cfg["compare"])
    assert all(v == 0 for v in sound.values())
    low = compare_range(
        ref.expected(pool, 2_000, 6_000, "bf16"), want, cfg["compare"]
    )
    assert max(v for k, v in low.items() if k.endswith("_err_over_tol")) > 3
    assert all(v == 0 for k, v in low.items() if k.endswith("_mismatches"))


def test_bf16_round():
    x = np.array([1.0, 1.00390625, 1.005, 3.14159, 100.0])
    got = bf16_round(x)
    assert got[0] == 1.0 and got[4] == 100.0
    assert np.all(np.abs(got - x) <= np.abs(x) * 2.0 ** -8)
    assert got[3] == 3.140625
