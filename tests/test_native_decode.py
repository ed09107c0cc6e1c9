"""Native C++ columnar decoder: correctness, interner-code consistency
with query compilation, fallback equivalence, and e2e ingest.

Reference analog: the schema/serializer bridge tests
(StreamSerializerTest.java:29-81) pin record->row conversion; here the
unit under test is bytes->columns with dictionary-interned strings.
"""

import io
import json

import numpy as np
import pytest

from flink_siddhi_tpu.native import (
    KIND_DOUBLE,
    KIND_INT,
    KIND_STRING,
    ColumnDecoder,
    available,
)
from flink_siddhi_tpu.schema.strings import StringTable


def make_decoder():
    table = StringTable()
    fields = [
        ("id", KIND_INT, None),
        ("name", KIND_STRING, table),
        ("price", KIND_DOUBLE, None),
    ]
    return ColumnDecoder(fields), table


def sample_lines(n=100):
    recs = [
        {"id": i, "name": f"n{i % 5}", "price": i * 0.5, "extra": [1, 2]}
        for i in range(n)
    ]
    return (
        "\n".join(json.dumps(r) for r in recs).encode() + b"\n",
        recs,
    )


def test_native_available():
    # the environment ships g++; the in-tree Makefile must build
    assert available(), "native decode library failed to build/load"


def test_json_decode_basic():
    dec, table = make_decoder()
    data, recs = sample_lines(100)
    cols, valid, n = dec.decode_json(data, 200)
    assert n == 100 and valid.all()
    assert cols[0].tolist() == [r["id"] for r in recs]
    assert [table.value(c) for c in cols[1]] == [r["name"] for r in recs]
    np.testing.assert_allclose(
        cols[2], [r["price"] for r in recs]
    )


def test_json_escapes_and_unicode():
    dec, table = make_decoder()
    line = (
        b'{"id": 1, "name": "a\\"b\\\\c\\nd\\u00e9\\ud83d\\ude00", '
        b'"price": -2.5e2}\n'
    )
    cols, valid, n = dec.decode_json(line, 10)
    assert n == 1 and valid[0]
    assert table.value(cols[1][0]) == 'a"b\\c\ndé\U0001F600'
    assert cols[2][0] == -250.0


def test_json_missing_fields_and_null():
    dec, table = make_decoder()
    data = (
        b'{"id": 7}\n'
        b'{"name": null, "price": 1.5, "id": 8}\n'
    )
    cols, valid, n = dec.decode_json(data, 10)
    assert n == 2 and valid.all()
    assert cols[0].tolist() == [7, 8]
    assert table.value(cols[1][0]) == "" and table.value(cols[1][1]) == ""
    assert cols[2].tolist() == [0.0, 1.5]


def test_json_malformed_rows_flagged():
    dec, _ = make_decoder()
    data = b'{"id": 1}\nnot json\n{"id": 3}\n{"id": oops}\n'
    cols, valid, n = dec.decode_json(data, 10)
    assert n == 4
    assert valid.tolist() == [1, 0, 1, 0]
    assert cols[0][0] == 1 and cols[0][2] == 3


def test_interner_codes_match_precompiled_constants():
    # query compilation interns constants FIRST; native decode must reuse
    # those codes, and newly discovered strings must round-trip back
    dec, table = make_decoder()
    pre = table.intern("n3")  # as a query predicate constant would
    data, recs = sample_lines(20)
    cols, valid, n = dec.decode_json(data, 30)
    codes = {table.value(c): int(c) for c in cols[1]}
    assert codes["n3"] == pre
    # every python-side lookup agrees with the decoded codes
    for name, code in codes.items():
        assert table.lookup(name) == code


def test_python_fallback_equivalence():
    data, recs = sample_lines(50)
    native_dec, t1 = make_decoder()
    if not native_dec.native:
        pytest.skip("no native library in this environment")
    py_dec, t2 = make_decoder()
    py_dec._lib = None  # force fallback
    py_dec._mirrors = []
    a_cols, a_valid, a_n = native_dec.decode_json(data, 100)
    b_cols, b_valid, b_n = py_dec.decode_json(data, 100)
    assert a_n == b_n and a_valid.tolist() == b_valid.tolist()
    assert a_cols[0].tolist() == b_cols[0].tolist()
    np.testing.assert_allclose(a_cols[2], b_cols[2])
    assert [t1.value(c) for c in a_cols[1]] == [
        t2.value(c) for c in b_cols[1]
    ]


def test_csv_decode():
    dec, table = make_decoder()
    data = b'1,alpha,0.5\n2,"beta,x",1.5\n3,alpha,2.5\nbad,row,zz\n'
    cols, valid, n = dec.decode_csv(data, 10)
    assert n == 4
    assert valid.tolist() == [1, 1, 1, 0]
    assert cols[0][:3].tolist() == [1, 2, 3]
    assert table.value(cols[1][1]) == "beta,x"
    assert cols[2][:3].tolist() == [0.5, 1.5, 2.5]


def test_json_lines_source_e2e(tmp_path):
    # file -> native decode -> CEP filter query -> typed results
    from flink_siddhi_tpu.compiler.plan import compile_plan
    from flink_siddhi_tpu.runtime.executor import Job
    from flink_siddhi_tpu.runtime.sources import JsonLinesSource
    from flink_siddhi_tpu.schema.stream_schema import StreamSchema
    from flink_siddhi_tpu.schema.types import AttributeType

    path = tmp_path / "events.jsonl"
    with open(path, "w") as f:
        for i in range(200):
            f.write(
                json.dumps(
                    {
                        "id": i % 4,
                        "name": f"n{i % 3}",
                        "price": float(i),
                        "timestamp": 1000 + i,
                    }
                )
                + "\n"
            )
    schema = StreamSchema(
        [
            ("id", AttributeType.INT),
            ("name", AttributeType.STRING),
            ("price", AttributeType.DOUBLE),
            ("timestamp", AttributeType.LONG),
        ]
    )
    src = JsonLinesSource(
        "S", schema, str(path), ts_field="timestamp", chunk_bytes=512
    )
    plan = compile_plan(
        "from S[id == 2] select name, price insert into out",
        {"S": schema},
    )
    job = Job([plan], [src], batch_size=64)
    job.run()
    rows = job.results("out")
    assert len(rows) == 50
    assert rows[0] == ("n2", 2.0)


def test_csv_source_e2e(tmp_path):
    from flink_siddhi_tpu.compiler.plan import compile_plan
    from flink_siddhi_tpu.runtime.executor import Job
    from flink_siddhi_tpu.runtime.sources import CsvSource
    from flink_siddhi_tpu.schema.stream_schema import StreamSchema
    from flink_siddhi_tpu.schema.types import AttributeType

    path = tmp_path / "events.csv"
    with open(path, "w") as f:
        f.write("id,name,price,timestamp\n")
        for i in range(100):
            f.write(f"{i % 4},n{i % 3},{float(i)},{1000 + i}\n")
    schema = StreamSchema(
        [
            ("id", AttributeType.INT),
            ("name", AttributeType.STRING),
            ("price", AttributeType.DOUBLE),
            ("timestamp", AttributeType.LONG),
        ]
    )
    src = CsvSource(
        "S", schema, str(path), header=True, ts_field="timestamp"
    )
    plan = compile_plan(
        "from S[price > 90.0] select id, price insert into big",
        {"S": schema},
    )
    job = Job([plan], [src], batch_size=64)
    job.run()
    assert len(job.results("big")) == 9


def test_csv_bool_literals_both_decoders():
    # bool cells accept case-insensitive true/false (and 0/1), matching
    # the JSON path; previously only strtoll parsed and 'true' cells
    # silently invalidated the row
    from flink_siddhi_tpu.native import KIND_BOOL

    def make_bool_decoder():
        table = StringTable()
        fields = [("id", KIND_INT, None), ("flag", KIND_BOOL, None)]
        return ColumnDecoder(fields)

    data = (
        b"1,true\n2,False\n3,TRUE\n4,0\n5,1\n6,maybe\n"
        b"+7,true \n 8 , FALSE\n"  # signs/whitespace: int()/float() parity
    )
    native_dec = make_bool_decoder()
    py_dec = make_bool_decoder()
    py_dec._lib = None  # force fallback
    py_dec._mirrors = []
    for dec in (native_dec, py_dec):
        cols, valid, n = dec.decode_csv(data, 10)
        assert n == 8
        assert valid.tolist() == [1, 1, 1, 1, 1, 0, 1, 1], dec.native
        assert cols[0][6:8].tolist() == [7, 8], dec.native
        assert (
            cols[1][:5].tolist() + cols[1][6:8].tolist()
        ) == [1, 0, 1, 0, 1, 1, 0], dec.native


def test_source_allowed_lateness(tmp_path):
    # bounded-disorder input: with allowed_lateness_ms the watermark holds
    # back, so a later chunk carrying older timestamps still reorders
    # correctly through the executor's reorder buffer
    from flink_siddhi_tpu.runtime.sources import JsonLinesSource
    from flink_siddhi_tpu.schema.stream_schema import StreamSchema
    from flink_siddhi_tpu.schema.types import AttributeType

    schema = StreamSchema(
        [("id", AttributeType.INT), ("timestamp", AttributeType.LONG)]
    )
    lines = [
        {"id": 0, "timestamp": 1000},
        {"id": 1, "timestamp": 1200},  # chunk 1 max ts = 1200
        {"id": 2, "timestamp": 1100},  # older than chunk 1's max
        {"id": 3, "timestamp": 1300},
    ]
    raw = "\n".join(json.dumps(r) for r in lines).encode() + b"\n"
    src = JsonLinesSource(
        "S", schema, io.BytesIO(raw), ts_field="timestamp",
        chunk_bytes=40, allowed_lateness_ms=200,
    )
    batch, wm, done = src.poll(10)
    assert wm == int(batch.timestamps.max()) - 200


def test_sink_streams_skip_retention_when_disabled():
    from flink_siddhi_tpu.compiler.plan import compile_plan
    from flink_siddhi_tpu.runtime.executor import Job
    from flink_siddhi_tpu.runtime.sources import BatchSource
    from flink_siddhi_tpu.schema.batch import EventBatch
    from flink_siddhi_tpu.schema.stream_schema import StreamSchema
    from flink_siddhi_tpu.schema.types import AttributeType

    schema = StreamSchema(
        [("id", AttributeType.INT), ("timestamp", AttributeType.LONG)]
    )
    ids = np.arange(100, dtype=np.int64) % 4
    ts = 1000 + np.arange(100, dtype=np.int64)
    batch = EventBatch("S", schema, {"id": ids, "timestamp": ts}, ts)
    plan = compile_plan(
        "from S[id == 2] select id, timestamp insert into out",
        {"S": schema},
    )
    got = []
    job = Job(
        [plan],
        [BatchSource("S", schema, iter([batch]))],
        batch_size=64,
        retain_results=False,
    )
    job.add_sink("out", lambda ts, row: got.append(row))
    job.run()
    assert len(got) == 25
    # sink consumed every row; host retention skipped, counter still live
    assert job.results("out") == []
    assert job.emitted_counts["out"] == 25


def test_library_is_built_from_the_source_next_to_it(tmp_path, monkeypatch):
    """Every load goes through ``make``: a checkout with no
    libfastdecode.so builds it on first use, and a library older than
    fast_decode.cpp is rebuilt instead of loaded as found."""
    import os
    import shutil

    from flink_siddhi_tpu import native

    for name in ("Makefile", "fast_decode.cpp"):
        shutil.copy(os.path.join(native._DIR, name), tmp_path / name)
    so = tmp_path / "libfastdecode.so"
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    monkeypatch.setattr(native, "_SO", str(so))

    def fresh_load():
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", False)
        return native._load()

    assert not so.exists()
    assert fresh_load() is not None and so.exists()
    # stale: the library predates its source
    src_mtime = os.path.getmtime(tmp_path / "fast_decode.cpp")
    os.utime(so, (src_mtime - 100, src_mtime - 100))
    assert fresh_load() is not None
    assert os.path.getmtime(so) > src_mtime
    # up to date: left alone
    built = os.path.getmtime(so)
    assert fresh_load() is not None
    assert os.path.getmtime(so) == built
