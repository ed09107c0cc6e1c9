"""A configuration, a traffic mix, a cell and a per-layer metric over an
existing counter are each added as new files plus one entry, editing no
file that is there. So is a configuration with a stream of its own:
another schema, another generator, another event clock."""

import json
import os
import shutil

from conftest import BENCH, REPO, TINY

from bmlib.cell import run_cell


DATA = os.path.join(BENCH, "tests", "data")


def _copy(tmp_path):
    """(a copy of ``benchmark/``, the mtime of every file in it)"""
    root = tmp_path / "benchmark"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    return root, {
        os.path.join(d, p): os.path.getmtime(os.path.join(d, p))
        for d, _, fs in os.walk(root) for p in fs
    }


def _untouched(before):
    return all(os.path.getmtime(p) == m for p, m in before.items())


def test_new_config_traffic_cell_and_metric_are_only_files(tmp_path):
    root, before = _copy(tmp_path)
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)

    # a configuration: the window query over 100 keys, its reference beside it
    cfg = json.load(open(root / "configs" / "window1k.json"))
    cfg.update(name="window100", n_ids=100)
    json.dump(cfg, open(root / "configs" / "window100.json", "w"))
    shutil.copy(root / "configs" / "window1k.py",
                root / "configs" / "window100.py")
    # a traffic mix: the replay with a shorter pool
    mix = json.load(open(root / "traffic" / "replay.json"))
    mix.update(name="replay_short", pool_batches=4)
    json.dump(mix, open(root / "traffic" / "replay_short.json", "w"))
    # a cell of the two
    json.dump(
        {"name": "window100.replay_short", "config": "window100",
         "traffic": "replay_short", "chips": 1, "params": {},
         "reports": {"rate": "events_per_s"}},
        open(root / "cells" / "window100.replay_short.json", "w"),
    )
    # a per-layer metric over counters the job already keeps
    json.dump(
        {"name": "drains_per_kbatch", "unit": "1/kbatch", "layer": "drain",
         "source": "program_counter", "moves": "events_per_s",
         "reader": "counter_ratio",
         "args": {"num": "drains.completed", "den": "fusion.batches",
                  "scale": 1000.0}},
        open(root / "metrics" / "drains_per_kbatch.json", "w"),
    )
    # ... and one entry each
    bench["configs"].append(
        {"name": "window100", "source": cfg["source"],
         "file": "benchmark/configs/window100.json",
         "reduced": cfg["reduced"], "why": "test"})
    bench["workloads"].append(
        {"name": "window100.replay_short", "config": "window100",
         "traffic": "replay_short", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "events_per_s":
            m["workloads"].append("window100.replay_short")
    bench["per_layer"].append(
        {"name": "drains_per_kbatch", "unit": "1/kbatch", "better": "lower",
         "source": "program_counter", "layer": "drain",
         "moves": "events_per_s",
         "workloads": ["window100.replay_short"]})
    json.dump(bench, open(tmp_path / "BENCHMARK.json", "w"))

    tiny = {k: v for k, v in TINY.items() if k != "pool_batches"}
    out = run_cell("window100.replay_short", 5, 2.0, True, overrides=tiny,
                   root=str(root), say=lambda _line: None)
    assert out["correct"] is True
    assert out["metrics"]["drains_per_kbatch"]["value"] > 0
    plain = run_cell("window100.replay_short", 5, 1.0, False, overrides=tiny,
                     root=str(root), say=lambda _line: None)
    assert set(plain["metrics"]) == {"setup_s", "events_per_s"}
    assert _untouched(before)


def test_a_stream_of_its_own_is_only_files(tmp_path):
    """The NEXmark stream at a toy size (``tests/data/nexmark_tiny``):
    five columns, ids that churn, two events a millisecond, a windowed
    group-by whose rows are stamped inside their pane. Three files in,
    none touched; ``correct`` and its control come out as they should."""
    root, before = _copy(tmp_path)
    for name, kind in (("nexmark_tiny.json", "configs"),
                       ("nexmark_tiny.py", "configs"),
                       ("nexmark_tiny.replay.json", "cells")):
        shutil.copy(os.path.join(DATA, name), root / kind / name)
    lines = []
    out = run_cell("nexmark_tiny.replay", 5, 2.0, False, root=str(root),
                   overrides={"slice_seconds": 0.25}, control=True,
                   say=lines.append)
    compared = json.loads(
        next(x for x in lines if x.startswith("[bench] compared"))[16:])
    assert out["correct"] is True and out["failed"] == 0
    assert compared["ranges"] > 0 and compared["rows"] > 0
    assert out["metrics"]["events_per_s"]["value"] > 0
    control = json.loads(
        next(x for x in lines if x.startswith("[bench] control"))[21:])
    assert control["correct"] is False
    assert _untouched(before)
