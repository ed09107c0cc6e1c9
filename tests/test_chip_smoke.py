"""chip_smoke.py cannot rot: its phase functions run here, on the CPU
mesh, at a tiny size — the Pallas kernel under the interpreter, the
four-chip phase on four virtual devices — and the script itself refuses
to run without the accelerator.

What this lane can say is that the phases still drive the entry points
and that their reference comparisons still hold; that they hold on the
chip at deployment size is what ``python chip_smoke.py`` is for.
"""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = chip_smoke.Sizes(
    events=4 * 8192, batch=8192, pipeline_lines=20_000, shard_batch=2048,
)


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setenv("FST_PALLAS_INTERPRET", "1")


def test_kernels_phase(interpreter):
    out = chip_smoke.phase_kernels(TINY, seed=7, expect_mode="interpret")
    assert out["reverse_cummin"]["mode"] == "interpret"
    assert set(out["reverse_cummin"]["shapes"]) == {4096, 8192}


def test_kernels_phase_refuses_the_xla_form():
    # on this lane reverse cummins run as XLA; a chip run that found
    # the same would not be reported as the kernel having run
    with pytest.raises(AssertionError, match="xla"):
        chip_smoke.phase_kernels(TINY, seed=7)


def test_headline_phases(interpreter):
    out = chip_smoke.phase_headline(TINY, seed=7, expect_mode="interpret")
    s, r = out["headline_streaming"], out["headline_resident"]
    assert s["events"] == r["events"] == TINY.events
    assert s["rows"] == r["rows"] > 0
    # four batches: one (partial) fused segment of chip_smoke.SEGMENT
    assert s["batches"] == 4 and s["dispatches"] == 1


def test_window_phase():
    out = chip_smoke.phase_window(TINY, seed=7)
    assert out["rows"] == out["events"] == TINY.events
    # the checkpoint landed mid-stream, inside the fused segment
    assert 0 < out["restored_at_event"] < TINY.events


def test_pipeline_phase():
    out = chip_smoke.phase_pipeline(TINY, seed=7)
    assert out["events"] == TINY.pipeline_lines
    assert out["rows"] > 0 and out["native_decoder"]


def test_four_chips_phase():
    out = chip_smoke.phase_four_chips(TINY, seed=7)
    assert out["shards"] == 4
    assert all(n > 0 for n in out["rows"].values())


def test_last_line_is_ok_and_device_only(capsys):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    chip_smoke.report(device, json.dumps({"phases": {}}))
    last = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert last == {"ok": True, "device": device}


def test_script_fails_without_an_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=240,
    )
    assert r.returncode != 0
    assert "platform is 'cpu'" in r.stderr
    assert r.stdout == ""  # nothing that could be read as a result
