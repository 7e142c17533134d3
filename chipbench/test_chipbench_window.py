"""The window: what it counts, that it stops the tuner at the deadline, and
that the harness refuses to run without a TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from chipbench.window import Sample, Window, WindowClosed

ROOT = Path(__file__).resolve().parent.parent
SMALL = (128, 256)


def _sample(t, src="main", final=False, value=1e-3):
    return Sample(t=t, src=src, key=f"k{t}", config={}, value=value,
                  repeats=[value], stage=None, final=final)


def test_counts_only_search_samples_recorded_inside_the_window():
    w = Window(start=10.0, deadline=20.0)
    for s in (_sample(9.9), _sample(10.0), _sample(15.0), _sample(15.5, final=True),
              _sample(20.0), _sample(20.001), _sample(31.0)):
        w.add(s)
    assert [s.t for s in w.counted()] == [10.0, 15.0, 20.0]


def test_work_counts_the_sample_in_flight_at_the_deadline_in_part():
    w = Window(start=10.0, deadline=20.0)
    for t, src, final in [(12, "a", False), (15, "a", False), (16, "a", True),
                          (18, "a", False), (24, "a", False), (30, "a", False),
                          (19, "b", False), (21, "b", False)]:
        w.add(_sample(t, src, final))
    # a: three whole samples (the final's time goes to the next sample),
    # then 18..24 with 2 of its 6 s inside; b: one whole, then half of 19..21
    assert w.work() == pytest.approx(3 + 2 / 6 + 1 + 1 / 2)
    assert len(w.counted()) == 4


def test_check_raises_after_the_deadline():
    now = time.perf_counter()
    w = Window(start=now, deadline=now + 60)
    w.check()
    w.deadline = time.perf_counter()
    with pytest.raises(WindowClosed):
        w.check()


def _rehearse(cell, seconds, **kw):
    from chipbench.harness import run_cell

    lines = []
    result = run_cell(cell, 3, seconds, False, t0=time.perf_counter(), require_tpu=False,
                      size=SMALL, log=lines.append, **kw)
    return result, lines


def test_window_stops_the_tuner_within_a_sample_of_the_deadline(tmp_path):
    from chipbench.harness import load_cell
    from chipbench.spans import read_events

    result, lines = _rehearse("add-8192.ga25", 2.0, keep_dir=str(tmp_path))
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 2
    window = next(line for line in lines if line.startswith("[window]"))
    fields = dict(f.split("=", 1) for f in window.split() if "=" in f)
    assert fields["persistent_cache_hits"] == "0"
    assert fields["program_compile_cache"] == "off"
    overshoot = float(fields["overshoot_s"])
    assert 0.0 <= overshoot < 2.0
    # the telemetry holds samples the tuner measured after the deadline only
    # up to the one in flight: the matrix itself was far from done
    records = [e for e in read_events(str(tmp_path / "telemetry" / "trace.jsonl"))
               if e.get("ev") == "stage" and e.get("stage") == "record"]
    assert len(records) <= result["attempted"] + 2
    traffic = load_cell("add-8192.ga25").traffic
    assert len(records) < traffic["sample_size"] * traffic["n_experiments"]
    assert set(result["metrics"]) == {"samples_per_s", "best_kernel_ms", "setup_s"}


def test_no_tpu_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "add-8192.ga25",
         "--seed", "2147483653", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "not a TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_checkout_without_the_program_prints_no_result(tmp_path):
    (tmp_path / "BENCHMARK.json").write_text((ROOT / "BENCHMARK.json").read_text())
    subprocess.run(["cp", "-r", str(ROOT / "chipbench"), str(tmp_path / "chipbench")],
                   check=True)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "add-8192.ga25",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
