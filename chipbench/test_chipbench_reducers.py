"""Each per-layer reader, on hand-made telemetry with known answers and on
a small telemetry file and profiler trace recorded on a TPU v5e (a 2-second
window of ``add-8192.ga25`` with ``--trace 1``)."""

from __future__ import annotations

import json
import math
from pathlib import Path

import pytest

from chipbench import devtrace, peaks, spans
from chipbench.harness import BENCH_DIR, RunView, load_cell, load_module, read_trace
from chipbench.window import Sample

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "add-8192.ga25"


def reader(name):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py").read


def _stage(t, dur, stage, src="main"):
    return {"ev": "stage", "t": t, "dur": dur, "stage": stage, "src": src}


def _span(ev, t, experiment, src="main"):
    return {"ev": ev, "t": t, "span": "experiment", "experiment": experiment,
            "unit": "u0", "src": src}


def _sample(t, src="main", value=2e-3):
    return Sample(t=t, src=src, key=f"k{t}{src}", config={}, value=value,
                  repeats=[value], stage=None)


# window [10, 20]: experiment 0 runs 9..14, experiment 1 runs 14..(open)
EVENTS = [
    _span("begin", 9.0, 0),
    _stage(9.5, 1.0, "compile"),            # 8.5..9.5: outside the window
    _stage(11.0, 1.0, "compile"),           # 10..11
    _stage(11.5, 0.5, "time"),              # 11..11.5
    _stage(11.6, 0.1, "record"),            # 11.5..11.6
    _span("end", 14.0, 0),
    _span("begin", 14.0, 1),
    _stage(16.0, 1.5, "compile"),           # 14.5..16
    _stage(16.25, 0.25, "time"),            # 16..16.25
    _stage(21.0, 1.0, "compile"),           # 20..21: outside the window
]


def _view(samples, events=EVENTS, **kw):
    return RunView(start=10.0, deadline=20.0, samples=samples, events=events,
                   workers=sorted({s.src for s in samples}), best=min(samples,
                   key=lambda s: s.value), bytes_moved=805306368,
                   peak=peaks.peak("TPU v5 lite"), **kw)


def test_stage_readers_clip_to_the_window():
    v = _view([_sample(11.6), _sample(16.3)])
    assert reader("compile_s_per_sample")(v) == pytest.approx((1.0 + 1.5) / 2)
    assert reader("compiles_per_sample")(v) == pytest.approx(2 / 2)
    assert reader("time_ms_per_sample")(v) == pytest.approx((0.5 + 0.25) * 1e3 / 2)


def test_search_host_time_is_experiment_time_outside_stages():
    v = _view([_sample(11.6), _sample(16.3)])
    # experiments cover 10..20 in the window; stages cover 1.6 + 1.75 of it
    assert reader("search_host_ms_per_sample")(v) == pytest.approx((10 - 3.35) * 1e3 / 2)


def test_spread_over_chips_and_absent_on_one():
    one = _view([_sample(11), _sample(12)])
    assert reader("chip_sample_spread_pct")(one) is None
    four = _view([_sample(11, "shard0"), _sample(12, "shard0"), _sample(13, "shard1"),
                  _sample(14, "shard2"), _sample(15, "shard3"), _sample(16, "shard3")])
    assert reader("chip_sample_spread_pct")(four) == pytest.approx(100 * (2 - 1) / 1.5)


def test_device_readers_need_a_trace():
    v = _view([_sample(11.6)])
    for name in ("host_over_device_pct", "best_kernel_roofline", "device_idle_pct"):
        assert reader(name)(v) is None
    v.best_device_s = 1.966e-3
    assert reader("best_kernel_roofline")(v) == pytest.approx(
        100 * 805306368 / 819e9 / 1.966e-3)
    assert reader("host_over_device_pct")(v) == pytest.approx(100 * 2e-3 / 1.966e-3)


def test_unknown_chip_has_no_peak():
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_span_pairs_and_open_spans():
    ivs = spans.span_intervals(EVENTS, "experiment", open_until=20.0)
    assert sorted(ivs["main"]) == [(9.0, 14.0, "experiment"), (14.0, 20.0, "experiment")]


def test_union_and_gaps():
    ivs = [(0.0, 1.0, "a"), (0.5, 2.0, "b"), (3.0, 4.0, "c")]
    assert devtrace.merged(ivs, 0.25, 3.5) == [(0.25, 2.0), (3.0, 3.5)]
    assert devtrace.covered(ivs, 0.0, 10.0) == pytest.approx(3.0)
    assert devtrace.op_name(
        '%_add.1 = f32[8192,8192]{1,0:T(8,128)} custom-call(f32[8192,8192] %a.1), x=1'
    ) == "_add.1 custom-call"


@pytest.fixture(scope="module")
def recorded():
    """The recorded run's window, samples, telemetry and profile."""
    w = json.loads((FIXTURE / "window.json").read_text())
    samples = [Sample(**s) for s in w["samples"]]
    counted = [s for s in samples if not s.final and w["start"] <= s.t <= w["deadline"]]
    finite = [s for s in counted if math.isfinite(s.value)]
    view = RunView(
        start=w["start"], deadline=w["deadline"], samples=counted,
        events=spans.read_events(str(FIXTURE / "trace.jsonl")),
        workers=sorted({s.src for s in samples}),
        best=min(finite, key=lambda s: s.value), bytes_moved=805306368,
        peak=peaks.peak(w["device_kind"]),
    )
    cell = load_cell(w["cell"])
    metrics, device, breakdown = read_trace(view, cell.per_layer,
                                            str(FIXTURE / "profile.xplane.pb"), cell.chips)
    return view, metrics, device, breakdown


def test_recorded_run_reads_every_metric_of_its_cell(recorded):
    view, metrics, device, breakdown = recorded
    want = {m["name"] for m in load_cell("add-8192.ga25").per_layer}
    assert set(metrics) == want
    for name, m in metrics.items():
        assert math.isfinite(m["value"]) and m["value"] >= 0, name
    assert 0 < metrics["best_kernel_roofline"]["value"] <= 100
    assert 0 <= metrics["device_idle_pct"]["value"] < 100
    assert 0 < device["busy_s"] < device["window_s"]
    assert 1 <= len(breakdown["device_ops"]) <= 10 and len(breakdown["idle_gaps"]) <= 10


def test_recorded_run_against_a_direct_reading(recorded):
    view, metrics, device, _ = recorded
    compile_s = sum(
        min(float(e["t"]), view.deadline) - max(float(e["t"]) - float(e["dur"]), view.start)
        for e in view.events
        if e.get("ev") == "stage" and e.get("stage") == "compile"
        and float(e["t"]) > view.start and float(e["t"]) - float(e["dur"]) < view.deadline
    )
    assert metrics["compile_s_per_sample"]["value"] == pytest.approx(
        compile_s / len(view.samples))
    # the best config's device time per call is the mean of its launches
    lo, hi = view.trace.mark("retime")
    runs = [e - s for s, e, _ in view.trace.modules[0] if lo <= s and e <= hi]
    assert view.best_device_s == pytest.approx(sum(runs) / len(runs))
    assert device["window_s"] == pytest.approx(
        view.trace.mark("window")[1] - view.trace.mark("window")[0])
