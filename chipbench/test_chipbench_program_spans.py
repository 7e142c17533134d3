"""The readers of the program's sub-stages, ask/tell spans and profiler
annotations, on hand-made telemetry and a hand-made ``DeviceTrace``."""

from __future__ import annotations

import pytest

from chipbench import peaks
from chipbench.devtrace import MARK_PREFIX, DeviceTrace
from chipbench.harness import BENCH_DIR, RunView, load_module
from chipbench.window import Sample


def reader(name):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py").read


def _stage(t, dur, stage, src="main", **attrs):
    return {"ev": "stage", "t": t, "dur": dur, "stage": stage, "src": src, **attrs}


def _span(ev, t, span, src="main", **attrs):
    return {"ev": ev, "t": t, "span": span, "src": src, **attrs}


def _sample(t, src="main"):
    return Sample(t=t, src=src, key=f"k{t}{src}", config={}, value=2e-3,
                  repeats=[2e-3], stage=None)


# window [10, 20]: experiment 0 runs 9..14, experiment 1 runs 14..(open)
PIPELINE = [
    _span("begin", 9.0, "experiment", experiment=0),
    _stage(9.5, 1.0, "compile"),            # 8.5..9.5: outside the window
    _stage(11.0, 1.0, "compile"),           # 10..11
    _stage(11.5, 0.5, "time"),              # 11..11.5
    _stage(11.6, 0.1, "record"),            # 11.5..11.6
    _span("end", 14.0, "experiment", experiment=0),
    _span("begin", 14.0, "experiment", experiment=1),
    _stage(16.0, 1.5, "compile"),           # 14.5..16
    _stage(16.25, 0.25, "time"),            # 16..16.25
    _stage(21.0, 1.0, "compile"),           # 20..21: outside the window
]

# what this change's program adds inside those stages and experiments
SUBSTAGES = [
    _stage(10.4, 0.4, "inputs", within="compile"),            # 10..10.4
    _stage(10.3, 0.3, "compile.trace", within="inputs"),      # 10..10.3, in inputs
    _stage(10.6, 0.2, "compile.trace", within="compile"),     # 10.4..10.6
    _stage(10.55, 0.1, "compile.trace", within="compile"),    # nested in the one above
    _stage(10.8, 0.2, "compile.lower", within="compile"),     # 10.6..10.8
    _stage(10.95, 0.15, "compile.backend", within="compile"),  # 10.8..10.95
    _stage(15.0, 0.5, "compile.trace", within="compile"),     # 14.5..15
    _stage(15.9, 0.9, "compile.backend", within="compile"),   # 15..15.9
    _stage(16.2, 0.1, "compile.backend", within="time"),      # a recompile in time
    _stage(20.5, 0.5, "compile.backend", within="compile"),   # outside the window
    _span("begin", 12.0, "ask", round=0),
    _span("end", 12.25, "ask", round=0),
    _span("begin", 13.0, "tell", round=0),
    _span("end", 13.5, "tell", round=0),
    _span("begin", 19.5, "ask", round=1),                     # open at the deadline
]

SAMPLES = [_sample(11.6), _sample(16.3)]


def _view(events, samples=SAMPLES, **kw):
    return RunView(start=10.0, deadline=20.0, samples=samples, events=events,
                   workers=sorted({s.src for s in samples}),
                   best=samples[0] if samples else None,
                   bytes_moved=805306368, peak=peaks.peak("TPU v5 lite"), **kw)


@pytest.mark.parametrize("name", [
    "compile_s_per_sample", "compiles_per_sample", "search_host_ms_per_sample",
    "time_ms_per_sample",
])
def test_nested_substages_leave_the_stage_readers_unchanged(name):
    before = reader(name)(_view(PIPELINE))
    after = reader(name)(_view(PIPELINE + SUBSTAGES))
    assert after == pytest.approx(before)


def test_substage_seconds_per_sample():
    v = _view(PIPELINE + SUBSTAGES)
    assert reader("inputs_s_per_sample")(v) == pytest.approx(0.4 / 2)
    # 10..10.3 and 10.4..10.6 (the nested trace counts once) and 14.5..15
    assert reader("jaxpr_trace_s_per_sample")(v) == pytest.approx((0.3 + 0.2 + 0.5) / 2)
    assert reader("mlir_lower_s_per_sample")(v) == pytest.approx(0.2 / 2)
    assert reader("backend_compile_s_per_sample")(v) == pytest.approx(
        (0.15 + 0.9 + 0.1) / 2)


def test_overlapping_traces_count_once_per_writer_and_add_over_writers():
    one = [_stage(12.0, 2.0, "compile.trace"), _stage(11.5, 1.0, "compile.trace"),
           _stage(14.0, 2.0, "compile.trace")]                    # 10..14 in all
    v = _view(one)
    assert reader("jaxpr_trace_s_per_sample")(v) == pytest.approx(4.0 / 2)
    two = one + [_stage(12.0, 2.0, "compile.trace", src="shard1")]
    assert reader("jaxpr_trace_s_per_sample")(_view(two)) == pytest.approx(6.0 / 2)


def test_backend_compiles_count_every_within_inside_the_window():
    v = _view(PIPELINE + SUBSTAGES)
    assert reader("backend_compiles_per_sample")(v) == pytest.approx(3 / 2)


def test_ask_and_tell_milliseconds_per_sample():
    v = _view(PIPELINE + SUBSTAGES)
    # ask 0.25 s, tell 0.5 s, the open ask 19.5..20
    assert reader("ask_tell_ms_per_sample")(v) == pytest.approx((0.25 + 0.5 + 0.5) * 1e3 / 2)


@pytest.mark.parametrize("name", [
    "inputs_s_per_sample", "jaxpr_trace_s_per_sample", "mlir_lower_s_per_sample",
    "backend_compile_s_per_sample", "backend_compiles_per_sample", "ask_tell_ms_per_sample",
])
def test_span_readers_need_samples(name):
    assert reader(name)(_view(PIPELINE + SUBSTAGES, samples=[])) is None


def _trace(ops, host, window=(0.0, 10.0)):
    return DeviceTrace(ops=ops, modules={}, host=host,
                       marks={MARK_PREFIX + "window": window})


def _unattributed(trace, workers):
    samples = [_sample(1.0, w) for w in workers]
    return reader("idle_unattributed_pct")(_view([], samples=samples, trace=trace))


def test_idle_gap_covered_by_a_leaf_is_attributed():
    # chip 0 runs 0..2 and 6..10; its gap 2..6 is covered by inputs 2..5
    ops = {0: [(0.0, 2.0, "a"), (6.0, 10.0, "a")]}
    trace = _trace(ops, [(2.0, 5.0, "repro.inputs@main")])
    assert _unattributed(trace, ["main"]) == pytest.approx(100 * 1.0 / 4.0)
    assert _unattributed(_trace(ops, [(1.0, 7.0, "repro.inputs@main")]), ["main"]) == 0.0


def test_idle_gap_covered_only_by_an_outer_span_is_not_attributed():
    ops = {0: [(0.0, 2.0, "a"), (6.0, 10.0, "a")]}
    host = [(0.0, 10.0, "repro.experiment@main"), (0.0, 10.0, "repro.round@main"),
            (2.0, 6.0, "XLA::Compile")]
    assert _unattributed(_trace(ops, host), ["main"]) == pytest.approx(100.0)


def test_no_annotations_leave_every_idle_second_unattributed():
    ops = {0: [(1.0, 2.0, "a")]}
    assert _unattributed(_trace(ops, []), ["main"]) == pytest.approx(100.0)
    assert reader("idle_unattributed_pct")(_view([])) is None    # no trace


def test_four_chips_attribute_each_gap_by_its_own_writer():
    # every chip idles 2..6; only shard2's annotation covers it, and
    # main's (the parent, which drives no chip here) covers nothing
    ops = {k: [(0.0, 2.0, "a"), (6.0, 10.0, "a")] for k in range(4)}
    host = [(2.0, 6.0, "repro.compile@shard2"), (2.0, 6.0, "repro.compile@main")]
    workers = ["shard0", "shard1", "shard2", "shard3"]
    assert _unattributed(_trace(ops, host), workers) == pytest.approx((100 * 3 + 0) / 4)
    only2 = {2: ops[2]}
    assert _unattributed(_trace(only2, host), ["shard2"]) == 0.0
