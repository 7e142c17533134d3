"""repro.telemetry: the tracer/null sink API, the observability-only
contract (telemetry on/off produces identical stores across serial /
process / device executors), deterministic shard-trace merging including
kill-and-resume recovery, progress scanning, summarize tables, Chrome
export schema, and the ``python -m repro.telemetry`` CLI."""

import itertools
import json
import os

import numpy as np
import pytest

from repro.core import (
    ExperimentDesign,
    MeasurementStore,
    TuningSession,
    TuningSpec,
)
from repro.telemetry import (
    NULL_TELEMETRY,
    TRACE_FILE,
    Telemetry,
    chrome_trace,
    export_chrome,
    for_run_dir,
    format_progress,
    read_events,
    read_run,
    scan_progress,
    summarize,
)
from repro.telemetry.__main__ import main as telemetry_cli
from repro.telemetry.null import _NULL_SPAN
from repro.telemetry.progress import scan_events

SPEC = TuningSpec(
    kernel="harris",
    backend_kwargs={"chip": "v5e"},
    algorithms=("rs", "ga"),
    design=ExperimentDesign(sample_sizes=(25,), n_experiments=(4,), final_repeats=3),
    seed=11,
    dataset_size=200,
)


def counter_clock():
    ticks = itertools.count()
    return lambda: float(next(ticks))


def store_values_bytes(path: str) -> bytes:
    """Canonical bytes of a store's measurement VALUES (journal metadata
    carries wall-clocks, which legitimately vary run to run)."""
    return json.dumps(
        sorted(MeasurementStore(path).items()), sort_keys=True
    ).encode()


def assert_same_cells(a, b):
    assert set(a.cells) == set(b.cells)
    for key in a.cells:
        np.testing.assert_array_equal(
            a.cells[key].final_values, b.cells[key].final_values
        )
        np.testing.assert_array_equal(
            a.cells[key].search_best_values, b.cells[key].search_best_values
        )


# ------------------------------------------------------------- null telemetry


def test_null_telemetry_is_the_default_and_allocation_free():
    """The disabled path must not pay for telemetry: ``span()`` hands back
    one shared context manager regardless of arguments, every counter/event
    method is a no-op, and the session wires the singleton by default."""
    tel = NULL_TELEMETRY
    assert tel.enabled is False
    assert tel.span("unit", unit="x") is _NULL_SPAN
    assert tel.span("matrix") is tel.span("experiment", experiment=3)
    with tel.span("round"):
        pass
    tel.inc("compiles")
    tel.gauge("depth", 4)
    tel.event("plan", units_total=8)
    tel.stage("compile", 0.5, key="g")
    tel.emit_counters()
    assert tel.counters_snapshot() == {}
    assert tel.shard_path(0) is None and tel.shard_src(0) is None
    assert tel.absorb(["anything"]) == 0 and tel.recover() == 0
    tel.close()
    assert TuningSession(SPEC).telemetry is NULL_TELEMETRY


# ------------------------------------------------------------------- tracer


def test_tracer_spans_counters_and_failed_span(tmp_path):
    path = str(tmp_path / TRACE_FILE)
    tel = Telemetry(path, clock=counter_clock())
    with tel.span("unit", unit="ga/S25"):
        tel.stage("compile", 0.5, key="g1")
        tel.inc("compiles")
        tel.inc("compiles")
    with pytest.raises(RuntimeError, match="boom"):
        with tel.span("unit", unit="bad"):
            raise RuntimeError("boom")
    tel.gauge("prefetch_inflight", 3)
    tel.close()

    events = read_events(path)
    # per-writer total order, all stamped with this writer's src
    assert [e["seq"] for e in events] == list(range(len(events)))
    assert {e["src"] for e in events} == {"main"}
    begin, stage, end = events[0], events[1], events[2]
    assert begin["ev"] == "begin" and begin["unit"] == "ga/S25"
    assert stage["ev"] == "stage" and stage["dur"] == 0.5 and stage["key"] == "g1"
    assert end["ev"] == "end" and end["dur"] > 0 and "ok" not in end
    bad = [e for e in events if e.get("unit") == "bad" and e["ev"] == "end"]
    assert bad and bad[0]["ok"] is False          # the span died visibly
    counters = [e for e in events if e["ev"] == "counters"]
    assert counters[-1]["counters"] == {"compiles": 2}
    gauge = [e for e in events if e["ev"] == "gauge"][0]
    assert gauge["gauge"] == "prefetch_inflight" and gauge["value"] == 3


def test_reader_skips_torn_and_malformed_lines(tmp_path):
    path = str(tmp_path / TRACE_FILE)
    with open(path, "w") as f:
        f.write('{"ev": "plan", "seq": 0}\n')
        f.write("not json\n")
        f.write('{"ev": "end", "seq": 1, "span": "unit"')   # torn tail
    events = read_events(path)
    assert [e["ev"] for e in events] == ["plan"]
    assert read_events(str(tmp_path / "missing.jsonl")) == []


# ------------------------------------------ on/off identity across executors


def run_pair(tmp_path, run_dir, **matrix_kwargs):
    """The same matrix with telemetry off and on; returns both sessions'
    results plus the store paths."""
    off_path = str(tmp_path / "off.json")
    on_path = str(tmp_path / "on.json")
    res_off = TuningSession(
        SPEC.replace(store="json", store_path=off_path)
    ).run_matrix(**matrix_kwargs)
    tel = for_run_dir(str(run_dir))
    on = TuningSession(
        SPEC.replace(store="json", store_path=on_path), telemetry=tel
    )
    res_on = on.run_matrix(**matrix_kwargs)
    tel.close()
    return res_off, res_on, on, off_path, on_path


def test_serial_identical_store_and_trace_covers_every_unit(tmp_path):
    run_dir = tmp_path / "run"
    res_off, res_on, session, off_path, on_path = run_pair(tmp_path, run_dir)
    assert store_values_bytes(off_path) == store_values_bytes(on_path)
    assert_same_cells(res_off, res_on)

    events = read_run(str(run_dir))
    n_units = len(session.last_unit_plan)
    assert n_units > 0
    unit_ends = [e for e in events if e["ev"] == "end" and e.get("span") == "unit"]
    assert len(unit_ends) == n_units
    plan = [e for e in events if e["ev"] == "plan"][0]
    assert plan["units_total"] == n_units
    assert plan["experiments_total"] == 8          # 2 algos x 4 experiments
    totals = [e for e in events if e["ev"] == "totals"][-1]["counters"]
    assert totals["units_completed"] == n_units
    assert totals["experiments_completed"] == 8
    # the merged counters ride along in the RunRecord for the report layer
    assert session.last_record.extra["telemetry"]["counters"] == totals
    prov = session.last_record.provenance
    assert "repro_version" in prov                 # satellite: build identity


def test_process_executor_identical_store_and_merged_shards(tmp_path):
    run_dir = tmp_path / "run"
    res_off, res_on, session, off_path, on_path = run_pair(
        tmp_path, run_dir, executor="process", max_workers=3
    )
    assert store_values_bytes(off_path) == store_values_bytes(on_path)
    assert_same_cells(res_off, res_on)
    # shard traces were absorbed into the main trace and deleted
    assert os.listdir(run_dir) == [TRACE_FILE]
    events = read_run(str(run_dir))
    srcs = {e["src"] for e in events}
    assert "main" in srcs and any(s.startswith("shard") for s in srcs)
    unit_ends = [e for e in events if e["ev"] == "end" and e.get("span") == "unit"]
    assert len(unit_ends) == len(session.last_unit_plan)
    totals = [e for e in events if e["ev"] == "totals"][-1]["counters"]
    assert totals["units_completed"] == len(session.last_unit_plan)


def test_device_executor_identical_store(tmp_path):
    run_dir = tmp_path / "run"
    with pytest.warns(UserWarning):      # single-device host: workers capped
        res_off, res_on, session, off_path, on_path = run_pair(
            tmp_path, run_dir, executor="device", max_workers=2
        )
    assert store_values_bytes(off_path) == store_values_bytes(on_path)
    assert_same_cells(res_off, res_on)
    assert os.listdir(run_dir) == [TRACE_FILE]


# --------------------------------------------------- shard merge + recovery


def shard_lines(run_dir, shard, lines):
    path = os.path.join(run_dir, f"trace.shard{shard}.jsonl")
    with open(path, "w") as f:
        f.write("\n".join(lines))
    return path


def test_absorb_is_deterministic_and_preserves_order(tmp_path):
    def build(run_dir):
        os.makedirs(run_dir)
        tel = Telemetry(os.path.join(run_dir, TRACE_FILE), clock=counter_clock())
        tel.event("plan", units_total=2)
        # absorb in shard order, each file's internal order preserved
        shard_lines(run_dir, 1, ['{"src": "shard1", "seq": 0, "ev": "x"}\n'])
        shard_lines(run_dir, 0, [
            '{"src": "shard0", "seq": 0, "ev": "a"}',
            '{"src": "shard0", "seq": 1, "ev": "b"}\n',
        ])
        n = tel.recover()
        tel.close()
        assert n == 2
        with open(os.path.join(run_dir, TRACE_FILE), "rb") as f:
            return f.read()

    a = build(str(tmp_path / "a"))
    b = build(str(tmp_path / "b"))
    assert a == b                                  # same inputs, same bytes
    events = read_events(str(tmp_path / "a" / TRACE_FILE))
    assert [e.get("src", "main") for e in events[-3:]] == [
        "shard0", "shard0", "shard1",
    ]
    assert not [n for n in os.listdir(tmp_path / "a") if "shard" in n]


def test_absorb_pads_torn_shard_tail(tmp_path):
    """A worker killed mid-write leaves a shard trace without a trailing
    newline; absorbing it must not glue the next file's first event onto
    the torn line."""
    run_dir = str(tmp_path)
    tel = for_run_dir(run_dir)
    tel.event("plan")
    shard_lines(run_dir, 0, ['{"ev": "stage", "src": "shard0"'])   # torn
    shard_lines(run_dir, 1, ['{"ev": "gauge", "src": "shard1", "value": 1}\n'])
    assert tel.recover() == 2
    tel.close()
    events = read_events(os.path.join(run_dir, TRACE_FILE))
    assert [e["ev"] for e in events] == ["plan", "gauge"]


def test_matrix_resume_recovers_orphan_shard_traces(tmp_path):
    """The kill-and-resume path end to end: a killed parallel run leaves
    ``trace.shard<k>.jsonl`` beside the trace; the resumed run absorbs them
    before emitting its own plan, so pre-kill spans sit before the new plan
    and never inflate the resumed session's progress."""
    run_dir = tmp_path / "run"
    os.makedirs(run_dir)
    orphan = shard_lines(
        str(run_dir), 0,
        ['{"src": "shard0", "seq": 0, "ev": "end", "span": "experiment"}\n'],
    )
    tel = for_run_dir(str(run_dir))
    spec = SPEC.replace(store="json", store_path=str(tmp_path / "s.json"))
    session = TuningSession(spec, telemetry=tel)
    session.run_matrix(resume=True, executor="process", max_workers=2)
    tel.close()
    assert not os.path.exists(orphan)
    events = read_run(str(run_dir))
    plan_idx = max(i for i, e in enumerate(events) if e["ev"] == "plan")
    orphan_idx = [
        i for i, e in enumerate(events)
        if e.get("src") == "shard0" and e.get("seq") == 0
    ]
    assert orphan_idx and orphan_idx[0] < plan_idx
    state = scan_events(events)
    assert state.complete
    assert state.experiments_done == 8             # the orphan didn't count


# ------------------------------------------------------------------ progress


def test_scan_events_is_positional_after_the_last_plan():
    events = [
        {"ev": "end", "span": "experiment"},       # stale pre-plan activity
        {"ev": "plan", "units_total": 4, "experiments_total": 8,
         "units_done_resume": 1, "experiments_done_resume": 2},
        {"ev": "end", "span": "unit"},
        {"ev": "end", "span": "experiment"},
        {"ev": "end", "span": "experiment"},
        {"ev": "begin", "span": "unit"},           # dangling begin: not done
    ]
    state = scan_events(events)
    assert state.has_plan
    assert (state.units_done, state.units_total) == (2, 4)
    assert (state.experiments_done, state.experiments_total) == (4, 8)
    assert not state.complete
    line = format_progress(state, eta_s=90.0)
    assert "units 2/4" in line and "experiments 4/8 (50%)" in line
    assert "ETA 90s" in line


# ------------------------------------------------- summarize / export / CLI


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    """One serial telemetry run shared by the consumer-side tests."""
    tmp = tmp_path_factory.mktemp("traced")
    run_dir = str(tmp / "run")
    tel = for_run_dir(run_dir)
    session = TuningSession(
        SPEC.replace(store="json", store_path=str(tmp / "s.json")),
        telemetry=tel,
    )
    session.run_matrix()
    tel.close()
    return run_dir


def test_summarize_counts_and_progress(traced_run):
    s = summarize(traced_run)
    assert s["units_done"] == 2 and s["experiments_done"] == 8
    assert s["counters"]["experiments_completed"] == 8
    assert s["counters"]["store_misses"] > 0
    state = scan_progress(traced_run)
    assert state.complete

    # per-cell aggregates come from the parent's merged cell events
    cells = {(c["algo"], c["sample_size"]): c for c in s["cells"]}
    assert set(cells) == {("rs", 25), ("ga", 25)}
    assert all(c["n_experiments"] == 4 for c in cells.values())


def test_chrome_export_schema(traced_run):
    path = export_chrome(traced_run)
    assert path == os.path.join(traced_run, "trace_chrome.json")
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert events
    phases = {e["ph"] for e in events}
    assert phases <= {"B", "E", "X", "C", "i", "M"}
    for e in events:
        assert isinstance(e["name"], str) and "pid" in e
        if e["ph"] != "M":
            assert e["ts"] >= 0                    # per-src normalized
        if e["ph"] == "X":
            assert e["dur"] >= 0
    # every span that began also ended (clean run: balanced flame stack)
    assert sum(e["ph"] == "B" for e in events) == sum(
        e["ph"] == "E" for e in events
    )
    # one process track per writer, named via metadata
    meta = [e for e in events if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} == {"main"}


def test_chrome_export_normalizes_per_writer_epochs():
    events = [
        {"t": 100.0, "seq": 0, "src": "main", "ev": "begin", "span": "matrix"},
        {"t": 5.0, "seq": 0, "src": "shard0", "ev": "stage",
         "stage": "compile", "dur": 0.25},
        {"t": 101.0, "seq": 1, "src": "main", "ev": "end", "span": "matrix",
         "dur": 1.0},
    ]
    doc = chrome_trace(events)
    by = {(e["ph"], e.get("name")): e for e in doc["traceEvents"]}
    assert by[("B", "matrix")]["ts"] == 0.0        # main's own epoch
    assert by[("X", "compile")]["ts"] == 0.0       # shard0's own epoch
    assert by[("X", "compile")]["dur"] == 0.25e6
    assert by[("B", "matrix")]["pid"] != by[("X", "compile")]["pid"]


def test_cli_summarize_tail_export(traced_run, tmp_path, capsys):
    assert telemetry_cli([traced_run]) == 0        # bare run dir summarizes
    out = capsys.readouterr().out
    assert "counter totals" in out and "per-cell stage breakdown" in out

    assert telemetry_cli(["tail", traced_run]) == 0
    out = capsys.readouterr().out
    assert "units 2/2" in out and "experiments 8/8 (100%)" in out

    dest = str(tmp_path / "chrome.json")
    assert telemetry_cli(["export", traced_run, "-o", dest]) == 0
    with open(dest) as f:
        assert json.load(f)["traceEvents"]


# ------------------------------ pallas stages: inputs, JAX compile phases


ADD_CONFIGS = [
    {"t_x": 1, "t_y": 1, "t_z": 1, "w_x": 1, "w_y": 1, "w_z": 1},
    {"t_x": 2, "t_y": 1, "t_z": 1, "w_x": 1, "w_y": 1, "w_z": 1},
    {"t_x": 1, "t_y": 2, "t_z": 1, "w_x": 1, "w_y": 1, "w_z": 1},
]
COMPILE_PHASES = ("compile.trace", "compile.lower", "compile.backend")
#: the writer rounds t and dur to the microsecond
EPS = 2e-6


@pytest.fixture
def cold_jax():
    """JAX's in-memory caches emptied, so the configs compile again here."""
    import jax

    jax.clear_caches()


def pallas_measurement(**kwargs):
    from repro.pallas_bench import PallasMeasurement, make_workload

    return PallasMeasurement(make_workload("add", 128, 256), **kwargs)


def interval(ev):
    return float(ev["t"]) - float(ev["dur"]), float(ev["t"])


def inside(inner, outer):
    return outer[0] - EPS <= inner[0] and inner[1] <= outer[1] + EPS


def stages_named(events, name, src=None):
    return [e for e in events if e["ev"] == "stage" and e["stage"] == name
            and src in (None, e["src"])]


def assert_phases_nest(events, src=None):
    """Every compile phase lies inside a stage of its own writer named by
    its ``within``, and every compile stage holds a backend compile."""
    phases = [e for e in events if e["ev"] == "stage" and e["stage"] in COMPILE_PHASES
              and src in (None, e["src"])]
    assert {e["stage"] for e in phases} == set(COMPILE_PHASES)
    for ph in phases:
        assert ph["within"] in ("compile", "inputs"), ph
        owners = stages_named(events, ph["within"], ph["src"])
        assert any(inside(interval(ph), interval(o)) for o in owners), ph
    for c in stages_named(events, "compile", src):
        assert any(inside(interval(b), interval(c))
                   for b in stages_named(events, "compile.backend", c["src"])), c


def test_pallas_stages_emit_inputs_and_compile_phases_inside_compile(tmp_path, cold_jax):
    tel = Telemetry(str(tmp_path / TRACE_FILE))
    m = pallas_measurement()
    m.set_telemetry(tel)
    values = m.measure_batch(ADD_CONFIGS)
    tel.close()
    assert np.isfinite(values).all()

    events = read_events(tel.path)
    compiles = stages_named(events, "compile")
    assert len(compiles) == m.n_compiles == 3
    (inputs,) = stages_named(events, "inputs")
    assert any(inside(interval(inputs), interval(c)) for c in compiles)
    assert_phases_nest(events)
    # the sub-stages are the trace's alone: the stage clock keeps four keys
    assert set(m.provenance()["stage_s"]) == {"screen", "compile", "time", "record"}


def test_prefetch_pool_compiles_land_in_the_measuring_writer(tmp_path, cold_jax):
    tel = Telemetry(str(tmp_path / TRACE_FILE))
    m = pallas_measurement(pipeline_workers=2)
    m.set_telemetry(tel)
    try:
        m.measure_batch(ADD_CONFIGS)
    finally:
        m.close()
    tel.close()
    events = read_events(tel.path)
    assert len(stages_named(events, "compile")) == m.n_compiles == 3
    assert_phases_nest(events)


def test_null_path_registers_no_listener_and_emits_nothing(tmp_path, monkeypatch, cold_jax):
    import jax

    from repro.telemetry import jaxbridge

    registered = []
    monkeypatch.setattr(jaxbridge, "_listening", False)
    monkeypatch.setattr(jax.monitoring, "register_event_duration_secs_listener",
                        registered.append)
    m = pallas_measurement()
    assert m.telemetry is NULL_TELEMETRY
    m.measure_batch(ADD_CONFIGS[:1])
    assert registered == [] and not jaxbridge._listening
    assert NULL_TELEMETRY.stage_scope("compile") is _NULL_SPAN
    monkeypatch.undo()

    # with the listener live, a compile under the null sink reaches no writer
    tel = Telemetry(str(tmp_path / TRACE_FILE))
    traced = pallas_measurement()
    traced.set_telemetry(tel)
    traced.measure_batch(ADD_CONFIGS[:1])
    with open(tel.path) as f:
        lines = f.read()
    pallas_measurement().measure_batch(ADD_CONFIGS[1:])
    with open(tel.path) as f:
        assert f.read() == lines
    assert getattr(jaxbridge._bound, "sink", None) is None
    tel.close()


def test_pallas_store_identical_with_telemetry_on_and_off(tmp_path):
    """On a deterministic timer the same search tells the same values and
    stores the same entries with a writer attached or not."""
    from repro.core.engine import DiskCachedMeasurement, drive
    from repro.core.searchers.base import make_searcher
    from repro.pallas_bench import default_space

    results = {}
    for name in ("off", "on"):
        tel = Telemetry(str(tmp_path / name / TRACE_FILE)) if name == "on" else None
        inner = pallas_measurement(timer=counter_clock())
        store = MeasurementStore(str(tmp_path / f"{name}.json"))
        m = DiskCachedMeasurement(inner, store, prefix="add")
        m.set_telemetry(tel)
        searcher = make_searcher("rs", default_space("add", 128, 256), seed=5)
        result = drive(searcher, m, 4, telemetry=tel)
        store.save()
        results[name] = (result.best_config, result.best_value,
                         sorted(inner.provenance()["stage_s"]))
        if tel is not None:
            tel.close()
            spans = {e.get("span") for e in read_events(tel.path)}
            assert {"ask", "tell", "round"} <= spans
    assert results["on"] == results["off"]
    assert results["on"][2] == ["compile", "record", "screen", "time"]
    assert store_values_bytes(str(tmp_path / "on.json")) == store_values_bytes(
        str(tmp_path / "off.json"))


SHARDED_COMPILES_SCRIPT = """
import sys
import jax
assert len(jax.devices()) == 4, jax.devices()
import repro
from repro.core import ExperimentDesign, TuningSpec
spec = TuningSpec(
    kernel="add", backend="pallas",
    backend_kwargs={"x": 16, "y": 256, "repeats": 1},
    algorithms=("rs",),
    design=ExperimentDesign(sample_sizes=(3,), n_experiments=(4,),
                            final_repeats=1),
    seed=3,
)
repro.tune_matrix(spec, executor="device", max_workers=4, scheduler="static",
                  telemetry_dir=sys.argv[1])
"""


def test_device_executor_shards_hold_only_their_own_compiles(tmp_path):
    """Four worker threads, one per (virtual) chip, compile at once: each
    shard writer's compile phases lie inside that shard's own stages."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.path.join(root, "src")}
    out = subprocess.run(
        [sys.executable, "-c", SHARDED_COMPILES_SCRIPT, str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    events = read_run(str(tmp_path))
    shards = sorted({e["src"] for e in events if e["src"].startswith("shard")})
    assert shards == ["shard0", "shard1", "shard2", "shard3"]
    for src in shards:
        assert_phases_nest(events, src)
    # the parent measured nothing and compiled nothing
    assert not [e for e in events if e["src"] == "main" and e["ev"] == "stage"]


def test_profiler_trace_holds_the_program_regions(tmp_path, cold_jax):
    """A JAX profiler session records the writer's spans and stages as host
    events ``repro.<name>@<src>``, as the chip benchmark reads them."""
    import sys

    import jax

    from repro.core.engine import drive
    from repro.core.searchers.base import make_searcher
    from repro.pallas_bench import default_space

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from chipbench import devtrace

    tel = Telemetry(str(tmp_path / "run" / TRACE_FILE))
    m = pallas_measurement()
    m.set_telemetry(tel)
    searcher = make_searcher("rs", default_space("add", 128, 256), seed=5)
    jax.profiler.start_trace(str(tmp_path / "profile"))
    try:
        with tel.span("experiment", experiment=0):
            drive(searcher, m, 2, telemetry=tel)
    finally:
        jax.profiler.stop_trace()
    tel.close()

    names = {name for _, _, name in devtrace.load(str(tmp_path / "profile")).host}
    regions = {n for n in names if n.startswith("repro.")}
    assert {"repro.experiment@main", "repro.ask@main", "repro.round@main",
            "repro.tell@main", "repro.screen@main", "repro.compile@main",
            "repro.inputs@main", "repro.time@main", "repro.record@main"} <= regions
    # no region is named like the native host activity the breakdown reads
    assert not [n for n in regions if devtrace.activity(n) is not None]


def test_bridge_writes_traces_outside_lowering_as_their_union(tmp_path):
    """JAX reports a trace per jitted function: nested traces are written
    as their union, and traces inside a lowering are left to it."""
    from repro.telemetry import jaxbridge

    now = [0.0]
    tel = Telemetry(str(tmp_path / TRACE_FILE), clock=lambda: now[0])
    with tel.stage_scope("compile"):
        for end, dur, event in [
            (1.8, 0.3, "jaxpr_trace_duration"),            # 1.5..1.8, inside the next
            (2.0, 1.0, "jaxpr_trace_duration"),            # 1.0..2.0
            (3.1, 0.1, "jaxpr_trace_duration"),            # helpers traced while lowering
            (3.5, 0.1, "jaxpr_trace_duration"),
            (4.0, 1.5, "jaxpr_to_mlir_module_duration"),   # 2.5..4.0
            (5.0, 0.5, "backend_compile_duration"),        # 4.5..5.0
            (6.0, 0.25, "jaxpr_trace_duration"),           # a trace after the backend
        ]:
            now[0] = end
            jaxbridge._on_duration(f"/jax/core/compile/{event}", dur, fun_name="f")
        now[0] = 7.0
    tel.close()
    got = [(e["stage"], e["t"] - e["dur"], e["t"], e["within"])
           for e in read_events(tel.path) if e["ev"] == "stage"]
    assert got == [
        ("compile.trace", 1.0, 2.0, "compile"),
        ("compile.lower", 2.5, 4.0, "compile"),
        ("compile.backend", 4.5, 5.0, "compile"),
        ("compile.trace", 5.75, 6.0, "compile"),
    ]
