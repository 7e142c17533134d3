"""Staged measurement pipeline + device executor: prefetch on/off equivalence
(identical values, identical compile counts), per-stage clocks and per-run
provenance counters, fail-fast future draining that journals completed work,
and `device`-executor bit-identity / resume (in-process and on a 4-fake-device
subprocess via XLA_FLAGS)."""

import itertools
import json
import os
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import repro
from repro.core import (
    ExperimentDesign,
    MeasurementStore,
    StageClock,
    TuningSession,
    TuningSpec,
    build_units,
)
from repro.core.api import STEAL_OVERSPLIT
from repro.core.stores import make_store

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPEC = TuningSpec(
    kernel="harris",
    backend_kwargs={"chip": "v5e"},
    algorithms=("rs", "ga"),
    design=ExperimentDesign(sample_sizes=(25,), n_experiments=(4,), final_repeats=3),
    seed=11,
)


def counter_timer():
    """Deterministic timing-stage clock: measured values become pure
    functions of call order, so pipelined and inline runs can be compared
    for exact equality."""
    ticks = itertools.count()
    return lambda: float(next(ticks))


def pallas_measurement(**kwargs):
    from repro.pallas_bench import PallasMeasurement, make_workload

    return PallasMeasurement(make_workload("add", x=16, y=256), **kwargs)


def batch_configs():
    """A batch mixing valid configs, screened-out configs, and geometry
    duplicates (w_z does not enter the add program)."""
    return [
        dict(t_x=tx, t_y=1, t_z=tz, w_x=1, w_y=1, w_z=wz)
        for tx, tz, wz in itertools.product((1, 2, 4, 16), (1, 2), (1, 2))
    ]


# ------------------------------------------------------------------ StageClock


def test_stage_clock_accumulates_and_resets():
    clock = StageClock()
    with clock.stage("compile"):
        pass
    clock.add("compile", 1.5)
    clock.add("time", 0.25)
    t = clock.times()
    assert t["compile"] >= 1.5 and t["time"] == 0.25
    clock.reset()
    assert clock.times() == {}


def test_stage_clock_is_thread_safe():
    clock = StageClock()

    def worker():
        for _ in range(1000):
            clock.add("compile", 0.001)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert abs(clock.times()["compile"] - 4.0) < 1e-6


# ------------------------------------------------- prefetch on/off equivalence


def test_prefetch_equivalence_in_interpret_mode():
    """The acceptance bar: with the compile prefetcher enabled, measured
    value arrays and n_compiles are identical to the inline path."""
    cfgs = batch_configs()
    inline = pallas_measurement(repeats=3, timer=counter_timer())
    v_inline = inline.measure_batch(cfgs)
    piped = pallas_measurement(repeats=3, pipeline_workers=3, timer=counter_timer())
    v_piped = piped.measure_batch(cfgs)
    piped.close()
    np.testing.assert_array_equal(v_inline, v_piped)
    assert inline.n_compiles == piped.n_compiles
    assert inline.run_compiles == piped.run_compiles
    assert np.isfinite(v_inline).any() and np.isinf(v_inline).any()


def test_prefetch_skips_screened_out_geometries():
    """The prefetcher must not compile configs the inline path would screen
    out — otherwise n_compiles diverges between the two paths."""
    # t_x=16 on a 16-row image fails the validity screen for add's geometry
    cfgs = batch_configs()
    inline = pallas_measurement(repeats=1)
    inline.measure_batch(cfgs)
    piped = pallas_measurement(repeats=1, pipeline_workers=4)
    piped.measure_batch(cfgs)
    piped.close()
    assert piped.n_compiles == inline.n_compiles
    assert sorted(piped._compiled) == sorted(inline._compiled)


def test_pipeline_pool_is_reusable_after_close():
    m = pallas_measurement(repeats=1, pipeline_workers=2)
    cfgs = batch_configs()[:4]
    a = m.measure_batch(cfgs)
    m.close()
    b = m.measure_batch(cfgs)           # pool rebuilds lazily
    np.testing.assert_array_equal(np.isfinite(a), np.isfinite(b))
    m.close()


def test_prefetched_compile_failures_are_penalties():
    """A geometry whose compile raises becomes a cached inf penalty through
    the prefetcher exactly as it does inline."""
    from repro.kernels.common import KernelBenchSpec
    from repro.pallas_bench import PallasMeasurement
    from repro.pallas_bench.workloads import PallasWorkload

    def boom(inputs, cfg, x, y):
        raise RuntimeError("no lowering for you")

    bench = KernelBenchSpec(
        name="boom", n_inputs=0, make_inputs=lambda x, y, seed: (), run=boom
    )
    m = PallasMeasurement(
        PallasWorkload(bench=bench, x=64, y=128),
        repeats=2, validate=False, pipeline_workers=2,
    )
    cfgs = [dict(t_x=1, t_y=1, t_z=z, w_x=1, w_y=1, w_z=1) for z in (1, 2, 2)]
    vals = m.measure_batch(cfgs)
    m.close()
    assert np.isinf(vals).all()
    assert m.n_compiles == 2            # one per distinct geometry, cached
    assert "no lowering" in m.reason_for(cfgs[0])


# ------------------------------------------------ per-run provenance counters


def test_provenance_counters_are_per_run():
    """n_compiles / n_invalid in provenance report work since the last
    reset(), not lifetime totals — a later matrix cell must not inherit an
    earlier cell's counts (the compile cache itself survives by design)."""
    m = pallas_measurement(repeats=1)
    m.measure_batch(batch_configs())
    first = m.provenance()
    assert first["n_compiles"] > 0 and first["n_invalid"] > 0
    assert first["n_compiles_total"] == m.n_compiles
    assert set(first["stage_s"]) == {"screen", "compile", "time", "record"}

    m.reset()
    blank = m.provenance()
    assert blank["n_compiles"] == 0 and blank["n_invalid"] == 0
    assert blank["n_compiles_total"] == first["n_compiles_total"]
    assert blank["stage_s"] == {}

    # warm re-measure: cache hits mean zero fresh compiles this run
    m.measure_batch(batch_configs())
    warm = m.provenance()
    assert warm["n_compiles"] == 0
    assert warm["n_invalid"] == first["n_invalid"]   # penalties re-served
    assert warm["n_compiles_total"] == first["n_compiles_total"]
    assert warm["stage_s"].get("compile", 0.0) == 0.0
    assert warm["stage_s"]["time"] > 0.0


def test_invalid_reasons_survive_reset():
    m = pallas_measurement(repeats=1)
    bad = dict(t_x=16, t_y=1, t_z=1, w_x=1, w_y=1, w_z=1)
    m.measure_batch([bad])
    reason = m.reason_for(bad)
    assert reason is not None
    m.reset()
    assert m.reason_for(bad) == reason


def test_stage_times_flow_through_wrappers_and_units(tmp_path):
    """Session-level plumbing: a staged backend's clocks land in the unit's
    stage_s (through the disk-cache wrapper) and in the record's compile/
    measure columns."""
    spec = TuningSpec(
        kernel="add",
        backend="pallas",
        backend_kwargs={"x": 16, "y": 256, "repeats": 1},
        algorithms=("rs",),
        design=ExperimentDesign(
            sample_sizes=(4,), n_experiments=(2,), final_repeats=2
        ),
        seed=3,
        store="json",
        store_path=str(tmp_path / "c.json"),
    )
    session = TuningSession(spec)
    session.run_matrix()
    rows = session.last_record.extra["cell_wall_s"]
    assert rows[0]["compile_s"] > 0.0 and rows[0]["measure_s"] >= 0.0
    assert rows[0]["wall_s"] >= rows[0]["compile_s"]

    # warm second run: everything served from the store, so no compile time
    warm = TuningSession(spec)
    warm.run_matrix()
    wrows = warm.last_record.extra["cell_wall_s"]
    assert wrows[0]["compile_s"] == 0.0 and wrows[0]["measure_s"] == 0.0


# --------------------------------------------------------- fail-fast draining


def arm_failing_unit(monkeypatch, bad_key: str):
    """Patch run_unit to raise once for the unit whose key is bad_key,
    recording every unit that actually ran."""
    ran = []
    armed = {"on": True}
    orig = TuningSession.run_unit

    def spy(self, u):
        ran.append(u.key)
        if armed["on"] and u.key == bad_key:
            raise RuntimeError(f"worker died on {u.key}")
        return orig(self, u)

    monkeypatch.setattr(TuningSession, "run_unit", spy)
    return ran, armed


def planned_units(spec, workers):
    """The decomposition a parallel run_matrix will build under the default
    stealing scheduler (cost-weighted oversplit)."""
    session = TuningSession(spec)
    return build_units(
        session.cells(),
        min_units=workers * STEAL_OVERSPLIT,
        cost=session._unit_cost(),
    )


def test_futures_failure_reraises_and_journals_completed(tmp_path, monkeypatch):
    """One failing worker no longer hides behind submission-order waits: the
    exception surfaces, and the healthy workers' journaled units are merged
    into the parent store so a resume re-runs only what actually failed."""
    spec = SPEC.replace(store="json", store_path=str(tmp_path / "c.json"))
    units = planned_units(spec, 2)
    bad = units[-1].key
    ran, armed = arm_failing_unit(monkeypatch, bad)

    with pytest.raises(RuntimeError, match="worker died"):
        TuningSession(spec).run_matrix(
            executor="futures", max_workers=2,
            futures_pool=ThreadPoolExecutor(max_workers=2),
        )
    assert bad in ran
    done_before = set(ran) - {bad}

    armed["on"] = False
    ran.clear()
    # resume with the same worker count so the decomposition matches the
    # journaled fragments exactly; with one pending unit the parallel
    # request degrades (with a warning) to serial
    with pytest.warns(UserWarning, match="degrades to serial"):
        res = TuningSession(spec).run_matrix(
            resume=True, executor="futures", max_workers=2,
            futures_pool=ThreadPoolExecutor(max_workers=2),
        )
    assert set(ran) == {bad}            # completed units served from journal
    assert not (done_before & set(ran))
    clean = repro.tune_matrix(SPEC)
    for key in clean.cells:
        np.testing.assert_array_equal(
            clean.cells[key].final_values, res.cells[key].final_values
        )


def test_device_executor_failure_then_resume(tmp_path, monkeypatch):
    """Kill-and-resume through the device executor's shard journals: a unit
    failure mid-run leaves the completed units journaled in the (merged)
    shard stores; the resumed device run re-executes only the failure."""
    spec = SPEC.replace(store="json", store_path=str(tmp_path / "c.json"))
    units = planned_units(spec, 2)
    bad = units[-1].key
    ran, armed = arm_failing_unit(monkeypatch, bad)

    with pytest.raises(RuntimeError, match="worker died"):
        with pytest.warns(UserWarning):   # 1 CPU device < 2 workers: capped
            TuningSession(spec).run_matrix(executor="device", max_workers=2)
    armed["on"] = False
    ran.clear()
    with pytest.warns(UserWarning):       # 1 pending unit: degrades to serial
        res = TuningSession(spec).run_matrix(
            resume=True, executor="device", max_workers=2
        )
    assert set(ran) == {bad}
    clean = repro.tune_matrix(SPEC)
    for key in clean.cells:
        np.testing.assert_array_equal(
            clean.cells[key].final_values, res.cells[key].final_values
        )


# ------------------------------------------------------------ device executor


def store_values_bytes(path: str, kind: str = "json") -> bytes:
    store = make_store(kind, path)
    items = sorted(store.items())
    if hasattr(store, "close"):
        store.close()
    return json.dumps(items, sort_keys=True).encode()


@pytest.mark.parametrize("kind", ["json", "sqlite"])
def test_device_executor_bit_identical_to_serial(tmp_path, kind):
    serial_path = str(tmp_path / f"serial.{kind}")
    device_path = str(tmp_path / f"device.{kind}")
    base = TuningSession(SPEC.replace(store=kind, store_path=serial_path))
    serial = base.run_matrix()
    dev_session = TuningSession(SPEC.replace(store=kind, store_path=device_path))
    with pytest.warns(UserWarning):       # single-device host: capped
        device = dev_session.run_matrix(executor="device", max_workers=2)
    for key in serial.cells:
        np.testing.assert_array_equal(
            serial.cells[key].final_values, device.cells[key].final_values
        )
    assert base.last_record.result["cells"] == dev_session.last_record.result["cells"]
    for session in (base, dev_session):
        if hasattr(session.store, "close"):
            session.store.close()
    assert store_values_bytes(serial_path, kind) == store_values_bytes(device_path, kind)
    # device threads close their shard stores before the parent absorbs them
    # (an open sqlite shard leaves "-wal"/"-shm" files beside it)
    assert not [f for f in os.listdir(tmp_path) if ".shard" in f]


FOUR_DEVICE_SCRIPT = """
import json, sys
import jax
assert len(jax.devices()) == 4, jax.devices()
from repro.core import (
    ExperimentDesign, MeasurementStore, TuningSession, TuningSpec,
)
tmp = sys.argv[1]
spec = TuningSpec(
    kernel="harris", backend_kwargs={"chip": "v5e"}, algorithms=("rs", "ga"),
    design=ExperimentDesign(sample_sizes=(25,), n_experiments=(4,),
                            final_repeats=3),
    seed=11,
)
paths = {}
for name, kwargs in (
    ("serial", {}),
    ("device", dict(executor="device", max_workers=4)),
):
    path = f"{tmp}/{name}.json"
    session = TuningSession(spec.replace(store="json", store_path=path))
    res = session.run_matrix(**kwargs)
    paths[name] = path

def values_bytes(p):
    return json.dumps(sorted(MeasurementStore(p).items()), sort_keys=True)

assert values_bytes(paths["serial"]) == values_bytes(paths["device"])
print("DEVICE_OK")
"""


def test_device_executor_on_four_fake_devices(tmp_path):
    """The acceptance bar: EXECUTORS["device"] on a host faked to 4 CPU
    devices produces a merged store byte-identical to serial.  XLA_FLAGS
    must be set before jax initializes, hence the subprocess."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICE_SCRIPT, str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr
    assert "DEVICE_OK" in out.stdout


PREFETCH_PIN_SCRIPT = """
import json, threading
import jax
assert len(jax.devices()) == 4, jax.devices()
from repro.core import ExperimentDesign, TuningSession, TuningSpec
from repro.pallas_bench.workloads import PallasWorkload

held = {"inputs": set(), "outputs": set()}
lock = threading.Lock()
materialize, run = PallasWorkload.materialize, PallasWorkload.run

def recording_materialize(self):
    arrays = materialize(self)
    with lock:
        held["inputs"].update(d.id for a in arrays for d in a.devices())
    return arrays

def recording_run(self, inputs, cfg):
    out = run(self, inputs, cfg)
    with lock:
        held["outputs"].update(d.id for d in out.devices())
    return out

PallasWorkload.materialize = recording_materialize
PallasWorkload.run = recording_run
spec = TuningSpec(
    kernel="add", backend="pallas",
    backend_kwargs={"x": 16, "y": 256, "repeats": 1},
    algorithms=("rs",),
    design=ExperimentDesign(sample_sizes=(4,), n_experiments=(4,),
                            final_repeats=1),
    seed=3,
)
TuningSession(spec).run_matrix(
    executor="device", max_workers=4, pipeline_workers=2, scheduler="static"
)
print("HELD", json.dumps({k: sorted(v) for k, v in held.items()}))
"""


def test_device_executor_prefetch_compiles_on_worker_device():
    """With the compile prefetcher on, each device worker's inputs and
    programs land on its own device: the prefetch pool's threads re-enter
    the worker's ``jax.default_device`` pin instead of defaulting to
    device 0."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", PREFETCH_PIN_SCRIPT],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr
    line = next(ln for ln in out.stdout.splitlines() if ln.startswith("HELD "))
    held = json.loads(line[len("HELD "):])
    assert held == {"inputs": [0, 1, 2, 3], "outputs": [0, 1, 2, 3]}
