"""The real-measurement subsystem: pallas_bench + its engine/API wiring.

Covers the ISSUE-3 acceptance surface: compile-and-time measurement with a
keyed compilation cache, the validity pre-screen mapping bad configs to
structured inf penalties (not exceptions), searchers surviving non-finite
tells, penalty reasons round-tripping through both measurement stores, the
name-serializable ``BACKENDS["pallas"]`` path through ``repro.tune`` /
sharded ``tune_matrix``, and zero-recompile warm-store re-runs.
"""

import json
import math
import shutil
import time

import numpy as np
import pytest

import repro
from repro.core import (
    CallableMeasurement,
    DiskCachedMeasurement,
    MeasurementStore,
    Param,
    SearchSpace,
    SqliteMeasurementStore,
    TimingMeasurement,
    TuningSession,
    TuningSpec,
    config_key,
    make_searcher,
)
from repro.core.experiment import ExperimentDesign
from repro.kernels.common import KernelBenchSpec, geometry_from_config
from repro.pallas_bench import (
    InvalidMeasurement,
    PallasMeasurement,
    PallasWorkload,
    default_space,
    make_workload,
    validate_config,
    vmem_footprint,
)

GOOD = dict(t_x=2, t_y=1, t_z=2, w_x=1, w_y=1, w_z=1)

# tiny all-valid space on a (64, 128) problem: <= 16 distinct geometries,
# so interpret-mode tests stay fast
SMALL_SPACE = SearchSpace(
    [
        Param.int_range("t_x", 1, 2),
        Param.choice("t_y", (1,)),
        Param.int_range("t_z", 1, 2),
        Param.int_range("w_x", 1, 2),
        Param.choice("w_y", (1,)),
        Param.int_range("w_z", 1, 2),
    ]
)


def small_spec(**overrides) -> TuningSpec:
    kw = dict(
        kernel="add",
        searcher="ga",
        backend="pallas",
        backend_kwargs={"x": 64, "y": 128, "repeats": 2, "warmup": 1},
        space=SMALL_SPACE,
        budget=6,
        final_repeats=2,
        seed=0,
    )
    kw.update(overrides)
    return TuningSpec(**kw)


# ------------------------------------------------------------- workloads


def test_workload_inputs_deterministic_across_instances():
    a1 = make_workload("add", x=64, y=128).materialize()
    a2 = make_workload("add", x=64, y=128).materialize()
    assert len(a1) == 2
    for u, v in zip(a1, a2, strict=True):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
    # a different input_seed gives a different problem
    b = make_workload("add", x=64, y=128, input_seed=1).materialize()
    assert not np.array_equal(np.asarray(a1[0]), np.asarray(b[0]))


def test_workload_unknown_kernel_and_tiny_problem():
    with pytest.raises(KeyError):
        make_workload("nope")
    with pytest.raises(ValueError):
        make_workload("add", x=4, y=64)


def test_mandelbrot_workload_has_no_inputs():
    w = make_workload("mandelbrot", x=64, y=128)
    assert w.materialize() == ()


# -------------------------------------------------------------- validity


def test_validate_rules():
    w = make_workload("add", x=64, y=128)
    assert validate_config(w, GOOD) is None
    # block taller than the padded image
    r = validate_config(w, dict(t_x=16, t_y=1, t_z=16, w_x=1, w_y=1, w_z=1))
    assert r is not None and r.startswith("block:")
    # block wider than the padded image
    r = validate_config(w, dict(t_x=1, t_y=2, t_z=1, w_x=1, w_y=1, w_z=1))
    assert r is not None and r.startswith("block:")
    # vmem blowout on a workload big enough that blocks fit the image
    big = make_workload("harris", x=4096, y=4096)
    cfg = dict(t_x=16, t_y=16, t_z=2, w_x=1, w_y=1, w_z=8)
    r = validate_config(big, cfg, vmem_limit=1 << 20)
    assert r is not None and r.startswith("vmem:")
    assert vmem_footprint(big.bench, geometry_from_config(cfg), big.y) > (1 << 20)
    # grid bound
    r = validate_config(w, GOOD, max_grid=1)
    assert r is not None and r.startswith("grid:")


def test_invalid_measurement_meta_roundtrip():
    bad = InvalidMeasurement(reason="vmem:9 bytes > 1", stage="compile")
    back = InvalidMeasurement.from_meta(bad.to_meta())
    assert back.stage == "compile"
    assert back.reason == "vmem:9 bytes > 1"
    assert math.isinf(back.penalty)


# ----------------------------------------------------- PallasMeasurement


def test_measure_valid_and_invalid():
    m = PallasMeasurement(make_workload("add", x=64, y=128), repeats=2)
    v = m.measure(GOOD)
    assert np.isfinite(v) and v > 0
    assert len(m.repeats_for(GOOD)) == 2
    bad = dict(t_x=16, t_y=16, t_z=16, w_x=1, w_y=1, w_z=1)
    assert math.isinf(m.measure(bad))
    assert m.reason_for(bad).startswith("validity:block:")
    assert m.reason_for(GOOD) is None
    # invalid configs never reach the compiler
    assert m.n_compiles == 1
    prov = m.provenance()
    assert prov["penalties"] == {"validity": 1} and prov["failures"] == {}


def test_compile_cache_shared_across_wz():
    m = PallasMeasurement(make_workload("add", x=64, y=128), repeats=1)
    for wz in (1, 2, 8):
        assert np.isfinite(m.measure({**GOOD, "w_z": wz}))
    assert m.n_compiles == 1
    m.measure({**GOOD, "t_x": 1})
    assert m.n_compiles == 2


def test_measure_batch_is_one_dispatch():
    m = PallasMeasurement(make_workload("add", x=64, y=128), repeats=1)
    vals = m.measure_batch([GOOD, {**GOOD, "w_z": 2}, {**GOOD, "t_x": 16, "t_z": 16}])
    assert vals.shape == (3,)
    assert np.isfinite(vals[:2]).all() and math.isinf(vals[2])
    assert m.n_dispatches == 1 and m.n_samples == 3


def test_run_failure_maps_to_penalty():
    def boom(inputs, cfg, x, y):
        raise RuntimeError("mosaic says no")

    bench = KernelBenchSpec(
        name="boom", n_inputs=0, make_inputs=lambda x, y, seed: (), run=boom
    )
    m = PallasMeasurement(PallasWorkload(bench=bench, x=64, y=128), repeats=1)
    v = m.measure(GOOD)
    assert math.isinf(v)
    assert "mosaic says no" in m.reason_for(GOOD)
    assert m.reason_for(GOOD).startswith("compile:")
    # the failed geometry is cached: no retry on the next proposal
    assert math.isinf(m.measure({**GOOD, "w_z": 2})) and m.n_compiles == 1
    # a screened-in config that failed to compile is counted and named
    prov = m.provenance()
    assert prov["penalties"] == {"compile": 2}
    assert set(prov["failures"]) == {config_key(GOOD), config_key({**GOOD, "w_z": 2})}
    assert all("mosaic says no" in r for r in prov["failures"].values())


def test_measure_final_reuses_compiled_program():
    m = PallasMeasurement(make_workload("add", x=64, y=128), repeats=1)
    m.measure(GOOD)
    final = m.measure_final(GOOD, repeats=4)
    assert np.isfinite(final)
    assert len(m.final_repeat_log[config_key(GOOD)]) == 4
    assert m.n_compiles == 1
    prov = m.provenance()
    assert prov["backend"] == "pallas" and prov["interpret"] is True
    assert prov["repeats"] == 1 and prov["warmup"] == 1
    assert prov["device_kind"]


# ------------------------------------------------- TimingMeasurement fix


class _AsyncResult:
    """Mimics a jax DeviceArray: work 'completes' only when fenced."""

    def __init__(self, log, delay_s):
        self._log = log
        self._delay = delay_s

    def block_until_ready(self):
        time.sleep(self._delay)
        self._log.append("fenced")


def test_timing_measurement_fences_inside_timed_region():
    log = []

    def runner(cfg):
        log.append("run")
        return _AsyncResult(log, 0.02)

    t = TimingMeasurement(runner, warmup=1)
    v = t.measure(dict(a=1))
    # warmup call + timed call, each fenced
    assert log == ["run", "fenced", "run", "fenced"]
    # the fence's sleep happened INSIDE the timed region
    assert v >= 0.015


def test_timing_measurement_always_warms_at_least_once():
    calls = []
    t = TimingMeasurement(lambda cfg: calls.append(1), warmup=0)
    t.measure(dict(a=1))
    assert len(calls) == 2  # 1 forced warmup (compile analogue) + 1 timed


# ------------------------------------------- searchers vs inf penalties

# roomier than SMALL_SPACE (64 configs) so a 16-sample budget cannot
# exhaust it — searcher behaviour, not exhaustion, is under test here
SEARCH_SPACE = SearchSpace(
    [
        Param.int_range("t_x", 1, 2),
        Param.choice("t_y", (1,)),
        Param.int_range("t_z", 1, 8),
        Param.int_range("w_x", 1, 2),
        Param.choice("w_y", (1,)),
        Param.int_range("w_z", 1, 2),
    ]
)


def _half_invalid_measurement():
    """Finite objective on t_x==1, inf otherwise (an invalid region)."""

    def fn(cfg):
        if cfg["t_x"] == 1:
            return 1.0 + 0.1 * cfg["t_z"] + 0.01 * cfg["w_x"]
        return float("inf")

    return CallableMeasurement(fn)


@pytest.mark.parametrize("algo", ["ga", "bo_gp", "bo_tpe", "rs", "sa"])
def test_searchers_survive_inf_tells(algo):
    s = make_searcher(algo, SEARCH_SPACE, seed=0)
    r = s.run(_half_invalid_measurement(), 16)
    assert r.n_samples == 16
    assert np.isfinite(r.best_value)
    assert r.best_config["t_x"] == 1
    # penalties are preserved verbatim in the history
    assert any(math.isinf(v) for v in r.history_values)


def test_ga_terminates_on_exhausted_space():
    """A space smaller than the budget must end the search, not livelock."""
    r = make_searcher("ga", SMALL_SPACE, seed=0).run(
        _half_invalid_measurement(), 16
    )
    assert 0 < r.n_samples <= 16
    assert np.isfinite(r.best_value)


def test_bo_gp_reclips_penalties_when_finite_max_grows():
    """An early penalty (clipped against nothing: 1.0) must not become the
    GP's incumbent once finite observations larger than it arrive — the
    stored penalties are re-clipped above the growing finite max."""
    space = SearchSpace([Param.int_range("t_x", 1, 2), Param.int_range("t_z", 1, 8)])

    def fn(cfg):  # invalid half; finite values all well above 1.0
        return float("inf") if cfg["t_x"] == 2 else 5.0 + 0.1 * cfg["t_z"]

    r = make_searcher("bo_gp", space, seed=3).run(CallableMeasurement(fn), 12)
    assert r.n_samples == 12
    assert np.isfinite(r.best_value) and r.best_value >= 5.0
    assert r.best_config["t_x"] == 1


def test_bo_gp_survives_all_inf_start():
    space = SearchSpace([Param.int_range("t_x", 2, 3), Param.int_range("t_z", 1, 4)])

    def fn(cfg):  # nothing is ever finite
        return float("inf")

    r = make_searcher("bo_gp", space, seed=0).run(CallableMeasurement(fn), 8)
    assert r.n_samples == 8 and math.isinf(r.best_value)


# ------------------------------------------------ store penalty metadata


@pytest.mark.parametrize("store_cls", [MeasurementStore, SqliteMeasurementStore])
def test_store_roundtrips_inf_and_reason(tmp_path, store_cls):
    path = str(tmp_path / "cache.bin")
    store = store_cls(path)
    store.put("k|a=1", float("inf"))
    store.put_meta("k|a=1", "validity:vmem:9 bytes > 1")
    store.put("k|a=2", 0.5)
    store.save()
    if hasattr(store, "close"):
        store.close()
    back = store_cls(path)
    assert math.isinf(back.get("k|a=1"))
    assert back.get("k|a=2") == 0.5
    assert back.get_meta("k|a=1") == "validity:vmem:9 bytes > 1"
    assert back.get_meta("k|a=2") is None
    assert dict(back.meta_items()) == {"k|a=1": "validity:vmem:9 bytes > 1"}


def test_json_store_without_meta_keeps_legacy_format(tmp_path):
    path = str(tmp_path / "cache.json")
    store = MeasurementStore(path)
    store.put("k", 1.0)
    store.save()
    with open(path) as f:
        assert json.load(f) == {"k": 1.0}


def test_disk_cache_records_and_serves_penalty_reasons(tmp_path):
    path = str(tmp_path / "cache.json")
    store = MeasurementStore(path)
    inner = PallasMeasurement(make_workload("add", x=64, y=128), repeats=1)
    m = DiskCachedMeasurement(inner, store, prefix="add/pallas/seed=0")
    bad = dict(t_x=16, t_y=16, t_z=16, w_x=1, w_y=1, w_z=1)
    m.measure_batch([GOOD, bad])
    store.save()

    # a FRESH wrapper over the persisted store serves the penalty from disk,
    # reason included, without touching the (cold) inner backend
    store2 = MeasurementStore(path)
    inner2 = PallasMeasurement(make_workload("add", x=64, y=128), repeats=1)
    m2 = DiskCachedMeasurement(inner2, store2, prefix="add/pallas/seed=0")
    vals = m2.measure_batch([GOOD, bad])
    assert np.isfinite(vals[0]) and math.isinf(vals[1])
    assert m2.n_misses == 0 and inner2.n_compiles == 0
    assert m2.reason_for(bad).startswith("validity:block:")


# ---------------------------------------------------- facade end-to-end


def test_tune_pallas_by_name_records_provenance(tmp_path):
    record_path = str(tmp_path / "record.json")
    spec = small_spec()
    spec.to_json()  # name-serializable — the whole point
    r = repro.tune(spec, record_path=record_path)
    assert 0 < r.n_samples <= 6
    assert np.isfinite(r.best_value) and np.isfinite(r.final_value)
    rec = repro.RunRecord.load(record_path)
    prov = rec.extra["backend_provenance"]
    assert prov["backend"] == "pallas"
    assert prov["interpret"] is True
    assert prov["repeats"] == 2 and prov["warmup"] == 1
    assert len(rec.result["final_repeat_times"]) == 2  # final_repeats
    assert rec.spec["backend"] == "pallas"


def test_tune_pallas_default_space_constraint_roundtrips():
    space = default_space("add", x=64, y=128)
    spec = TuningSpec(kernel="add", backend="pallas",
                      backend_kwargs={"x": 64, "y": 128}, space=space, budget=4)
    back = TuningSpec.from_json(spec.to_json())
    assert back.space.constraint is not None
    ok = dict(t_x=1, t_y=1, t_z=1, w_x=1, w_y=1, w_z=1)
    bad = dict(t_x=16, t_y=16, t_z=16, w_x=1, w_y=1, w_z=1)
    assert back.space.is_valid(ok) and not back.space.is_valid(bad)


def test_warm_store_rerun_zero_recompiles(tmp_path):
    spec = small_spec(store="json", store_path=str(tmp_path / "cache.json"),
                      budget=4)
    s1 = TuningSession(spec)
    s1.run()
    inner1 = s1.measurement.provenance()
    assert inner1["n_compiles"] > 0

    s2 = TuningSession(spec)
    r2 = s2.run()
    prov = s2.measurement.provenance()
    assert prov["n_compiles"] == 0
    assert prov["cache_misses"] == 0
    assert np.isfinite(r2.final_value)


def store_sections(path):
    """(values, non-journal meta, journal keys) of a JSON store file.  Unit-
    journal entries carry per-run wall-clocks, which legitimately differ
    between two runs of the same matrix; everything else must not.  Serving
    winners (format 3) fold into values minus their wall-clock ``fresh``
    stamp — the winner's config/value/provenance must be run-invariant."""
    import json

    with open(path) as f:
        raw = json.load(f)
    if not (isinstance(raw, dict) and raw.get("__format__") in (2, 3)):
        return raw, {}, set()
    meta = raw.get("meta", {})
    journal = {k for k in meta if k.startswith("__unit__|")}
    values = dict(raw["values"])
    for key, payload in raw.get("winners", {}).items():
        rec = json.loads(payload)
        rec.pop("fresh", None)
        values["__winner__|" + key] = json.dumps(rec, sort_keys=True)
    return (
        values,
        {k: v for k, v in meta.items() if k not in journal},
        journal,
    )


def test_matrix_sharded_warm_store_bit_identical(tmp_path):
    design = ExperimentDesign(sample_sizes=(3, 4), n_experiments=(2, 1),
                              final_repeats=2)
    single = str(tmp_path / "single.json")
    spec = small_spec(budget=None, design=design, algorithms=("rs", "ga"),
                      store="json", store_path=single)
    res1 = repro.tune_matrix(spec)
    vals1, meta1, journal1 = store_sections(single)

    # warm sharded re-run against a COPY of the single-process store:
    # workers seed their shard stores from it, so nothing is re-measured
    # and the merged store's measurements come back bit-identical (the unit
    # journal's wall-clocks are the only thing allowed to move)
    shard_path = str(tmp_path / "shard.json")
    shutil.copy(single, shard_path)
    res2 = repro.tune_matrix(spec.replace(store_path=shard_path), shards=2)
    vals2, meta2, journal2 = store_sections(shard_path)
    assert vals2 == vals1
    assert meta2 == meta1
    # the stealing scheduler over-splits cells, journaling finer-grained
    # fragments on top of the serial run's whole-cell entries — every
    # original entry survives, measurements untouched
    assert journal1 <= journal2
    for key in res1.cells:
        np.testing.assert_array_equal(
            res1.cells[key].final_values, res2.cells[key].final_values
        )
