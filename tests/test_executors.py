"""Work-unit executor layer: decomposition, executor equivalence (serial ≡
process ≡ futures ≡ legacy shards=N, bit-identical), within-cell splits of
big-E rows, journal-based kill-and-resume, and degrade warnings."""

import json
import os
import shutil
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import repro
from repro.core import (
    EXECUTORS,
    ExperimentDesign,
    ExperimentUnit,
    MeasurementStore,
    TuningSession,
    TuningSpec,
    UnitResult,
    build_units,
    merge_unit_results,
)
from repro.core.executors import (
    ExecutionPlan,
    run_units,
    shard_namespace,
    shard_store_path,
)

SMOKE = dict(kernel="harris", backend_kwargs={"chip": "v5e"})

SPEC = TuningSpec(
    **SMOKE,
    algorithms=("rs", "rf", "ga"),
    design=ExperimentDesign(sample_sizes=(25,), n_experiments=(4,), final_repeats=3),
    seed=11,
    dataset_size=200,
)


def unit(algo="ga", s=25, lo=0, hi=4, e=4):
    return ExperimentUnit(algo=algo, sample_size=s, exp_lo=lo, exp_hi=hi, n_exp=e)


def assert_same_cells(a, b):
    assert set(a.cells) == set(b.cells)
    for key in a.cells:
        np.testing.assert_array_equal(
            a.cells[key].final_values, b.cells[key].final_values
        )
        np.testing.assert_array_equal(
            a.cells[key].search_best_values, b.cells[key].search_best_values
        )
        np.testing.assert_array_equal(
            a.cells[key].n_samples_used, b.cells[key].n_samples_used
        )


def store_values_bytes(path: str) -> bytes:
    """Canonical bytes of a JSON store's measurement VALUES (journal entries
    in the metadata side-channel carry wall-clocks, which legitimately vary
    run to run)."""
    return json.dumps(
        sorted(MeasurementStore(path).items()), sort_keys=True
    ).encode()


# ------------------------------------------------------------- decomposition


def test_build_units_one_per_cell_by_default():
    cells = [("rs", 25, 8), ("ga", 50, 4)]
    units = build_units(cells)
    assert [u.key for u in units] == ["rs/S25/E8/e0:8", "ga/S50/E4/e0:4"]


def test_build_units_splits_largest_until_min_units():
    units = build_units([("ga", 25, 8)], min_units=4)
    assert [(u.exp_lo, u.exp_hi) for u in units] == [(0, 2), (2, 4), (4, 6), (6, 8)]
    assert all(u.n_exp == 8 for u in units)
    # more workers than experiments: stops at one experiment per unit
    units = build_units([("ga", 25, 2)], min_units=16)
    assert len(units) == 2


def test_build_units_caps_unit_experiments():
    units = build_units([("rs", 25, 5)], max_unit_experiments=2)
    assert [(u.exp_lo, u.exp_hi) for u in units] == [(0, 2), (2, 4), (4, 5)]


def test_unit_validation_and_roundtrip():
    with pytest.raises(ValueError, match="invalid experiment range"):
        ExperimentUnit(algo="ga", sample_size=25, exp_lo=3, exp_hi=3, n_exp=4)
    u = unit(lo=1, hi=3)
    assert ExperimentUnit.from_dict(u.to_dict()) == u
    r = UnitResult(
        unit=u,
        final_values=np.array([1.0, 2.0]),
        search_best_values=np.array([1.5, 2.5]),
        n_samples_used=np.array([25, 25]),
        wall_s=0.5,
    )
    again = UnitResult.from_dict(json.loads(json.dumps(r.to_dict())))
    np.testing.assert_array_equal(again.final_values, r.final_values)
    assert again.unit == u


def test_merge_detects_gaps_and_duplicates():
    cells = [("ga", 25, 4)]
    a = UnitResult(unit=unit(lo=0, hi=2), final_values=np.ones(2),
                   search_best_values=np.ones(2), n_samples_used=np.ones(2))
    b = UnitResult(unit=unit(lo=2, hi=4), final_values=np.ones(2),
                   search_best_values=np.ones(2), n_samples_used=np.ones(2))
    merged, walls = merge_unit_results(cells, [b, a])   # order-insensitive
    assert len(merged) == 1 and len(merged[0].final_values) == 4
    assert walls[("ga", 25)]["wall_s"] == a.wall_s + b.wall_s
    assert walls[("ga", 25)]["compile_s"] == 0.0   # unstaged: no breakdown
    assert walls[("ga", 25)]["measure_s"] == 0.0
    with pytest.raises(ValueError, match="duplicate unit"):
        merge_unit_results(cells, [a, a, b])
    with pytest.raises(ValueError, match="coverage gap|covered only"):
        merge_unit_results(cells, [a])


def test_executor_registry():
    assert {"serial", "process", "futures", "device"} <= set(EXECUTORS)
    assert repro.EXECUTORS is EXECUTORS
    with pytest.raises(KeyError, match="unknown executor"):
        run_units("warp", ExecutionPlan(session=None))
    with pytest.raises(KeyError, match="unknown executor"):
        TuningSession(SPEC).run_matrix(executor="warp")


# ------------------------------------------------------- executor equivalence


def test_all_executors_bit_identical(tmp_path):
    """serial ≡ legacy shards=N ≡ process ≡ futures: identical CellResults,
    identical RunRecord cell summaries, byte-identical merged store values —
    including within-cell splits of the rf/rs dataset-served paths."""
    runs = {
        "serial": dict(),
        "legacy": dict(shards=2),
        "process": dict(executor="process", max_workers=3),
        "futures": dict(
            executor="futures", max_workers=3,
            futures_pool=ThreadPoolExecutor(max_workers=3),
        ),
    }
    results, records, bytes_ = {}, {}, {}
    for name, kwargs in runs.items():
        path = str(tmp_path / f"{name}.json")
        session = TuningSession(
            SPEC.replace(store="json", store_path=path)
        )
        results[name] = session.run_matrix(**kwargs)
        records[name] = session.last_record.result
        bytes_[name] = store_values_bytes(path)
    for name in ("legacy", "process", "futures"):
        assert_same_cells(results["serial"], results[name])
        assert records[name]["cells"] == records["serial"]["cells"]
        assert bytes_[name] == bytes_["serial"]
    # shard stores were merged and cleaned up
    assert not [f for f in os.listdir(tmp_path) if ".shard" in f]


def test_within_cell_split_of_big_e_row():
    """A single-cell matrix — where the old `len(cells) > 1` guard silently
    ran serial — now splits the cell across workers, bit-identically."""
    spec = SPEC.replace(
        algorithms=("ga",),
        design=ExperimentDesign(sample_sizes=(25,), n_experiments=(6,), final_repeats=3),
        dataset_size=None,
    )
    serial = TuningSession(spec)
    base = serial.run_matrix()
    assert len(serial.last_unit_plan) == 1
    sharded = TuningSession(spec)
    split = sharded.run_matrix(executor="process", max_workers=3)
    assert len(sharded.last_unit_plan) >= 3      # the cell actually split
    assert_same_cells(base, split)


def test_unit_experiments_cap_is_bit_identical():
    spec = SPEC.replace(algorithms=("rs", "rf"))
    base = repro.tune_matrix(spec)
    session = TuningSession(spec)
    capped = session.run_matrix(unit_experiments=1)
    assert len(session.last_unit_plan) == 8      # 2 cells x 4 experiments
    assert_same_cells(base, capped)


def test_futures_pool_alone_implies_parallel_executor():
    """Passing a pool IS the parallelism request: no max_workers/executor
    needed, and the pool must actually be used (not silently degraded).
    Under the default stealing scheduler every unit is its own submission;
    under static there is exactly one payload per worker."""
    class CountingPool(ThreadPoolExecutor):
        submits = 0

        def submit(self, *args, **kwargs):
            type(self).submits += 1
            return super().submit(*args, **kwargs)

    spec = SPEC.replace(algorithms=("rs", "ga"), dataset_size=None)
    base = repro.tune_matrix(spec)
    session = TuningSession(spec)
    res = session.run_matrix(futures_pool=CountingPool(max_workers=2))
    assert CountingPool.submits == len(session.last_unit_plan) >= 2
    assert_same_cells(base, res)
    CountingPool.submits = 0
    res = repro.tune_matrix(
        spec, futures_pool=CountingPool(max_workers=2), scheduler="static"
    )
    assert CountingPool.submits == 2
    assert_same_cells(base, res)
    with pytest.raises(ValueError, match="futures_pool"):
        repro.tune_matrix(spec, executor="process",
                          futures_pool=ThreadPoolExecutor(max_workers=2))


def test_futures_default_pool_spawns_processes(tmp_path):
    spec = SPEC.replace(
        algorithms=("rs",), dataset_size=None,
        store="json", store_path=str(tmp_path / "f.json"),
    )
    base = repro.tune_matrix(spec.replace(store=None, store_path=None))
    res = repro.tune_matrix(spec, executor="futures", max_workers=2)
    assert_same_cells(base, res)


# --------------------------------------------------------- degrade + errors


def test_parallel_request_degrades_to_serial_with_warning():
    spec = SPEC.replace(
        algorithms=("ga",),
        design=ExperimentDesign(sample_sizes=(25,), n_experiments=(1,), final_repeats=3),
        dataset_size=None,
    )
    with pytest.warns(UserWarning, match="degrades to serial"):
        res = TuningSession(spec).run_matrix(shards=4)
    assert set(res.cells) == {("ga", 25)}


def test_resume_without_store_warns():
    spec = SPEC.replace(algorithms=("ga",), dataset_size=None)
    with pytest.warns(UserWarning, match="persistent store"):
        repro.tune_matrix(spec, resume=True)


def test_parallel_run_rejects_in_process_overrides():
    from repro.core import make_measurement

    session = TuningSession(
        SPEC,
        measurement_factory=lambda s: make_measurement(
            "costmodel", kernel="harris", seed=s
        ),
    )
    for executor in ("process", "futures"):
        with pytest.raises(RuntimeError, match="serialized spec"):
            session.run_matrix(executor=executor, max_workers=2)


@pytest.mark.parametrize("scheduler", ["steal", "static"])
def test_child_executors_refuse_device_backend_on_tpu(monkeypatch, scheduler):
    """Worker processes cannot share a chip: on a TPU host the pallas
    backend (``uses_device``) runs under the device executor, and the others
    say so."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = TuningSpec(
        kernel="add", backend="pallas", backend_kwargs={"x": 16, "y": 256},
        algorithms=("rs",), design=ExperimentDesign(sample_sizes=(2,), n_experiments=(2,)),
    )
    for executor in ("process", "futures"):
        with pytest.raises(RuntimeError, match="executor='device'"):
            TuningSession(spec).run_matrix(
                executor=executor, max_workers=2, scheduler=scheduler
            )


# ------------------------------------------------------------ kill-and-resume


def spy_run_unit(monkeypatch):
    ran = []
    orig = TuningSession.run_unit

    def spy(self, u):
        ran.append(u.key)
        return orig(self, u)

    monkeypatch.setattr(TuningSession, "run_unit", spy)
    return ran


def test_resume_skips_journaled_units(tmp_path, monkeypatch):
    """A run interrupted after K units resumes from the journal: completed
    units are never re-executed (zero re-measurements — run_unit is not even
    called) and the final matrix is bit-identical to an uninterrupted run."""
    clean = repro.tune_matrix(SPEC)
    spec = SPEC.replace(store="json", store_path=str(tmp_path / "c.json"))
    # "interrupted" run: execute + journal only the first 2 of 4+ units
    partial = TuningSession(spec)
    units = build_units(partial.cells(), min_units=4)
    journal = partial.unit_journal()
    for u in units[:2]:
        journal.put(partial.run_unit(u))
    partial.save_store()

    ran = spy_run_unit(monkeypatch)
    resumed = TuningSession(spec)
    res = resumed.run_matrix(resume=True, max_workers=4, executor="serial",
                             unit_experiments=None)
    # the serial resume re-plans with min_units=1 (whole cells); journaled
    # fine-grained fragments must still be composed/skipped
    done_keys = {u.key for u in units[:2]}
    assert not (done_keys & set(ran))
    assert_same_cells(clean, res)


def test_resume_ignores_journal_from_a_different_spec(tmp_path, monkeypatch):
    """The journal namespace fingerprints the WHOLE spec (minus storage
    fields): entries written under different searcher_kwargs / dataset
    settings must never be served to a resumed run."""
    spec = SPEC.replace(
        algorithms=("ga",), dataset_size=None,
        searcher="ga", searcher_kwargs={"pop_size": 8},
        store="json", store_path=str(tmp_path / "c.json"),
    )
    first = TuningSession(spec)
    first.run_matrix(resume=True)

    changed = spec.replace(searcher_kwargs={"pop_size": 12})
    ran = spy_run_unit(monkeypatch)
    res = TuningSession(changed).run_matrix(resume=True)
    assert len(ran) == len(build_units(TuningSession(changed).cells()))
    assert_same_cells(repro.tune_matrix(changed.replace(store=None, store_path=None)), res)


def test_resume_with_process_executor_after_serial_partial(tmp_path):
    """Cross-executor resume: units journaled by an interrupted serial run
    are skipped by a process-executor resume (journal payload bytes are
    untouched — a re-run would rewrite its wall-clock)."""
    spec = SPEC.replace(store="json", store_path=str(tmp_path / "c.json"))
    partial = TuningSession(spec)
    units = build_units(partial.cells(), min_units=3)
    journal = partial.unit_journal()
    done = [partial.run_unit(u) for u in units[:2]]
    for r in done:
        journal.put(r)
    partial.save_store()
    before = {
        journal.key(r.unit): partial.store.get_meta(journal.key(r.unit))
        for r in done
    }

    resumed = TuningSession(spec)
    res = resumed.run_matrix(resume=True, executor="process", max_workers=3)
    after_store = MeasurementStore(spec.store_path)
    for k, v in before.items():
        assert after_store.get_meta(k) == v     # entry untouched => not re-run
    assert_same_cells(repro.tune_matrix(SPEC), res)


def test_resume_recovers_killed_workers_shard_stores(tmp_path, monkeypatch):
    """A parallel run killed before the merge leaves *.shard<k> stores whose
    journals hold the workers' completed units; a resumed run absorbs them
    and re-executes nothing that finished."""
    spec = SPEC.replace(
        algorithms=("rs", "ga"),
        store="json", store_path=str(tmp_path / "c.json"),
    )
    # simulate the killed worker: a full serial run journaled into a store
    # that never became the parent store
    ghost = TuningSession(spec.replace(store_path=str(tmp_path / "ghost.json")))
    ghost_res = ghost.run_matrix()

    ran = spy_run_unit(monkeypatch)
    resumed = TuningSession(spec)
    shard = shard_store_path(resumed, 0)
    shutil.move(str(tmp_path / "ghost.json"), shard)
    res = resumed.run_matrix(resume=True)
    assert ran == []                            # everything recovered
    assert not os.path.exists(shard)
    assert_same_cells(ghost_res, res)


# ----------------------------------------------------- stealing scheduler


def test_steal_static_and_device_schedulers_bit_identical(tmp_path):
    """serial ≡ process(steal) ≡ process(static) ≡ device(steal) ≡
    futures(steal): identical cells and byte-identical store values, no
    leftover shard stores — the scheduler is pure wall-clock."""
    spec = SPEC.replace(algorithms=("rs", "ga"), dataset_size=None)
    runs = {
        "serial": dict(),
        "steal": dict(executor="process", max_workers=2, scheduler="steal"),
        "static": dict(executor="process", max_workers=2, scheduler="static"),
        "futures": dict(
            executor="futures", max_workers=2,
            futures_pool=ThreadPoolExecutor(max_workers=2),
        ),
    }
    results, bytes_ = {}, {}
    for name, kwargs in runs.items():
        path = str(tmp_path / f"{name}.json")
        session = TuningSession(spec.replace(store="json", store_path=path))
        results[name] = session.run_matrix(**kwargs)
        bytes_[name] = store_values_bytes(path)
    path = str(tmp_path / "device.json")
    session = TuningSession(spec.replace(store="json", store_path=path))
    with pytest.warns(UserWarning):          # single-device host: capped
        results["device"] = session.run_matrix(executor="device", max_workers=2)
    bytes_["device"] = store_values_bytes(path)
    for name in ("steal", "static", "futures", "device"):
        assert_same_cells(results["serial"], results[name])
        assert bytes_[name] == bytes_["serial"]
    assert not [f for f in os.listdir(tmp_path) if ".shard" in f]


def test_steal_run_emits_scheduler_telemetry(tmp_path):
    from repro.telemetry import for_run_dir, read_run

    run_dir = str(tmp_path / "run")
    tel = for_run_dir(run_dir)
    spec = SPEC.replace(
        algorithms=("rs", "ga"), dataset_size=None,
        store="json", store_path=str(tmp_path / "c.json"),
    )
    session = TuningSession(spec, telemetry=tel)
    session.run_matrix(executor="process", max_workers=2)
    tel.close()
    events = read_run(run_dir)
    plan = [e for e in events if e["ev"] == "plan"][0]
    assert plan["scheduler"] == "steal"
    # the queue drains one gauge tick per retired unit, ending at zero
    depths = [
        e["value"] for e in events
        if e["ev"] == "gauge" and e["gauge"] == "scheduler.queue_depth"
    ]
    assert len(depths) == len(session.last_unit_plan)
    assert sorted(depths, reverse=True) == depths and depths[-1] == 0
    # steals may legitimately be zero on a fast matrix; the counter must
    # simply never exceed what could have been rebalanced
    totals = [e for e in events if e["ev"] == "totals"][-1]["counters"]
    assert 0 <= totals.get("scheduler.steals", 0) <= len(depths)
    assert totals["units_completed"] == len(session.last_unit_plan)


def test_static_scheduler_plan_event_and_rejects_unknown(tmp_path):
    from repro.telemetry import for_run_dir, read_run

    run_dir = str(tmp_path / "run")
    tel = for_run_dir(run_dir)
    spec = SPEC.replace(algorithms=("rs", "ga"), dataset_size=None)
    TuningSession(spec, telemetry=tel).run_matrix(
        executor="process", max_workers=2, scheduler="static"
    )
    tel.close()
    plan = [e for e in read_run(run_dir) if e["ev"] == "plan"][0]
    assert plan["scheduler"] == "static"
    with pytest.raises(ValueError, match="unknown scheduler"):
        TuningSession(spec).run_matrix(scheduler="warp")


def test_process_steal_parent_failure_still_merges_shards(tmp_path, monkeypatch):
    """Fail-fast parity for the stealing path: when the parent's drain dies,
    completed workers' shard stores are absorbed before the error surfaces,
    so a resume re-executes nothing that finished."""
    import concurrent.futures as cf

    import repro.core.executors as ex

    spec = SPEC.replace(
        algorithms=("rs", "ga"), dataset_size=None,
        store="json", store_path=str(tmp_path / "c.json"),
    )
    clean = repro.tune_matrix(spec.replace(store=None, store_path=None))

    def dying_drain(plan, futures, n_workers):
        cf.wait(list(futures))               # let every unit finish first
        raise RuntimeError("parent died mid-drain")

    monkeypatch.setattr(ex, "_drain_steal", dying_drain)
    with pytest.raises(RuntimeError, match="parent died mid-drain"):
        TuningSession(spec).run_matrix(executor="process", max_workers=2)
    monkeypatch.undo()
    assert not [f for f in os.listdir(tmp_path) if ".shard" in f]

    ran = spy_run_unit(monkeypatch)
    res = TuningSession(spec).run_matrix(resume=True)
    assert ran == []                         # every unit came from the journal
    assert_same_cells(clean, res)


def test_resume_recovers_pid_shaped_steal_shards(tmp_path, monkeypatch):
    """Steal workers name shards by pid, not slot index — recovery globs, so
    a leftover ``*.shard31337`` from a killed stealing run is absorbed the
    same as the legacy ``*.shard0``."""
    spec = SPEC.replace(
        algorithms=("rs", "ga"),
        store="json", store_path=str(tmp_path / "c.json"),
    )
    ghost = TuningSession(spec.replace(store_path=str(tmp_path / "ghost.json")))
    ghost_res = ghost.run_matrix()

    ran = spy_run_unit(monkeypatch)
    resumed = TuningSession(spec)
    shard = shard_store_path(resumed, 31337)
    shutil.move(str(tmp_path / "ghost.json"), shard)
    res = resumed.run_matrix(resume=True)
    assert ran == []
    assert not os.path.exists(shard)
    assert_same_cells(ghost_res, res)


def test_recovery_ignores_other_specs_shards(tmp_path, monkeypatch):
    """Regression: shard filenames carry the journal-namespace digest, so a
    resumed run must NOT absorb a shard left behind by a *different* spec
    writing through the same store path (absorbing it would orphan journal
    entries and serve values from the wrong experiment)."""
    spec_a = SPEC.replace(
        algorithms=("rs",), store="json", store_path=str(tmp_path / "c.json"),
    )
    spec_b = spec_a.replace(seed=SPEC.seed + 1)   # different experiment stream
    assert (shard_namespace(TuningSession(spec_a))
            != shard_namespace(TuningSession(spec_b)))

    # a killed run of spec B left a fully-journaled shard beside c.json
    ghost = TuningSession(spec_b.replace(store_path=str(tmp_path / "ghost.json")))
    ghost.run_matrix()
    foreign = shard_store_path(TuningSession(spec_b), 0)
    shutil.move(str(tmp_path / "ghost.json"), foreign)

    ran = spy_run_unit(monkeypatch)
    res_a = TuningSession(spec_a).run_matrix(resume=True)
    assert ran != []                    # nothing recovered: A ran its own units
    assert os.path.exists(foreign)      # B's shard survives untouched

    # and B itself can still resume from its shard afterwards
    ran_b = spy_run_unit(monkeypatch)
    res_b = TuningSession(spec_b).run_matrix(resume=True)
    assert ran_b == []
    assert not os.path.exists(foreign)
    del res_a, res_b


# ------------------------------------------------------------- wall-clock


def test_cell_wall_clock_lands_in_record_and_figures(tmp_path):
    out = str(tmp_path / "out")
    repro.tune_matrix(SPEC.replace(cache_key="harris/v5e"), out_dir=out)
    rec = repro.RunRecord.load(os.path.join(out, "harris_v5e.json"))
    walls = rec.extra["cell_wall_s"]
    assert {(w["algo"], w["sample_size"]) for w in walls} == {
        ("rs", 25), ("rf", 25), ("ga", 25)
    }
    assert all(w["wall_s"] >= 0 for w in walls)
    # the costmodel backend is unstaged: breakdown columns exist but are 0
    assert all(w["compile_s"] == 0.0 and w["measure_s"] == 0.0 for w in walls)

    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmarks.figures import load_all, render_grid, search_cost

    table = search_cost(load_all(out))
    cell = table[("harris", "v5e")]["ga"][25]
    assert cell["wall"] >= 0 and cell["compile"] == 0.0 and cell["measure"] == 0.0
    assert "search cost" in render_grid(
        table, fmt="{0[wall]:.2f}s", title="search cost"
    )


# ------------------------------------------------------- fleet chaos (SIGKILL)


def test_fleet_sigkill_peer_steals_and_store_is_byte_identical(tmp_path):
    """Three cross-process fleet workers; one is SIGKILLed mid-unit (inside
    its ``--stall-s`` window, holding a claim).  The peers must steal the
    dead worker's claim, finish the job, and the collected parent store must
    be byte-identical to a serial run of the same spec."""
    import importlib.util
    import signal
    import subprocess
    import sys
    import time

    from repro.core.stores import make_store
    from repro.serving import JobQueue, collect_jobs, job_id_for_spec

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"))
    serve_dir = str(tmp_path / "serve")
    os.makedirs(serve_dir)
    store_path = os.path.join(serve_dir, "store.json")
    qdir = os.path.join(serve_dir, "queue")

    spec = SPEC.replace(store="json", store_path=store_path)
    store = make_store("json", store_path)
    queue = JobQueue(store, "json", store_path, qdir)
    jid = queue.enqueue(spec)
    assert jid == job_id_for_spec(
        spec.replace(store="json", store_path=store_path).to_dict()
    )

    def worker_cmd(ident, stall_s, claim_timeout_s, timeout_s):
        return [
            sys.executable, "-m", "repro.serving", "worker",
            "--dir", serve_dir, "--store", "json", "--ident", ident,
            "--stall-s", str(stall_s), "--claim-timeout-s", str(claim_timeout_s),
            "--timeout-s", str(timeout_s), "--poll-s", "0.05",
        ]

    # the victim stalls 60s after its first claim: an arbitrarily wide kill
    # window (we kill as soon as the claim file appears)
    victim = subprocess.Popen(
        worker_cmd("victim", 60, 1000, 120), env=env, cwd=repo,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    try:
        deadline = time.monotonic() + 60
        claimed = None
        while time.monotonic() < deadline:
            for f in os.listdir(qdir) if os.path.isdir(qdir) else []:
                if f.endswith(".claim"):
                    with open(os.path.join(qdir, f)) as fh:
                        if fh.read() == "victim":
                            claimed = f
                            break
            if claimed or victim.poll() is not None:
                break
            time.sleep(0.05)
        assert claimed, (
            f"victim never claimed a unit: {victim.communicate()[0]!r}"
        )
        os.kill(victim.pid, signal.SIGKILL)
    finally:
        victim.wait(timeout=30)

    # peers arrive late: the victim's claim is already stale for them
    peers = [
        subprocess.Popen(
            worker_cmd(ident, 0, 1.0, 90), env=env, cwd=repo,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for ident in ("w2", "w3")
    ]
    outs = [p.communicate(timeout=120)[0] for p in peers]
    for p, out in zip(peers, outs, strict=True):
        assert p.returncode == 0, out

    # done markers, inspected BEFORE collect cleans them up: every unit has
    # one, none was run by the victim, and the victim's unit was stolen
    done = []
    for f in sorted(os.listdir(qdir)):
        if f.endswith(".done"):
            done.append(json.load(open(os.path.join(qdir, f))))
    assert done, "no done markers published"
    assert all(d["ident"] in ("w2", "w3") for d in done)
    stolen = [d for d in done if d["stolen"]]
    assert len(stolen) == 1, stolen
    assert stolen[0]["ident"] != "victim"

    assert collect_jobs("json", store_path, qdir) == [jid]
    q2 = JobQueue(make_store("json", store_path), "json", store_path, qdir)
    assert q2.job(jid)["state"] == "done"
    assert q2.job(jid)["done_ident"] == "collect"

    # byte-identity against the serial reference, through the same tool the
    # executor-equivalence contract ships (tools/compare_stores.py)
    serial_path = str(tmp_path / "serial.json")
    TuningSession(spec.replace(store_path=serial_path)).run_matrix()
    tool_spec = importlib.util.spec_from_file_location(
        "compare_stores", os.path.join(repo, "tools", "compare_stores.py")
    )
    tool = importlib.util.module_from_spec(tool_spec)
    tool_spec.loader.exec_module(tool)
    assert tool.values_bytes(tool.load(store_path)) == tool.values_bytes(
        tool.load(serial_path)
    )
    assert tool.main([store_path, serial_path]) == 0
