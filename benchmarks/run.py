"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * per (benchmark x chip x algorithm x sample-size): the median tuned
    runtime in µs, with pct-of-optimum as the derived column (Fig. 2),
  * aggregate mean + CI rows (Fig. 3),
  * speedup-over-RS and CLES rows (Fig. 4a / 4b),
  * searcher-overhead microbenchmarks (µs per sample of algorithm cost),
  * Pallas-kernel interpret-mode microbenchmarks vs their oracles.

By default reuses results/paper_matrix if the full background run exists;
otherwise runs a budget-scaled matrix (--budget, default 500 — a few
minutes on one core).  ``--full`` forces the paper-exact design.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from benchmarks.paper_matrix import BENCHMARKS, CHIP_NAMES, combo_path, run_combo
from repro.analysis import load_all, validate
from repro.analysis.stats import (
    fig2_pct_optimum,
    fig3_aggregate,
    fig4a_speedup,
    fig4b_cles,
)
from repro.core import ExperimentDesign, TuningSession, TuningSpec


def ensure_matrix(out_dir: str, budget: int, shards: int = 1) -> str:
    full_dir = os.path.join("results", "paper_matrix")
    if all(
        os.path.exists(combo_path(full_dir, b, c))
        for b in BENCHMARKS
        for c in CHIP_NAMES
    ):
        return full_dir
    design = ExperimentDesign.scaled(budget=budget)
    os.makedirs(out_dir, exist_ok=True)
    for b in BENCHMARKS:
        for c in CHIP_NAMES:
            if not os.path.exists(combo_path(out_dir, b, c)):
                run_combo(b, c, design, out_dir, verbose=False, shards=shards)
    return out_dir


def table_fig2(results_dir: str) -> None:
    results = load_all(results_dir)
    f2 = fig2_pct_optimum(results)
    for (bench, chip), algos in sorted(f2.items()):
        res, meta = results[(bench, chip)]
        for algo, row in algos.items():
            for s, pct in row.items():
                med = float(np.median(res.finals(algo, s)))
                print(f"fig2/{bench}_{chip}/{algo}/S{s},{med*1e6:.2f},{pct:.2f}")


def table_fig3(results_dir: str) -> None:
    agg = fig3_aggregate(load_all(results_dir))
    for algo, rows in agg.items():
        for s, (m, lo, hi) in rows.items():
            print(f"fig3/{algo}/S{s},{m:.3f},{lo:.2f}..{hi:.2f}")


def table_fig4(results_dir: str) -> None:
    results = load_all(results_dir)
    sp = fig4a_speedup(results)
    cl = fig4b_cles(results)
    for key in sorted(sp):
        bench, chip = key
        for algo in sp[key]:
            for s in sp[key][algo]:
                print(
                    f"fig4a/{bench}_{chip}/{algo}/S{s},{sp[key][algo][s]:.4f},"
                    f"cles={cl[key][algo][s]:.4f}"
                )


def table_searcher_overhead() -> None:
    """Algorithm cost per sample (the paper ignores it by design — section V
    — but the framework reports it for completeness)."""
    for algo in ("rs", "rf", "ga", "bo_gp", "bo_tpe", "sa", "pso"):
        session = TuningSession(
            TuningSpec(kernel="harris", searcher=algo, budget=100, seed=0)
        )
        t0 = time.perf_counter()
        session.run()
        dt = time.perf_counter() - t0
        print(f"searcher_overhead/{algo},{dt/100*1e6:.1f},budget=100")


def table_engine_dispatch(budget: int = 400) -> None:
    """Batched ask/tell engine vs sequential dispatch on the vectorized
    cost-model backend: Python-level measurement dispatches and wall clock
    per searcher.  The batched path must dispatch >=5x less (it does ~100x
    less for the batch-friendly searchers)."""
    tot_b = tot_o = 0
    for algo in ("rs", "rf", "ga", "pso", "grid"):
        spec = TuningSpec(kernel="harris", searcher=algo, budget=budget, seed=0)
        sb = TuningSession(spec)
        t0 = time.perf_counter()
        sb.run()
        t_batch = time.perf_counter() - t0
        so = TuningSession(spec.replace(dispatch="one"))
        t0 = time.perf_counter()
        so.run()
        t_one = time.perf_counter() - t0
        tot_b += sb.measurement.n_dispatches
        tot_o += so.measurement.n_dispatches
        ratio = so.measurement.n_dispatches / max(1, sb.measurement.n_dispatches)
        print(
            f"engine_dispatch/{algo},{t_batch*1e6:.0f},"
            f"dispatches={sb.measurement.n_dispatches}v{so.measurement.n_dispatches} "
            f"ratio={ratio:.0f}x wall={t_one/max(t_batch,1e-9):.1f}x"
        )
    print(
        f"engine_dispatch/aggregate,{tot_b},"
        f"sequential={tot_o} ratio={tot_o/max(1,tot_b):.1f}x"
    )


def table_kernels() -> None:
    """Interpret-mode wall time of the real Pallas kernels (small images —
    interpret mode is a correctness vehicle, not a performance one)."""
    import jax.numpy as jnp

    from repro.kernels import TUNABLE_KERNELS, add_ref, harris_ref, mandelbrot_ref

    rng = np.random.default_rng(0)
    img = jnp.asarray(rng.normal(size=(128, 256)), jnp.float32)
    cfg = dict(t_x=2, t_y=1, t_z=2, w_x=1, w_y=1, w_z=2)

    def timeit(fn, *a, **k):
        fn(*a, **k)  # compile/warm
        t0 = time.perf_counter()
        for _ in range(3):
            np.asarray(fn(*a, **k))
        return (time.perf_counter() - t0) / 3

    t = timeit(TUNABLE_KERNELS["add"], img, img, cfg)
    r = timeit(add_ref, img, img)
    print(f"kernel_interpret/add,{t*1e6:.0f},ref_us={r*1e6:.0f}")
    t = timeit(TUNABLE_KERNELS["harris"], img, cfg)
    r = timeit(harris_ref, img)
    print(f"kernel_interpret/harris,{t*1e6:.0f},ref_us={r*1e6:.0f}")
    t = timeit(TUNABLE_KERNELS["mandelbrot"], 128, 256, cfg)
    r = timeit(mandelbrot_ref, 128, 256)
    print(f"kernel_interpret/mandelbrot,{t*1e6:.0f},ref_us={r*1e6:.0f}")


def table_pallas_backend(budget: int = 10) -> None:
    """The real-measurement path end-to-end: tune the add kernel through
    ``backend="pallas"`` (compile-and-time, validity pre-screen, compile
    cache) and report the tuned time plus the cache's figure of merit —
    compiles per sample served."""
    session = TuningSession(
        TuningSpec(
            kernel="add",
            searcher="ga",
            backend="pallas",
            backend_kwargs={"x": 128, "y": 256, "repeats": 3},
            budget=budget,
            final_repeats=3,
            seed=0,
        )
    )
    r = session.run()
    prov = session.measurement.provenance()
    print(
        f"pallas_backend/add,{r.final_value*1e6:.0f},"
        f"compiles={prov['n_compiles']}/{r.n_samples} "
        f"invalid={prov['n_invalid']} interpret={int(prov['interpret'])}"
    )


def table_pipeline_overlap(n_cfgs: int = 8, compile_ms: float = 25.0) -> None:
    """Compile-prefetch pipeline on a compile-bound synthetic workload: the
    first call per geometry sleeps ``compile_ms`` (standing in for Mosaic
    compilation), so the whole batch's compile cost is the serial floor the
    prefetcher exists to overlap.  Values must be identical pipelined or
    not; the wall-clock ratio is the PR's tracked perf number."""
    from repro.kernels.common import KernelBenchSpec
    from repro.pallas_bench import PallasMeasurement
    from repro.pallas_bench.workloads import PallasWorkload

    seen: set = set()

    def run(inputs, cfg, x, y):
        key = tuple(sorted(cfg.items()))
        if key not in seen:          # "compilation": first call per geometry
            seen.add(key)
            time.sleep(compile_ms / 1e3)
        return None

    bench = KernelBenchSpec(
        name="synthetic_compile", n_inputs=0,
        make_inputs=lambda x, y, seed: (), run=run,
    )
    cfgs = [
        dict(t_x=1 << i, t_y=1, t_z=1, w_x=1, w_y=1, w_z=1)
        for i in range(n_cfgs)
    ]
    walls, values = {}, {}
    for workers in (0, 4):
        seen.clear()
        # deterministic timing-stage clock: the VALUES must be identical
        # pipelined or not (only the wall-clock may differ), and a real
        # clock could never show that
        ticks = iter(range(10**9))
        m = PallasMeasurement(
            PallasWorkload(bench=bench, x=64, y=128),
            repeats=1, warmup=1, validate=False, pipeline_workers=workers,
            timer=lambda: float(next(ticks)),
        )
        t0 = time.perf_counter()
        values[workers] = m.measure_batch(cfgs)
        walls[workers] = time.perf_counter() - t0
        m.close()
    same = int(np.array_equal(values[0], values[4]))
    print(
        f"pipeline_overlap/prefetch_off,{walls[0]*1e6:.0f},configs={n_cfgs}"
    )
    print(
        f"pipeline_overlap/prefetch_on,{walls[4]*1e6:.0f},"
        f"speedup={walls[0]/max(walls[4], 1e-9):.2f}x identical={same}"
    )


def table_scheduler_tail(slow_ms: float = 30.0, workers: int = 2) -> None:
    """Work-stealing vs static scheduling around a straggler cell: one cell's
    experiments each pay a slow synthetic dispatch (standing in for a
    geometry that compiles/runs far slower than its neighbours), so the
    static one-partition-per-worker schedule stalls its join behind whichever
    worker drew the straggler while the stealing scheduler splits it by
    predicted cost and rebalances.  Values must be identical across serial /
    static / steal; the wall-clock ratio is the PR's tracked perf number."""
    from concurrent.futures import ThreadPoolExecutor

    from repro.core.backends import BACKENDS, Backend, register_backend
    from repro.core.measurement import BaseMeasurement
    from repro.core.runner import stable_seed
    from repro.core.space import Param, SearchSpace

    slow_s, n_exp, seed0 = 32, 4, 3

    class StragglerMeasurement(BaseMeasurement):
        """Deterministic pure-function values; experiments whose seed is in
        ``slow_seeds`` pay one ``slow_ms`` sleep per search dispatch."""

        def __init__(self, slow: bool, sleep_s: float):
            super().__init__()
            self._slow = slow
            self._sleep_s = sleep_s

        def _value(self, config) -> float:
            key = ",".join(f"{k}={v}" for k, v in sorted(config.items()))
            return 0.1 + (stable_seed(key) % 4096) / 4096.0

        def _measure_one(self, config) -> float:
            return self._value(config)

        def measure_batch(self, configs):
            self.n_samples += len(configs)
            self.n_dispatches += 1
            if self._slow:
                time.sleep(self._sleep_s)
            return np.array([self._value(c) for c in configs], dtype=np.float64)

    # the straggler cell is the largest sample size: the cost model's
    # samples-x-experiments weight marks it most expensive, so the stealing
    # split slices it first
    slow_seeds = tuple(stable_seed(seed0, "rs", slow_s, e) for e in range(n_exp))
    if "straggler" not in BACKENDS:
        register_backend(
            Backend(
                name="straggler",
                make=lambda kernel="straggler", seed=0, slow_seeds=(),
                slow_ms=0.0, **_: StragglerMeasurement(
                    seed in set(slow_seeds), slow_ms / 1e3
                ),
                default_space=lambda kernel="straggler", **_: SearchSpace(
                    [Param.int_range("t_x", 1, 16), Param.int_range("t_y", 1, 16)]
                ),
            )
        )
    spec = TuningSpec(
        kernel="straggler",
        backend="straggler",
        backend_kwargs={"slow_seeds": list(slow_seeds), "slow_ms": slow_ms},
        searcher="rs",
        algorithms=("rs",),
        design=ExperimentDesign(
            sample_sizes=(slow_s, 8, 10, 12),
            n_experiments=(n_exp,) * 4,
            final_repeats=3,
        ),
        dataset_size=None,
        seed=seed0,
    )

    def run(**kw):
        session = TuningSession(spec)
        t0 = time.perf_counter()
        res = session.run_matrix(**kw)
        return res, time.perf_counter() - t0

    serial, t_serial = run()
    static, t_static = run(
        executor="futures", max_workers=workers, scheduler="static",
        futures_pool=ThreadPoolExecutor(max_workers=workers),
    )
    steal, t_steal = run(
        executor="futures", max_workers=workers,
        futures_pool=ThreadPoolExecutor(max_workers=workers),
    )
    same = int(
        all(
            np.array_equal(
                serial.cells[k].final_values, other.cells[k].final_values
            )
            and np.array_equal(
                serial.cells[k].search_best_values,
                other.cells[k].search_best_values,
            )
            for other in (static, steal)
            for k in serial.cells
        )
    )
    assert same, "scheduler changed values — the speed-knob contract broke"
    print(f"scheduler_tail/serial,{t_serial*1e6:.0f},cells=4 straggler=S32")
    print(
        f"scheduler_tail/static,{t_static*1e6:.0f},"
        f"workers={workers} speedup_vs_serial="
        f"{t_serial/max(t_static,1e-9):.2f}x"
    )
    print(
        f"scheduler_tail/steal,{t_steal*1e6:.0f},"
        f"workers={workers} speedup_vs_static="
        f"{t_static/max(t_steal,1e-9):.2f}x identical={same}"
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=500)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--shards", type=int, default=1)
    args = ap.parse_args()

    t0 = time.time()
    if args.full:
        out = os.path.join("results", "paper_matrix")
        os.makedirs(out, exist_ok=True)
        for b in BENCHMARKS:
            for c in CHIP_NAMES:
                if not os.path.exists(combo_path(out, b, c)):
                    run_combo(b, c, ExperimentDesign.paper(), out,
                              shards=args.shards)
        results_dir = out
    else:
        results_dir = ensure_matrix(
            os.path.join("results", f"matrix_{args.budget}"), args.budget,
            shards=args.shards,
        )
    print(f"# matrix: {results_dir}")
    table_fig2(results_dir)
    table_fig3(results_dir)
    table_fig4(results_dir)
    table_searcher_overhead()
    table_engine_dispatch()
    table_kernels()
    table_pallas_backend()
    table_pipeline_overlap()
    table_scheduler_tail()
    print("# paper-claims validation")
    checks = validate(results_dir)
    for name, v in checks.items():
        print(f"claim/{name},{v.status},{v.detail}")
    print(f"# total {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
