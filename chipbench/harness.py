"""One run of one cell: set-up, the window, re-timing, the check, metrics.

Everything that belongs to one cell is data found by name: the cell in
``BENCHMARK.json``, its configuration file (kernel, size, reference,
limits), its traffic file (searcher, budget, executor, cache state) and the
per-layer metric readers under ``metrics/``.  The harness has no branch per
kernel, cell or metric.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import random
import statistics
import sys
import tempfile
import time
from contextlib import contextmanager, nullcontext
from dataclasses import dataclass
from pathlib import Path

from . import compare, devtrace, peaks, spans
from .window import Sample, Timed, Window, WindowClosed, install

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
#: JAX's persistent compilation cache for set-up and the check, at a fixed
#: path inside the checkout (the path is part of the cache's key)
CACHE_DIR = BENCH_DIR / ".cache" / "jax"
#: re-timing: blocks of back-to-back calls, each at least this long on the
#: host clock, whose per-call means give ``best_kernel_ms`` by their median
RETIME_BLOCK_S = 0.25
RETIME_BLOCKS = 5
#: timed programs whose output the check compares: the best and more of
#: the window's, drawn from the seed
CHECKED_CONFIGS = 4


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    per_layer: list[dict]
    end_to_end: list[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())

    def applies(metric):
        return name in metric.get("workloads", [name])

    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        per_layer=[m for m in bench["per_layer"] if applies(m)],
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
    )


def load_module(path: Path):
    """A module of the benchmark's own, by file path (references, metrics)."""
    modname = "chipbench._loaded." + path.stem.replace("-", "_")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class RunView:
    """What the per-layer metric readers read (``metrics/<name>.py``)."""

    start: float                 # window start, perf_counter seconds
    deadline: float              # window end
    samples: list[Sample]        # search samples recorded inside the window
    events: list[dict]           # the program's telemetry
    workers: list[str]           # telemetry writers that measured
    best: Sample | None          # the window's best sample
    best_chip: int = 0           # the chip its timed program ran on
    bytes_moved: int = 0         # least HBM traffic of one kernel call
    peak: dict | None = None     # peaks.PEAKS entry of the chip
    trace: devtrace.DeviceTrace | None = None
    best_device_s: float | None = None   # best config's device seconds per call
    busy_s: float | None = None          # device busy seconds in the window
    window_s: float | None = None        # the traced window's length


class CacheEvents:
    """Counts JAX persistent-cache hits while open, by a monitoring
    listener."""

    def __init__(self):
        self.hits = 0

    def _listen(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_listener(self._listen)


@contextmanager
def jax_config(**values):
    """Set JAX options for the duration and put back what was there; a
    change to the persistent cache's options takes effect at its reset."""
    import jax
    from jax._src import compilation_cache

    before = {k: getattr(jax.config, k) for k in values}
    for k, v in values.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def persistent_cache(enabled: bool):
    return jax_config(jax_enable_compilation_cache=enabled)


def retime(fn):
    """Host-clock seconds per call of ``fn`` over back-to-back calls that
    end in ``block_until_ready``: the median over blocks of each block's
    mean.  Returns (seconds per call, calls timed, last output)."""
    import jax

    out = jax.block_until_ready(fn())
    t = time.perf_counter()
    out = jax.block_until_ready(fn())
    one = max(time.perf_counter() - t, 1e-6)
    n = max(1, math.ceil(RETIME_BLOCK_S / one))
    per_call = []
    for _ in range(RETIME_BLOCKS):
        t = time.perf_counter()
        for _ in range(n):
            out = fn()
        jax.block_until_ready(out)
        per_call.append((time.perf_counter() - t) / n)
    return statistics.median(per_call), n * RETIME_BLOCKS, out


def _annotate(name: str, on: bool):
    import jax

    return jax.profiler.TraceAnnotation(devtrace.MARK_PREFIX + name) if on else nullcontext()


def read_trace(view: RunView, per_layer: list[dict], path: str, chips: int):
    """Per-layer metrics, the device's busy and window seconds and the
    breakdown of a traced run, from its profile at ``path`` (the trace
    directory or its ``.xplane.pb``): the ``window`` mark bounds the
    device's busy time, the ``retime`` mark the best config's calls."""
    view.trace = devtrace.load(path)
    lo, hi = view.trace.mark("window")
    busy = devtrace.busy_s(view.trace, lo, hi)
    view.busy_s = sum(busy.get(k, 0.0) for k in range(chips)) / chips
    view.window_s = hi - lo
    if view.best is not None:
        view.best_device_s, _ = devtrace.per_call_s(view.trace, view.best_chip,
                                                    *view.trace.mark("retime"))
    metrics = {}
    for m in per_layer:
        value = load_module(BENCH_DIR / "metrics" / f"{m['name']}.py").read(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"busy_s": view.busy_s, "window_s": view.window_s}
    return metrics, device, devtrace.breakdown(view.trace, lo, hi)


def same_program(timed: Timed, kbench, x: int, y: int, log=print) -> bool:
    """Whether the program the timing stage ran for a sample is the one the
    kernel's entry builds for the sample's config: their jaxprs, traced on
    the same inputs, are the same text.  Configs whose parameters do not
    enter the program (``w_z`` for all kernels, ``bn`` and ``w_y`` for
    harris) may share a program, and do share a jaxpr."""
    import jax

    want = jax.make_jaxpr(lambda: kbench.run(timed.inputs, timed.sample.config, x, y))()
    try:
        got = jax.make_jaxpr(timed.runner)()
    except Exception as e:  # noqa: BLE001 - a program that cannot be traced is not the config's
        log(f"[check] the timed program of {timed.sample.key} cannot be traced: {e!r}")
        return False
    return str(got) == str(want)


def check_programs(checked: list[Timed], kbench, ref, config: dict, rdev, x: int, y: int,
                   log=print) -> dict:
    """The kernel layer, on the programs the window timed: each one's output
    on the inputs its measurement ran it on, against the reference on those
    inputs (``out_err``, the largest over the programs); each program is the
    one its config asks for (``program_mismatches``); and the inputs are the
    configured problem (``input_flaws``)."""
    import jax

    if not checked:
        return {"out_err": float("inf"), "program_mismatches": 0, "input_flaws": 0}
    err, programs, flaws = 0.0, 0, 0
    for t in checked:
        programs += not same_program(t, kbench, x, y, log)
        flaws += compare.input_flaws(t.inputs, int(config["inputs"]), (x, y))
        out = jax.block_until_ready(t.runner())
        with jax.default_device(rdev):
            want = jax.jit(ref.reference)(*jax.device_put(t.inputs, rdev))
            err = max(err, compare.out_err(jax.device_put(out, rdev), want))
        del out, want
    return {"out_err": err, "program_mismatches": programs, "input_flaws": flaws}


def check_measurements(window: Window, samples: list[Sample], best: Sample | None,
                       screen) -> dict[str, int]:
    """The measurement layer: the best config is one the screen admits;
    every sample's value is the median of the repeats its timing stage
    measured (a penalty: inf); every value the searcher was told is what
    the session's store holds after the executor merged its workers."""
    import numpy as np

    record = 0
    for s in samples:
        if s.stage is None and s.repeats:
            record += s.value != float(np.median(s.repeats))
        else:
            record += not math.isinf(s.value)
    store = 0
    parent = window.parent()
    for s in samples:
        if s.key in window.told:
            stored = None if parent is None else parent.store.get(s.key)
            told = window.told[s.key]
            store += stored is None or stored != told or told != s.value
    return {
        "screen_rejects": int(best is not None and screen(best.config) is not None),
        "record_mismatches": int(record),
        "store_mismatches": int(store),
    }


def run_cell(
    name: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t0: float,
    require_tpu: bool = True,
    size: tuple[int, int] | None = None,
    keep_dir: str | None = None,
    log=print,
    root: Path = ROOT,
) -> dict:
    """One run of cell ``name``; returns the result object (the last line
    of a run's output).  ``require_tpu=False`` and ``size`` serve the CPU
    rehearsal and the tests, which drive the same path in interpret mode;
    ``root`` is where ``BENCHMARK.json`` is read."""
    cell = load_cell(name, root)
    if cell.traffic["compile_caches"] != "cold":
        raise ValueError(
            f"traffic {cell.traffic['name']!r}: only cold compile caches are measured"
        )

    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found {devices[0].platform!r} devices, not a TPU")
    if len(devices) < cell.chips:
        raise NoChip(f"cell {name!r} needs {cell.chips} chips, JAX found {len(devices)}")
    with jax_config(
        jax_compilation_cache_dir=str(CACHE_DIR),
        jax_persistent_cache_min_compile_time_secs=0,
    ), CacheEvents() as cache:
        return _run(cell, seed, seconds, trace, cache, t0=t0, require_tpu=require_tpu,
                    size=size, keep_dir=keep_dir, log=log)


def _run(cell, seed, seconds, trace, cache, *, t0, require_tpu, size, keep_dir, log) -> dict:
    import jax

    name, config, traffic = cell.name, cell.config, cell.traffic
    devices = jax.devices()
    chips = devices[: cell.chips]
    kind = devices[0].device_kind
    peak = peaks.peak(kind) if require_tpu else None
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import repro
    from repro.core import ExperimentDesign, TuningSpec
    from repro.kernels import KERNEL_BENCHES
    from repro.pallas_bench import make_workload, validate_config

    kernel = config["kernel"]
    x, y = size or (config["x"], config["y"])
    ref = load_module(BENCH_DIR / config["reference"])
    kbench = KERNEL_BENCHES[kernel]

    t_jax = time.perf_counter()
    # set-up: one compile of the kernel on each chip at a size the window
    # never uses, so the compiler's one-time start is not the first sample's
    wx, wy = traffic["warmup_size"]
    with persistent_cache(False):
        for d in chips:
            with jax.default_device(d):
                jax.block_until_ready(kbench.run(kbench.make_inputs(wx, wy, 0), {}, wx, wy))
    t_warm = time.perf_counter()
    log(f"[setup] start_to_jax_s={t_jax - t0!r} warmup_s={t_warm - t_jax!r}")

    work_ctx = tempfile.TemporaryDirectory(prefix="chipbench_") if keep_dir is None \
        else nullcontext(keep_dir)
    with work_ctx as work:
        os.makedirs(work, exist_ok=True)
        tel_dir = os.path.join(work, "telemetry")
        prof_dir = os.path.join(work, "profile")
        spec = TuningSpec(
            kernel=kernel,
            searcher=traffic["searcher"],
            algorithms=(traffic["searcher"],),
            backend="pallas",
            backend_kwargs={"x": x, "y": y, "input_seed": int(seed)},
            design=ExperimentDesign(
                sample_sizes=(int(traffic["sample_size"]),),
                n_experiments=(int(traffic["n_experiments"]),),
                final_repeats=int(traffic["final_repeats"]),
            ),
            seed=int(traffic["tuning_seed"]),
            store="sqlite",
            store_path=os.path.join(work, "store.sqlite"),
        )
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(prof_dir, profiler_options=opts)

        # the window: both compile caches off, the tuner stopped at the deadline
        window = Window(start=0.0, deadline=0.0, rng=random.Random(seed),
                        drawn_size=CHECKED_CONFIGS - 1)
        with install(window), persistent_cache(False), _annotate("window", trace):
            hits0 = cache.hits
            window.start = time.perf_counter()
            window.deadline = window.start + seconds
            try:
                repro.tune_matrix(
                    spec,
                    executor=traffic["executor"],
                    max_workers=int(traffic["max_workers"]),
                    telemetry_dir=tel_dir,
                )
            except WindowClosed:
                pass
            else:
                raise RuntimeError(
                    f"the matrix ended inside the window; raise n_experiments "
                    f"of traffic {traffic['name']!r}"
                )
            stopped = time.perf_counter()
            window_hits = cache.hits - hits0
        # the tuner's peak, before the harness's own re-timing and check
        memory_peak = max(
            ((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in chips), default=0
        )
        setup_s = window.start - t0
        samples = window.counted()
        best = window.best.sample if window.best is not None else None
        log(
            f"[window] cell={name} seed={seed} setup_s={setup_s!r} samples={len(samples)} "
            f"work={window.work()!r} finals={sum(s.final for s in window.samples)} "
            f"persistent_cache_hits={window_hits} program_compile_cache=off "
            f"overshoot_s={stopped - window.deadline!r} "
            f"best={json.dumps(best.config, sort_keys=True) if best else None} "
            f"best_recorded_s={best.value if best else None!r}"
        )

        if keep_dir is not None:
            with open(os.path.join(work, "window.json"), "w") as f:
                json.dump({
                    "cell": name, "kernel": kernel, "x": x, "y": y, "device_kind": kind,
                    "start": window.start, "deadline": window.deadline,
                    "samples": [dataclasses.asdict(s) for s in window.samples],
                }, f)
        workload = make_workload(kernel, x, y)
        checks = check_measurements(
            window, samples, best, lambda cfg: validate_config(workload, cfg)
        )
        parent = window.parent()
        if parent is not None and hasattr(parent.store, "close"):
            parent.store.close()
        window.sessions.clear()
        gc.collect()

        # after the window: re-time the best config's own timed program by
        # the harness's clock, on the chip and the inputs it was timed on
        best_s, best_chip = float("nan"), 0
        if window.best is not None:
            best_chip = next(iter(window.best.inputs[0].devices())).id
            with _annotate("retime", trace):
                best_s, n_calls, _ = retime(window.best.runner)
            log(f"[retime] calls={n_calls} seconds_per_call={best_s!r}")
        if trace:
            jax.profiler.stop_trace()

        # the kernel layer: the timed programs of the best sample and of a
        # few more of the window's, drawn from the seed as it went
        t_check = time.perf_counter()
        # a rehearsal has no chip: its reference runs where it ran
        rplat = config["reference_platform"] if require_tpu else devices[0].platform
        checked = window.checked()
        checks = {**check_programs(checked, kbench, ref, config, jax.devices(rplat)[0], x, y,
                                   log), **checks}
        log(f"[check] programs compared={len(checked)} of "
            f"{sum(math.isfinite(s.value) for s in samples)} "
            f"seconds={time.perf_counter() - t_check!r}")
        window.best = None
        window.drawn.clear()
        limits = config["limits"]
        correct = all(checks[k] <= limits[k] for k in checks)

        view = RunView(
            start=window.start,
            deadline=window.deadline,
            samples=samples,
            events=spans.read_events(os.path.join(tel_dir, "trace.jsonl")),
            workers=sorted({s.src for s in window.samples}),
            best=best,
            best_chip=best_chip,
            bytes_moved=int(ref.bytes_moved(x, y)),
            peak=peak,
        )
        device = {
            "platform": devices[0].platform,
            "kind": kind,
            "count": len(devices),
            "memory_peak_bytes": int(memory_peak),
        }
        result = {
            "correct": bool(correct),
            "attempted": len(samples),
            "failed": sum(s.stage in ("compile", "run") for s in samples),
        }
        if trace:
            metrics, busy, result["breakdown"] = read_trace(view, cell.per_layer, prof_dir,
                                                            cell.chips)
            device.update(busy)
        else:
            e2e = {
                "samples_per_s": window.work() / seconds,
                "best_kernel_ms": best_s * 1e3,
                "setup_s": setup_s,
            }
            metrics = {
                m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                for m in cell.end_to_end
            }
        result["metrics"] = metrics
        result["device"] = device
        result["checks"] = {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}
    return result
