"""Smoke run of the tuning path on one TPU chip.

    python3 chip_smoke.py                  # one chip, 8192x8192 f32
    python3 chip_smoke.py --four-chips     # only the device-executor phase
    python3 chip_smoke.py --cpu-rehearsal  # 128x256 in Pallas interpret mode
                                           # (add --four-chips and
                                           # XLA_FLAGS=--xla_force_host_platform_device_count=4
                                           # for the four-chip path)

Phases, in one process that starts no child touching JAX:

1. device check: the first device must be a TPU (a CPU with
   ``--cpu-rehearsal``); otherwise exit 2 before anything is tuned;
2. compile cache: ``JAX_COMPILATION_CACHE_DIR`` if set, else
   ``<checkout>/.jax_cache``; the persistent-cache hits are printed;
3. tune: ``repro.tune`` with the pallas backend and GA, S=25, for add,
   harris and mandelbrot;
4. check: Mosaic (not the interpreter) ran on the TPU, the winner is finite,
   no screened-in config failed to compile or run, and the winner's output
   matches the kernel's ``jnp`` oracle;
5. serve: the winners, indexed under the measured ``device_kind`` in a
   sqlite store, answer ``best_config`` over the HTTP endpoint on
   localhost: a hit per kernel at the tuned size and a nearest at half it.

``--four-chips`` runs one small matrix (add, GA, S=25, 8 experiments) under
``executor="device"`` on four chips and again serially on chip 0, and checks
that every chip held its worker's inputs and ran units, that both runs agree
on which configs are penalized, and that every winner is finite.

The numbers printed are smoke output, not benchmark numbers.  The last line
of standard output is the JSON result; it is printed only when every check
passed.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import threading
import urllib.parse
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
KERNELS = ("add", "harris", "mandelbrot")
CHIP_SIZE = (8192, 8192)
REHEARSAL_SIZE = (128, 256)
BUDGET = 25
FINAL_REPEATS = 10
FOUR_CHIP_EXPERIMENTS = 8
SEED = 0


def device_check(expect: str):
    """The first device's platform, before anything else touches JAX."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != expect:
        print(
            f"chip_smoke: expected platform {expect!r}, JAX found "
            f"{dev.platform!r} ({dev.device_kind}); nothing was run",
            file=sys.stderr,
        )
        sys.exit(2)
    return dev


class CacheEvents:
    """Counts JAX's persistent compilation-cache hits and misses."""

    def __init__(self):
        import jax

        self.counts = {"hits": 0, "misses": 0}
        names = {
            "/jax/compilation_cache/cache_hits": "hits",
            "/jax/compilation_cache/cache_misses": "misses",
        }

        def listen(event, **_):
            if event in names:
                self.counts[names[event]] += 1

        jax.monitoring.register_event_listener(listen)


def setup_compile_cache() -> tuple[str, CacheEvents]:
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(HERE, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # kernels compile in well under JAX's default 1 s threshold
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir, CacheEvents()


def tune_kernel(kernel: str, x: int, y: int, workdir: str) -> dict:
    import repro
    from repro.core import TuningSpec

    spec = TuningSpec(
        kernel=kernel,
        searcher="ga",
        backend="pallas",
        backend_kwargs={"x": x, "y": y},
        budget=BUDGET,
        final_repeats=FINAL_REPEATS,
        seed=SEED,
        store="sqlite",
        store_path=os.path.join(workdir, "winners.sqlite"),
    )
    record_path = os.path.join(workdir, f"{kernel}.json")
    result = repro.tune(spec, record_path=record_path)
    record = repro.RunRecord.load(record_path)
    return {"result": result, "prov": record.extra["backend_provenance"]}


def check_kernel(kernel: str, x: int, y: int, tuned: dict, on_chip: bool) -> list[str]:
    """Everything the run must show for one kernel; each failure named."""
    import jax

    from repro.kernels import TUNABLE_KERNELS, reference_mismatch
    from repro.kernels.add.ref import add_ref
    from repro.kernels.harris.ref import harris_ref
    from repro.kernels.mandelbrot.ref import mandelbrot_ref
    from repro.pallas_bench import make_workload

    prov, result = tuned["prov"], tuned["result"]
    errors = []
    want = (False, "tpu") if on_chip else (True, "cpu")
    if (prov["interpret"], prov["platform"]) != want:
        errors.append(
            f"ran with interpret={prov['interpret']} on {prov['platform']!r}, "
            f"expected interpret={want[0]} on {want[1]!r}"
        )
    if not (math.isfinite(result.best_value) and math.isfinite(result.final_value)):
        errors.append(
            f"winner not finite: best={result.best_value} final={result.final_value}"
        )
    for key, reason in prov["failures"].items():
        errors.append(f"screened-in config failed: {key}: {reason}")
    cfg = result.best_config
    inputs = make_workload(kernel, x, y).materialize()
    if kernel == "mandelbrot":
        out = TUNABLE_KERNELS[kernel](x, y, cfg)
    else:
        out = TUNABLE_KERNELS[kernel](*inputs, cfg)
    # an oracle runs on the device the kernel ran on, in the same f32
    # arithmetic — except harris's, on the host CPU: a TPU lowers its
    # 1-channel f32 convolutions to a layout larger than its HBM
    dev = jax.devices("cpu")[0] if kernel == "harris" else jax.devices()[0]
    with jax.default_device(dev):
        if kernel == "mandelbrot":
            ref = mandelbrot_ref(x, y)
        else:
            oracle = {"add": add_ref, "harris": harris_ref}[kernel]
            ref = oracle(*jax.device_put(inputs, dev))
        mismatch = reference_mismatch(kernel, jax.device_put(out, dev), ref)
    if mismatch is not None:
        errors.append(f"winner output vs jnp reference: {mismatch}")
    return errors


def serve_queries(store_path: str, device: str, x: int, y: int) -> list[tuple]:
    """(kernel, x, y, status) for each query answered by the HTTP endpoint."""
    from repro.serving import open_serve_store
    from repro.serving.http import ServingState, make_server

    store, _ = open_serve_store(store_path)
    server = make_server(ServingState(store), host="127.0.0.1", port=0)
    host, port = server.server_address[:2]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    answers = []
    try:
        queries = [(k, x, y) for k in KERNELS] + [("add", x // 2, y // 2)]
        for kernel, qx, qy in queries:
            q = urllib.parse.urlencode(
                {"kernel": kernel, "x": qx, "y": qy, "device": device}
            )
            url = f"http://{host}:{port}/best_config?{q}"
            with urllib.request.urlopen(url, timeout=30) as r:
                body = json.loads(r.read())
            answers.append((kernel, qx, qy, body["status"]))
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=30)
        store.close()
    return answers


def run_one_chip(on_chip: bool) -> list[str]:
    import jax

    x, y = CHIP_SIZE if on_chip else REHEARSAL_SIZE
    device_kind = jax.devices()[0].device_kind
    failures = []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        for kernel in KERNELS:
            tuned = tune_kernel(kernel, x, y, workdir)
            prov, result = tuned["prov"], tuned["result"]
            stage_s = prov["stage_s"]
            print(
                f"[smoke output, not a benchmark] {kernel} {x}x{y} "
                f"samples={result.n_samples} compiles={prov['n_compiles']} "
                f"penalties={json.dumps(prov['penalties'], sort_keys=True)} "
                f"compile_s={stage_s.get('compile', 0.0)} "
                f"time_s={stage_s.get('time', 0.0)} "
                f"winner={json.dumps(result.best_config, sort_keys=True)} "
                f"final_s={result.final_value}",
                flush=True,
            )
            errors = check_kernel(kernel, x, y, tuned, on_chip)
            print(f"[check] {kernel}: {'ok' if not errors else errors}", flush=True)
            failures += [f"{kernel}: {e}" for e in errors]
        answers = serve_queries(
            os.path.join(workdir, "winners.sqlite"), device_kind, x, y
        )
    expected = ["hit"] * len(KERNELS) + ["nearest"]
    for (kernel, qx, qy, status), want in zip(answers, expected, strict=True):
        print(f"[serve] {kernel} {qx}x{qy} {device_kind!r}: {status}", flush=True)
        if status != want:
            failures.append(f"serve {kernel} {qx}x{qy}: {status}, expected {want}")
    return failures


def _trace_units_by_device(trace_path: str) -> dict[int, int]:
    """Completed units per device-executor worker (``shard<k>`` = device k)."""
    units: dict[int, int] = {}
    with open(trace_path) as f:
        for line in f:
            ev = json.loads(line)
            if ev.get("ev") == "end" and ev.get("span") == "unit":
                src = str(ev.get("src", ""))
                if src.startswith("shard"):
                    k = int(src[len("shard"):])
                    units[k] = units.get(k, 0) + 1
    return units


def _penalized(store_path: str) -> tuple[set, set]:
    """(all measured keys, penalized keys) of a finished run's store."""
    from repro.core.stores import make_store

    store = make_store("sqlite", store_path)
    try:
        items = [(k, v) for k, v in store.items() if "|" in k]
    finally:
        store.close()
    return {k for k, _ in items}, {k for k, v in items if not math.isfinite(v)}


def run_four_chips(on_chip: bool) -> list[str]:
    """The device executor on four chips against the same matrix on chip 0
    (with ``--cpu-rehearsal``: four virtual CPU devices, made with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=4``)."""
    import jax

    import repro
    from repro.core import ExperimentDesign, TuningSpec

    devices = jax.devices()
    if len(devices) != 4:
        return [f"--four-chips needs 4 devices, JAX found {len(devices)}"]
    x, y = CHIP_SIZE if on_chip else REHEARSAL_SIZE
    in_bytes = 2 * x * y * 4                      # add's two f32 inputs
    failures = []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_4_") as workdir:
        spec = TuningSpec(
            kernel="add",
            searcher="ga",
            algorithms=("ga",),
            backend="pallas",
            backend_kwargs={"x": x, "y": y},
            design=ExperimentDesign(
                sample_sizes=(BUDGET,), n_experiments=(FOUR_CHIP_EXPERIMENTS,),
                final_repeats=FINAL_REPEATS,
            ),
            seed=SEED,
            store="sqlite",
        )
        runs = {}
        for name, executor, workers in (("device", "device", 4), ("serial", "serial", 1)):
            store_path = os.path.join(workdir, f"{name}.sqlite")
            tdir = os.path.join(workdir, f"trace_{name}")
            res = repro.tune_matrix(
                spec.replace(store_path=store_path),
                executor=executor, max_workers=workers, telemetry_dir=tdir,
            )
            finals = res.cells[("ga", BUDGET)].final_values
            measured, penalized = _penalized(store_path)
            runs[name] = (finals, measured, penalized)
            print(
                f"[smoke output, not a benchmark] four-chip {name}: "
                f"experiments={len(finals)} measured={len(measured)} "
                f"penalized={len(penalized)} "
                f"median_final_s={float(sorted(finals)[len(finals) // 2])}",
                flush=True,
            )
            if name == "device":
                units = _trace_units_by_device(os.path.join(tdir, "trace.jsonl"))
                for k, dev in enumerate(devices):
                    # the CPU backend keeps no memory statistics
                    stats = dev.memory_stats() or {}
                    peak = stats.get("peak_bytes_in_use")
                    print(
                        f"[four-chip] device {k} ({dev.device_kind}): "
                        f"units={units.get(k, 0)} peak_bytes_in_use={peak}",
                        flush=True,
                    )
                    if units.get(k, 0) < 1:
                        failures.append(f"device {k} ran no units")
                    if on_chip and (peak or 0) < in_bytes:
                        failures.append(
                            f"device {k} peak {peak} B < its worker's inputs {in_bytes} B"
                        )
            if not all(math.isfinite(v) for v in finals):
                failures.append(f"{name}: non-finite winner in {list(finals)}")
    (_, m_dev, p_dev), (_, m_ser, p_ser) = runs["device"], runs["serial"]
    both = m_dev & m_ser
    if (p_dev & both) != (p_ser & both):
        failures.append(
            f"penalized configs differ: device {sorted(p_dev & both)} "
            f"vs serial {sorted(p_ser & both)}"
        )
    print(
        f"[four-chip] penalized: device={len(p_dev)} serial={len(p_ser)} "
        f"(configs measured by both: {len(both)})",
        flush=True,
    )
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip device-executor phase")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="128x256 in Pallas interpret mode on the CPU")
    args = ap.parse_args(argv)

    dev = device_check("cpu" if args.cpu_rehearsal else "tpu")
    cache_dir, cache = setup_compile_cache()
    sys.path.insert(0, os.path.join(HERE, "src"))
    print(f"[device] {dev.platform} {dev.device_kind!r}; compile cache {cache_dir}",
          flush=True)

    if args.four_chips:
        failures = run_four_chips(on_chip=not args.cpu_rehearsal)
    else:
        failures = run_one_chip(on_chip=not args.cpu_rehearsal)
    print(f"[compile cache] persistent-cache hits={cache.counts['hits']} "
          f"misses={cache.counts['misses']}", flush=True)
    if failures:
        for f in failures:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    import jax

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
