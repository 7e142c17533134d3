"""Compile-and-time measurement of real ``pl.pallas_call`` kernels.

:class:`PallasMeasurement` is the objective function the ISSUE's real-
measurement path plugs into the batched ask/tell engine.  Measurement is a
staged pipeline — **screen → compile → time → record** — with each stage a
method of its own and a :class:`~repro.core.measurement.StageClock` charging
per-stage wall-clock into provenance (``screen_s`` / ``compile_s`` /
``time_s``), so the analysis layer can split search cost into "compiling"
vs "measuring":

* **screen** — the validity pre-screen (:mod:`.validity`) rejects bad
  geometries before any compile; failures become structured
  :class:`~repro.pallas_bench.validity.InvalidMeasurement` penalties
  (``float("inf")`` through the ordinary ``tell`` path, kernel_tuner-style)
  whose reasons survive into the measurement store.
* **compile once per geometry** — a keyed compilation cache maps each
  distinct kernel geometry to its warmed, ready-to-time callable.  Configs
  that lower to the same program (today: any two configs differing only in
  ``w_z``, which the Mosaic pipeliner owns) share one cache entry.
  ``n_compiles`` counts actual compilations — the figure a warm disk cache
  drives to zero.  With ``pipeline_workers > 0``, ``measure_batch`` runs
  two-phase: a *compile phase* resolves the whole batch's geometry keys
  through a thread-pool prefetcher (upcoming geometries compile while the
  device times the current config), then the *timing phase* walks the batch
  strictly sequentially — device measurements never overlap each other, only
  host-side compilation overlaps them.  ``pipeline_workers=0`` (default) is
  byte-for-byte today's inline path.
* **warmup + N-repeat timing** — every measurement runs ``warmup`` fenced
  calls (the compile call counts as the first), then ``repeats`` timed calls,
  each fenced with ``jax.block_until_ready`` INSIDE the timed region (the
  analogue of the paper timing after H2D and before D2H).  The robust
  aggregate is the median; all repeats are recorded (``repeats_for``) so the
  run record can carry the raw distribution.

On CPU the kernels run in Pallas interpret mode (``kernels.common
.use_interpret``); on a real TPU the same ``pallas_call`` lowers to Mosaic
with no change here — only the provenance dict's ``interpret``/
``device_kind`` fields flip.
"""

from __future__ import annotations

import threading
from collections import Counter
from concurrent.futures import Future, ThreadPoolExecutor
from contextlib import contextmanager
from typing import Callable, Sequence

import numpy as np

from ..core.clock import monotonic
from ..core.engine import config_key
from ..core.measurement import BaseMeasurement, StageClock, fence
from ..kernels.common import Config, geometry_from_config
from .compile_cache import CompileCache, deserialize_compiled, serialize_compiled
from .validity import (
    DEFAULT_MAX_GRID,
    DEFAULT_VMEM_LIMIT,
    InvalidMeasurement,
    validate_config,
)
from .workloads import PallasWorkload


class PallasMeasurement(BaseMeasurement):
    """Measures real kernel wall-clock; never raises on a bad config.

    ``repeats``/``warmup`` follow the kernel_tuner defaults (time several
    runs, keep a robust aggregate).  ``validate=False`` disables the
    pre-screen (compile/run failures are still caught) — useful to audit the
    screen itself.  ``pipeline_workers=N`` enables the batch compile
    prefetcher (N pool threads); 0 keeps the inline compile-then-time loop.
    ``timer`` is the timing-stage clock (default: the injectable monotonic
    seam in :mod:`repro.core.clock`, i.e. ``perf_counter``) —
    injectable so tests can prove pipeline on/off equivalence on
    deterministic timestamps.  ``seed`` is accepted for backend-factory
    uniformity; wall-clock timing has no noise stream to seed.

    ``compile_cache`` layers the persistent cross-process compile cache
    (:class:`~repro.pallas_bench.compile_cache.CompileCache`, or a cache
    directory path) under the in-memory one: compiled executables are
    served across measurement instances, worker processes, and runs, and
    in-flight compiles dedup across process boundaries.  A pure speed knob —
    ``n_compiles`` drops (to zero against a fully warm cache), values do
    not change.
    """

    def __init__(
        self,
        workload: PallasWorkload,
        *,
        repeats: int = 5,
        warmup: int = 1,
        vmem_limit: int = DEFAULT_VMEM_LIMIT,
        max_grid: int = DEFAULT_MAX_GRID,
        validate: bool = True,
        pipeline_workers: int = 0,
        timer: Callable[[], float] | None = None,
        compile_cache: "CompileCache | str | None" = None,
    ):
        super().__init__()
        if repeats < 1:
            raise ValueError("repeats must be >= 1")
        if pipeline_workers < 0:
            raise ValueError("pipeline_workers must be >= 0")
        self.workload = workload
        self.repeats = int(repeats)
        self.warmup = int(warmup)
        self.vmem_limit = int(vmem_limit)
        self.max_grid = int(max_grid)
        self.validate = validate
        self.pipeline_workers = int(pipeline_workers)
        # default to the injectable clock seam (repro.core.clock) rather than
        # a direct perf_counter reference: one allowlist entry, one override
        self._timer = timer if timer is not None else monotonic
        #: per-stage wall-clock (screen / compile / time), per run — reset()
        #: zeroes it together with the per-run counters below
        self.clock = StageClock()
        if isinstance(compile_cache, str):
            compile_cache = CompileCache(compile_cache)
        #: persistent cross-process compile cache, or None (memory-only)
        self.pcache: CompileCache | None = compile_cache
        #: lifetime compile count == compilation-cache fills (the cache
        #: survives reset() by design, and so does this)
        self.n_compiles = 0
        #: lifetime persistent-cache hits (entries served instead of compiled)
        self.n_pcache_hits = 0
        #: per-run counters — what provenance reports, so a later matrix
        #: cell reusing this instance never over-reports earlier cells' work
        self.run_compiles = 0
        self.run_pcache_hits = 0
        self._run_invalid: set[str] = set()
        #: config_key -> InvalidMeasurement for every penalized config served
        #: (lifetime, like the compile cache: reasons stay addressable)
        self.invalid: dict[str, InvalidMeasurement] = {}
        #: config_key -> per-repeat seconds of the last search measurement
        self.repeat_log: dict[str, list[float]] = {}
        #: config_key -> per-repeat seconds of the last final re-measurement
        self.final_repeat_log: dict[str, list[float]] = {}
        self._inputs: tuple | None = None
        #: geometry key -> warmed callable (or InvalidMeasurement for a
        #: geometry whose compile failed — retrying would fail identically)
        self._compiled: dict[tuple, Callable | InvalidMeasurement] = {}
        #: geometry key -> in-flight prefetch compile (pipelined batches)
        self._inflight: dict[tuple, Future] = {}
        self._cache_lock = threading.RLock()
        self._pool: ThreadPoolExecutor | None = None

    # -- compilation cache -----------------------------------------------------
    def _geom_key(self, cfg: Config) -> tuple:
        g = geometry_from_config(cfg)
        key = (g.bm, g.bn, g.tz, g.wx, g.wy)
        return key + (g.wz,) if self.workload.bench.wz_in_program else key

    def _run_config(self, cfg: Config) -> Config:
        """The config actually launched: ``w_z`` is pinned when it does not
        enter the program, so jax's jit cache coalesces with ours."""
        if self.workload.bench.wz_in_program:
            return cfg
        return {**cfg, "w_z": 1}

    def _pcache_key(self, gkey: tuple) -> str:
        w = self.workload
        return self.pcache.key(
            kernel=w.name,
            x=w.x,
            y=w.y,
            input_seed=w.input_seed,
            interpret=bool(w.interpret()),
            geometry=list(gkey),
        )

    def _pcache_hit(self) -> None:
        with self._cache_lock:
            self.n_pcache_hits += 1
            self.run_pcache_hits += 1
        if self.telemetry.enabled:
            self.telemetry.inc("pcache.hits")

    def _pcache_serve(
        self, entry: dict, gkey: tuple, inputs: tuple
    ) -> Callable | InvalidMeasurement | None:
        """Turn a persistent-cache entry into a warmed callable (or cached
        penalty); ``None`` means the entry cannot substitute for a compile
        here (no artifact, or the artifact fails to load) and the caller
        compiles locally."""
        if entry.get("status") == "invalid":
            bad = InvalidMeasurement(
                reason=entry.get("reason") or "cached compile failure",
                stage=entry.get("stage") or "compile",
            )
            with self._cache_lock:
                self._compiled[gkey] = bad
            self._pcache_hit()
            return bad
        blob = entry.get("artifact")
        if blob is None:
            return None
        try:
            loaded = deserialize_compiled(blob)

            def fn():
                return loaded(*inputs)

            for _ in range(max(1, self.warmup)):
                fence(fn())
        except Exception:  # noqa: BLE001 — a bad artifact degrades to a recompile
            return None
        with self._cache_lock:
            self._compiled[gkey] = fn
        self._pcache_hit()
        return fn

    def _compile_warm(self, inputs: tuple, run_cfg: Config):
        """Compile and warm cfg's program; raises what the compiler or the
        first runs raise.  Returns ``(warmed callable, serialized blob |
        None)``: with a persistent cache attached the program is compiled
        ahead of time (``jit(...).lower().compile()``) so its executable can
        be published; without one, the kernel's own jitted entry point
        compiles on its first call."""
        if self.pcache is None:
            def fn():
                return self.workload.run(inputs, run_cfg)

            blob = None
        else:
            import jax

            compiled = (
                jax.jit(lambda *arrays: self.workload.run(arrays, run_cfg))
                .lower(*inputs)
                .compile()
            )

            def fn():
                return compiled(*inputs)

            blob = serialize_compiled(compiled)
        for _ in range(max(1, self.warmup)):
            fence(fn())
        return fn, blob

    def _compile_now(self, cfg: Config, gkey: tuple) -> Callable | InvalidMeasurement:
        """Trace + lower + warm cfg's geometry, populating the cache.  Called
        from the main thread (inline path) or a prefetch pool thread; all
        shared state mutates under the cache lock.

        With a persistent cache attached, the order is: serve the on-disk
        entry (no compile counted) -> claim the key and compile -> or, when
        another process holds the claim, wait for its entry.  Claim holders
        publish ok/invalid entries so every other process — including ones
        started later — skips this geometry entirely."""
        with self._cache_lock:
            if self._inputs is None:
                self._inputs = self.workload.materialize()
            inputs = self._inputs
        run_cfg = self._run_config(cfg)
        pc = self.pcache
        pckey = None
        claimed = False
        if pc is not None:
            pckey = self._pcache_key(gkey)
            entry = pc.get(pckey)
            if entry is None:
                claimed = pc.claim(pckey)
                if claimed:
                    # double-check under the claim: the previous holder may
                    # have published between our miss and our claim (entries
                    # land before claims are released), so this read is
                    # authoritative — each geometry compiles exactly once
                    # across processes
                    entry = pc.get(pckey)
                else:
                    # another process is compiling this geometry right now;
                    # waiting is the cross-process analogue of the prefetch
                    # future join
                    if self.telemetry.enabled:
                        self.telemetry.inc("pcache.waits")
                    entry = pc.wait(pckey)
            if entry is not None:
                got = self._pcache_serve(entry, gkey, inputs)
                if got is not None:
                    if claimed:
                        pc.release(pckey)
                    return got
            if self.telemetry.enabled:
                self.telemetry.inc("pcache.misses")
        try:
            with self._cache_lock:
                self.n_compiles += 1
                self.run_compiles += 1
            if self.telemetry.enabled:
                self.telemetry.inc("compiles")
            try:
                fn, artifact = self._compile_warm(inputs, run_cfg)
            except Exception as e:  # noqa: BLE001 — any compile failure is a penalty
                # counted per stage in provenance(): a screened-in config
                # the compiler refuses is a screen defect to report
                bad = InvalidMeasurement(
                    reason=f"{type(e).__name__}: {e}", stage="compile"
                )
                with self._cache_lock:
                    self._compiled[gkey] = bad
                if claimed:
                    pc.put(
                        pckey, status="invalid",
                        reason=bad.reason, stage="compile",
                    )
                return bad
            with self._cache_lock:
                self._compiled[gkey] = fn
            if claimed:
                pc.put(pckey, status="ok", artifact=artifact)
                if self.telemetry.enabled:
                    self.telemetry.inc("pcache.stores")
            return fn
        finally:
            if claimed:
                pc.release(pckey)

    # -- pipeline stages -------------------------------------------------------
    @contextmanager
    def _staged(self, name: str, **attrs):
        """Charge the stage clock AND (when telemetry is on) emit a ``stage``
        trace event with the same duration — one timing source for both, so
        the trace's per-stage totals reconcile exactly with ``stage_times``.
        Thread-safe like the clock: prefetch pool threads use it too."""
        t0 = monotonic()
        try:
            yield
        finally:
            dur = monotonic() - t0
            self.clock.add(name, dur)
            if self.telemetry.enabled:
                self.telemetry.stage(
                    name, dur,
                    **{k: v for k, v in attrs.items() if v is not None},
                )

    def _stage_screen(self, config: Config) -> InvalidMeasurement | None:
        """Validity pre-screen; ``None`` means the config may compile."""
        if not self.validate:
            return None
        with self._staged("screen"):
            reason = validate_config(
                self.workload, config, self.vmem_limit, self.max_grid
            )
        if reason is None:
            return None
        return InvalidMeasurement(reason=reason, stage="validity")

    def _stage_compile(self, config: Config) -> Callable | InvalidMeasurement:
        """Warmed zero-arg runner for cfg's geometry: cache hit, prefetched
        compile (pipelined batches), or inline compile on first use."""
        gkey = self._geom_key(config)
        with self._cache_lock:
            hit = self._compiled.get(gkey)
            fut = None if hit is not None else self._inflight.pop(gkey, None)
        if hit is not None:
            if self.telemetry.enabled:
                self.telemetry.inc("compile_cache_hits")
            return hit
        if fut is not None:
            # the pool thread charged the compile stage; waiting here is the
            # pipeline's (ideally zero) bubble
            return fut.result()
        with self._staged("compile", key=str(gkey)):
            return self._compile_now(config, gkey)

    def _stage_time(
        self, fn: Callable, repeats: int, key: str | None = None
    ) -> list[float] | InvalidMeasurement:
        """Strictly sequential fenced timing — never overlapped, so device
        measurements stay honest even while the prefetcher compiles."""
        times = []
        with self._staged("time", key=key):
            for _ in range(repeats):
                try:
                    t0 = self._timer()
                    fence(fn())
                    times.append(self._timer() - t0)
                except Exception as e:  # noqa: BLE001 — runtime failure -> penalty
                    return InvalidMeasurement(
                        reason=f"{type(e).__name__}: {e}", stage="run"
                    )
        return times

    def _stage_record(
        self,
        key: str,
        out: list[float] | InvalidMeasurement,
        log: dict[str, list[float]],
    ) -> float:
        """Fold a stage-pipeline outcome into the served value + the logs."""
        with self._staged("record", key=key):
            if isinstance(out, InvalidMeasurement):
                self.invalid[key] = out
                self._run_invalid.add(key)
                if self.telemetry.enabled:
                    # histogram by validity rule (align:/block:/grid:/vmem:)
                    # or by the failing stage for compile/run penalties
                    rule = (
                        out.reason.split(":", 1)[0]
                        if out.stage == "validity"
                        else out.stage
                    )
                    self.telemetry.inc(f"invalid.{rule}")
                return out.penalty
            log[key] = out
            return float(np.median(out))

    def _measure_repeats(
        self, config: Config, repeats: int
    ) -> list[float] | InvalidMeasurement:
        bad = self._stage_screen(config)
        if bad is not None:
            return bad
        fn = self._stage_compile(config)
        if isinstance(fn, InvalidMeasurement):
            return fn
        return self._stage_time(fn, repeats, key=config_key(config))

    def _measure_one(self, config: Config) -> float:
        return self._stage_record(
            config_key(config),
            self._measure_repeats(config, self.repeats),
            self.repeat_log,
        )

    # -- the two-phase batch path ----------------------------------------------
    def _prefetch_compiles(self, configs: Sequence[Config]) -> None:
        """Compile phase: submit every geometry this batch will compile to
        the pool, in batch order.  Only configs that pass the pre-screen are
        prefetched (the inline path never compiles a screened-out config),
        so ``n_compiles`` is identical with the pipeline on or off.

        Pool threads do not inherit the caller's thread-local
        ``jax.default_device`` (the device executor pins each worker thread
        to its chip that way), so each task re-enters the caller's pin:
        inputs and executables land on the caller's device, not device 0."""
        import jax

        device = jax.config.jax_default_device
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=self.pipeline_workers,
                thread_name_prefix="pallas-compile",
            )
        for cfg in configs:
            if self.validate and validate_config(
                self.workload, cfg, self.vmem_limit, self.max_grid
            ) is not None:
                continue
            gkey = self._geom_key(cfg)
            with self._cache_lock:
                if gkey in self._compiled or gkey in self._inflight:
                    continue
                self._inflight[gkey] = self._pool.submit(
                    self._prefetch_task, dict(cfg), gkey, device
                )
                depth = len(self._inflight)
            if self.telemetry.enabled:
                self.telemetry.gauge("prefetch_inflight", depth)

    def _prefetch_task(self, cfg: Config, gkey: tuple, device):
        import jax

        with jax.default_device(device), self._staged("compile", key=str(gkey)):
            return self._compile_now(cfg, gkey)

    def measure_batch(self, configs: Sequence[Config]) -> np.ndarray:
        """One Python-level dispatch per batch.  With ``pipeline_workers``
        set, the batch runs two-phase — compile prefetch, then timing —
        but the timing phase itself walks configs strictly sequentially
        (device measurements must not overlap each other)."""
        self.n_samples += len(configs)
        self.n_dispatches += 1
        if self.pipeline_workers > 0 and len(configs) > 1:
            self._prefetch_compiles(configs)
        return np.array(
            [float(self._measure_one(c)) for c in configs], dtype=np.float64
        )

    def measure_final(self, config: Config, repeats: int = 10) -> float:
        """Paper protocol: the winner re-measured ``repeats`` times, median
        kept; raw repeats land in ``final_repeat_log`` for the run record."""
        return self._stage_record(
            config_key(config),
            self._measure_repeats(config, repeats),
            self.final_repeat_log,
        )

    def close(self) -> None:
        """Shut the prefetch pool down (idempotent; the pool is rebuilt on
        the next pipelined batch if the instance keeps measuring)."""
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None

    def __del__(self):  # pragma: no cover — interpreter-exit ordering
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass

    # -- introspection (RunRecord provenance, disk-cache metadata) ------------
    def reason_for(self, config: Config) -> str | None:
        bad = self.invalid.get(config_key(config))
        return None if bad is None else bad.to_meta()

    def repeats_for(self, config: Config) -> list[float] | None:
        key = config_key(config)
        return self.final_repeat_log.get(key) or self.repeat_log.get(key)

    def stage_times(self) -> dict[str, float]:
        return self.clock.times()

    @property
    def device_kind(self) -> str:
        """The kind of device the kernels run on: the thread's
        ``jax.default_device`` (the device executor's pin), else the default
        backend's first device."""
        import jax

        dev = jax.config.jax_default_device
        if dev is None or isinstance(dev, str):
            dev = jax.devices(dev)[0]
        return dev.device_kind

    def provenance(self) -> dict:
        """Backend provenance for the versioned RunRecord: how timings were
        taken and on what — the fields that distinguish an interpret-mode CPU
        run from a real-TPU run of the same spec.  Counters are per-run
        (since the last ``reset()``): a later matrix cell reports its own
        compiles/penalties, not lifetime totals; ``n_compiles_total`` keeps
        the lifetime figure (== compilation-cache fills).  ``penalties``
        counts penalized configs by stage (``validity`` / ``compile`` /
        ``run``) and ``failures`` lists the compile- and run-stage ones
        with their reasons."""
        import jax

        stage_s = {k: round(v, 6) for k, v in self.clock.times().items()}
        penalties = Counter(self.invalid[k].stage for k in self._run_invalid)
        # configs that passed the screen and still failed: what a tuning
        # run on the chip must report rather than absorb as inf
        failures = {
            k: self.invalid[k].to_meta()
            for k in sorted(self._run_invalid)
            if self.invalid[k].stage != "validity"
        }
        return {
            "backend": "pallas",
            "kernel": self.workload.name,
            "x": self.workload.x,
            "y": self.workload.y,
            "input_seed": self.workload.input_seed,
            "interpret": bool(self.workload.interpret()),
            "platform": jax.default_backend(),
            "device_kind": self.device_kind,
            "repeats": self.repeats,
            "warmup": self.warmup,
            "timer": "perf_counter",
            "pipeline_workers": self.pipeline_workers,
            "compile_cache": self.pcache is not None,
            "stage_s": stage_s,
            "n_compiles": self.run_compiles,
            "n_compiles_total": self.n_compiles,
            "n_pcache_hits": self.run_pcache_hits,
            "n_invalid": len(self._run_invalid),
            "penalties": dict(penalties),
            "failures": failures,
        }

    def reset(self) -> None:
        """Clear per-run counters, logs, and stage clocks; the compilation
        cache — and its lifetime ``n_compiles`` — survives (compiled
        programs are still valid — that is the point of the cache)."""
        super().reset()
        self.run_compiles = 0
        self.run_pcache_hits = 0
        self._run_invalid.clear()
        self.repeat_log.clear()
        self.final_repeat_log.clear()
        self.clock.reset()
