"""The measured window: a deadline on the tuner and a record of its samples.

The tuner runs through the program's own entry, ``repro.tune_matrix``.  The
harness puts a thin subclass of ``TuningSession`` in its place for the
window (``install``), so that the parent session and the sessions the
``device`` executor's worker threads build alike get the same two hooks on
every measurement they make:

* a deadline check before each config, which raises :class:`WindowClosed`
  and so stops every worker within one sample of the deadline;
* a record of each sample: when its measurement returned (on the clock of
  the program's telemetry, ``time.perf_counter``), which writer made it
  (the telemetry ``src``: ``main``, or ``shard<k>`` for chip ``k``), its
  store key, the value returned to the searcher, and the timing stage's
  raw repeats behind it;
* the program the timing stage timed for the samples the check compares
  after the window (the best, and a few drawn from the seed as the window
  goes), with the inputs the measurement ran it on.
"""

from __future__ import annotations

import math
import random
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field


class WindowClosed(RuntimeError):
    """Raised inside the tuner at the first config asked after the deadline."""


@dataclass
class Sample:
    t: float                  # when the measurement returned
    src: str                  # telemetry writer: "main" or "shard<k>"
    key: str                  # the measurement store's key for the config
    config: dict
    value: float              # what the searcher was told
    repeats: list | None      # the timing stage's raw seconds (None: not timed)
    stage: str | None         # penalty stage, None for a timed sample
    final: bool = False       # the paper's final re-measurement of a job


@dataclass
class Timed:
    """A sample's program as the timing stage ran it: the zero-argument
    runner the measurement timed, and the inputs it runs on."""

    sample: Sample
    runner: object
    inputs: tuple


@dataclass
class Window:
    start: float
    deadline: float
    samples: list[Sample] = field(default_factory=list)
    told: dict[str, float] = field(default_factory=dict)   # store key -> value
    sessions: list = field(default_factory=list)
    #: the timed programs held for the check: the window's best so far and
    #: a uniform draw (reservoir, from ``rng``) of ``drawn_size`` others
    rng: random.Random = field(default_factory=random.Random)
    drawn_size: int = 3
    best: Timed | None = None
    drawn: list[Timed] = field(default_factory=list)
    _timed_seen: int = 0
    _lock: threading.Lock = field(default_factory=threading.Lock, repr=False)

    def check(self) -> None:
        if time.perf_counter() >= self.deadline:
            raise WindowClosed(f"window closed at {self.deadline:.6f}")

    def add(self, sample: Sample) -> None:
        with self._lock:
            self.samples.append(sample)

    def hold(self, timed: Timed) -> None:
        """Keep ``timed`` if the check may compare it: only a finite search
        sample that ended inside the window, as :meth:`counted` counts."""
        s = timed.sample
        if s.final or not math.isfinite(s.value) or not self.start <= s.t <= self.deadline:
            return
        with self._lock:
            if self.best is None or s.value < self.best.sample.value:
                self.best = timed
            self._timed_seen += 1
            if len(self.drawn) < self.drawn_size:
                self.drawn.append(timed)
            else:
                j = self.rng.randrange(self._timed_seen)
                if j < self.drawn_size:
                    self.drawn[j] = timed

    def checked(self) -> list[Timed]:
        """The timed programs the check compares: the best first, then the
        drawn ones that are not it."""
        if self.best is None:
            return []
        return [self.best] + [t for t in self.drawn if t is not self.best]

    def add_told(self, keys, values) -> None:
        with self._lock:
            for k, v in zip(keys, values, strict=True):
                self.told[k] = float(v)

    def work(self) -> float:
        """Samples' worth of work done inside the window.  A search sample
        spans from its writer's previous record (or the window's start) to
        its own, so job start-up and the searcher's host time belong to the
        sample that follows them; a sample counts with the share of its span
        that lies inside the window.  So the one in flight at the deadline
        counts in part, and a cell whose samples take seconds is not read
        in whole samples."""
        total = 0.0
        for src in {s.src for s in self.samples}:
            prev = self.start
            for s in sorted((s for s in self.samples if s.src == src), key=lambda s: s.t):
                lo = max(prev, self.start)
                if not s.final and s.t > lo:
                    total += max(0.0, min(s.t, self.deadline) - lo) / (s.t - lo)
                prev = max(prev, s.t)
        return total

    def counted(self) -> list[Sample]:
        """Search samples whose measurement ended inside the window."""
        return [
            s for s in self.samples
            if not s.final and self.start <= s.t <= self.deadline
        ]

    def parent(self):
        """The session ``tune_matrix`` built (the one whose store the
        executor merges worker stores into)."""
        for s in self.sessions:
            if s.telemetry.src == "main":
                return s
        return None


def session_class(window: Window):
    """A ``TuningSession`` subclass bound to ``window``."""
    from repro.core.api import TuningSession
    from repro.core.engine import DiskCachedMeasurement, config_key
    from repro.pallas_bench import PallasMeasurement

    class WindowSession(TuningSession):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            window.sessions.append(self)

        def _make_measurement(self, exp_seed):
            outer = super()._make_measurement(exp_seed)
            if not isinstance(outer, DiskCachedMeasurement):
                raise TypeError("the window needs the session's measurement store")
            inner = outer._inner
            if not isinstance(inner, PallasMeasurement):
                raise TypeError(f"the window measures the pallas backend, not {inner!r}")
            if inner.pcache is not None:
                raise ValueError("the window compiles cold: no CompileCache may be attached")
            src = self.telemetry.src if self.telemetry.enabled else "main"
            _instrument(window, outer, inner, src, config_key)
            return outer

    return WindowSession


def _instrument(window: Window, outer, inner, src: str, config_key) -> None:
    local = threading.local()
    time_stage, measure_one = inner._stage_time, inner._measure_one
    measure_final = inner.measure_final
    outer_batch = outer.measure_batch

    def store_key(config):
        return f"{outer.prefix}|{config_key(config)}"

    def timed(fn, repeats, key=None):
        out = time_stage(fn, repeats, key)
        local.repeats = out if isinstance(out, list) else None
        local.runner = fn
        return out

    def record(config, value, final):
        bad = inner.invalid.get(config_key(config))
        sample = Sample(
            t=time.perf_counter(), src=src, key=store_key(config),
            config=dict(config), value=float(value),
            repeats=local.repeats,
            stage=bad.stage if bad is not None and not math.isfinite(value) else None,
            final=final,
        )
        window.add(sample)
        if local.runner is not None:
            window.hold(Timed(sample, local.runner, inner._inputs))

    def one(config):
        window.check()
        local.repeats = local.runner = None
        value = measure_one(config)
        record(config, value, final=False)
        return value

    def final(config, repeats=10):
        window.check()
        local.repeats = local.runner = None
        value = measure_final(config, repeats)
        record(config, value, final=True)
        return value

    def batch(configs):
        values = outer_batch(configs)
        window.add_told([store_key(c) for c in configs], values)
        return values

    inner._stage_time = timed
    inner._measure_one = one
    inner.measure_final = final
    outer.measure_batch = batch


@contextmanager
def install(window: Window):
    """Put the window's session class in the program's place for the
    duration: ``tune_matrix`` and the executors' workers look
    ``TuningSession`` up in ``repro.core.api`` when they build a session."""
    import repro.core.api as api

    original = api.TuningSession
    api.TuningSession = session_class(window)
    try:
        yield
    finally:
        api.TuningSession = original
