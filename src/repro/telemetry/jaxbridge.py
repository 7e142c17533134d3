"""JAX's side of a trace: its compile phases, and the profiler's clock.

Two bridges between a :class:`~repro.telemetry.tracer.Telemetry` writer and
JAX, both entered per region by :meth:`Telemetry.stage_scope` and
:meth:`Telemetry.span`:

* **compile phases** — JAX reports each compile's phases through
  ``jax.monitoring``: the jaxpr trace, the lowering to an MLIR module
  (Pallas's lowering to Mosaic included) and the backend compile.  One
  process-wide duration listener, registered lazily the first time a real
  writer opens a stage, forwards each to the writer bound to the calling
  thread as a ``stage`` event ``compile.trace`` / ``compile.lower`` /
  ``compile.backend``, with the enclosing stage's name as ``within``.  A
  stage binds its writer for its own duration only, so the compile-prefetch
  pool's threads and the device executor's per-chip threads each report to
  the writer that opened the stage, and a compile on a thread with no
  stage open (a caller's own set-up or checks) is not reported.
* **the profiler's clock** — every region also enters a
  ``jax.profiler.TraceAnnotation`` named ``repro.<name>@<src>``, so a JAX
  profiler session (TensorBoard, Perfetto) shows the tuner's spans and
  stages on the host timeline next to the chip's operations, and a reader
  of the profile can tell each writer's (each chip's) regions apart.  With
  no profiler session an annotation costs one check of whether the
  profiler is on.

Only :class:`Telemetry` calls into this module: :data:`NULL_TELEMETRY`
registers no listener and enters no annotation.
"""

from __future__ import annotations

import sys
import threading
from contextlib import contextmanager, nullcontext

#: jax.monitoring duration events -> the stage event each becomes
COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}

#: prefix of every profiler annotation a writer enters
ANNOTATION_PREFIX = "repro."

_listener_lock = threading.Lock()
_listening = False
_NO_ANNOTATION = nullcontext()
#: per thread: the :class:`_Sink` of the innermost open stage
_bound = threading.local()


class _Sink:
    """An open stage's receiver of compile phases: its writer, its name and
    its start on the writer's clock.  Each phase is stamped on the writer's
    clock as it ends; JAX times phases on the wall clock, so a duration is
    held inside the open stage.

    JAX reports a trace for every jitted function it traces: tens per
    program, most of them helpers that lowering rules trace while lowering.
    So traces are held until the next other phase or the stage's end:
    those that lie inside a lowering are left to ``compile.lower``, whose
    duration holds them, and the rest are written as their union, one
    event per disjoint stretch."""

    __slots__ = ("tel", "within", "t0", "traces")

    def __init__(self, tel, within: str):
        self.tel, self.within, self.t0 = tel, within, tel.now()
        self.traces: list[tuple[float, float]] = []

    def phase(self, stage: str, duration: float) -> None:
        now = self.tel.now()
        start = now - min(float(duration), now - self.t0)
        if stage == "compile.trace":
            self.traces.append((start, now))
            return
        if stage == "compile.lower":
            self.traces = [iv for iv in self.traces if iv[0] < start]
        self.flush_traces()
        self.tel.stage(stage, now - start, end=now, within=self.within)

    def flush_traces(self) -> None:
        pieces: list[list[float]] = []
        for s, e in sorted(self.traces):
            if pieces and s <= pieces[-1][1]:
                pieces[-1][1] = max(pieces[-1][1], e)
            else:
                pieces.append([s, e])
        for s, e in pieces:
            self.tel.stage("compile.trace", e - s, end=e, within=self.within)
        self.traces.clear()


def annotation(name: str, src: str):
    """The profiler annotation of region ``name`` of writer ``src``; none
    in a process that has not imported JAX (it can hold no profiler
    session), so a run that never touches JAX does not import it."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return _NO_ANNOTATION
    return profiler.TraceAnnotation(f"{ANNOTATION_PREFIX}{name}@{src}")


def _on_duration(event: str, duration: float, **_) -> None:
    stage = COMPILE_STAGES.get(event)
    if stage is None:
        return
    sink = getattr(_bound, "sink", None)
    if sink is not None:
        sink.phase(stage, duration)


def _listen() -> None:
    global _listening
    with _listener_lock:
        if not _listening:
            import jax

            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True


@contextmanager
def stage_scope(tel, name: str):
    """Bind ``tel`` as this thread's sink for compile phases, with ``name``
    as their ``within``, and annotate the region for the profiler.  Yields
    the region's start on the writer's clock."""
    if not _listening:
        _listen()
    prev = getattr(_bound, "sink", None)
    sink = _bound.sink = _Sink(tel, name)
    try:
        with annotation(name, tel.src):
            yield sink.t0
    finally:
        _bound.sink = prev
        sink.flush_traces()
