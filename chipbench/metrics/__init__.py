"""Per-layer metric readers, one file per metric in ``BENCHMARK.json``.

Each gives ``read(run) -> float | None`` over a ``harness.RunView``: the
window's samples and bounds, the program's telemetry, and in a traced run
the profiler trace.  A reader that finds nothing to read returns ``None``
and the run leaves the metric out.
"""
