"""Reduction of a JAX profiler trace to device time, kernel time and idle gaps.

A trace (``<dir>/plugins/profile/<time>/<host>.xplane.pb``) has one plane
per chip, ``/device:TPU:<k>``, whose ``XLA Ops`` line holds every operation
the chip ran and whose ``XLA Modules`` line holds every program launch, and
a ``/host:CPU`` plane with the host's native events (compiles, executes)
and the harness's own ``TraceAnnotation`` marks.  All timestamps share one
clock: nanoseconds from the start of the profile.  Here they become
seconds.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK_PREFIX = "chipbench."

#: what the host was doing, by the names of its native events; the first
#: rule that matches names the event, and an idle gap that none covers for
#: half its length is "other" (Python with no native event: the searcher,
#: the stores, making inputs with numpy, Mosaic's lowering)
HOST_ACTIVITY = (
    ("compile", re.compile(r"Compile|^XLA::|LoadProgram|backend_compile")),
    ("tracing", re.compile(r"trace_to_jaxpr|jaxpr|[Ll]ower|Linearize")),
    ("transfer", re.compile(r"^Transpose::|TransferTo|FromPyval|FromHost")),
    ("timing", re.compile(r"LoadedExecutable.*Execute|System::Execute|BlockHostUntilReady")),
)

Interval = tuple[float, float, str]


@dataclass
class DeviceTrace:
    ops: dict[int, list[Interval]]       # chip -> operations it ran
    modules: dict[int, list[Interval]]   # chip -> program launches
    host: list[Interval]                 # host native events, all threads
    marks: dict[str, tuple[float, float]]

    def mark(self, name: str) -> tuple[float, float]:
        try:
            return self.marks[MARK_PREFIX + name]
        except KeyError:
            raise KeyError(
                f"trace has no {MARK_PREFIX + name!r} annotation; has {sorted(self.marks)}"
            ) from None


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, found {files}")
    return files[0]


def load(path: str) -> DeviceTrace:
    """Read an ``.xplane.pb`` file (or the one under a trace directory)."""
    import jax

    if os.path.isdir(path):
        path = find_xplane(path)
    data = jax.profiler.ProfileData.from_file(path)
    ops: dict[int, list[Interval]] = {}
    modules: dict[int, list[Interval]] = {}
    host: list[Interval] = []
    marks: dict[str, tuple[float, float]] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                dst = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dst is not None:
                    dst.setdefault(chip, []).extend(
                        (e.start_ns * 1e-9, e.end_ns * 1e-9, e.name) for e in line.events
                    )
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    iv = (e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                    if e.name.startswith(MARK_PREFIX):
                        marks[e.name] = iv[:2]
                    else:
                        host.append(iv)
    for d in (ops, modules):
        for evs in d.values():
            evs.sort()
    host.sort()
    return DeviceTrace(ops=ops, modules=modules, host=host, marks=marks)


def merged(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The union of ``intervals`` clipped to ``[lo, hi]``, as sorted
    disjoint pieces."""
    out: list[list[float]] = []
    for iv in sorted(intervals):
        s, e = max(iv[0], lo), min(iv[1], hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in merged(intervals, lo, hi))


def busy_s(trace: DeviceTrace, lo: float, hi: float) -> dict[int, float]:
    """Seconds in ``[lo, hi]`` in which each chip ran an operation."""
    return {chip: covered(evs, lo, hi) for chip, evs in sorted(trace.ops.items())}


def per_call_s(trace: DeviceTrace, chip: int, lo: float, hi: float) -> tuple[float | None, int]:
    """(mean device seconds per program launch, launches) on ``chip`` for
    the launches that lie wholly inside ``[lo, hi]``; ``None`` for none."""
    runs = [e - s for s, e, _ in trace.modules.get(chip, []) if s >= lo and e <= hi]
    if not runs:
        return None, 0
    return sum(runs) / len(runs), len(runs)


_OP_NAME = re.compile(r"^%?([^\s=]+)\s*=\s*\S+\s+([\w-]+)")


def op_name(hlo: str) -> str:
    """``%_add.1 = f32[...] custom-call(...), ...`` -> ``_add.1 custom-call``."""
    m = _OP_NAME.match(hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:64]


def activity(name: str) -> str | None:
    for label, rule in HOST_ACTIVITY:
        if rule.search(name):
            return label
    return None


def breakdown(trace: DeviceTrace, lo: float, hi: float, top: int = 10) -> dict:
    """The device operations that took most time in ``[lo, hi]``, and the
    longest idle gaps there, each named by what the host was doing in most
    of it."""
    totals: dict[str, float] = {}
    for chip, evs in sorted(trace.ops.items()):
        for s, e, name in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = op_name(name) if len(trace.ops) == 1 else f"tpu{chip} {op_name(name)}"
                totals[key] = totals.get(key, 0.0) + d
    device_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]

    by_activity: dict[str, list[Interval]] = {}
    for iv in trace.host:
        label = activity(iv[2])
        if label is not None:
            by_activity.setdefault(label, []).append(iv)
    gaps = []
    for chip, evs in sorted(trace.ops.items()):
        edge = lo
        for s, e in merged(evs, lo, hi) + [(hi, hi)]:
            if s > edge:
                gaps.append((chip, edge, s))
            edge = max(edge, e)
    gaps.sort(key=lambda g: -(g[2] - g[1]))
    idle = []
    for chip, s, e in gaps[:top]:
        share = {k: covered(v, s, e) for k, v in by_activity.items()}
        label, most = max(share.items(), key=lambda kv: kv[1], default=("other", 0.0))
        if most < 0.5 * (e - s):
            label = "other"
        name = label if len(trace.ops) == 1 else f"tpu{chip} {label}"
        idle.append([name, e - s])
    return {"device_ops": [[k, v] for k, v in device_ops], "idle_gaps": idle}
