"""Reading the program's JSONL telemetry against the window.

The program writes one JSON object per line (``repro.telemetry``): ``stage``
events with the stage's ``dur`` stamped ``t`` at its end, and ``begin`` /
``end`` pairs of spans, all on ``time.perf_counter`` within one process,
each tagged with its writer ``src`` (``main``, or ``shard<k>`` for the
device executor's worker on chip ``k``).
"""

from __future__ import annotations

import json

from .devtrace import covered, merged


def read_events(path: str) -> list[dict]:
    events = []
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                try:
                    ev = json.loads(line)
                except json.JSONDecodeError:
                    continue   # a torn last line
                if isinstance(ev, dict):
                    events.append(ev)
    except FileNotFoundError:
        return []
    return events


def stage_intervals(events, stage: str | None = None) -> dict[str, list]:
    """src -> the intervals of every ``stage`` event named ``stage`` (of
    every stage for ``None``)."""
    out: dict[str, list] = {}
    for ev in events:
        if ev.get("ev") == "stage" and stage in (None, ev.get("stage")):
            t = float(ev["t"])
            out.setdefault(ev.get("src", "main"), []).append(
                (t - float(ev["dur"]), t, ev["stage"])
            )
    return out


def stage_seconds(events, stage: str, lo: float, hi: float) -> float:
    """Seconds of ``stage`` inside ``[lo, hi]``, summed over writers."""
    return sum(covered(ivs, lo, hi) for ivs in stage_intervals(events, stage).values())


def stage_count(events, stage: str, lo: float, hi: float) -> int:
    """``stage`` events that ended inside ``[lo, hi]``."""
    return sum(
        1
        for ev in events
        if ev.get("ev") == "stage" and ev.get("stage") == stage and lo <= float(ev["t"]) <= hi
    )


def span_intervals(events, span: str, open_until: float) -> dict[str, list]:
    """src -> the intervals of every ``span`` span; one that never ended
    (its writer stopped) runs to ``open_until``."""
    begins: dict[tuple, float] = {}
    out: dict[str, list] = {}
    for ev in events:
        if ev.get("span") != span or ev.get("ev") not in ("begin", "end"):
            continue
        ident = (
            ev.get("src", "main"),
            *(str(ev.get(k)) for k in sorted(ev) if k not in ("t", "seq", "ev", "dur", "ok")),
        )
        if ev["ev"] == "begin":
            begins[ident] = float(ev["t"])
        elif ident in begins:
            out.setdefault(ident[0], []).append((begins.pop(ident), float(ev["t"]), span))
    for ident, t in begins.items():
        out.setdefault(ident[0], []).append((t, open_until, span))
    return out


def uncovered_seconds(events, span: str, lo: float, hi: float) -> float:
    """Seconds inside ``[lo, hi]`` spent in ``span`` spans outside every
    stage event of the same writer, summed over writers."""
    stages = stage_intervals(events)
    total = 0.0
    for src, ivs in span_intervals(events, span, open_until=hi).items():
        for s, e in merged(ivs, lo, hi):
            total += (e - s) - covered(stages.get(src, []), s, e)
    return total
