"""Searcher and session: milliseconds per sample of the searcher's own
``ask`` and ``tell`` inside the window, from the program's spans of those
names, summed over writers (chips)."""

from chipbench import devtrace, spans


def read(run):
    if not run.samples:
        return None
    total = 0.0
    for name in ("ask", "tell"):
        for ivs in spans.span_intervals(run.events, name, open_until=run.deadline).values():
            total += devtrace.covered(ivs, run.start, run.deadline)
    return total * 1e3 / len(run.samples)
