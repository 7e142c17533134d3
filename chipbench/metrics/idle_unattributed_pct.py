"""Device: the share of each chip's idle time in the window that no leaf
region of the program covers, averaged over the cell's chips.  The program
annotates the profiler's trace with its regions as ``repro.<name>@<src>``;
a chip's leaves are the pipeline stages and the searcher's ask and tell of
the writer that drives it (``main`` drives chip 0 under the serial
executor, ``shard<k>`` chip ``k`` under the device executor).  With no
annotations every idle second is unattributed: 100."""

from chipbench import devtrace

LEAVES = ("screen", "compile", "inputs", "time", "record", "ask", "tell")


def chip_of(writer: str) -> int:
    return 0 if writer == "main" else int(writer.removeprefix("shard"))


def read(run):
    if run.trace is None or not run.workers:
        return None
    lo, hi = run.trace.mark("window")
    shares = []
    for writer in run.workers:
        ops = run.trace.ops.get(chip_of(writer), [])
        names = {f"repro.{leaf}@{writer}" for leaf in LEAVES}
        leaves = [iv for iv in run.trace.host if iv[2] in names]
        idle = (hi - lo) - devtrace.covered(ops, lo, hi)
        if idle > 0:
            unattributed = (hi - lo) - devtrace.covered(ops + leaves, lo, hi)
            shares.append(100.0 * unattributed / idle)
    return sum(shares) / len(shares) if shares else None
