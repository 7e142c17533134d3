"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (process start to window start, ``setup_s``): one compile of the
cell's kernel per chip at a small size the window never uses.  The window:
the program's own entry, ``repro.tune_matrix``, tunes back-to-back jobs as
the cell's traffic file says, on images the tuner draws from ``--seed``,
with JAX's persistent compilation cache and the program's CompileCache both
off, and is stopped within one sample of ``--seconds``.  After it: the
program the tuner timed for the window's best config is re-timed by this
harness's clock (``best_kernel_ms``), and its output and those of a few
more of the window's timed programs are compared with a plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics, read from the JAX profiler's
trace and the program's telemetry), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``: each number compared with its limit,
which also close standard error.  Without a TPU, or with fewer chips than
the cell asks for, the run exits 2 and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-dir", default=None,
                    help="keep the run's telemetry, store and profile here")
    args = ap.parse_args(argv)

    from chipbench.harness import NoChip, run_cell

    try:
        result = run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t0=T0, keep_dir=args.keep_dir,
            log=lambda s: print(s, flush=True),
        )
    except NoChip as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 2
    print(f"correct={result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(_finite(result), allow_nan=False), flush=True)
    return 0


def _finite(obj):
    """JSON has no inf or nan: a reading that is not finite (no output to
    compare, no best config to time) is printed as null, and such a run is
    never correct."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_finite(v) for v in obj]
    return obj


if __name__ == "__main__":
    sys.exit(main())
