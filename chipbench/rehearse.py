"""CPU rehearsal of one benchmark cell, before any chip run.

    python3 chipbench/rehearse.py --workload add-8192.ga25 [--seconds 8] [--seed 1]

Drives the same path as ``run.py`` (set-up, the window through
``repro.tune_matrix``, the deadline, re-timing, the checks) on the CPU at
128x256 in Pallas interpret mode; a four-chip cell gets four virtual CPU
devices.  It prints what the run counted and the checks, marked as a
rehearsal, and no number under a device metric's name: a CPU run times the
interpreter, not the chip.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REHEARSAL_SIZE = (128, 256)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = {w["name"]: w for w in json.load(f)["workloads"]}
    chips = cells[args.workload]["chips"]
    # before JAX starts: the CPU, with as many virtual devices as chips
    os.environ["JAX_PLATFORMS"] = "cpu"
    if chips > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}"
        )
    sys.path.insert(0, ROOT)
    from chipbench.harness import run_cell

    result = run_cell(
        args.workload, args.seed, args.seconds, False, t0=T0,
        require_tpu=False, size=REHEARSAL_SIZE,
        log=lambda s: print(f"[rehearsal] {s}", flush=True),
    )
    print(json.dumps({
        "rehearsal": True,
        "correct": result["correct"],
        "attempted": result["attempted"],
        "failed": result["failed"],
        "checks": result["checks"],
    }, default=str))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
