"""The control of the kernel comparison, read on the chip at a cell's size.

    python3 chipbench/control.py --workload add-8192.ga25 --seeds 1,2,3 \
        [--configs '[{"t_x": 1}, {"t_x": 2, "t_z": 4}]']

For each seed it makes the run's data as the tuner does in a run of the
cell (the program's own inputs for ``input_seed``), computes the
configuration's reference in float32, and reads ``out_err`` (the number
``correct`` compares) of two things put in the program's place:

* the control: the same reference computed in bfloat16, the nearest
  precision below the configuration's float32.  It has to read above the
  limit, or the comparison could not tell a lower-precision kernel from a
  sound one;
* the program's own kernel entry at each given config (default: one),
  which reads what sound runs read.

The benchmark's runs never run this.  One JSON line per seed, then a
summary line with the largest sound reading and the smallest control
reading.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def readings(config: dict, seed: int, configs: list[dict],
             size: tuple[int, int] | None = None, on_chip: bool = True) -> dict:
    """``{"control": out_err, "program": [out_err per config]}`` for one seed
    of the configuration ``config`` (a file of ``configs/``, loaded)."""
    import jax
    import jax.numpy as jnp

    from chipbench import compare
    from chipbench.harness import BENCH_DIR, ROOT as root, load_module

    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    from repro.kernels import KERNEL_BENCHES
    from repro.pallas_bench import make_workload

    x, y = size or (config["x"], config["y"])
    ref = load_module(BENCH_DIR / config["reference"])
    kbench = KERNEL_BENCHES[config["kernel"]]
    chip = jax.devices()[0]
    rdev = jax.devices(config["reference_platform"] if on_chip else chip.platform)[0]
    with jax.default_device(chip):
        inputs = make_workload(config["kernel"], x, y, input_seed=seed).materialize()
        program = [kbench.run(inputs, cfg, x, y) for cfg in configs]
    with jax.default_device(rdev):
        on_ref = jax.device_put(inputs, rdev)
        want = jax.jit(ref.reference)(*on_ref)
        # the casts run as operations of their own: inside one compiled
        # program with the reference, XLA may keep the float32 values
        # (excess precision) and the control would not be a bfloat16 one
        low_in = [jnp.asarray(a, jnp.bfloat16) for a in on_ref]
        low = jnp.asarray(jax.jit(ref.reference)(*low_in), jnp.float32)
        return {
            "control": compare.out_err(low, want),
            "program": [compare.out_err(jax.device_put(o, rdev), want) for o in program],
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--configs", default='[{}]', help="JSON list of kernel configs")
    args = ap.parse_args(argv)
    from chipbench.harness import load_cell

    config = load_cell(args.workload).config
    configs = json.loads(args.configs)
    control, sound = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(config, seed, configs)
        control.append(r["control"])
        sound.extend(r["program"])
        print(json.dumps({"seed": seed, **r}), flush=True)
    limit = config["limits"]["out_err"]
    print(json.dumps({
        "workload": args.workload,
        "out_err_limit": limit,
        "sound_max": max(sound),
        "control_min": min(control),
        "control_fails_all": all(c > limit for c in control),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
