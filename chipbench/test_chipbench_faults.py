"""The comparison that decides ``correct`` fails what it has to fail.

The control (the reference computed in bfloat16, put in the kernel's place)
reads above each configuration's limit, while the program's kernel reads
within it.  And a whole run, with the look for a chip skipped and the timed
path broken underneath, comes out not correct for the control and for each
fault the cells can have: an answer altered where it is produced, half of
the image left out, one config's program timed for another's, a value told
to the searcher that is not what was timed, a measurement that returns its
first value unchanged, and (four devices) the exchange of the workers'
stores left out.
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
SMALL = (128, 256)
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


@pytest.mark.parametrize("name", ["add-8192", "harris-8192"])
def test_control_fails_the_limit_and_the_program_meets_it(name):
    from chipbench.control import readings

    config = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json").read_text())
    limit = config["limits"]["out_err"]
    for seed in (1, 2**31 + 11):
        r = readings(config, seed, [{}, {"t_x": 2, "t_z": 2}], size=SMALL, on_chip=False)
        assert r["control"] > 3 * max(limit, 1e-7), r
        assert max(r["program"]) <= limit, r


def _altered(out):
    return out.at[0, 0].add(1.0)


def _half_left_out(out):
    return out.at[out.shape[0] // 2:].set(0.0)


def _break_kernel(monkeypatch, damage):
    # the package's ``add`` name is the function, so the module is looked up
    ops = importlib.import_module("repro.kernels.add.ops")
    good = ops.add
    monkeypatch.setattr(ops, "add", lambda a, b, cfg=None: damage(good(a, b, cfg)))


def _bf16_reference_in_the_kernels_place(monkeypatch):
    import jax.numpy as jnp

    from chipbench.references import add as ref

    ops = importlib.import_module("repro.kernels.add.ops")
    low = lambda a, b, cfg=None: ref.reference(  # noqa: E731
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)).astype(jnp.float32)
    monkeypatch.setattr(ops, "add", low)


def _first_program_for_every_config(monkeypatch):
    from repro.pallas_bench import PallasMeasurement

    good = PallasMeasurement._stage_compile

    def stage_compile(self, config):
        fn = good(self, config)
        if callable(fn) and not hasattr(self, "_first_program"):
            self._first_program = fn
        return getattr(self, "_first_program", fn)

    monkeypatch.setattr(PallasMeasurement, "_stage_compile", stage_compile)


def _inputs_not_drawn(monkeypatch):
    import jax.numpy as jnp
    from repro.pallas_bench import PallasWorkload

    def zeros(self):
        return tuple(jnp.zeros((self.x, self.y), jnp.float32)
                     for _ in range(self.bench.n_inputs))

    monkeypatch.setattr(PallasWorkload, "materialize", zeros)


def _break_record(monkeypatch, served):
    from repro.pallas_bench import PallasMeasurement

    good = PallasMeasurement._stage_record

    def record(self, key, out, log):
        return served(self, good(self, key, out, log))

    monkeypatch.setattr(PallasMeasurement, "_stage_record", record)


def _told_scaled(self, value):
    return value * 0.5 if np.isfinite(value) else value


def _first_value(self, value):
    if not hasattr(self, "_first_value"):
        self._first_value = value
    return self._first_value


#: fault -> how it is planted, and the check that has to catch it
FAULTS = {
    "control": (_bf16_reference_in_the_kernels_place, "out_err"),
    "answer_altered": (lambda mp: _break_kernel(mp, _altered), "out_err"),
    "half_left_out": (lambda mp: _break_kernel(mp, _half_left_out), "out_err"),
    "program_of_another_config": (_first_program_for_every_config, "program_mismatches"),
    "inputs_not_drawn": (_inputs_not_drawn, "input_flaws"),
    "told_value_altered": (lambda mp: _break_record(mp, _told_scaled), "record_mismatches"),
    "state_unchanged": (lambda mp: _break_record(mp, _first_value), "record_mismatches"),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    from chipbench.harness import run_cell

    plant, caught_by = FAULTS[fault]
    plant(monkeypatch)
    result = run_cell("add-8192.ga25", 7, 1.5, False, t0=time.perf_counter(),
                      require_tpu=False, size=SMALL, log=lambda s: None)
    assert result["attempted"] >= 2
    assert not result["correct"], result["checks"]
    check = result["checks"][caught_by]
    assert check["value"] > check["limit"], result["checks"]


EXCHANGE_LEFT_OUT = """
import json, sys, time
from pathlib import Path
sys.path.insert(0, {root!r}); sys.path.insert(0, {src!r})
import repro.core.executors as ex
ex._merge_steal_shards = lambda session: None
from chipbench.harness import run_cell
r = run_cell("add-8192.ga25.x4", 7, 16.0, False, t0=time.perf_counter(),
             require_tpu=False, size=(128, 256), log=lambda s: None, root=Path({bench!r}))
print(json.dumps({{"correct": r["correct"], "checks": r["checks"],
                   "attempted": r["attempted"]}}))
"""

#: the four-chip cell of the device executor, over the ``ga25-cold-x4``
#: traffic file, whether or not BENCHMARK.json holds it
X4_CELL = {"name": "add-8192.ga25.x4", "config": "add-8192", "traffic": "ga25-cold-x4",
           "chips": 4, "why": "device executor on four devices"}


def test_exchange_between_chips_left_out_is_not_correct(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if X4_CELL["name"] not in {w["name"] for w in bench["workloads"]}:
        bench["workloads"].append(X4_CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "chipbench").symlink_to(ROOT / "chipbench")
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    }
    code = EXCHANGE_LEFT_OUT.format(root=str(ROOT), src=str(ROOT / "src"),
                                    bench=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    r = json.loads(proc.stdout.splitlines()[-1])
    assert not r["correct"], r
    assert r["checks"]["store_mismatches"]["value"] > 0, r
