"""``correct`` for the four-chip training cell, at a size a test run
holds, on four virtual CPU devices (in a child process, which needs its
own device count): a sound run passes; a run whose step returns its
state unchanged, whose sharded loss leaves out half of the batch (the
mean over the rest scaled back), or whose sharded loss leaves out the
exchange between chips (the psum over ``model``) does not."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "train-d3m-2x2"
FAULTS = ("state_unchanged", "half_batch", "no_exchange")

CHILD = """
import argparse, json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import run, spec
from bench.calibrate import half_batch_sharded_fault, no_exchange_fault
from repro.optim import OWLQNPlus
cell = spec.cell({cell!r})
cell = cell._replace(config=dict(cell.config, num_features=6_000, regions=4),
                     traffic=dict(cell.traffic, sessions_per_day=256))
limits = spec.load_json(spec.ROOT / "bench" / "limits" / ({cell!r} + ".json"))
args = argparse.Namespace(seed=2_147_483_659, seconds=0.3, trace=0)
execute = lambda: run.execute(args, jax.devices()[:4], cell, limits)
out = {{"sound": execute()}}
step = OWLQNPlus.step
OWLQNPlus.step = lambda self, state: (state, step(self, state)[1])
out["state_unchanged"] = execute()
OWLQNPlus.step = step
for name, fault in (("half_batch", half_batch_sharded_fault),
                    ("no_exchange", no_exchange_fault)):
    undo = fault()
    out[name] = execute()
    undo()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def lines():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(root=str(ROOT), src=str(ROOT / "src"), cell=CELL)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct(lines):
    assert lines["sound"]["correct"], lines["sound"]["checks"]
    assert lines["sound"]["device"]["count"] == 4


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(lines, fault):
    assert not lines[fault]["correct"], lines[fault]["checks"]
