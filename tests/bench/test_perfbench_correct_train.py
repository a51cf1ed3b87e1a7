"""``correct`` for the training cell, at a size a test run holds: a sound
run passes, the control (the reference in bfloat16 in the program's
place) fails, and so does a run with the timed path broken underneath.
The harness's look for a chip is skipped; the rest of a run is driven
through ``bench/run.py``'s ``execute`` on the CPU."""
import argparse
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run, spec  # noqa: E402
from bench.reference import train as reference  # noqa: E402

CELL = "train-d1m-window"
LIMITS = spec.load_json(ROOT / "bench" / "limits" / f"{CELL}.json")
TINY_CONFIG = dict(spec.cell(CELL).config, num_features=3_000, regions=4)
TINY_MIX = dict(spec.cell(CELL).traffic, sessions_per_day=256)
DRIVER = spec.load_module("drivers", "train_window")


def tiny_run(seed=2_147_483_659):
    cell = spec.cell(CELL)._replace(config=TINY_CONFIG, traffic=TINY_MIX)
    args = argparse.Namespace(seed=seed, seconds=0.3, trace=0)
    return run.execute(args, jax.devices()[:1], cell, LIMITS)


def test_sound_run_is_correct():
    line = tiny_run()
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) == {"f_gap", "grad_norm_gap", "change_norm_gap"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_impressions_per_s", "setup_s"}


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_control_fails(seed):
    """The reference computed in bfloat16, put in the program's place."""
    d, m2 = TINY_CONFIG["num_features"], 2 * TINY_CONFIG["regions"]
    win = DRIVER.daystream.window(TINY_MIX, d)
    theta0 = np.asarray(DRIVER.init_theta(seed, d, m2))
    ref = reference.run(TINY_CONFIG, win, theta0, DRIVER.CHECKED_STEPS)
    f, g, th = reference.run(TINY_CONFIG, win, theta0, DRIVER.CHECKED_STEPS,
                             dtype=jnp.bfloat16)
    control = {"theta0": theta0, "grad0": np.where(theta0 != 0, g, 0.0),
               "theta3": th, "f": f.tolist()}
    user_lo = int(TINY_MIX["user_frac"] * d)
    checks = DRIVER.compare(user_lo, control, ref, LIMITS)
    assert not all(c.ok for c in checks), checks


def test_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from repro.optim import OWLQNPlus

    step = OWLQNPlus.step

    def stuck(self, state):
        _, stats = step(self, state)
        return state, stats

    monkeypatch.setattr(OWLQNPlus, "step", stuck)
    line = tiny_run()
    assert not line["correct"]
    assert line["checks"]["change_norm_gap"]["value"] > LIMITS["change_norm_gap"]


def test_half_the_batch_with_the_mean_over_the_rest_is_not_correct():
    from bench.calibrate import half_batch_fault

    undo = half_batch_fault()
    try:
        line = tiny_run()
    finally:
        undo()
    assert not line["correct"], line["checks"]
