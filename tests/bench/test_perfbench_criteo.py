"""The sessionless training cell (``train-criteo39``): its traffic equals
the program's Criteo generator, its readers take the driver's layout,
and ``correct`` at a size a test run holds: a sound run passes, the
control (the reference in bfloat16 in the program's place) fails, and
so does each planted fault. The harness's look for a chip is skipped;
the rest of a run is driven through ``bench/run.py``'s ``execute`` on
the CPU."""
import argparse
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import calibrate_flat, run, spec  # noqa: E402
from bench.reference import train_flat as reference  # noqa: E402
from bench.roofline import Work  # noqa: E402
from bench.tracing import Reduced  # noqa: E402
from bench.traffic import criteo  # noqa: E402

CELL = "train-criteo39"
LIMITS = spec.load_json(ROOT / "bench" / "limits" / f"{CELL}.json")
# 13 x 4 integer rows, categorical fields capped at 60 rows: 1,293 rows,
# two more for the two largest raw fields
TINY_CONFIG = dict(spec.cell(CELL).config, num_features=1_295, regions=4)
TINY_MIX = dict(spec.cell(CELL).traffic, impressions=512, int_buckets=4, cap=60)
DRIVER = spec.load_module("drivers", "train_flat_window")
INT_ROWS = TINY_MIX["int_fields"] * TINY_MIX["int_buckets"]


def tiny_run(seed=2_147_483_659, trace=0):
    cell = spec.cell(CELL)._replace(config=TINY_CONFIG, traffic=TINY_MIX)
    args = argparse.Namespace(seed=seed, seconds=0.3, trace=trace)
    return run.execute(args, jax.devices()[:1], cell, LIMITS)


def test_criteo_copy_matches_the_programs_generator():
    """The program's generator, on its own constants, makes the cell's
    rows: so its layout, Zipf exponent and label offset are the mix's."""
    from repro.data.criteo import generate_criteo

    mix = dict(spec.mix("criteo39-day-196k"), impressions=3_000)
    d = spec.cell(CELL).config["num_features"]
    ours = criteo.window(mix, d)
    prog = generate_criteo(mix["impressions"], seed=mix["data_seed"],
                           with_plans=False)
    for field in ("ids", "vals", "y"):
        np.testing.assert_array_equal(np.asarray(getattr(prog, field)),
                                      getattr(ours, field), err_msg=field)
    assert ours.num_features == prog.num_features == 998_960


def test_cell_layout_one_id_per_field_and_every_row_laid_out():
    mix = spec.mix("criteo39-day-196k")
    d = spec.cell(CELL).config["num_features"]
    sizes = criteo.field_sizes(mix, d)
    assert sizes.size == 39 and int(sizes.sum()) == d == 998_960
    rows = criteo.window(TINY_MIX, TINY_CONFIG["num_features"])
    sizes = criteo.field_sizes(TINY_MIX, TINY_CONFIG["num_features"])
    lo = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    assert rows.ids.shape == (512, 39)
    assert ((rows.ids >= lo) & (rows.ids < lo + sizes)).all()
    with pytest.raises(ValueError, match="cannot be laid out"):
        criteo.field_sizes(mix, d + 8)


def test_training_readers_take_the_flat_layout():
    """The six training readers and ``scatter_calls.train`` read the
    driver's ``work`` (one call as the ad side, no user side) and
    counters."""
    rows = criteo.window(TINY_MIX, TINY_CONFIG["num_features"])
    d, m2 = TINY_CONFIG["num_features"], 8
    out = DRIVER.gather.work(rows.ids, d, m2)
    x = {"kind": "TPU v5 lite", "chips": 1,
         "reduced": Reduced(window_s=1.0, busy_s=0.9,
                            kernel_s={"gather": 0.3, "scatter": 0.2},
                            collective_exposed_s=0.0, ops=[], gaps=[]),
         "counters": {"impressions": 512, "ls_evals": [1, 1, 2],
                      "scatter_calls": 2},
         "work": {"gather": [Work(0.0, 0.0), out],
                  "scatter": [Work(0.0, 0.0),
                              DRIVER.scatter.work(rows.ids, d, m2)],
                  "d": d, "m2": m2, "memory": 10, "chips": 1}}
    cell = spec.cell(CELL)
    names = [m["name"] for m in cell.per_layer]
    assert names == ["train_step_mfu", "gather_roofline.train",
                     "scatter_roofline.train", "ls_evals_per_iter",
                     "device_idle.train", "scatter_calls.train"]
    values = {n: spec.load_module("metrics", n).read(x) for n in names}
    assert all(v is not None and 0 < v for v in values.values()), values
    assert values["scatter_calls.train"] == 2
    assert values["device_idle.train"] == pytest.approx(10.0)
    # a program that does not count its scatter calls reports nothing
    x["counters"].pop("scatter_calls")
    assert spec.load_module("metrics", "scatter_calls.train").read(x) is None


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
    rows = criteo.window(TINY_MIX, d)
    theta0 = np.asarray(DRIVER.init_theta(seed, d, m2))
    ref = reference.run(TINY_CONFIG, rows, theta0, DRIVER.CHECKED_STEPS)
    f, g, th = reference.run(TINY_CONFIG, rows, theta0, DRIVER.CHECKED_STEPS,
                             dtype=jnp.bfloat16)
    control = {"theta0": theta0, "grad0": np.where(theta0 != 0, g, 0.0),
               "theta3": th, "f": f.tolist()}
    checks = DRIVER.window_driver.compare(INT_ROWS, control, ref, LIMITS)
    assert not all(c.ok for c in checks), checks


def test_reference_blocks_sum_to_the_whole(monkeypatch):
    """Blocks of rows, the last one padded with weight 0, give the loss
    of the whole batch at once."""
    d, m2 = TINY_CONFIG["num_features"], 8
    rows = criteo.window(TINY_MIX, d)
    theta = jnp.asarray(DRIVER.init_theta(5, d, m2)) * 30
    args = (jnp.asarray(rows.ids), jnp.asarray(rows.vals), jnp.asarray(rows.y))
    whole = reference.nll(theta, *args)
    monkeypatch.setattr(reference, "BLOCK", 100)  # 6 blocks, 88 pad rows
    np.testing.assert_allclose(float(reference.nll(theta, *args)),
                               float(whole), rtol=1e-6)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "drop_slice"])
@pytest.mark.parametrize("seed", [2_147_483_659, 2_147_483_671, 2_147_483_693])
def test_planted_fault_is_not_correct(fault, seed):
    """Each fault breaks the timed path underneath; the drop of a slice
    plans the tiny batch in 3 slices (a ceiling of 200 samples' dz)."""
    kw = {"max_dz_bytes": 200 * 512} if fault == "drop_slice" else {}
    undo = calibrate_flat.FAULTS[fault](**kw)
    try:
        line = tiny_run(seed)
    finally:
        undo()
    assert not line["correct"], line["checks"]
