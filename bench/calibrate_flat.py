#!/usr/bin/env python3
"""Readings that the limits of a sessionless training cell's ``correct``
are set from (``train_flat_window`` cells). Not part of a benchmark run.

    python3 bench/calibrate_flat.py --workload train-criteo39 --seeds 1001-1006 --control-seeds 2001-2003
    python3 bench/calibrate_flat.py --workload train-criteo39 --seeds 3001-3003 --control-seeds "" --fault drop_slice

As ``bench/calibrate.py`` does for the session cells: the step is
compiled once; for each seed it is driven from that seed's Theta0
through the checked steps and compared with the fp32 reference (the
program's readings, or a planted fault's with ``--fault``); for each
control seed the reference computed in bfloat16 takes the program's
place. One JSON line per reading on stdout.

Planted faults, each breaking the timed path underneath:
  half_batch  the smooth loss sees the first half of the rows, its
              mean scaled back to the whole batch;
  unchanged   each OWLQN+ step returns its state unchanged;
  drop_slice  the backward leaves out the last slice's scatter of a
              batch planned in slices of samples.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def half_batch_fault():
    """The loss and gradient of the first half of the rows, doubled.
    Returns an undo function."""
    import importlib

    import jax

    from repro.data.sparse import build_batch_plans

    # repro.core's own ``objective`` function hides the module attribute
    objective = importlib.import_module("repro.core.objective")
    full = objective.smooth_loss_and_grad

    def half(theta, batch, **kw):
        n = batch.ids.shape[0] // 2
        with jax.ensure_compile_time_eval():  # the batch is a constant
            kept = build_batch_plans(batch._replace(
                ids=batch.ids[:n], vals=batch.vals[:n], y=batch.y[:n],
                plan=None))
        loss, grad = full(theta, kept, **kw)
        return 2.0 * loss, 2.0 * grad

    objective.smooth_loss_and_grad = half
    return lambda: setattr(objective, "smooth_loss_and_grad", full)


def unchanged_fault():
    """Every OWLQN+ step returns the state it was given. Returns an undo
    function."""
    from repro.optim import OWLQNPlus

    step = OWLQNPlus.step

    def stuck(self, state):
        _, stats = step(self, state)
        return state, stats

    OWLQNPlus.step = stuck
    return lambda: setattr(OWLQNPlus, "step", step)


def drop_slice_fault(max_dz_bytes: int | None = None):
    """The backward of a batch planned in slices of samples leaves out
    the last slice's scatter. ``max_dz_bytes`` (a smaller ceiling than
    the kernel's, for a batch small enough to test) makes the planner
    slice. Returns an undo function."""
    import functools

    from repro.data import sparse
    from repro.kernels.lsplm_sparse_fused import ops as fused
    from repro.kernels.lsplm_sparse_scatter.ops import SlicedPlan

    scatter, plans = fused.scatter_add_planned, sparse.build_batch_plans

    def dropped(plan, vals, dz, **kw):
        if isinstance(plan, SlicedPlan):
            lo = plan.bounds[-2]
            plan = SlicedPlan(plan.plans[:-1], plan.bounds[:-1])
            vals, dz = vals[:lo], dz[:lo]
        return scatter(plan, vals, dz, **kw)

    fused.scatter_add_planned = dropped
    if max_dz_bytes is not None:
        sparse.build_batch_plans = functools.partial(plans,
                                                     max_dz_bytes=max_dz_bytes)

    def undo():
        fused.scatter_add_planned, sparse.build_batch_plans = scatter, plans
    return undo


FAULTS = {"half_batch": half_batch_fault, "unchanged": unchanged_fault,
          "drop_slice": drop_slice_fault}


def train(cell, args):
    import jax.numpy as jnp
    import numpy as np

    from bench import spec
    from bench.calibrate import _seeds, emit
    from bench.common import init_theta
    from bench.reference import train_flat as reference
    from bench.traffic import criteo

    drv = spec.load_module("drivers", "train_flat_window")
    cfg, mix = cell.config, cell.traffic
    d, m2 = cfg["num_features"], 2 * cfg["regions"]
    int_rows = mix["int_fields"] * mix["int_buckets"]
    limits = {k: float("inf") for k in ("f_gap", "grad_norm_gap", "change_norm_gap")}
    rows = criteo.window(mix, d)
    seeds = _seeds(args.seeds)
    kind = "program"
    if args.fault:
        FAULTS[args.fault]()
        kind = f"fault:{args.fault}"
    if seeds:
        step, init, calls = drv.train_step(cfg, rows, init_theta(seeds[0], d, m2))
    for seed in seeds:
        prog = drv.window_driver.checked_cycle(
            step, init, init_theta(seed, d, m2), np.asarray, cfg,
            drv.CHECKED_STEPS)
        ref = reference.run(cfg, rows, prog["theta0"], drv.CHECKED_STEPS)
        emit(kind=kind, seed=seed, scatter_calls=calls, readings={
            c.name: c.value for c in drv.window_driver.compare(
                int_rows, prog, ref, limits)},
             f=prog["f"], f_ref=ref[0].tolist())
    for seed in _seeds(args.control_seeds):
        theta0 = np.asarray(init_theta(seed, d, m2))
        ref = reference.run(cfg, rows, theta0, drv.CHECKED_STEPS)
        f, g, th = reference.run(cfg, rows, theta0, drv.CHECKED_STEPS,
                                 dtype=jnp.bfloat16)
        ctrl = {"theta0": theta0, "grad0": np.where(theta0 != 0, g, 0.0),
                "theta3": th, "f": f.tolist()}
        emit(kind="control", seed=seed, readings={
            c.name: c.value for c in drv.window_driver.compare(
                int_rows, ctrl, ref, limits)},
             f=f.tolist(), f_ref=ref[0].tolist())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1001-1006")
    ap.add_argument("--control-seeds", default="2001-2003")
    ap.add_argument("--fault", choices=("", *FAULTS), default="")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import spec
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate_flat: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    train(spec.cell(args.workload), args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
