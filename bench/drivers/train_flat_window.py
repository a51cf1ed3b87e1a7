"""Driver ``train_flat_window``: OWLQN+ iterations over one planned window
of sessionless rows (the public Criteo schema).

Set-up builds what ``launch.train --sparse --schema criteo39`` builds:
the mix's ``FlatCTRBatch`` with its transpose plan (split by samples
where one scatter call's dz would not fit the kernel's VMEM),
``OWLQNPlus`` over ``core.objective.smooth_loss_and_grad``, Theta0 from
the seed, and the step compiled ahead of time. The checked cycle, the
measured window of whole cycles with ``f_new`` read after every
iteration, the traced cycle and the comparison with the reference are
``train_window``'s. The reference is ``bench/reference/train_flat.py``;
its leaves are the integer-field rows against the categorical rows.

A program without a sessionless batch (``repro.data.sparse.
FlatCTRBatch``) cannot run this cell: loading the driver fails at once.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import spec
from bench.common import CompileCounter, init_theta, memory_peak_bytes
from bench.reference import train_flat as reference
from bench.roofline import ZERO, gather, scatter
from bench.traffic import criteo
from repro import obs
from repro.data import sparse
from repro.data.sparse import FlatCTRBatch

window_driver = spec.load_module("drivers", "train_window")
CHECKED_STEPS = window_driver.CHECKED_STEPS


def train_step(cfg: dict, rows, theta0):
    """The compiled OWLQN+ step over ``rows``, ``init(theta0) -> state``
    jitted, and the planned-scatter calls one backward makes (the
    planner's ``train_plan_slices`` counter)."""
    from repro.core.objective import smooth_loss_and_grad
    from repro.optim import OWLQNPlus

    opt_cfg = cfg["optimizer"]
    slices = obs.get_registry().counter("train_plan_slices")
    before = slices.value
    batch = sparse.build_batch_plans(FlatCTRBatch(
        ids=jnp.asarray(rows.ids), vals=jnp.asarray(rows.vals),
        y=jnp.asarray(rows.y), num_features=rows.num_features))
    calls = int(slices.value - before)
    opt = OWLQNPlus(lambda t: smooth_loss_and_grad(t, batch), lam=cfg["lam"],
                    beta=cfg["beta"], memory=opt_cfg["memory"],
                    c1=opt_cfg["c1"], max_ls=opt_cfg["max_ls"],
                    ls_shrink=opt_cfg["ls_shrink"])
    init = jax.jit(opt.init)
    return jax.jit(opt.step).lower(init(theta0)).compile(), init, calls


def run(ctx) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    d, m2 = cfg["num_features"], 2 * cfg["regions"]
    cycle = int(mix["cycle_iters"])
    int_rows = mix["int_fields"] * mix["int_buckets"]
    rows = criteo.window(mix, d)
    theta0 = init_theta(ctx.seed, d, m2)
    counter = CompileCounter()
    with counter:
        step, init, calls = train_step(cfg, rows, theta0)
    ctx.log(f"set-up: {counter.compiles} compiles ({counter.cache_hits} "
            f"from the persistent cache), {calls} scatter calls a backward")

    prog = window_driver.checked_cycle(step, init, theta0, np.asarray, cfg,
                                       cycle)
    impressions = rows.ids.shape[0]
    out = {"counters": {"impressions": impressions, "cycle_iters": cycle,
                        "scatter_calls": calls}}

    if not ctx.trace:
        counter.compiles = counter.cache_hits = 0
        with counter:
            iters, elapsed, window_stats = window_driver.measure(
                step, init, theta0, cycle, ctx.seconds, ctx.mark_setup_end)
        ctx.log(f"window: {iters} iterations in {elapsed:.3f} s, "
                f"{counter.compiles} compiles inside it")
        out["metrics"] = {"train_impressions_per_s": impressions * iters / elapsed}
        out["attempted"] = iters
        out["failed"] = sum(float(s.alpha) == 0.0 for s in window_stats)
        out["compiles_in_window"] = counter.compiles
    else:
        from bench import tracing

        traced = []

        def slice_():
            st_ = init(theta0)
            for _ in range(cycle):
                with jax.profiler.TraceAnnotation("bench/dispatch"):
                    st_, s = step(st_)
                with jax.profiler.TraceAnnotation("bench/readback"):
                    float(s.f_new)
                traced.append(s)
            jax.block_until_ready(st_)

        ctx.mark_setup_end(time.perf_counter())
        red, _ = tracing.capture(ctx, slice_)
        traced = jax.device_get(traced)
        out["reduced"] = red
        out["attempted"] = len(traced)
        out["failed"] = sum(float(s.alpha) == 0.0 for s in traced)
        out["counters"]["ls_evals"] = [int(s.ls_iters) for s in traced]
        # the layout the training readers read: one call as the ad side,
        # no user side; the scatter's required work is the whole batch's
        # (the slices' repeated row writes are not required work)
        out["work"] = {
            "gather": [ZERO, gather.work(rows.ids, d, m2)],
            "scatter": [ZERO, scatter.work(rows.ids, d, m2)],
            "d": d, "m2": m2, "memory": cfg["optimizer"]["memory"],
            "chips": cfg["mesh"]["data"] * cfg["mesh"]["model"],
        }
    out["memory_peak_bytes"] = memory_peak_bytes(ctx.devices)

    # free the program's state before the reference runs on the chip
    del step, init, theta0
    gc.collect()
    ref = reference.run(cfg, rows, prog["theta0"], CHECKED_STEPS)
    out["checks"] = window_driver.compare(int_rows, prog, ref, ctx.limits)
    return out
