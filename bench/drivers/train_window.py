"""Driver ``train_window``: OWLQN+ iterations over one planned window.

Set-up builds what ``launch.train --sparse`` builds: the window's
``SparseCTRBatch`` with its transpose plans, ``OWLQNPlus`` over
``core.objective.smooth_loss_and_grad`` (one chip) or over
``shard.make_sharded_sparse_loss`` on the routed batch with
``dist.make_distributed_step`` (a (data, model) mesh), Theta0 from the
seed, and the step compiled ahead of time. It then drives that compiled
step through one cycle from Theta0: the first three iterations are the
ones the reference follows, and the cycle warms every shape.

The window runs whole cycles of ``cycle_iters`` iterations, each from
the same Theta0 with a fresh history, so every cycle does the same
work. After each iteration the host reads ``f_new``, as
``StreamTrainer.run`` does. The window closes at the first cycle end
past ``--seconds``; the rate is impressions x iterations over the whole
window.

``--trace 1`` traces one cycle instead of the window.
"""
from __future__ import annotations

import gc
import time

import jax
import numpy as np

from bench import check
from bench.common import CompileCounter, init_theta, memory_peak_bytes
from bench.program import train_step
from bench.reference import train as reference
from bench.roofline import gather, scatter
from bench.traffic import daystream

CHECKED_STEPS = 3


def _first_gradient(theta0: np.ndarray, d0: np.ndarray, lam, beta) -> np.ndarray:
    """The smooth gradient the optimizer got at Theta0, from the Eq. 9
    direction it kept: where Theta0 != 0, d = -g - lam * Theta /
    ||Theta_i.|| - beta * sign(Theta). Elements where Theta0 == 0 read 0."""
    rn = np.linalg.norm(theta0, axis=1, keepdims=True)
    g = -(d0 + lam * theta0 / np.where(rn > 0, rn, 1.0) + beta * np.sign(theta0))
    return np.where(theta0 != 0, g, 0.0).astype(np.float32)


def checked_cycle(step, init, theta0, to_global, cfg: dict, cycle: int) -> dict:
    """Drive the compiled step through one cycle from ``init(theta0)``
    (this warms it) and keep what the reference is compared with: Theta0,
    the first gradient, f at Theta0..Theta3 and Theta3, as numpy arrays.
    The state is made here, so that nothing outside holds it and each
    state is freed once the next exists, as in the window."""
    state, stats = init(theta0), []
    for k in range(cycle):
        state, st = step(state)
        float(st.f_new)
        stats.append(st)
        if k == 0:
            first = jax.device_get((state.prev_theta, state.prev_d))
        if k == CHECKED_STEPS - 1:
            theta_k = jax.device_get(state.theta)
    stats = jax.device_get(stats)
    theta0 = to_global(first[0])
    return {"theta0": theta0,
            "grad0": _first_gradient(theta0, to_global(first[1]),
                                     cfg["lam"], cfg["beta"]),
            "theta3": to_global(theta_k),
            "f": [float(stats[0].f)] + [float(s.f_new)
                                        for s in stats[:CHECKED_STEPS]]}


def compare(user_lo, prog: dict, ref: tuple, limits: dict) -> list:
    """The numbers that decide ``correct`` (see ``bench/check.py``)."""
    f_ref, g_ref, th_ref = ref
    theta0 = prog["theta0"]
    mask = theta0 != 0
    quiet = check.quiet_leaves(g_ref, user_lo)
    return [
        check.Check("f_gap", check.rel_gap(prog["f"], f_ref), limits["f_gap"]),
        check.Check("grad_norm_gap", check.leaf_gap(
            prog["grad0"], np.where(mask, g_ref, 0.0), user_lo),
            limits["grad_norm_gap"]),
        check.Check("change_norm_gap", check.leaf_gap(
            prog["theta3"] - theta0, th_ref - theta0, user_lo, quiet),
            limits["change_norm_gap"]),
    ]


def measure(step, init, theta0, cycle: int, seconds: float, on_start=None):
    """The measured window: whole cycles, each from ``init(theta0)`` (a
    fresh history, as a stream window starts), until ``seconds`` have
    passed, reading ``f_new`` after every iteration. Returns (iterations,
    elapsed seconds of the whole window, per-iteration stats fetched to
    the host)."""
    stats, iters = [], 0
    t0 = time.perf_counter()
    if on_start is not None:
        on_start(t0)
    while True:
        state = init(theta0)
        for _ in range(cycle):
            state, st = step(state)
            float(st.f_new)
            stats.append(st)
        iters += cycle
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready(state)
    elapsed = time.perf_counter() - t0
    return iters, elapsed, jax.device_get(stats)


def run(ctx) -> dict:
    cfg, mix = ctx.config, ctx.traffic
    d, m2 = cfg["num_features"], 2 * cfg["regions"]
    cycle = int(mix["cycle_iters"])
    user_lo = max(1, int(mix["user_frac"] * d))
    win = daystream.window(mix, d)
    theta0 = init_theta(ctx.seed, d, m2)
    counter = CompileCounter()
    with counter:
        step, init, to_global = train_step(cfg, win, theta0)
    ctx.log(f"set-up: {counter.compiles} compiles ({counter.cache_hits} "
            f"from the persistent cache)")

    prog = checked_cycle(step, init, theta0, to_global, cfg, cycle)
    impressions = win.ad_ids.shape[0]
    out = {"counters": {"impressions": impressions, "cycle_iters": cycle}}

    if not ctx.trace:
        counter.compiles = counter.cache_hits = 0
        with counter:
            iters, elapsed, window_stats = measure(
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
            for k in range(cycle):
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
        ls = [int(s.ls_iters) for s in traced]
        out["counters"]["ls_evals"] = ls
        out["work"] = {
            "gather": [gather.work(win.user_ids, d, m2),
                       gather.work(win.ad_ids, d, m2)],
            "scatter": [scatter.work(win.user_ids, d, m2),
                        scatter.work(win.ad_ids, d, m2)],
            "d": d, "m2": m2, "memory": cfg["optimizer"]["memory"],
            "chips": cfg["mesh"]["data"] * cfg["mesh"]["model"],
        }
    out["memory_peak_bytes"] = memory_peak_bytes(ctx.devices)

    # free the program's state before the reference runs on the chip
    del step, init, theta0
    gc.collect()
    ref = reference.run(cfg, win, prog["theta0"], CHECKED_STEPS)
    out["checks"] = compare(user_lo, prog, ref, ctx.limits)
    return out
