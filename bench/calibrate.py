#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, and the serving
knee. Not part of a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seeds 1001-1012 --control-seeds 2001-2003
    python3 bench/calibrate.py --workload <cell> --sweep 1000,2000,4000 --seconds 10

For a training cell it builds the compiled step once, and for each seed
drives it from that seed's Theta0 through the checked steps and
compares them with the fp32 reference: the program's readings. For each
control seed it puts the reference computed in bfloat16 in the
program's place: the control's readings. For a serving cell it sets up
the model and the engine once, offers each seed's requests for
``--seconds`` at the mix's rate and compares every score; the control
scores the same requests with the reference in bfloat16. ``--sweep``
offers the mix's traffic at each rate and reports latency, shed requests
and backlog, to find the knee. One JSON line per reading on stdout.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _seeds(text: str) -> list[int]:
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


def half_batch_fault():
    """Break the timed path underneath: the smooth loss sees only the
    first half of the window's impressions, its mean scaled back to the
    whole window. Returns an undo function."""
    import importlib

    import jax

    from repro.data.sparse import build_batch_plans

    # repro.core's own ``objective`` function hides the module attribute
    objective = importlib.import_module("repro.core.objective")
    full = objective.smooth_loss_and_grad

    def half(theta, batch, **kw):
        n = batch.ad_ids.shape[0] // 2
        with jax.ensure_compile_time_eval():  # the batch is a constant
            kept = build_batch_plans(batch._replace(
                ad_ids=batch.ad_ids[:n], ad_vals=batch.ad_vals[:n],
                session_id=batch.session_id[:n], y=batch.y[:n],
                user_plan=None, ad_plan=None))
        loss, grad = full(theta, kept, **kw)
        return 2.0 * loss, 2.0 * grad

    objective.smooth_loss_and_grad = half
    return lambda: setattr(objective, "smooth_loss_and_grad", full)


def half_batch_sharded_fault():
    """The sharded path's half-batch fault: the routed batch keeps the
    first half of each session's ads, and the sharded loss and gradient
    are doubled, the mean over the rest scaled back to the whole window.
    Returns an undo function."""
    import jax
    import numpy as np

    import repro.shard as shard
    from repro.data import sparse

    plans, loss = sparse.build_batch_plans, shard.make_sharded_sparse_loss

    def half_plans(batch, **kw):
        ads = batch.ad_ids.shape[0] // batch.user_ids.shape[0]
        keep = np.flatnonzero(np.arange(batch.ad_ids.shape[0]) % ads < ads // 2)
        return plans(batch._replace(
            ad_ids=batch.ad_ids[keep], ad_vals=batch.ad_vals[keep],
            session_id=batch.session_id[keep], y=batch.y[keep]), **kw)

    def doubled(sbatch, mesh, **kw):
        full = loss(sbatch, mesh, **kw)
        return lambda theta: jax.tree.map(lambda x: 2.0 * x, full(theta))

    sparse.build_batch_plans, shard.make_sharded_sparse_loss = half_plans, doubled

    def undo():
        sparse.build_batch_plans, shard.make_sharded_sparse_loss = plans, loss
    return undo


def no_exchange_fault():
    """Break the timed path underneath: the sharded loss's psum of the
    region logits over ``model`` no longer brings the other server
    shards' parts: every chip gets server shard 0's part alone (the
    reduction keeps its type, so ``shard_map`` accepts the program).
    Returns an undo function."""
    import jax
    import jax.numpy as jnp

    psum = jax.lax.psum

    def partial(x, axis_name, **kw):
        if axis_name != "model":
            return psum(x, axis_name, **kw)
        own = jax.lax.axis_index("model") == 0
        return psum(jnp.where(own, x, jnp.zeros_like(x)), axis_name, **kw)

    jax.lax.psum = partial
    return lambda: setattr(jax.lax, "psum", psum)


FAULTS = {"half_batch": half_batch_fault,
          "half_batch_sharded": half_batch_sharded_fault,
          "no_exchange": no_exchange_fault}


def train(cell, args):
    import jax.numpy as jnp
    import numpy as np

    from bench import spec
    from bench.common import init_theta
    from bench.program import train_step
    from bench.reference import train as reference
    from bench.traffic import daystream

    drv = spec.load_module("drivers", "train_window")
    cfg, mix = cell.config, cell.traffic
    d, m2 = cfg["num_features"], 2 * cfg["regions"]
    user_lo = max(1, int(mix["user_frac"] * d))
    limits = {k: float("inf") for k in ("f_gap", "grad_norm_gap", "change_norm_gap")}
    win = daystream.window(mix, d)
    seeds = _seeds(args.seeds)
    kind = "program"
    if args.fault:
        FAULTS[args.fault]()
        kind = f"fault:{args.fault}"
    step, init, to_global = train_step(cfg, win, init_theta(seeds[0], d, m2))
    for seed in seeds:
        theta0 = init_theta(seed, d, m2)
        prog = drv.checked_cycle(step, init, theta0, to_global, cfg,
                                 drv.CHECKED_STEPS)
        ref = reference.run(cfg, win, prog["theta0"], drv.CHECKED_STEPS)
        emit(kind=kind, seed=seed, readings={
            c.name: c.value for c in drv.compare(user_lo, prog, ref, limits)},
             f=prog["f"], f_ref=ref[0].tolist())
    for seed in _seeds(args.control_seeds):
        theta0 = np.asarray(init_theta(seed, d, m2))
        ref = reference.run(cfg, win, theta0, drv.CHECKED_STEPS)
        f, g, th = reference.run(cfg, win, theta0, drv.CHECKED_STEPS,
                                 dtype=jnp.bfloat16)
        ctrl = {"theta0": theta0, "grad0": np.where(theta0 != 0, g, 0.0),
                "theta3": th, "f": f.tolist()}
        emit(kind="control", seed=seed, readings={
            c.name: c.value for c in drv.compare(user_lo, ctrl, ref, limits)},
             f=f.tolist(), f_ref=ref[0].tolist())


def serve(cell, args):
    import jax.numpy as jnp
    import numpy as np

    from bench import spec
    from bench.reference import train as reference
    from bench.traffic import pageviews
    from repro.serve import (BundleRequest, MicroBatchQueue, QueueConfig,
                             RealClockPump, ScoringEngine)

    drv = spec.load_module("drivers", "serve_open_loop")
    cfg, mix = cell.config, cell.traffic
    d = cfg["num_features"]

    class Ctx:
        config, traffic = cfg, mix

    art, (cfg_, win, theta0, iters) = drv.served_model(Ctx)
    engine = ScoringEngine(art, g_buckets=tuple(mix["g_buckets"]))

    def offer(rate, seed):
        m = dict(mix, rate_per_s=rate)
        due, sizes = pageviews.schedule(m, args.seconds, seed)
        reqs = [BundleRequest(*pv) for pv in pageviews.requests(m, d, sizes, seed)]
        t = time.perf_counter()
        w_due, w_sizes = pageviews.schedule(m, mix["warm_seconds"], seed + 1)
        drv.warm_up(engine, reqs, [BundleRequest(*pv) for pv in pageviews.requests(
            m, d, w_sizes, seed + 1)], w_due, mix["queue"])
        warm_s = time.perf_counter() - t
        timed = drv.TimedEngine(engine)
        queue = MicroBatchQueue(timed, QueueConfig(**mix["queue"]))
        with RealClockPump(queue) as pump:
            t0, late, tickets = drv.offer_open_loop(pump, reqs, due)
            pending_end = queue.pending
        lat = np.array([timed.done.get(id(r), time.perf_counter()) - (t0 + o)
                        for r, o in zip(reqs, due)])
        q = max(len(lat) // 5, 1)
        rec = dict(seed=seed, rate=rate, requests=len(reqs), warm_s=warm_s,
                   shed=sum(t is None for t in tickets), pending_end=pending_end,
                   p50_ms=1e3 * float(np.percentile(lat, 50)),
                   p99_ms=1e3 * float(np.percentile(lat, 99)),
                   p50_first_ms=1e3 * float(np.median(lat[:q])),
                   p50_last_ms=1e3 * float(np.median(lat[-q:])),
                   gen_late_p99_ms=1e3 * float(np.percentile(late, 99)))
        comps = {c.ticket: c for c in queue.completions}
        served = [i for i, t in enumerate(tickets) if t in comps]
        return rec, [reqs[i] for i in served], [comps[tickets[i]].scores for i in served]

    if args.sweep:
        for rate in (float(r) for r in args.sweep.split(",")):
            emit(kind="sweep", **offer(rate, args.sweep_seed)[0])
        return
    _, _, theta_ref = reference.run(cfg_, win, theta0, iters)
    for seed in _seeds(args.seeds):
        rec, reqs, scores = offer(mix["rate_per_s"], seed)
        p_ref = drv.reference_scores(theta_ref, reqs)
        rec["readings"] = {"score_gap": max(
            float(np.max(np.abs(p - r))) for p, r in zip(scores, p_ref))}
        emit(kind="program", **rec)
    for seed in _seeds(args.control_seeds):
        due, sizes = pageviews.schedule(mix, args.seconds, seed)
        reqs = [BundleRequest(*pv) for pv in pageviews.requests(mix, d, sizes, seed)]
        p_ref = drv.reference_scores(theta_ref, reqs)
        p_low = drv.reference_scores(theta_ref, reqs, dtype=jnp.bfloat16)
        emit(kind="control", seed=seed, readings={"score_gap": max(
            float(np.max(np.abs(a - b))) for a, b in zip(p_low, p_ref))})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1001-1012")
    ap.add_argument("--control-seeds", default="2001-2003")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--sweep-seed", type=int, default=3001)
    ap.add_argument("--fault", choices=("", *FAULTS), default="",
                    help="training: read the program with this fault planted")
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import spec
    from repro.launch.compile_cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    cell = spec.cell(args.workload)
    {"train_window": train, "serve_open_loop": serve}[cell.traffic["kind"]](cell, args)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
