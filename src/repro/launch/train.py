"""End-to-end LS-PLM training driver (the paper's production job).

Trains LS-PLM with Algorithm 1 on the synthetic CTR workload using the
paper's distribution plan (DESIGN.md §3): batch over the data axis
(workers), Theta feature-rows over the model axis (servers), the
common-feature trick enabled.

Run (CPU simulation of the cluster with 8 host devices):
  PYTHONPATH=src REPRO_DEVICES=8 python -m repro.launch.train \
      --sessions 4000 --regions 12 --lam 1.0 --beta 1.0 --iters 60 \
      --mesh-data 4 --mesh-model 2 --ckpt /tmp/lsplm.npz

Sparse production mode (padded-COO ids over --sparse-features columns,
running on the fused sparse kernel — Pallas on TPU, chunked jnp on CPU):
  PYTHONPATH=src python -m repro.launch.train --sparse \
      --sparse-features 1000000 --sessions 1024 --regions 4 --iters 30

Sessionless sparse mode on the public Criteo schema (39 one-hot fields,
d = 998,960, no shared user side; repro.data.criteo), one device:
  PYTHONPATH=src python -m repro.launch.train --sparse --schema criteo39 \
      --impressions 196608 --regions 12 --lam 0.05 --beta 0.05 --iters 30

Distributed sparse mode (the paper's worker/server split on the sparse
path: samples over 'data', Theta rows over 'model' with id-range
routing via repro.shard):
  PYTHONPATH=src REPRO_DEVICES=8 python -m repro.launch.train --sparse \
      --sessions 512 --sparse-features 100000 --regions 4 \
      --mesh-data 2 --mesh-model 4 --iters 30

Streaming mode (production cadence: day-sliced stream, sliding-window
minibatch OWLQN+ warm-started across windows, host re-planning +
compilation overlapped with the device step; composes with the mesh
flags for the sharded path):
  PYTHONPATH=src python -m repro.launch.train --stream \
      --days 8 --window 2 --inner-iters 5 --sessions 256 \
      --sparse-features 100000 --regions 4 --ckpt /tmp/stream.npz
"""
import os
if "REPRO_DEVICES" in os.environ:  # must precede jax import
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={os.environ['REPRO_DEVICES']}"
    )

import argparse
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import predict_proba
from repro.core.lsplm import params_from_theta
from repro.core.objective import smooth_loss_and_grad
from repro.data import CTRDataConfig, auc, generate, pad_to_multiple, to_dense_batch
from repro.dist import make_distributed_step, shard_batch, shard_state
from repro.io import checkpoint
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.launch.tuning import (
    add_tuning_flags,
    apply_tuning_flags,
    tune_job_shapes,
    tuning_flags_set,
)
from repro.optim import OWLQNPlus


class SparseRun(NamedTuple):
    """What :func:`train_sparse` leaves behind: the trained global Theta,
    the per-iteration OWLQN+ stats (device values, fetched by the caller),
    and the jitted step with its final state (for inspecting the program)."""

    theta: jax.Array
    stats: list
    step: object
    state: object


def train_sparse(args) -> SparseRun:
    """Production-format training: padded-COO ids/vals over d columns,
    OWLQN+ on the fused sparse kernel's custom-VJP loss. Dense (B, d)
    matrices never exist; the backward touches only active Theta rows,
    scheduled by per-batch transpose plans (built once, host-side — no
    sort or scatter inside the optimizer step).

    With --mesh-data/--mesh-model the job runs the paper's worker/server
    split end to end (repro.shard): samples over 'data', Theta rows over
    'model' by id range, plan slices per shard, one z psum per step."""
    from repro.data import auc as auc_fn
    from repro.data.sparse import (
        build_batch_plans,
        generate_sparse,
        plan_sides,
        plan_slices,
        sparse_predict,
    )

    distributed = args.mesh_data > 0 and args.mesh_model > 0
    if (args.mesh_data > 0) != (args.mesh_model > 0):
        raise SystemExit(
            "--mesh-data and --mesh-model must be set together (sparse "
            "mode shards samples x Theta rows as one (data, model) mesh)")

    m = args.regions
    if args.schema == "criteo39":
        from repro.data.criteo import NUM_FEATURES, generate_criteo

        if distributed:
            raise SystemExit("--schema criteo39 trains on one device: the "
                             "sharded path takes session batches only")
        d = NUM_FEATURES
        train = generate_criteo(args.impressions, seed=args.seed + 1,
                                with_plans=False)
        test = generate_criteo(max(args.impressions // 5, 128),
                               seed=args.seed + 2, with_plans=False)
    else:
        d = args.sparse_features
        user_range = (max(1, int(0.6 * d)), d)
        train = generate_sparse(num_features=d,
                                num_user_features_range=user_range,
                                sessions=args.sessions, seed=args.seed + 1,
                                with_plans=False)
        test = generate_sparse(num_features=d,
                               num_user_features_range=user_range,
                               sessions=max(args.sessions // 5, 32),
                               seed=args.seed + 2, with_plans=False)
    sides = plan_sides(train)
    theta0 = jnp.asarray(
        0.01 * np.random.default_rng(args.seed).normal(size=(d, 2 * m)),
        jnp.float32)
    apply_tuning_flags(args, batch_n=sides[-1][1].shape[0],
                       batch_k=max(ids.shape[-1] for _, ids, _ in sides))
    if args.tune:
        tune_job_shapes([(*ids.shape, d, m) for _, ids, _ in sides])
    kern = ("pipelined block-DMA kernel" if jax.default_backend() == "tpu"
            else "scan-chunked jnp fallback")
    obs.log(f"sparse mode ({args.schema} schema): d={d:,} columns, Theta "
            f"{theta0.shape} ({theta0.size:,} params), "
            f"backend={jax.default_backend()} ({kern})")
    # the training batch is planned once: here on one device (sliced by
    # samples past one scatter call), below whole and routed on a mesh;
    # the held-out batch is only scored, so it is never planned
    if not distributed:
        train = build_batch_plans(train)
        for side, _, plan in plan_sides(train):
            parts = plan_slices(plan)
            for i, p in enumerate(parts):
                name = side if len(parts) == 1 else f"{side} slice {i}"
                obs.log(f"  {name} transpose plan: {p.num_kept:,} entries, "
                        f"{p.num_unique:,} unique ids, "
                        f"{len(p.class_width)} popularity classes")

    part = None
    if distributed:
        from repro.dist import shard_sparse_batch
        from repro.shard import make_partition, make_sharded_sparse_loss

        assert jax.device_count() >= args.mesh_data * args.mesh_model, (
            f"need {args.mesh_data * args.mesh_model} devices, "
            f"have {jax.device_count()} (set REPRO_DEVICES)")
        if args.sessions % args.mesh_data:
            raise SystemExit(f"--sessions {args.sessions} must divide by "
                             f"--mesh-data {args.mesh_data}")
        mesh = make_debug_mesh(data=args.mesh_data, model=args.mesh_model)
        part = make_partition(d, args.mesh_model)
        sbatch = shard_sparse_batch(mesh, build_batch_plans(
            train, shards=part, data_shards=args.mesh_data))
        opt = OWLQNPlus(make_sharded_sparse_loss(sbatch, mesh),
                        lam=args.lam, beta=args.beta)
        state = shard_state(opt.init(part.pad_rows(theta0)), mesh)
        step = make_distributed_step(opt, mesh)
        obs.log(f"mesh: data={args.mesh_data} x model={args.mesh_model} "
                f"(PS mapping: workers x servers); Theta rows id-range "
                f"sharded, {part.rows_per_shard:,} rows/shard, routed "
                f"K user={sbatch.user_ids.shape[-1]} "
                f"ad={sbatch.ad_ids.shape[-1]}")
    else:
        opt = OWLQNPlus(lambda t: smooth_loss_and_grad(t, train),
                        lam=args.lam, beta=args.beta)
        state = opt.init(theta0)
        step = jax.jit(opt.step)

    tracer = obs.get_tracer()
    history = []
    for k in range(args.iters):
        t0 = time.perf_counter()
        with tracer.step_span("train/iter", k):
            state, stats = step(state)
        history.append(stats)
        dt = time.perf_counter() - t0
        if k % 5 == 0 or k == args.iters - 1:
            theta_eval = state.theta if part is None else part.unpad_rows(
                jnp.asarray(jax.device_get(state.theta)))
            p = np.asarray(sparse_predict(theta_eval, test))
            a = auc_fn(np.asarray(test.y), p)
            st = jax.device_get(stats)
            rec = dict(step=k, f=float(st.f), f_new=float(st.f_new),
                       alpha=float(st.alpha), ls_iters=int(st.ls_iters),
                       grad_norm=float(st.grad_norm), nnz=int(st.nnz),
                       test_auc=float(a), wall_s=dt)
            obs.log(obs.render_train_iter(rec), kind="train_iter", **rec)
    theta = state.theta if part is None else part.unpad_rows(
        jnp.asarray(jax.device_get(state.theta)))
    if args.drift_ref:
        p = np.asarray(sparse_predict(theta, test))
        ids = np.concatenate([np.asarray(a).ravel()
                              for _, a, _ in plan_sides(test)])
        ref = obs.capture_reference(p, np.asarray(test.y), ids,
                                    num_features=d)
        obs.log(f"drift reference (held-out test, {p.shape[0]} scores, "
                f"ratio={ref.ratio:.3f}) -> "
                f"{obs.save_drift_reference(args.drift_ref, ref)}")
    if args.ckpt:
        checkpoint.save(args.ckpt, {"theta": theta})
        obs.log(f"checkpoint -> {args.ckpt}")
    return SparseRun(theta, history, step, state)


def train_stream(args) -> list:
    """Day-by-day streaming training (repro.stream): per day, the last
    --window days are re-planned on the host — overlapped with the
    previous window's device iterations — and OWLQN+ runs --inner-iters
    warm-started steps. --mesh-data/--mesh-model runs every window on
    the sharded path (fixed equal id-range partition). --ckpt saves the
    resumable stream state (Theta + history + day cursor); --resume
    continues from it. Returns the trainer's per-window stats."""
    from repro.core.objective import nll_sparse
    from repro.data import auc as auc_fn
    from repro.data.sparse import sparse_predict
    from repro.stream import DayStream, StreamTrainer

    distributed = args.mesh_data > 0 and args.mesh_model > 0
    if (args.mesh_data > 0) != (args.mesh_model > 0):
        raise SystemExit("--mesh-data and --mesh-model must be set together")
    # np.savez appends .npz to suffix-less paths; normalize up front so
    # the --resume existence probe and the printed path match the file
    ckpt = args.ckpt and (args.ckpt if args.ckpt.endswith(".npz")
                          else args.ckpt + ".npz")
    d, m = args.sparse_features, args.regions
    stream = DayStream(args.days, sessions_per_day=args.sessions,
                       num_features=d, drift=args.drift, seed=args.seed)
    theta0 = jnp.asarray(
        0.01 * np.random.default_rng(args.seed).normal(size=(d, 2 * m)),
        jnp.float32)
    mesh = None
    if distributed:
        assert jax.device_count() >= args.mesh_data * args.mesh_model, (
            f"need {args.mesh_data * args.mesh_model} devices, "
            f"have {jax.device_count()} (set REPRO_DEVICES)")
        mesh = make_debug_mesh(data=args.mesh_data, model=args.mesh_model)
    if tuning_flags_set(args):
        day0 = stream.day(0)
        ku, ka = day0.user_ids.shape[-1], day0.ad_ids.shape[-1]
        apply_tuning_flags(args, batch_k=max(ku, ka))
        if args.tune:
            g, b = day0.user_ids.shape[0], day0.ad_ids.shape[0]
            w = args.window
            tune_job_shapes({(g, ku, d, m), (b, ka, d, m),
                             (g * w, ku, d, m), (b * w, ka, d, m)})
    trainer = StreamTrainer(
        stream, lam=args.lam, beta=args.beta, window=args.window,
        inner_iters=args.inner_iters, history=args.history, mesh=mesh,
        overlap=not args.sync_planner)
    obs.log(f"stream: {args.days} days x {args.sessions} sessions, d={d:,}, "
            f"window={args.window}, {args.inner_iters} inner iters/window, "
            f"history={args.history}, planner="
            f"{'synchronous' if args.sync_planner else 'overlapped'}"
            + (f", mesh data={args.mesh_data} x model={args.mesh_model}"
               if mesh is not None else ""))

    if args.resume and ckpt and os.path.exists(ckpt):
        state = trainer.load(ckpt, theta0)
        obs.log(f"resumed from {ckpt} at day {state.day}")
    else:
        state = trainer.init(theta0)

    last_eval: dict = {}  # scores/labels/ids of the newest held-out day

    def cb(t, ws, st):
        # the structured twin of this line is the trainer's own
        # stream_window record; the held-out eval is the driver's
        msg = (f"day {t:3d}  window={ws.days_in_window}d "
               f"f={ws.fs[-1]:12.2f} alpha={ws.alpha:.3g} "
               f"nnz={ws.nnz:8d} plan={ws.build_seconds * 1e3:6.0f}ms "
               f"step={ws.step_seconds * 1e3:6.0f}ms")
        if t + 1 < stream.num_days:  # held-out NEXT-day quality
            nxt = stream.day(t + 1)
            theta = trainer.theta(st)
            nll = float(nll_sparse(theta, nxt)) / nxt.y.shape[0]
            p = np.asarray(sparse_predict(theta, nxt))
            y = np.asarray(nxt.y)
            a = auc_fn(y, p)
            msg += f"  next-day nll={nll:.4f} auc={a:.4f}"
            obs.log(msg, kind="stream_eval", day=t, next_day_nll=nll,
                    next_day_auc=float(a))
            obs.get_monitor().observe_predictions(p, y)
            if args.drift_ref:
                last_eval.update(scores=p, labels=y, ids=np.concatenate(
                    [np.asarray(nxt.user_ids).ravel(),
                     np.asarray(nxt.ad_ids).ravel()]))
        else:
            obs.log(msg)
        if ckpt:  # every window is a resumable checkpoint
            trainer.save(ckpt, st)

    t0 = time.perf_counter()
    days_left = stream.num_days - state.day
    state, trace = trainer.run(state, callback=cb)
    dt = time.perf_counter() - t0
    ps = trainer.planner_stats
    obs.log(f"trained {days_left} windows in {dt:.1f}s; planner: "
            f"{ps.build_seconds:.2f}s host build, {ps.wait_seconds:.2f}s "
            f"exposed, overlap ratio {ps.overlap_ratio:.2f}")
    if args.drift_ref:
        if not last_eval:
            raise SystemExit(
                "--drift-ref needs at least one held-out next-day eval; "
                "run with --days >= 2 (or resume earlier in the stream)")
        ref = obs.capture_reference(last_eval["scores"], last_eval["labels"],
                                    last_eval["ids"],
                                    num_features=args.sparse_features)
        obs.log(f"drift reference (last held-out day, "
                f"{last_eval['scores'].shape[0]} scores, "
                f"ratio={ref.ratio:.3f}) -> "
                f"{obs.save_drift_reference(args.drift_ref, ref)}")
    if ckpt:
        obs.log(f"stream checkpoint -> {ckpt} (resume with --resume)")
    return trace


def train_dense(args) -> int:
    """Dense-matrix training on the common-feature objective (the
    original small-d path; the default when neither --sparse nor
    --stream is given)."""
    cfg = CTRDataConfig(
        num_user_features=args.user_features, num_ad_features=args.ad_features,
        noise_features=args.noise_features, seed=args.seed,
    )
    train_cf, _ = generate(cfg, args.sessions, seed=1)
    test_cf, _ = generate(cfg, max(args.sessions // 5, 64), seed=2)
    d, m = cfg.num_features, args.regions
    theta0 = jnp.asarray(
        0.01 * np.random.default_rng(args.seed).normal(size=(d, 2 * m)),
        jnp.float32)

    distributed = args.mesh_data > 0 and args.mesh_model > 0
    if distributed:
        assert jax.device_count() >= args.mesh_data * args.mesh_model, (
            f"need {args.mesh_data * args.mesh_model} devices, "
            f"have {jax.device_count()} (set REPRO_DEVICES)")
        mesh = make_debug_mesh(data=args.mesh_data, model=args.mesh_model)
        batch = pad_to_multiple(train_cf, args.mesh_data)
        batch = shard_batch(mesh, jax.tree.map(jnp.asarray, batch),
                            common_feature=True)
        opt = OWLQNPlus(
            lambda t: smooth_loss_and_grad(t, batch, common_feature=True),
            lam=args.lam, beta=args.beta)
        state = shard_state(opt.init(theta0), mesh)
        step = make_distributed_step(opt, mesh)
        obs.log(f"mesh: data={args.mesh_data} x model={args.mesh_model} "
                f"(PS mapping: workers x servers)")
    else:
        batch = jax.tree.map(jnp.asarray, pad_to_multiple(train_cf, 1))
        opt = OWLQNPlus(
            lambda t: smooth_loss_and_grad(t, batch, common_feature=True),
            lam=args.lam, beta=args.beta)
        state = opt.init(theta0)
        step = jax.jit(opt.step)

    test_dense = to_dense_batch(test_cf)
    xs_test = jnp.asarray(test_dense.x)
    tracer = obs.get_tracer()
    for k in range(args.iters):
        t0 = time.perf_counter()
        with tracer.step_span("train/iter", k):
            state, stats = step(state)
        dt = time.perf_counter() - t0
        if k % 5 == 0 or k == args.iters - 1:
            theta_host = jax.device_get(state.theta)
            p = predict_proba(params_from_theta(jnp.asarray(theta_host)), xs_test)
            a = auc(test_dense.y, np.asarray(p))
            st = jax.device_get(stats)
            rec = dict(step=k, f=float(st.f), f_new=float(st.f_new),
                       alpha=float(st.alpha), ls_iters=int(st.ls_iters),
                       grad_norm=float(st.grad_norm), nnz=int(st.nnz),
                       test_auc=float(a), wall_s=dt)
            obs.log(obs.render_train_iter(rec, nnz_width=7),
                    kind="train_iter", **rec)
    if args.ckpt:
        checkpoint.save(args.ckpt, {"theta": state.theta})
        obs.log(f"checkpoint -> {args.ckpt}")
    return 0


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sessions", type=int, default=4000)
    ap.add_argument("--user-features", type=int, default=64)
    ap.add_argument("--ad-features", type=int, default=48)
    ap.add_argument("--noise-features", type=int, default=16)
    ap.add_argument("--regions", type=int, default=12, help="m (Fig. 4)")
    ap.add_argument("--lam", type=float, default=1.0, help="L2,1 weight")
    ap.add_argument("--beta", type=float, default=1.0, help="L1 weight")
    ap.add_argument("--iters", type=int, default=60)
    ap.add_argument("--mesh-data", type=int, default=0, help="0 = single device")
    ap.add_argument("--mesh-model", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sparse", action="store_true",
                    help="train on padded-COO sparse features via the "
                         "fused sparse kernel (the paper's input format)")
    ap.add_argument("--sparse-features", type=int, default=1_000_000,
                    help="d for --sparse mode (feature columns)")
    ap.add_argument("--schema", choices=("session", "criteo39"),
                    default="session",
                    help="--sparse: the batch layout: 'session' (user ids "
                         "shared by a page view's ads) or 'criteo39' (the "
                         "public Criteo schema: 39 one-hot fields per "
                         "impression, d = 998,960, no sessions)")
    ap.add_argument("--impressions", type=int, default=196_608,
                    help="--schema criteo39: impressions in the batch")
    ap.add_argument("--stream", action="store_true",
                    help="streaming day-by-day training on the sparse path "
                         "(repro.stream): sliding-window minibatch OWLQN+ "
                         "with an overlapped host re-planner")
    ap.add_argument("--days", type=int, default=8,
                    help="--stream: days in the synthetic stream")
    ap.add_argument("--window", type=int, default=2,
                    help="--stream: sliding window width (days)")
    ap.add_argument("--inner-iters", type=int, default=5,
                    help="--stream: OWLQN+ iterations per window")
    ap.add_argument("--history", choices=("reset", "carry"), default="reset",
                    help="--stream: L-BFGS history policy at window "
                         "boundaries (Theta always carries)")
    ap.add_argument("--drift", type=float, default=0.02,
                    help="--stream: per-day id-traffic drift fraction")
    ap.add_argument("--sync-planner", action="store_true",
                    help="--stream: disable the overlapped background "
                         "re-planner (synchronous fallback)")
    ap.add_argument("--resume", action="store_true",
                    help="--stream: resume from --ckpt if it exists")
    add_tuning_flags(ap)
    obs.add_flags(ap)
    return ap


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    if tuning_flags_set(args) and not (args.sparse or args.stream):
        raise SystemExit(
            "--block-n/--block-k/--chunk/--tune steer the sparse kernels; "
            "combine them with --sparse or --stream (the dense path has "
            "no tunable block sizes)")
    if args.schema != "session" and (args.stream or not args.sparse):
        raise SystemExit("--schema criteo39 trains on the sparse path; "
                         "combine it with --sparse (not --stream)")
    mode = "stream" if args.stream else "sparse" if args.sparse else "dense"
    if args.drift_ref and mode == "dense":
        raise SystemExit(
            "--drift-ref captures a sparse-id traffic reference; combine "
            "it with --sparse or --stream (the dense path has no feature "
            "ids to histogram)")
    session = obs.configure_from_args(args, driver="repro.launch.train",
                                      mode=mode)
    try:
        if args.stream:
            train_stream(args)
        elif args.sparse:
            train_sparse(args)
        else:
            train_dense(args)
        return 0
    finally:
        session.close()


if __name__ == "__main__":
    raise SystemExit(main())
