"""Serving driver: train-or-load -> prune -> engine replay (the §4
deploy path as one command).

Quick smoke (train a small sparse model, prune it, serve ragged traffic):
  PYTHONPATH=src python -m repro.launch.serve --train-iters 10 \
      --sparse-features 20000 --sessions 256 --regions 4 \
      --lam 0.05 --beta 0.05 --requests 256 --artifact /tmp/lsplm_art.npz

Serve an existing training checkpoint (``repro.launch.train --ckpt``,
which saves ``{"theta": ...}``):
  PYTHONPATH=src python -m repro.launch.serve --ckpt /tmp/lsplm.npz \
      --requests 512

The driver prints the prune ledger (rows alive, MiB shipped), proves
pruned-vs-full score parity on a probe batch, then replays ragged
synthetic bundles through the :class:`~repro.serve.engine.ScoringEngine`
— one request per dispatch AND stacked same-envelope G>1 dispatches
(parity-asserted) — and reports the latency/throughput ledger,
asserting the steady state (everything after the warmup pass) triggered
ZERO recompiles.

``--int8`` additionally quantises the artifact (int8 rows + per-row
fp32 scale), round-trips it through save/load, and serves THAT
INT8-NATIVE — the engine compiles its own dtype-keyed executables over
the scale-fused int8 gather (fp32 rows never materialise) — printing
the size win and the bounded probability drift vs fp32.

``--load-qps`` switches on the traffic mode: open-loop Poisson arrivals
at the given rate(s) through the micro-batching queue (deadline-aware
flushing, admission control), reporting p50/p99 latency, achieved QPS
and candidates/sec per offered rate:
  PYTHONPATH=src python -m repro.launch.serve --train-iters 4 \
      --sparse-features 5000 --sessions 96 --regions 2 --requests 128 \
      --int8 --load-qps 500,2000 --max-batch 8 --max-delay-us 3000

``--coalesce`` merges several due per-envelope groups into single
dispatches (bitwise-identical scores, fewer device rounds — the flush
mix line shows how many rounds coalesced); ``--real-clock`` additionally
replays each rate through the wall-clock :class:`RealClockPump` front
door — Poisson-paced REAL sleeps, the pump's timer thread firing the
deadline flushes — and asserts the deterministic drain served every
accepted request.
"""
import argparse
import time
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.io import checkpoint
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.tuning import (
    add_tuning_flags,
    apply_tuning_flags,
    tune_job_shapes,
)
from repro.serve import (
    MicroBatchQueue,
    QueueConfig,
    RealClockPump,
    ScoringEngine,
    as_model,
    compress,
    load_artifact,
    poisson_arrivals,
    quantize,
    replay_open_loop,
    save_artifact,
    score_sparse,
    synthetic_requests,
)


def _trained_theta(args) -> jnp.ndarray:
    """--ckpt loads a saved Theta; otherwise train a small sparse model
    (same path as ``repro.launch.train --sparse``) so the artifact has
    REAL OWLQN+ sparsity, not a synthetic mask."""
    if args.ckpt:
        data = checkpoint.load_nested(args.ckpt)
        if "theta" not in data:
            raise SystemExit(f"--ckpt {args.ckpt!r} has no 'theta' entry")
        theta = jnp.asarray(data["theta"])
        obs.log(f"loaded theta {theta.shape} from {args.ckpt}")
        return theta

    from repro.core.objective import smooth_loss_and_grad
    from repro.data.sparse import generate_sparse
    from repro.optim import OWLQNPlus

    d, m = args.sparse_features, args.regions
    train = generate_sparse(
        num_features=d, num_user_features_range=(max(1, int(0.6 * d)), d),
        sessions=args.sessions, seed=args.seed)
    theta0 = jnp.asarray(
        0.01 * np.random.default_rng(args.seed).normal(size=(d, 2 * m)),
        jnp.float32)
    opt = OWLQNPlus(lambda t: smooth_loss_and_grad(t, train),
                    lam=args.lam, beta=args.beta)
    t0 = time.perf_counter()
    theta, trace = opt.run(theta0, max_iters=args.train_iters)
    obs.log(f"trained {args.train_iters} OWLQN+ iters on d={d:,} in "
            f"{time.perf_counter() - t0:.1f}s (f={float(trace[-1].f_new):.2f}, "
            f"nnz={int(trace[-1].nnz):,})")
    return theta


class ServeRun(NamedTuple):
    """What :func:`serve` leaves behind: the warmed engine, the replayed
    requests and the engine's per-request scores (single dispatches)."""

    engine: ScoringEngine
    requests: list
    scores: list


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt", default=None,
                    help="training checkpoint with a 'theta' entry; "
                         "omitted -> train a small sparse model first")
    ap.add_argument("--artifact", default=None,
                    help="write the pruned serving artifact here")
    ap.add_argument("--train-iters", type=int, default=10)
    ap.add_argument("--sparse-features", type=int, default=20_000)
    ap.add_argument("--sessions", type=int, default=256)
    ap.add_argument("--regions", type=int, default=4)
    ap.add_argument("--lam", type=float, default=0.05)
    ap.add_argument("--beta", type=float, default=0.05)
    ap.add_argument("--requests", type=int, default=256,
                    help="ragged synthetic bundles to replay")
    ap.add_argument("--int8", action="store_true",
                    help="quantise the artifact (int8 rows + fp32 row "
                         "scales), round-trip through save/load, serve that")
    ap.add_argument("--load-qps", default=None,
                    help="traffic mode: comma-separated offered QPS rates "
                         "for the open-loop Poisson replay through the "
                         "micro-batching queue")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="queue full-flush size (requests per dispatch)")
    ap.add_argument("--max-delay-us", type=float, default=3_000.0,
                    help="queue deadline: max micro-batching delay")
    ap.add_argument("--max-pending", type=int, default=256,
                    help="admission control: shed load past this backlog")
    ap.add_argument("--coalesce", action="store_true",
                    help="merge several due per-envelope groups into one "
                         "dispatch at the widest due envelope (bitwise-"
                         "identical scores, fewer device rounds)")
    ap.add_argument("--real-clock", action="store_true",
                    help="also replay each --load-qps rate through the "
                         "wall-clock RealClockPump front door (real "
                         "Poisson-paced sleeps, timer-thread flushes)")
    ap.add_argument("--seed", type=int, default=0)
    add_tuning_flags(ap)
    obs.add_flags(ap)
    return ap


def main() -> int:
    args = build_parser().parse_args()
    enable_compile_cache()
    apply_tuning_flags(args)  # value check up front; geometry check below
    if args.drift_ref and not args.monitor:
        raise SystemExit(
            "--drift-ref arms the health monitor's drift detectors; "
            "combine it with --monitor")
    if args.real_clock and not args.load_qps:
        raise SystemExit(
            "--real-clock paces the queue with wall-time Poisson arrivals; "
            "combine it with --load-qps")

    session = obs.configure_from_args(args, driver="repro.launch.serve")
    try:
        serve(args)
        return 0
    finally:
        session.close()


def _real_clock_smoke(engine, requests, *, qps: float, config: QueueConfig,
                      seed: int) -> None:
    """Wall-clock front door: Poisson-paced REAL sleeps feed a
    :class:`RealClockPump`, whose timer thread fires the deadline
    flushes; ``stop()`` joins then drains, so afterwards every accepted
    request must have a completion (the determinism being smoked)."""
    queue = MicroBatchQueue(engine, config)
    arrivals = poisson_arrivals(len(requests), qps, seed)
    gaps = np.diff(np.concatenate([[0.0], arrivals]))
    before = engine.stats.compiles
    t0 = time.perf_counter()
    accepted = 0
    with RealClockPump(queue) as pump:
        for gap, req in zip(gaps, requests):
            time.sleep(gap)
            if pump.submit(req) is not None:
                accepted += 1
    wall = time.perf_counter() - t0
    comps = queue.completions
    assert len(comps) == accepted, \
        f"pump drained {len(comps)} of {accepted} accepted requests"
    assert engine.stats.compiles == before, "real-clock replay recompiled"
    lat = np.array([c.latency_us for c in comps]) if comps else np.zeros(1)
    fl = queue.stats.flushes
    obs.log(f"real-clock {qps:,.0f} qps: {accepted}/{len(requests)} accepted,"
            f" all drained in {wall:.2f}s wall; "
            f"p50 {np.percentile(lat, 50):,.0f} us, "
            f"p99 {np.percentile(lat, 99):,.0f} us "
            f"({fl['full']} full / {fl['deadline']} deadline / "
            f"{fl['drain']} drain / {fl['coalesced']} coalesced)")


def serve(args, theta=None) -> ServeRun:
    """Prune ``theta`` (default: ``--ckpt`` or a fresh small training run),
    optionally quantise it, warm an engine on the traffic's envelopes and
    replay the requests, asserting zero steady-state recompiles."""
    if theta is None:
        theta = _trained_theta(args)
    d = theta.shape[0]

    art = compress(theta)
    full_mb = theta.size * 4 / 2**20
    art_mb = (art.theta.size + art.remap.size + art.alive_ids.size) * 4 / 2**20
    obs.log(f"pruned: {art.num_alive:,}/{d:,} rows alive "
            f"({art.compression:.2%}); ship {art_mb:.2f} MiB vs "
            f"{full_mb:.2f} MiB full")
    if args.artifact:
        obs.log(f"artifact -> {save_artifact(args.artifact, art)}")

    # pruned-vs-full parity probe (bit-identical on the sparse path)
    rng = np.random.default_rng(args.seed + 7)
    ids = jnp.asarray(rng.integers(0, d, (512, 16)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(512, 16)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(score_sparse(as_model(theta), ids, vals)),
        np.asarray(score_sparse(art, ids, vals)))
    obs.log("parity: pruned scoring bit-identical to full Theta (512 probes)")

    model = art
    if args.int8:
        import tempfile

        q = quantize(art)
        with tempfile.TemporaryDirectory() as tmp:
            model = load_artifact(save_artifact(f"{tmp}/art_int8", q))
        dp = float(np.abs(
            np.asarray(score_sparse(model, ids, vals))
            - np.asarray(score_sparse(art, ids, vals))).max())
        assert dp <= 1e-2, f"int8 moved p by {dp:.2e} (> 1e-2)"
        obs.log(f"int8-native: rows payload "
                f"{q.codes.size + q.scales.size * 4:,} B "
                f"vs {art.theta.size * 4:,} B fp32 "
                f"({art.theta.size * 4 / (q.codes.size + q.scales.size * 4):.1f}x"
                f" smaller rows AND row-gather DMA bytes); round-tripped "
                f"save/load; serving the codes directly (scale fused into "
                f"the gather); max |dp| = {dp:.1e} vs fp32")

    engine = ScoringEngine(model)
    mon = obs.get_monitor()
    if args.drift_ref:
        ref = obs.load_drift_reference(args.drift_ref)
        mon.arm_drift(ref)
        obs.log(f"monitor armed from {args.drift_ref}: "
                f"{ref.num_bins} score bins, top-{ref.top_ids.shape[0]} id "
                f"traffic, reference calibration ratio {ref.ratio:.3f}")
    requests = synthetic_requests(args.requests, num_features=d,
                                  seed=args.seed + 1)
    # deploy-time warmup: compile the traffic's bucket set (all batch
    # sizes the G>1 path can round onto) up front, then the whole replay
    # is steady state
    envelopes = {engine.envelope(r) for r in requests}
    # the engine pads K/N up to its buckets before the kernels run, so
    # the geometry the knobs must fit is the PADDED envelope set
    kmax = max(max(ku, ka) for ku, ka, _n in envelopes)
    nmax = engine.max_batch * max(n for _ku, _ka, n in envelopes)
    apply_tuning_flags(args, batch_n=nmax, batch_k=kmax)
    if args.tune:
        m = theta.shape[1] // 2
        tune_job_shapes(
            {(g * n, ka, d, m) for _ku, ka, n in envelopes
             for g in (1, engine.max_batch)}
            | {(g, ku, d, m) for ku, _ka, _n in envelopes
               for g in (1, engine.max_batch)})
    if args.coalesce:
        # coalesced flushes dispatch at the elementwise max of merged
        # envelopes: warm the closure so they stay recompile-free too
        from repro.serve import envelope_closure

        envelopes = envelope_closure(envelopes)
    engine.warm(envelopes, batch_sizes=engine.g_buckets)
    warm_compiles = engine.stats.compiles
    single = engine.score_many(requests)
    batched = engine.score_batch(requests)
    for p_one, p_many in zip(single, batched):
        np.testing.assert_array_equal(p_one, p_many)
    s = engine.stats
    assert s.compiles == warm_compiles, \
        f"steady state recompiled: {s.compiles} != {warm_compiles}"
    obs.log(f"engine: {s.requests} requests / {s.candidates} candidates "
            f"over {len(s.bucket_hits)} buckets; {s.compiles} compiles "
            f"({s.compile_seconds:.2f}s, all in warmup), steady state "
            f"0 recompiles; single-vs-batched scores bit-identical; "
            f"{s.latency_us:.0f} us/request, {s.candidates_per_sec:,.0f} ads/s, "
            f"batched occupancy {s.occupancy:.2f}")

    if args.load_qps:
        cfg = QueueConfig(max_batch=args.max_batch,
                          max_delay_us=args.max_delay_us,
                          max_pending=args.max_pending,
                          coalesce=args.coalesce)
        for qps in (float(x) for x in args.load_qps.split(",") if x.strip()):
            before = engine.stats.compiles
            rep = replay_open_loop(engine, requests, qps=qps, config=cfg,
                                   seed=args.seed + 2)
            assert engine.stats.compiles == before, \
                "queue replay recompiled in steady state"
            obs.log(f"load {qps:,.0f} qps offered: "
                    f"p50 {rep['latency_p50_us']:,.0f} us, "
                    f"p99 {rep['latency_p99_us']:,.0f} us, "
                    f"achieved {rep['achieved_qps']:,.0f} qps, "
                    f"{rep['candidates_per_sec']:,.0f} ads/s, "
                    f"occupancy {rep['occupancy']:.2f}, "
                    f"{rep['dispatches']} dispatches "
                    f"({rep['flushes']['full']} full / "
                    f"{rep['flushes']['deadline']} deadline / "
                    f"{rep['flushes']['drain']} drain / "
                    f"{rep['flushes']['coalesced']} coalesced"
                    + (f" merging {rep['coalesced_groups']} groups"
                       if rep["flushes"]["coalesced"] else "")
                    + f"), rejected {rep['rejected']}")
            if args.real_clock:
                _real_clock_smoke(engine, requests, qps=qps, config=cfg,
                                  seed=args.seed + 3)

    if mon.enabled:
        mon.evaluate()  # settle the last partial eval_every window
        summ = mon.summary()
        active = ", ".join(summ["active"]) if summ["active"] else "none"
        drift = {k: v for k, v in summ["signals"].items()
                 if k.startswith(("drift.", "calib."))}
        obs.log(f"monitor: {summ['alerts']} alert state changes, "
                f"active: {active}"
                + (f"; drift signals: "
                   + ", ".join(f"{k}={v:.4f}" for k, v in sorted(drift.items()))
                   if drift else ""))
    return ServeRun(engine, requests, single)


if __name__ == "__main__":
    raise SystemExit(main())
