"""Driver ``serve_open_loop``: page views offered at a fixed rate.

Set-up follows ``launch.serve``'s deploy path: train the mix's model
(``model.iters`` OWLQN+ iterations of the program on the named training
mix, Theta0 from ``model.seed``, the same model on every run), prune it
with ``serve.compress``, build a ``ScoringEngine`` with the mix's G
buckets and warm exactly the envelopes this window's requests need at
every G bucket. Each of those executables then runs once (a first run
loads the program onto the chip), and ``warm_seconds`` of other requests
pass open loop through their own queue and pump to warm the host path.
The set-up's objects are then collected and frozen out of the garbage
collector's scans, so its pauses in the window come from the window's
own objects.

The window offers every request due in ``--seconds`` open loop: the
generator sleeps until each request is due and submits it through
``RealClockPump`` into a ``MicroBatchQueue`` (the mix's queue settings),
whose full flushes run on the generator's thread and deadline flushes
on the pump's. A request is timed from when it was due to when
``score_batch`` returned its scores to the host; a shed request counts
as failed and its latency runs to the end of the drain. A ``--trace 1``
run offers the same window untraced, whose latency tail it reports,
then a traced slice of the mix's ``trace_seconds`` of other requests,
from which the other per-layer metrics are read. After the windows
every request's scores are compared with the reference's.
"""
from __future__ import annotations

import functools
import gc
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from bench import check, spec
from bench.common import CompileCounter, init_theta, memory_peak_bytes
from bench.program import train_step
from bench.reference import lsplm as ref_model
from bench.reference import train as reference
from bench.roofline import serve_step
from bench.traffic import daystream, pageviews


class TimedEngine:
    """The engine as the queue sees it, stamping when each request's
    scores reach the host and which requests each dispatch carried."""

    def __init__(self, engine):
        self._engine = engine
        self.done: dict[int, float] = {}
        self.dispatches: list = []

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def score_batch(self, requests):
        scores = self._engine.score_batch(requests)
        now = time.perf_counter()
        for r in requests:
            self.done[id(r)] = now
        self.dispatches.append(list(requests))
        return scores


def served_model(ctx):
    """The served model: the program's trained, pruned Theta; and the
    reference's own Theta from the same start on the same window."""
    from repro.serve import compress

    cfg, m = ctx.config, ctx.traffic["model"]
    d, m2 = cfg["num_features"], 2 * cfg["regions"]
    win = daystream.window(spec.mix(m["traffic"]), d)
    theta0 = init_theta(m["seed"], d, m2)
    step, init, to_global = train_step(cfg, win, theta0)
    state = init(theta0)
    for _ in range(m["iters"]):
        state, st = step(state)
        float(st.f_new)
    art = compress(to_global(jax.device_get(state.theta)))
    return art, (cfg, win, np.asarray(theta0), m["iters"])


def offer_open_loop(pump, requests, due):
    """Submit each request when due; returns (t0, submit lateness, tickets)."""
    late = np.zeros(len(requests))
    tickets = []
    t0 = time.perf_counter()
    for i, (r, off) in enumerate(zip(requests, due)):
        target = t0 + off
        wait = target - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        late[i] = time.perf_counter() - target
        tickets.append(pump.submit(r))
    return t0, late, tickets


def warm_up(engine, requests, warm, warm_due, queue_config: dict) -> None:
    """Compile every envelope of ``requests`` at every G bucket, run each
    executable once, then offer ``warm`` open loop through a queue."""
    from repro.serve import MicroBatchQueue, QueueConfig, RealClockPump

    first = {}
    for r in requests:
        first.setdefault(engine.envelope(r), r)
    engine.warm(sorted(first), batch_sizes=engine.g_buckets)
    for env in sorted(first):
        for g in engine.g_buckets:
            engine.score_batch([first[env]] * g)
    with RealClockPump(MicroBatchQueue(engine, QueueConfig(**queue_config))) as pump:
        offer_open_loop(pump, warm, warm_due)


def offer_window(engine, queue_config: dict, requests, due, on_start=None,
                 capture=None):
    """Offer ``requests`` open loop through a queue and pump of their own;
    ``on_start(t)`` is called as the first is offered, and ``capture``
    (``tracing.capture`` bound to the run) traces the window, drain
    included, so that every dispatch of it is in the trace."""
    from repro.serve import MicroBatchQueue, QueueConfig, RealClockPump

    timed = TimedEngine(engine)
    queue = MicroBatchQueue(timed, QueueConfig(**queue_config))
    before = engine.stats.as_dict()
    red = None
    gc.collect()
    gc.freeze()
    with RealClockPump(queue) as pump:
        if on_start is not None:
            on_start(time.perf_counter())
        if capture is not None:
            red, (t0, late, tickets) = capture(
                lambda: (offer_open_loop(pump, requests, due), pump.stop())[0])
        else:
            t0, late, tickets = offer_open_loop(pump, requests, due)
    t_end = time.perf_counter()
    gc.unfreeze()
    after = engine.stats.as_dict()
    comps = {c.ticket: c for c in queue.completions}
    served = [i for i, t in enumerate(tickets) if t is not None and t in comps]
    return SimpleNamespace(
        requests=requests, due_at=t0 + np.asarray(due), t0=t0, t_end=t_end,
        late=late, done=timed.done, dispatches=timed.dispatches, served=served,
        reduced=red, scores=[comps[tickets[i]].scores for i in served],
        queue_delay_s=[comps[tickets[i]].started - comps[tickets[i]].arrival
                       for i in served],
        requests_scored=after["requests"] - before["requests"],
        slots=after["slots"] - before["slots"])


def latencies(requests, due_at, done: dict, t_end: float) -> np.ndarray:
    """Seconds from when each request was due (``due_at``, perf_counter
    seconds) to when its scores were on the host (``done`` by request id;
    a request never served counts to ``t_end``)."""
    return np.array([done.get(id(r), t_end) - at for r, at in zip(requests, due_at)])


def end_to_end(requests, lat: np.ndarray, done: dict, served: list, t0: float,
               t_end: float) -> dict:
    """The median latency over every request due in the window (``lat``)
    and the candidates scored over the whole window."""
    cands = sum(requests[i].ad_ids.shape[0] for i in served)
    last = max((done[id(requests[i])] for i in served), default=t_end)
    p50, p99 = np.percentile(lat, (50, 99))
    print(f"[bench] latency p50 {1e3 * p50:.3f} ms, p99 {1e3 * p99:.3f} ms "
          f"over {len(lat)} requests", file=sys.stderr)
    return {"serve_p50_ms": 1e3 * float(p50),
            "serve_candidates_per_s": cands / (last - t0)}


def reference_scores(theta, requests, block: int = 4096, dtype=jnp.float32):
    """Eq. 2 scores of every request with the reference Theta, padded
    into blocks of requests on the device (``dtype`` bfloat16: the
    control)."""
    ku = max(r.user_ids.shape[0] for r in requests)
    ka = max(r.ad_ids.shape[1] for r in requests)
    n = max(r.ad_ids.shape[0] for r in requests)
    d = theta.shape[0]
    theta = jnp.asarray(theta)

    @jax.jit
    def score(theta, ui, uv, ai, av):
        g = ui.shape[0]
        sid = jnp.repeat(jnp.arange(g), n)
        z = ref_model.region_logits(theta, ui, uv, ai, av, sid, dtype)
        return ref_model.probability(z).astype(jnp.float32).reshape(g, n)

    out = []
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        for s in range(0, len(requests), block):
            chunk = requests[s:s + block]
            g = block
            ui = np.full((g, ku), d, np.int32)
            uv = np.zeros((g, ku), np.float32)
            ai = np.full((g * n, ka), d, np.int32)
            av = np.zeros((g * n, ka), np.float32)
            for j, r in enumerate(chunk):
                ui[j, :r.user_ids.shape[0]] = r.user_ids
                uv[j, :r.user_vals.shape[0]] = r.user_vals
                nn, kk = r.ad_ids.shape
                ai[j * n:j * n + nn, :kk] = r.ad_ids
                av[j * n:j * n + nn, :kk] = r.ad_vals
            p = np.asarray(score(theta, ui, uv, ai, av))
            out += [p[j, :r.ad_ids.shape[0]] for j, r in enumerate(chunk)]
    return out


def run(ctx) -> dict:
    from repro import obs
    from repro.serve import BundleRequest, ScoringEngine

    cfg, mix = ctx.config, ctx.traffic
    d, m2 = cfg["num_features"], 2 * cfg["regions"]

    def page_views(seconds, seed):
        due, sizes = pageviews.schedule(mix, seconds, seed)
        return due, [BundleRequest(*pv)
                     for pv in pageviews.requests(mix, d, sizes, seed)]

    due, requests = page_views(ctx.seconds, ctx.seed)
    s_due, sliced = page_views(mix["trace_seconds"], ctx.seed + 2) if ctx.trace \
        else ([], [])
    w_due, warm = page_views(mix["warm_seconds"], ctx.seed + 1)

    counter = CompileCounter()
    with counter:
        art, ref_inputs = served_model(ctx)
        engine = ScoringEngine(art, g_buckets=tuple(mix["g_buckets"]))
        warm_up(engine, requests + sliced, warm, w_due, mix["queue"])
    ctx.log(f"set-up: {counter.compiles} compiles ({counter.cache_hits} from "
            f"the persistent cache), {art.num_alive:,} of {d:,} rows served")
    keep = np.asarray(art.remap) != art.pad_id

    counter.compiles = counter.cache_hits = 0
    with counter:
        win = offer_window(engine, mix["queue"], requests, due,
                           on_start=ctx.mark_setup_end)
        windows = [win]
        if ctx.trace:
            from bench import tracing

            prev_tracer = obs.set_tracer(obs.Tracer(enabled=True, annotate=True))
            try:
                windows.append(offer_window(
                    engine, mix["queue"], sliced, s_due,
                    capture=functools.partial(tracing.capture, ctx)))
            finally:
                obs.set_tracer(prev_tracer)
    ctx.log(f"window: {len(requests)} requests over {win.t_end - win.t0:.3f} s, "
            f"{counter.compiles} compiles inside the windows")

    lat = latencies(requests, win.due_at, win.done, win.t_end)
    sl = windows[-1]
    attempted = sum(len(w.requests) for w in windows)
    out = {
        "metrics": end_to_end(requests, lat, win.done, win.served, win.t0, win.t_end),
        "attempted": attempted,
        "failed": attempted - sum(len(w.served) for w in windows),
        "compiles_in_window": counter.compiles,
        "counters": {
            "window_latency_s": lat.tolist(),
            "requests": sl.requests_scored, "slots": sl.slots,
            "queue_delay_s": sl.queue_delay_s, "gen_late_s": sl.late.tolist()},
        "reduced": sl.reduced,
        "work": {"dispatches": [serve_step.dispatch(reqs, d, m2, keep)
                                for reqs in sl.dispatches]} if ctx.trace else None,
        "memory_peak_bytes": memory_peak_bytes(ctx.devices),
    }

    # free the program's state before the reference runs on the chip
    served = [w.requests[i] for w in windows for i in w.served]
    scores = [p for w in windows for p in w.scores]
    del engine, art, win, sl, windows
    gc.collect()
    cfg_, train_win, theta0, iters = ref_inputs
    _, _, theta_ref = reference.run(cfg_, train_win, theta0, iters)
    p_ref = reference_scores(theta_ref, served)
    gap = max((float(np.max(np.abs(p - r))) for p, r in zip(scores, p_ref)),
              default=float("inf"))
    out["checks"] = [check.Check("score_gap", gap, ctx.limits["score_gap"])]
    return out
