"""Micro-batching queue + open-loop load generator: flush triggers
(full / deadline / drain / coalesced), admission control, the
virtual-clock server model (sealed batches, serial service, monotonic
completions), score parity with direct engine calls (coalesced rounds
bitwise vs per-envelope), the wall-clock pump, queue-derived g_buckets,
Poisson arrival statistics, the replay report's steady-state
zero-recompile guarantee, the pump's measured timeline and the queue's
spans."""
import contextlib
import time

import numpy as np
import jax.numpy as jnp
import pytest

from repro import obs
from repro.serve import (
    MicroBatchQueue,
    QueueConfig,
    RealClockPump,
    ScoringEngine,
    compress,
    derive_g_buckets,
    poisson_arrivals,
    replay_open_loop,
    synthetic_requests,
)

D, M = 500, 2


@pytest.fixture(scope="module")
def engine():
    rng = np.random.default_rng(0)
    th = rng.normal(size=(D, 2 * M)).astype(np.float32) * 0.3
    th[rng.random(D) >= 0.2] = 0.0
    return ScoringEngine(compress(jnp.asarray(th)))


def _uniform_requests(num, seed=1, ku=6, ka=4, n=3):
    """Same-envelope traffic (one group in the queue)."""
    return synthetic_requests(num, num_features=D, k_user=(ku, ku),
                              k_ad=(ka, ka), n_ads=(n, n), seed=seed)


# --------------------------------------------------------- flush triggers
def test_full_flush_at_max_batch(engine):
    q = MicroBatchQueue(engine, QueueConfig(max_batch=3, max_delay_us=1e6))
    reqs = _uniform_requests(3)
    assert q.submit(reqs[0], 0.0) == 0
    assert q.submit(reqs[1], 0.0) == 1
    assert q.pending == 2 and not q.completions
    assert q.submit(reqs[2], 0.0) == 2  # hits max_batch -> flushes now
    assert q.pending == 0
    assert len(q.completions) == 3
    assert all(c.reason == "full" for c in q.completions)
    assert q.stats.flushes == {"full": 1, "deadline": 0, "drain": 0,
                               "coalesced": 0}
    assert q.stats.flush_sizes == {3: 1}


def test_deadline_flush(engine):
    q = MicroBatchQueue(engine, QueueConfig(max_batch=8, max_delay_us=1000.0))
    req = _uniform_requests(1)[0]
    q.submit(req, 0.0)
    assert q.next_deadline() == pytest.approx(1e-3)
    assert q.flush_due(0.5e-3) == []  # not due yet
    done = q.flush_due(2e-3)
    assert [c.reason for c in done] == ["deadline"]
    # the batch seals and starts AT its deadline, not at poll time
    assert done[0].started == pytest.approx(1e-3)
    assert done[0].completed > done[0].started  # real service time
    assert q.next_deadline() is None


def test_flush_due_handles_multiple_groups_in_deadline_order(engine):
    q = MicroBatchQueue(engine, QueueConfig(max_batch=8, max_delay_us=1000.0))
    small = _uniform_requests(1, ku=4)[0]
    big = _uniform_requests(1, ku=20, seed=2)[0]
    q.submit(small, 0.0)
    q.submit(big, 0.4e-3)  # different envelope -> its own group
    done = q.flush_due(5e-3)
    assert len(done) == 2
    assert done[0].arrival < done[1].arrival  # oldest deadline first
    # serial server: the second flush cannot start before the first ends
    assert done[1].started >= done[0].completed


def test_admission_control_sheds_load(engine):
    q = MicroBatchQueue(engine, QueueConfig(max_batch=8, max_delay_us=1e6,
                                            max_pending=2))
    reqs = _uniform_requests(4)
    assert q.submit(reqs[0], 0.0) is not None
    assert q.submit(reqs[1], 0.0) is not None
    assert q.submit(reqs[2], 0.0) is None  # backlog full -> shed
    assert q.stats.rejected == 1 and q.stats.accepted == 2
    q.drain(1.0)
    assert q.submit(reqs[3], 2.0) is not None  # space again after flush


def test_drain_flushes_everything(engine):
    q = MicroBatchQueue(engine, QueueConfig(max_batch=8, max_delay_us=1e6))
    q.submit(_uniform_requests(1, ku=4)[0], 0.0)
    q.submit(_uniform_requests(1, ku=20, seed=2)[0], 0.1)
    done = q.drain(0.2)
    assert len(done) == 2 and q.pending == 0
    assert all(c.reason == "drain" for c in done)


def test_queue_rejects_bad_config(engine):
    with pytest.raises(ValueError):
        MicroBatchQueue(engine, QueueConfig(max_batch=0))


# ----------------------------------------------------------- score parity
def test_queue_scores_match_direct_engine(engine):
    """Tickets map completions back to submissions and each completion
    carries exactly the scores a direct engine call produces."""
    reqs = synthetic_requests(17, num_features=D, seed=3)
    q = MicroBatchQueue(engine, QueueConfig(max_batch=4, max_delay_us=500.0))
    tickets = {}
    for i, r in enumerate(reqs):
        t = float(i) * 1e-4
        q.flush_due(t)
        tickets[q.submit(r, t)] = i
    q.drain(len(reqs) * 1e-4)
    assert len(q.completions) == len(reqs)
    fresh = ScoringEngine(engine._model)
    for c in q.completions:
        r = reqs[tickets[c.ticket]]
        np.testing.assert_array_equal(c.scores, fresh.score(r))
        assert c.completed >= c.started >= c.arrival
        assert c.latency_us > 0


# ------------------------------------------------------- coalesced flush
def _mixed_envelope_run(eng, reqs, arrivals, *, coalesce, max_batch=8):
    q = MicroBatchQueue(eng, QueueConfig(max_batch=max_batch,
                                         max_delay_us=2000.0,
                                         coalesce=coalesce))
    for t, r in zip(arrivals, reqs):
        q.flush_due(t)
        q.submit(r, t)
    q.flush_due(arrivals[-1] + 1.0)
    q.drain(arrivals[-1] + 1.0)
    return q


def test_coalesced_dispatch_bitwise_matches_per_envelope(engine):
    """Same arrivals, coalesce on vs off: every ticket's scores are
    BITWISE identical (widening to the max due envelope only adds pad
    slots) and coalescing strictly reduces device rounds."""
    reqs = synthetic_requests(24, num_features=D, seed=11)
    arrivals = poisson_arrivals(len(reqs), qps=500.0, seed=12)
    q_off = _mixed_envelope_run(ScoringEngine(engine._model), reqs,
                                arrivals, coalesce=False)
    q_on = _mixed_envelope_run(ScoringEngine(engine._model), reqs,
                               arrivals, coalesce=True)
    off = {c.ticket: c.scores for c in q_off.completions}
    on = {c.ticket: c.scores for c in q_on.completions}
    assert off.keys() == on.keys() and len(off) == len(reqs)
    for t in off:
        np.testing.assert_array_equal(off[t], on[t])
    assert q_on.stats.flushes["coalesced"] > 0
    assert sum(q_on.stats.flushes.values()) < sum(q_off.stats.flushes.values())
    # every coalesced round merged >= 2 groups
    assert q_on.stats.coalesced_groups >= 2 * q_on.stats.flushes["coalesced"]
    assert all(c.reason in ("full", "deadline", "drain", "coalesced")
               for c in q_on.completions)


def test_coalesced_flush_respects_max_batch(engine):
    """Groups merge only while the combined round fits max_batch; the
    overflow group flushes on its own deadline instead."""
    q = MicroBatchQueue(engine, QueueConfig(max_batch=3, max_delay_us=1000.0,
                                            coalesce=True))
    for r in _uniform_requests(2, ku=4, seed=21):
        q.submit(r, 0.0)
    for r in _uniform_requests(2, ku=20, seed=22):
        q.submit(r, 0.0)
    done = q.flush_due(1.0)
    assert len(done) == 4 and q.pending == 0
    sizes = [len({c.started for c in done if c.reason == r})
             for r in ("coalesced", "deadline")]
    # one coalesced round couldn't fit both 2-request groups (2+2 > 3):
    # the first group went out alone as a deadline flush, leaving one
    # group -> also a plain deadline flush (coalescing needs >= 2 due)
    assert q.stats.flushes["coalesced"] == 0 and sizes[1] == 2
    # with room for both, one round serves all four
    q2 = MicroBatchQueue(engine, QueueConfig(max_batch=4, max_delay_us=1000.0,
                                             coalesce=True))
    for r in _uniform_requests(2, ku=4, seed=21):
        q2.submit(r, 0.0)
    for r in _uniform_requests(2, ku=20, seed=22):
        q2.submit(r, 0.0)
    done2 = q2.flush_due(1.0)
    assert len(done2) == 4
    assert q2.stats.flushes["coalesced"] == 1
    assert q2.stats.coalesced_groups == 2
    assert len({c.started for c in done2}) == 1  # one device round


def test_coalesce_off_by_default(engine):
    assert QueueConfig().coalesce is False


# ----------------------------------------------------------- wall clock
def test_real_clock_pump_serves_and_drains_deterministically(engine):
    """The pump's timer thread fires deadline flushes on wall time and
    stop() joins-then-drains: afterwards every accepted request has a
    completion with direct-engine scores, whatever the thread timing."""
    reqs = synthetic_requests(10, num_features=D, seed=31)
    eng = ScoringEngine(engine._model)
    eng.warm({eng.envelope(r) for r in reqs}, batch_sizes=eng.g_buckets)
    q = MicroBatchQueue(eng, QueueConfig(max_batch=4, max_delay_us=3000.0))
    with RealClockPump(q) as pump:
        tickets = [pump.submit(r) for r in reqs]
    assert all(t is not None for t in tickets)
    comps = {c.ticket: c for c in q.completions}
    assert sorted(comps) == sorted(tickets)
    fresh = ScoringEngine(engine._model)
    for t, r in zip(tickets, reqs):
        np.testing.assert_array_equal(comps[t].scores, fresh.score(r))
    assert pump._thread is None  # joined
    assert pump.stop() == []  # idempotent, nothing left to drain


def test_real_clock_pump_deadline_fires_without_further_submits(engine):
    """A lone queued request must flush from the timer thread alone."""
    req = _uniform_requests(1, seed=41)[0]
    eng = ScoringEngine(engine._model)
    eng.warm({eng.envelope(req)}, batch_sizes=eng.g_buckets)
    q = MicroBatchQueue(eng, QueueConfig(max_batch=8, max_delay_us=2000.0))
    pump = RealClockPump(q).start()
    try:
        pump.submit(req)
        deadline = 2e-3
        for _ in range(200):  # ~2s budget for the 2ms deadline
            if pump.completions():
                break
            time.sleep(0.01)
        comps = pump.completions()
        assert len(comps) == 1 and comps[0].reason == "deadline"
        assert comps[0].completed - comps[0].arrival >= deadline
    finally:
        pump.stop()
    with pytest.raises(RuntimeError):
        RealClockPump(q).start().start()


# ------------------------------------------------- g_buckets autoscaling
def test_derive_g_buckets_from_flush_mix():
    # pow2 rounding, {1} always present, top edge covers the max size
    assert derive_g_buckets({1: 3, 3: 5, 7: 50}) == (1, 4, 8)
    assert derive_g_buckets({2: 10}) == (1, 2)
    # cap keeps the most frequent edges + the top
    got = derive_g_buckets({1: 9, 2: 8, 3: 7, 5: 6, 9: 5, 17: 1},
                           max_buckets=4)
    assert got[0] == 1 and got[-1] == 32 and len(got) == 4
    assert 2 in got  # most frequent non-forced edge survives
    # no observations -> builtin default
    from repro.serve.engine import DEFAULT_G_BUCKETS
    assert derive_g_buckets({}) == DEFAULT_G_BUCKETS
    with pytest.raises(TypeError):
        derive_g_buckets([(1, 2)])


def test_derive_g_buckets_accepts_queue_stats_and_warns(engine, capsys):
    q = MicroBatchQueue(engine, QueueConfig(max_batch=3, max_delay_us=1e6))
    for r in _uniform_requests(6, seed=51):
        q.submit(r, 0.0)
    q.drain(0.0)
    assert q.stats.flush_sizes == {3: 2}
    assert derive_g_buckets(q.stats) == (1, 4)
    assert "saturate" in capsys.readouterr().out  # all flushes at the top
    # an unsaturated mix stays quiet
    derive_g_buckets({1: 99, 8: 1})
    assert "saturate" not in capsys.readouterr().out


# ------------------------------------------------------------ arrivals
def test_poisson_arrivals_statistics():
    a = poisson_arrivals(4000, qps=1000.0, seed=0)
    assert a.shape == (4000,)
    assert (np.diff(a) > 0).all()  # strictly increasing
    gaps = np.diff(np.concatenate([[0.0], a]))
    assert np.isclose(gaps.mean(), 1e-3, rtol=0.1)  # mean gap ~ 1/qps
    np.testing.assert_array_equal(a, poisson_arrivals(4000, 1000.0, seed=0))
    assert not np.array_equal(a, poisson_arrivals(4000, 1000.0, seed=1))
    with pytest.raises(ValueError):
        poisson_arrivals(10, qps=0.0)


# ---------------------------------------------------------- open loop
def test_replay_open_loop_report_and_steady_state(engine):
    reqs = synthetic_requests(48, num_features=D, seed=5)
    eng = ScoringEngine(engine._model)
    eng.warm({eng.envelope(r) for r in reqs}, batch_sizes=eng.g_buckets)
    warm = eng.stats.compiles
    rep = replay_open_loop(eng, reqs, qps=3000.0,
                           config=QueueConfig(max_batch=8,
                                              max_delay_us=2000.0), seed=6)
    assert eng.stats.compiles == warm, "load replay recompiled"
    assert rep["requests"] == 48
    assert rep["served"] + rep["rejected"] == 48
    assert rep["served"] > 0
    assert 0 < rep["latency_p50_us"] <= rep["latency_p99_us"]
    assert rep["candidates_per_sec"] > 0 and rep["achieved_qps"] > 0
    assert 0 < rep["occupancy"] <= 1.0
    # one dispatch per flush unless a flush outgrew the top G bucket
    assert rep["dispatches"] >= sum(rep["flushes"].values())
    assert rep["offered_qps"] == 3000.0


def test_replay_open_loop_sheds_under_overload(engine):
    """A tiny backlog cap + a burst far above the flush rate must shed
    load: arrivals land inside the deadline window faster than any
    flush trigger fires, the backlog caps at max_pending, and the rest
    are rejected (every served request still gets real scores)."""
    reqs = synthetic_requests(60, num_features=D, seed=7)
    eng = ScoringEngine(engine._model)
    eng.warm({eng.envelope(r) for r in reqs}, batch_sizes=eng.g_buckets)
    rep = replay_open_loop(eng, reqs, qps=2_000_000.0,
                           config=QueueConfig(max_batch=64,
                                              max_delay_us=50_000.0,
                                              max_pending=4), seed=8)
    assert rep["rejected"] > 0
    assert rep["served"] == 60 - rep["rejected"]


# ------------------------------------------------- measured timeline
class _Clock:
    """A synthetic pump clock: reads do not advance it."""

    def __init__(self, t: float):
        self.t = t

    def __call__(self) -> float:
        return self.t


class _AdvancingEngine:
    """The real engine, with ``clock`` moved on by ``step`` seconds
    inside every ``score_batch``."""

    def __init__(self, engine, clock: _Clock, step: float):
        self._engine, self._clock, self._step = engine, clock, step

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def score_batch(self, requests):
        out = self._engine.score_batch(requests)
        self._clock.t += self._step
        return out


def test_real_clock_pump_stamps_measured_start_and_end(engine):
    """Under RealClockPump a flush's start and end are the pump clock's
    readings when the flush begins and when score_batch returns, and the
    ledger's and the histogram's queue delay is measured from that
    start (modelled, the deadline flush would start at its deadline and
    both would end after the engine's own wall time)."""
    reqs = _uniform_requests(3, seed=51)
    eng = ScoringEngine(engine._model)
    eng.warm({eng.envelope(reqs[0])}, batch_sizes=eng.g_buckets)
    clock = _Clock(1.0)
    q = MicroBatchQueue(_AdvancingEngine(eng, clock, 0.25),
                        QueueConfig(max_batch=2, max_delay_us=3000.0))
    pump = RealClockPump(q, clock=clock)
    led = obs.RunLedger(None)
    prev = obs.set_ledger(led)
    try:
        pump.submit(reqs[0])
        clock.t = 1.001
        pump.submit(reqs[1])  # full flush, inline
        clock.t = 1.3
        pump.submit(reqs[2])
        clock.t = 1.5  # past its 1.303 deadline
        pump.start()
        for _ in range(500):
            if len(pump.completions()) == 3:
                break
            time.sleep(0.01)
    finally:
        pump.stop()
        obs.set_ledger(prev)
    c = sorted(q.completions, key=lambda c: c.ticket)
    assert [x.reason for x in c] == ["full", "full", "deadline"]
    assert [x.arrival for x in c] == [1.0, 1.001, 1.3]
    assert c[0].started == c[1].started == 1.001
    assert c[0].completed == c[1].completed == 1.001 + 0.25
    assert c[2].started == 1.5 and c[2].completed == 1.5 + 0.25
    delays = [r["queue_delay_us"] for r in led.events("serve_dispatch")]
    assert delays == [pytest.approx(1e3), pytest.approx(2e5)]
    assert q.stats._delay_hist.sum == pytest.approx(0.001 + 0.2)


class _StubStats:
    def __init__(self):
        self.score_seconds, self.dispatches, self.slots = 0.0, 0, 0

    def as_dict(self):
        return {"dispatches": self.dispatches, "slots": self.slots}


class _StubEngine:
    """Fixed service time per dispatch (a power of two, so the modelled
    sums are exact) and the request's own shape as its envelope."""

    WALL = 2.0 ** -10

    def __init__(self):
        self.stats = _StubStats()

    def envelope(self, r):
        return (r.user_ids.shape[-1], r.ad_ids.shape[-1], r.ad_ids.shape[0])

    def dispatch_context(self, reason, queue_delay_us):
        return contextlib.nullcontext()

    def score_batch(self, requests):
        self.stats.score_seconds += self.WALL
        self.stats.dispatches += 1
        self.stats.slots += len(requests)
        return [np.zeros(r.ad_ids.shape[0]) for r in requests]


def _modelled_timeline(arrivals, envs, cfg, wall):
    """The virtual-clock server written out: groups by envelope, full
    flushes at their arrival, deadline flushes at oldest + delay, then a
    drain; start = max(trigger, server free), end = start + wall.
    {ticket: (reason, start, end, tickets flushed together)}."""
    delay = cfg.max_delay_us * 1e-6
    pending: dict = {}
    busy = 0.0
    out = {}

    def flush(env, trigger, reason):
        nonlocal busy
        group = pending.pop(env)
        start = max(trigger, busy)
        busy = start + wall
        tickets = tuple(t for t, _ in group)
        for t in tickets:
            out[t] = (reason, start, busy, tickets)

    def due(now):
        while True:
            ready = sorted((g[0][1], env) for env, g in pending.items()
                           if g[0][1] + delay <= now)
            if not ready:
                return
            arr, env = ready[0]
            flush(env, arr + delay, "deadline")

    for t, (arr, env) in enumerate(zip(arrivals, envs)):
        due(arr)
        pending.setdefault(env, []).append((t, arr))
        if len(pending[env]) >= cfg.max_batch:
            flush(env, arr, "full")
    due(arrivals[-1])
    for env in sorted(pending, key=lambda e: pending[e][0][1]):
        flush(env, arrivals[-1], "drain")
    return out


def test_replay_open_loop_keeps_the_modelled_timeline(monkeypatch):
    """The virtual-clock replay's flush grouping, reasons, starts and
    ends are the modelled server's, stamp for stamp."""
    import repro.serve.traffic as traffic

    reqs = synthetic_requests(120, num_features=D, k_user=(6, 7),
                              k_ad=(4, 4), n_ads=(3, 4), seed=61)
    cfg = QueueConfig(max_batch=4, max_delay_us=2000.0)
    eng = _StubEngine()
    seen = []

    class Recording(MicroBatchQueue):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            seen.append(self)

    monkeypatch.setattr(traffic, "MicroBatchQueue", Recording)
    rep = replay_open_loop(eng, reqs, qps=1500.0, config=cfg, seed=62)
    assert rep["served"] == 120 and rep["rejected"] == 0
    arrivals = poisson_arrivals(120, 1500.0, seed=62)
    want = _modelled_timeline(arrivals, [eng.envelope(r) for r in reqs],
                              cfg, _StubEngine.WALL)
    comps = seen[0].completions
    groups: dict = {}
    for c in comps:
        groups.setdefault((c.started, c.reason), []).append(c.ticket)
    got = {c.ticket: (c.reason, c.started, c.completed,
                      tuple(groups[(c.started, c.reason)])) for c in comps}
    assert got == want
    assert {r for r, *_ in want.values()} == {"full", "deadline", "drain"}


# ------------------------------------------------------------- spans
def test_pump_full_flush_spans_nest_admit_flush_dispatch(engine):
    reqs = _uniform_requests(2, seed=71)
    eng = ScoringEngine(engine._model)
    eng.warm({eng.envelope(reqs[0])}, batch_sizes=eng.g_buckets)
    q = MicroBatchQueue(eng, QueueConfig(max_batch=2, max_delay_us=1e6))
    tracer = obs.Tracer(enabled=True)
    prev = obs.set_tracer(tracer)
    try:
        pump = RealClockPump(q)
        for r in reqs:
            pump.submit(r)  # the second runs the full flush inline
        pump.stop()
    finally:
        obs.set_tracer(prev)
    evs = [e for e in tracer.events() if e["ph"] == "X"]
    by = {}
    for e in evs:
        by.setdefault(e["name"], []).append(e)
    assert len(by["serve/admit"]) == 2
    assert len(by["serve/flush"]) == len(by["serve/dispatch"]) == 1
    admit = max(by["serve/admit"], key=lambda e: e["ts"])
    flush, disp = by["serve/flush"][0], by["serve/dispatch"][0]
    assert flush["args"] == {"reason": "full", "size": 2}

    def inside(inner, outer):
        return (outer["ts"] - 1e-6 <= inner["ts"] and inner["ts"] + inner["dur"]
                <= outer["ts"] + outer["dur"] + 1e-6)

    assert inside(flush, admit) and inside(disp, flush)
    assert len({e["tid"] for e in evs}) == 1
