"""The readers of the serving path's program spans: on a hand-made
trace, and on a CPU trace of a tiny pump, queue and engine taken with
``jax.profiler`` and reduced as a chip's trace is."""
import sys
import tempfile
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec, tracing  # noqa: E402
from bench.tracing import Event, reduce  # noqa: E402

DEV0, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6  # ns
READERS = ("dispatch_host_idle.serve", "dispatch_sync_idle.serve",
           "queue_idle.serve")
SERVE_SPANS = ("serve/pad", "serve/launch", "serve/sync", "serve/readback",
               "serve/flush", "serve/admit")


def op(start_ms, dur_ms=2):
    return Event(DEV0, "XLA Ops", "%fusion.1 = f32[8] fusion()",
                 start_ms * MS, dur_ms * MS, {})


def span(name, start_ms, end_ms, line="python"):
    return Event(HOST, line, name, start_ms * MS, (end_ms - start_ms) * MS, {})


def two_dispatches():
    """A full flush inside a submit on the generator's thread, a deadline
    flush on the pump's, then a submit with no flush. Idle gaps (ms):
    0-10 pad, 12-14 launch, 16-20 sync, 22-30 readback, 32-35 the
    dispatch outside its children, 37-45 flush, 47-60 launch, 62-75
    readback, 77-90 admit, 92-100 no span."""
    return [
        span("bench/window", 0, 100),
        span("serve/admit", 0, 39, line="gen"),
        span("serve/flush", 3, 38, line="gen"),
        span("serve/dispatch", 4, 36, line="gen"),
        span("serve/pad", 4, 8, line="gen"),
        span("serve/launch", 8, 15, line="gen"),
        span("serve/sync", 15, 24, line="gen"),
        span("serve/readback", 24, 30, line="gen"),
        span("serve/flush", 40, 72, line="pump"),
        span("serve/dispatch", 50, 70, line="pump"),
        span("serve/pad", 50, 52, line="pump"),
        span("serve/launch", 52, 56, line="pump"),
        span("serve/sync", 56, 64, line="pump"),
        span("serve/readback", 64, 70, line="pump"),
        span("serve/admit", 80, 86, line="gen"),
    ] + [op(t) for t in (10, 14, 20, 30, 35, 45, 60, 75, 90)]


def read(name, red):
    return spec.load_module("metrics", name).read({"reduced": red})


def test_readers_book_each_span_and_add_up_to_device_idle():
    red = reduce(two_dispatches(), devices=1)
    got = {n: read(n, red) for n in READERS}
    assert got == {"dispatch_host_idle.serve": pytest.approx(10 + 2 + 8 + 13 + 13),
                   "dispatch_sync_idle.serve": pytest.approx(4.0),
                   "queue_idle.serve": pytest.approx(8 + 13)}
    gaps = dict(red.gaps)
    rest = 100.0 * (gaps["serve/dispatch"] + gaps["(no span)"]) / red.window_s
    assert rest == pytest.approx(3 + 8)
    idle = read("device_idle.serve", red)
    assert sum(got.values()) + rest == pytest.approx(idle) == pytest.approx(82.0)


def test_readers_return_nothing_without_the_serving_spans():
    """A program whose dispatch is one span (no children, no queue
    spans), or a run with no trace, reports none of the three."""
    events = [span("bench/window", 0, 10), span("serve/dispatch", 0, 8),
              op(2), op(6)]
    red = reduce(events, devices=1)
    assert dict(red.gaps)["serve/dispatch"] > 0
    for name in READERS:
        assert read(name, red) is None
        assert read(name, None) is None


def test_cpu_trace_of_pump_queue_and_engine_books_gaps_to_serve_spans():
    """Tracer(annotate=True) puts the serving spans on the profiler's
    clock: a CPU trace of a tiny pump, queue and engine, its XLA CPU
    operations read as one device's, books its idle gaps to the spans
    inside a dispatch and around it."""
    import jax
    import jax.numpy as jnp

    from repro import obs
    from repro.serve import (MicroBatchQueue, QueueConfig, RealClockPump,
                             ScoringEngine, synthetic_requests)

    rng = np.random.default_rng(0)
    theta = jnp.asarray(rng.normal(size=(300, 6)).astype(np.float32) * 0.3)
    reqs = synthetic_requests(12, num_features=300, seed=3, k_user=(4, 4),
                              k_ad=(2, 2), n_ads=(3, 3))
    eng = ScoringEngine(theta)
    eng.warm({eng.envelope(reqs[0])}, batch_sizes=eng.g_buckets)
    eng.score_batch(reqs[:4])
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    prev = obs.set_tracer(obs.Tracer(enabled=True, annotate=True))
    with tempfile.TemporaryDirectory() as logdir:
        try:
            jax.profiler.start_trace(logdir, profiler_options=options)
            try:
                with jax.profiler.TraceAnnotation(tracing.WINDOW):
                    q = MicroBatchQueue(eng, QueueConfig(max_batch=4,
                                                         max_delay_us=2000.0))
                    with RealClockPump(q) as pump:
                        for r in reqs:
                            pump.submit(r)
            finally:
                jax.profiler.stop_trace()
        finally:
            obs.set_tracer(prev)
        events = tracing.load(logdir)
    assert len(q.completions) == len(reqs)
    # an XLA CPU operation carries its instruction as ``hlo_op``
    ops = [Event(DEV0, tracing.OPS_LINE, f"%{e.stats['hlo_op']} = cpu()",
                 e.start_ns, e.dur_ns, e.stats)
           for e in events if "hlo_op" in e.stats]
    assert ops
    red = reduce([e for e in events if "hlo_op" not in e.stats] + ops,
                 devices=1)
    gaps = dict(red.gaps)
    assert set(gaps) <= set(SERVE_SPANS) | {"serve/dispatch", "(no span)"}
    assert set(gaps) & set(SERVE_SPANS)
    shares = sum(read(n, red) for n in READERS)
    rest = 100.0 * (gaps.get("serve/dispatch", 0.0)
                    + gaps.get("(no span)", 0.0)) / red.window_s
    assert shares + rest == pytest.approx(read("device_idle.serve", red))
