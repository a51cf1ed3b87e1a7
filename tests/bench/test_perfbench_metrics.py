"""The benchmark's metric arithmetic and roofline counts."""
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402
from bench.roofline import Work, least_seconds, peaks, share  # noqa: E402
from bench.roofline import gather, scatter, serve_step, train_step  # noqa: E402
from bench.tracing import Reduced  # noqa: E402

KIND = "TPU v5 lite"
TRAIN = spec.load_module("drivers", "train_window")
SERVE = spec.load_module("drivers", "serve_open_loop")


def test_gather_and_scatter_counts_by_hand():
    d, m2 = 10, 4
    ids = np.array([[1, 2, 2], [3, d, d]])  # 4 real slots, 3 distinct ids
    # flops 2 * 4 slots * 4 columns; bytes 8 per slot + 16 per distinct
    # row read + 16 per z row written
    assert gather.work(ids, d, m2) == Work(32.0, 8 * 4 + 16 * (3 + 2))
    # a pruned row is not required work
    keep = np.ones(d + 1, bool)
    keep[2] = False
    assert gather.work(ids, d, m2, keep) == Work(16.0, 8 * 2 + 16 * (2 + 2))
    # scatter: dz rows read once, distinct gradient rows written once
    assert scatter.work(ids, d, m2) == Work(32.0, 8 * 4 + 16 * (2 + 3))


def test_serve_dispatch_counts_real_requests_only():
    d, m2 = 10, 4
    r1 = SimpleNamespace(user_ids=np.array([1, 2]), ad_ids=np.array([[3, 4], [3, d]]))
    r2 = SimpleNamespace(user_ids=np.array([2]), ad_ids=np.array([[5, 6]]))
    keep = np.ones(d + 1, bool)
    user, ad, total = serve_step.dispatch([r1, r2], d, m2, keep)
    # user: slots 1, 2, 2 (3 real, 2 distinct), 2 z rows (one a request)
    assert user == Work(24.0, 8 * 3 + 16 * (2 + 2))
    # ads: slots 3, 4, 3, 5, 6 (5 real, 4 distinct), 3 candidates
    assert ad == Work(40.0, 8 * 5 + 16 * (4 + 3))
    assert total == user + ad + Work(0.0, 4 * 3)


def test_train_iteration_count_by_hand():
    fwd, bwd = Work(10.0, 100.0), Work(20.0, 200.0)
    w = train_step.iteration(forward=fwd, backward=bwd, impressions=5, d=3,
                             m2=2, memory=10, evals=2)
    passes = 2 + 2 + 20 + 1 + 4 + 1
    assert train_step.dense_passes(10, 2) == passes
    assert w.bytes == 3 * (100 + 4 * 5) + 200 + passes * 4 * 3 * 2
    assert w.flops == 3 * 10 + 20 + 2 * passes * 3 * 2


def test_least_time_takes_the_larger_bound_and_refuses_unknown_devices():
    pk = peaks(KIND)
    t, bound = least_seconds(Work(0.0, pk["hbm_bytes_per_s"]), pk)
    assert (t, bound) == (pytest.approx(1.0), "bytes")
    t, bound = least_seconds(Work(pk["flops_bf16"] * 2, 1.0), pk, chips=2)
    assert (t, bound) == (pytest.approx(1.0), "flops")
    with pytest.raises(KeyError, match="no peaks"):
        peaks("cpu")


def test_share_is_100_at_the_least_time_and_none_without_a_measurement():
    pk = peaks(KIND)
    x = {"kind": KIND, "chips": 1}
    w = Work(0.0, pk["hbm_bytes_per_s"] * 1e-3)  # 1 ms at peak bandwidth
    assert share(x, 2e-3, [w, w], 1, "t") == pytest.approx(100.0)
    assert share(x, 4e-3, [w], 2, "t") == pytest.approx(50.0)
    assert share(x, 0.0, [w], 1, "t") is None
    assert share(x, None, [w], 1, "t") is None


def _inputs(**red):
    base = dict(window_s=1.0, busy_s=0.5, kernel_s={}, collective_exposed_s=0.0,
                ops=[], gaps=[])
    base.update(red)
    return {"kind": KIND, "chips": 1, "reduced": Reduced(**base),
            "counters": {"ls_evals": [1, 2], "impressions": 8, "requests": 6,
                         "slots": 8, "queue_delay_s": [0.001] * 99 + [0.05],
                         "gen_late_s": [0.0] * 200},
            "work": {"gather": [Work(0, 1e6), Work(0, 2e6)],
                     "scatter": [Work(0, 1e6), Work(0, 1e6)],
                     "d": 100, "m2": 4, "memory": 10, "chips": 1,
                     "dispatches": []}}


@pytest.mark.parametrize("name,expect", [
    ("device_idle.train", 50.0), ("device_idle.serve", 50.0),
    ("ls_evals_per_iter", 1.5), ("serve_occupancy", 0.75),
    ("gen_late_p99_ms", 0.0)])
def test_counter_and_idle_readers(name, expect):
    value = spec.load_module("metrics", name).read(_inputs())
    assert value == pytest.approx(expect)


def test_queue_delay_tail_is_over_all_samples():
    # 99 samples of 1 ms and one of 50 ms: numpy's 99th percentile sits
    # between the top two samples, so the one slow request shows
    value = spec.load_module("metrics", "queue_delay_p99_ms").read(_inputs())
    assert 1.0 < value < 50.0


@pytest.mark.parametrize("name", ["gather_roofline.train", "scatter_roofline.train",
                                  "gather_roofline.serve", "collective_exposed.train",
                                  "serve_step_mfu", "serve_p99_ms"])
def test_readers_without_anything_to_read_return_nothing(name):
    assert spec.load_module("metrics", name).read(_inputs()) is None


def test_kernel_rooflines_from_kernel_time():
    pk = peaks(KIND)
    x = _inputs(kernel_s={"gather": 5 * 3e6 / pk["hbm_bytes_per_s"],
                          "scatter": 2 * 2e6 / pk["hbm_bytes_per_s"]})
    # 5 forwards (1 + 1, 1 + 2) of 3 MB; 2 backwards of 2 MB
    assert spec.load_module("metrics", "gather_roofline.train").read(x) == pytest.approx(100.0)
    assert spec.load_module("metrics", "scatter_roofline.train").read(x) == pytest.approx(100.0)
    mfu = spec.load_module("metrics", "train_step_mfu").read(x)
    assert 0 < mfu < 100


class _FakeStep:
    """A step that takes ``dt`` seconds, once ``stall`` more."""

    def __init__(self, dt, stall=0.0, at=3):
        self.dt, self.stall, self.at, self.calls = dt, stall, at, 0

    def __call__(self, state):
        self.calls += 1
        time.sleep(self.dt + (self.stall if self.calls == self.at else 0.0))
        return state + 1, SimpleNamespace(f_new=np.float32(state), alpha=1.0)


def test_train_rate_is_over_the_whole_window_so_a_stall_lowers_it():
    iters, elapsed, stats = TRAIN.measure(_FakeStep(0.002), int, 0, 5, 0.1)
    assert iters % 5 == 0 and iters == len(stats) and elapsed >= 0.1
    base = iters / elapsed
    iters2, elapsed2, _ = TRAIN.measure(_FakeStep(0.002, stall=0.1), int, 0, 5, 0.1)
    assert iters2 / elapsed2 < 0.8 * base


def test_serve_tails_are_over_all_requests_so_a_stall_moves_them():
    reqs = [SimpleNamespace(ad_ids=np.zeros((10, 6))) for _ in range(200)]
    due = np.arange(200) * 1e-3
    done = {id(r): at + 2e-3 for r, at in zip(reqs, due)}
    served = list(range(200))
    p99 = spec.load_module("metrics", "serve_p99_ms")

    def measure(done, served):
        lat = SERVE.latencies(reqs, due, done, 1.0)
        return (SERVE.end_to_end(reqs, lat, done, served, 0.0, 1.0),
                p99.read({"counters": {"window_latency_s": lat.tolist()}}))

    m, tail = measure(done, served)
    assert m["serve_p50_ms"] == pytest.approx(2.0) and tail == pytest.approx(2.0)
    assert m["serve_candidates_per_s"] == pytest.approx(2000 / (0.199 + 0.002))
    # a 30 ms stall at request 100 holds the next ones until it clears
    stalled = dict(done)
    for i in range(100, 130):
        stalled[id(reqs[i])] = max(done[id(reqs[i])], 0.130)
    m2, tail2 = measure(stalled, served)
    assert tail2 > 20.0
    assert m2["serve_p50_ms"] == pytest.approx(2.0)
    # shed requests count until the end of the run (t_end = 1 s)
    shed = {id(r) for r in reqs[:12]}
    _, tail3 = measure({k: v for k, v in done.items() if k not in shed}, served[12:])
    assert tail3 > 900.0
