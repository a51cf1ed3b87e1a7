"""The unified observability layer (``repro.obs``): metrics registry
thread-safety and export, span nesting + Chrome-trace round-trip,
ledger schema round-trip and validation errors, instrumented-vs-clean
trajectory parity, and the engine/queue dispatch records."""
import json
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs.ledger import render_train_iter, validate_event
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer


# ----------------------------------------------------------- registry
def test_registry_get_or_create_identity_and_kind_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("builds", planner="0")
    assert reg.counter("builds", planner="0") is c
    assert reg.counter("builds", planner="1") is not c  # distinct series
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("builds", planner="0")


def test_counter_thread_safety_exact_total():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    h = reg.histogram("walls")
    n_threads, per_thread = 4, 5000

    def work():
        for _ in range(per_thread):
            c.inc(1.0)
            h.observe(1e-3)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == float(n_threads * per_thread)
    assert h.count == n_threads * per_thread
    assert h.sum == pytest.approx(n_threads * per_thread * 1e-3)


def test_histogram_quantiles_interpolate_and_clamp():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    for v in np.linspace(1e-4, 1e-2, 100):
        h.observe(float(v))
    assert 5e-4 <= h.quantile(0.5) <= 5e-3
    assert h.quantile(0.0) == pytest.approx(1e-4)
    assert h.quantile(1.0) == pytest.approx(1e-2)
    single = reg.histogram("one")
    single.observe(0.42)
    # clamped to the observed range, never extrapolated into the bucket
    assert single.quantile(0.99) == pytest.approx(0.42)
    assert reg.histogram("empty").quantile(0.5) == 0.0
    # empty histogram: every q (including the edges) reads 0.0
    assert reg.histogram("empty").quantile(0.0) == 0.0
    assert reg.histogram("empty").quantile(1.0) == 0.0
    # single observation: every q collapses to that value
    assert single.quantile(0.0) == pytest.approx(0.42)
    assert single.quantile(0.5) == pytest.approx(0.42)
    assert single.quantile(1.0) == pytest.approx(0.42)


def test_histogram_rejects_out_of_range_q_and_nan():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    h.observe(1e-3)
    with pytest.raises(ValueError, match=r"pass 0.99, not 99"):
        h.quantile(99)
    with pytest.raises(ValueError, match="must be in"):
        h.quantile(-0.1)
    # NaN would silently poison min/max and every later quantile
    with pytest.raises(ValueError, match="NaN observation"):
        h.observe(float("nan"))
    assert h.count == 1  # the rejected observation left no trace
    assert h.quantile(1.0) == pytest.approx(1e-3)


def test_registry_write_jsonl_and_json(tmp_path):
    reg = MetricsRegistry()
    reg.counter("a", k="x").inc(2.0)
    reg.gauge("b").set(7.0)
    reg.histogram("c").observe(1e-3)

    p = reg.write(str(tmp_path / "m.jsonl"))
    lines = [json.loads(ln) for ln in open(p) if ln.strip()]
    assert {ln["series"] for ln in lines} == {"a{k=x}", "b", "c"}
    by = {ln["series"]: ln for ln in lines}
    assert by["a{k=x}"] == {"series": "a{k=x}", "type": "counter",
                            "value": 2.0}
    assert by["c"]["count"] == 1

    p2 = reg.write(str(tmp_path / "m.json"))
    doc = json.load(open(p2))
    assert doc["b"] == {"type": "gauge", "value": 7.0}


# ------------------------------------------------------- atomic writes
def test_atomic_write_interruption_preserves_previous_file(tmp_path):
    from repro.obs.fileio import atomic_write

    target = tmp_path / "snap.json"
    with atomic_write(str(target)) as f:
        f.write("good")
    assert target.read_text() == "good"

    # a crash mid-write must leave the previous bytes, not a prefix
    with pytest.raises(RuntimeError, match="simulated crash"):
        with atomic_write(str(target)) as f:
            f.write("partial garbage that must never be seen")
            raise RuntimeError("simulated crash")
    assert target.read_text() == "good"
    # and no temp litter survives the failure
    assert [p.name for p in tmp_path.iterdir()] == ["snap.json"]

    with pytest.raises(ValueError, match="write mode"):
        with atomic_write(str(target), mode="r"):
            pass


def test_registry_and_tracer_writes_are_atomic(tmp_path, monkeypatch):
    import repro.obs.fileio as fileio

    reg = MetricsRegistry()
    reg.counter("a").inc()
    mpath = str(tmp_path / "m.json")
    reg.write(mpath)
    tr = Tracer(enabled=True)
    with tr.span("w"):
        pass
    tpath = str(tmp_path / "t.json")
    tr.write(tpath)
    before_m, before_t = open(mpath).read(), open(tpath).read()

    def boom(src, dst):
        raise RuntimeError("simulated replace crash")

    monkeypatch.setattr(fileio.os, "replace", boom)
    reg.counter("a").inc()
    with pytest.raises(RuntimeError):
        reg.write(mpath)
    with tr.span("w2"):
        pass
    with pytest.raises(RuntimeError):
        tr.write(tpath)
    # both snapshots still read as complete documents from BEFORE
    assert open(mpath).read() == before_m
    assert open(tpath).read() == before_t
    json.load(open(mpath)), json.load(open(tpath))


# -------------------------------------------------------------- tracing
def test_span_nesting_and_chrome_trace_round_trip(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("outer", day=3):
        with tr.step_span("train/iter", 7):
            pass
    path = tr.write(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert set(evs) == {"outer", "train/iter"}
    outer, inner = evs["outer"], evs["train/iter"]
    # proper containment in the exported timeline (spans record on exit)
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-9
    assert outer["args"] == {"day": 3}
    assert inner["args"] == {"step": 7}
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(metas) == 1 and metas[0]["name"] == "thread_name"


def test_tracer_separates_threads():
    tr = Tracer(enabled=True)
    with tr.span("main-side"):
        pass

    def worker():
        with tr.span("worker-side"):
            pass

    t = threading.Thread(target=worker, name="bg")
    t.start()
    t.join()
    evs = tr.events()
    tids = {e["tid"] for e in evs if e["ph"] == "X"}
    assert len(tids) == 2
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert "bg" in names


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("never", x=1):
        with tr.step_span("inner", 0):
            pass
    assert tr.events() == []
    assert tr.span("a") is tr.step_span("b", 1)  # one shared null span


def test_disabled_tracer_records_no_serve_span_and_builds_no_dispatch_args():
    """The serving path under a disabled tracer: every span is asked for
    and none recorded, and the dispatch's envelope label is not built."""
    from repro.serve import (MicroBatchQueue, QueueConfig, RealClockPump,
                             ScoringEngine, synthetic_requests)

    class Asked(Tracer):
        def __init__(self):
            super().__init__(enabled=False)
            self.asked = []

        def span(self, name, **args):
            self.asked.append((name, args))
            return super().span(name, **args)

    rng = np.random.default_rng(0)
    theta = jnp.asarray(rng.normal(size=(300, 6)).astype(np.float32) * 0.3)
    reqs = synthetic_requests(2, num_features=300, seed=1,
                              k_user=(4, 4), k_ad=(2, 2), n_ads=(3, 3))
    eng = ScoringEngine(theta)
    eng.warm({eng.envelope(reqs[0])}, batch_sizes=eng.g_buckets)
    tracer = Asked()
    prev = obs.set_tracer(tracer)
    try:
        pump = RealClockPump(MicroBatchQueue(eng, QueueConfig(max_batch=2)))
        for r in reqs:
            pump.submit(r)
        pump.stop()
    finally:
        obs.set_tracer(prev)
    assert tracer.events() == []
    assert [n for n, _ in tracer.asked] == [
        "serve/admit", "serve/admit", "serve/flush", "serve/dispatch",
        "serve/pad", "serve/launch", "serve/sync", "serve/readback"]
    assert dict(tracer.asked)["serve/dispatch"] == {}


# --------------------------------------------------------------- ledger
def test_ledger_round_trip_and_offline_validation(tmp_path):
    path = str(tmp_path / "run.jsonl")
    with obs.RunLedger(path) as led:
        led.emit("run_meta", driver="test", mode="unit")
        led.emit("train_iter", step=0, f=2.0, f_new=1.5, alpha=0.5,
                 grad_norm=0.1, nnz=12, ls_iters=1)
        led.emit("stream_window", day=0, days_in_window=1, plan_s=0.01,
                 compile_s=0.1, build_s=0.02, wait_s=0.0, prefetched=False,
                 step_s=0.2, carry="reset", alpha=0.5, nnz=12, fs=[2.0, 1.5])
    recs = obs.read_jsonl(path)
    assert [r["kind"] for r in recs] == ["run_meta", "train_iter",
                                         "stream_window"]
    assert obs.validate_file(path) == []
    assert all("t" in r for r in recs)
    # in-memory mirror kept the same records (minus json round-trip)
    led2 = obs.RunLedger(None)
    led2.emit("log", text="hi")
    assert led2.events("log")[0]["text"] == "hi"


def test_ledger_schema_rejects_bad_records():
    assert "unknown kind" in validate_event({"kind": "nope"})
    assert "missing required" in validate_event(
        {"kind": "train_iter", "step": 0})
    good = {"kind": "train_iter", "step": 0, "f": 1.0, "f_new": 0.9,
            "alpha": 0.5, "grad_norm": 0.1, "nnz": 3}
    assert validate_event(good) is None
    assert validate_event({**good, "extra_field": "ok"}) is None  # extras ok
    # bool is not an int and an int is not a bool (bool subclasses int)
    assert "expected int" in validate_event({**good, "nnz": True})
    win = {"kind": "stream_window", "day": 0, "days_in_window": 1,
           "plan_s": 0.0, "compile_s": 0.0, "build_s": 0.0, "wait_s": 0.0,
           "prefetched": 1, "step_s": 0.0, "carry": "reset", "alpha": 0.1,
           "nnz": 1, "fs": []}
    assert "expected bool" in validate_event(win)
    led = obs.RunLedger(None)
    with pytest.raises(ValueError, match="invalid ledger record"):
        led.emit("train_iter", step="zero")


def test_alert_records_validate_like_any_other_kind():
    good = {"kind": "alert", "rule": "p99", "state": "firing",
            "signal": "serve.p99_wall_us", "value": 3e5, "threshold": 2.5e5}
    assert validate_event(good) is None
    assert validate_event({**good, "op": "<=", "breach_n": 3,
                           "clear_n": 3}) is None
    assert "missing required" in validate_event(
        {"kind": "alert", "rule": "p99"})
    assert "expected str" in validate_event({**good, "state": 1})
    led = obs.RunLedger(None)
    with pytest.raises(ValueError, match="invalid ledger record"):
        led.emit("alert", rule="r", state="firing", signal="s",
                 value="high", threshold=1.0)


def test_ledger_observers_see_records_and_can_emit_back():
    led = obs.RunLedger(None)
    seen: list[dict] = []

    def observer(event):
        seen.append(event["kind"])
        # re-entrant emit from inside an observer must not deadlock
        # (observers run outside the ledger lock)
        if event["kind"] == "log":
            led.emit("alert", rule="r", state="firing", signal="s",
                     value=1.0, threshold=0.5)

    led.add_observer(observer)
    led.add_observer(observer)  # deduped: one subscription
    led.emit("log", text="x")
    assert seen == ["log", "alert"]
    led.remove_observer(observer)
    led.emit("log", text="y")
    assert seen == ["log", "alert"]
    # the null ledger accepts (and ignores) observers
    obs.NULL_LEDGER.add_observer(observer)
    obs.NULL_LEDGER.remove_observer(observer)


def test_null_ledger_is_inert():
    assert obs.NULL_LEDGER.enabled is False
    assert obs.NULL_LEDGER.emit("anything_goes", junk=object()) is None
    assert obs.NULL_LEDGER.events() == []


def test_log_prints_exact_text_and_records():
    led = obs.RunLedger(None)
    out = []
    obs.log("hello world", ledger=led, printer=out.append)
    obs.log("iter line", kind="train_iter", ledger=led, printer=out.append,
            step=0, f=1.0, f_new=0.9, alpha=0.5, grad_norm=0.1, nnz=3)
    assert out == ["hello world", "iter line"]
    assert [e["kind"] for e in led.events()] == ["log", "train_iter"]
    assert led.events("train_iter")[0]["text"] == "iter line"
    # disabled ledger: still prints, records nothing
    out2 = []
    obs.log("quiet", ledger=obs.NULL_LEDGER, printer=out2.append)
    assert out2 == ["quiet"]


def test_render_train_iter_matches_driver_format():
    rec = {"step": 7, "f_new": 123.456, "alpha": 0.25, "nnz": 42}
    assert render_train_iter(rec) == \
        f"iter {7:3d}  f={123.456:12.2f} alpha={0.25:.3g} nnz={42:8d}"
    full = {**rec, "test_auc": 0.87654, "wall_s": 0.0123}
    assert render_train_iter(full, nnz_width=7) == (
        f"iter {7:3d}  f={123.456:12.2f} alpha={0.25:.3g} nnz={42:7d}"
        f" test_auc={0.87654:.4f}  ({12.3:.0f} ms/iter)")


def test_ledger_cli_check(tmp_path, capsys):
    from repro.obs.ledger import main

    good = tmp_path / "good.jsonl"
    with obs.RunLedger(str(good)) as led:
        led.emit("log", text="ok")
    assert main(["--check", str(good)]) == 0
    assert "ledger OK" in capsys.readouterr().out

    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"kind": "mystery"}\n')
    assert main(["--check", str(bad)]) == 1
    assert "FAIL" in capsys.readouterr().err
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert main([str(empty)]) == 1


# ------------------------------------------------- configure / session
def test_configure_writes_all_outputs_and_restores_defaults(tmp_path):
    prev_tracer, prev_ledger = obs.get_tracer(), obs.get_ledger()
    m, tr, led = (str(tmp_path / "m.jsonl"), str(tmp_path / "t.json"),
                  str(tmp_path / "l.jsonl"))
    session = obs.configure(metrics_out=m, trace_out=tr, ledger_out=led,
                            meta={"driver": "test", "mode": "unit"})
    try:
        assert obs.get_tracer().enabled and obs.get_ledger().enabled
        with obs.get_tracer().span("work"):
            pass
        obs.get_registry().counter("obs_test_configure").inc()
        obs.log("one line", printer=lambda s: None)
    finally:
        session.close()
    session.close()  # idempotent
    assert obs.get_tracer() is prev_tracer
    assert obs.get_ledger() is prev_ledger
    assert obs.validate_file(led) == []
    recs = obs.read_jsonl(led)
    assert recs[0]["kind"] == "run_meta" and recs[0]["driver"] == "test"
    assert [e["name"] for e in json.load(open(tr))["traceEvents"]
            if e["ph"] == "X"] == ["work"]
    assert any(json.loads(ln)["series"] == "obs_test_configure"
               for ln in open(m))


# -------------------------------------- trajectory parity (obs on/off)
def test_owlqn_trajectory_bitwise_identical_with_obs_on():
    from repro.optim import OWLQNPlus

    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.normal(size=(40, 20)) / np.sqrt(20), jnp.float32)
    b = A @ jnp.asarray(rng.normal(size=(20, 6)).astype(np.float32))

    def lg(theta):
        r = A @ theta - b
        return 0.5 * jnp.vdot(r, r), A.T @ r

    theta0 = jnp.zeros((20, 6), jnp.float32)
    opt = OWLQNPlus(lg, lam=0.2, beta=0.2)
    t_off, trace_off = opt.run(theta0, max_iters=12)
    led = obs.RunLedger(None)
    tracer = Tracer(enabled=True)
    t_on, trace_on = opt.run(theta0, max_iters=12, ledger=led, tracer=tracer)
    np.testing.assert_array_equal(np.asarray(t_off), np.asarray(t_on))
    fs_off = [float(s.f_new) for s in trace_off]
    fs_on = [float(s.f_new) for s in trace_on]
    assert fs_off == fs_on
    # and the ledger/trace captured exactly that trajectory
    recs = led.events("train_iter")
    assert [r["f_new"] for r in recs] == fs_on
    assert [r["nnz"] for r in recs] == [int(s.nnz) for s in trace_on]
    steps = [e["args"]["step"] for e in tracer.events()
             if e.get("name") == "train/iter"]
    assert steps == list(range(len(recs)))


# ------------------------------------------- serve dispatch records
def test_engine_and_queue_emit_serve_dispatch_records():
    from repro.serve import (MicroBatchQueue, QueueConfig, ScoringEngine,
                             synthetic_requests)

    rng = np.random.default_rng(0)
    theta = jnp.asarray(rng.normal(size=(300, 6)).astype(np.float32) * 0.3)
    reqs = synthetic_requests(6, num_features=300, seed=1,
                              k_user=(4, 4), k_ad=(2, 2), n_ads=(3, 3))
    led = obs.RunLedger(None)
    prev = obs.set_ledger(led)
    try:
        eng = ScoringEngine(theta)
        eng.score(reqs[0])
        direct = led.events("serve_dispatch")
        assert len(direct) == 1
        assert direct[0]["flush_reason"] == "direct"
        assert direct[0]["requests"] == 1
        assert direct[0]["queue_delay_us"] == 0.0
        assert direct[0]["envelope"][0] == direct[0]["g"]

        queue = MicroBatchQueue(eng, QueueConfig(max_batch=4,
                                                 max_delay_us=1000.0))
        for i, r in enumerate(reqs[:4]):
            queue.submit(r, now=i * 1e-5)  # 4th submit -> full flush
        queue.submit(reqs[4], now=1.0)
        queue.drain(now=2.0)
        recs = led.events("serve_dispatch")[1:]
        assert [r["flush_reason"] for r in recs] == ["full", "drain"]
        assert recs[0]["requests"] == 4
        assert recs[0]["queue_delay_us"] >= 0.0
        for r in recs:
            assert validate_event(r) is None
    finally:
        obs.set_ledger(prev)
