"""``correct`` for the serving cell, at a size a test run holds: a sound
run passes, the control (the reference scoring in bfloat16) fails, and
so does a run whose engine alters an answer where it is produced. The
harness's look for a chip is skipped; the rest of a run is driven
through ``bench/run.py``'s ``execute`` on the CPU."""
import argparse
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run, spec  # noqa: E402
from bench.reference import train as reference  # noqa: E402

CELL = "serve-d1m-steady"
LIMITS = spec.load_json(ROOT / "bench" / "limits" / f"{CELL}.json")
TINY_CONFIG = dict(spec.cell(CELL).config, num_features=3_000, regions=4)
TINY_TRAIN = dict(spec.mix("daystream-2d-4096s"), sessions_per_day=64)
TINY_MIX = dict(spec.cell(CELL).traffic, rate_per_s=300.0)
DRIVER = spec.load_module("drivers", "serve_open_loop")


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setattr(spec, "mix", lambda name, bench=None: TINY_TRAIN)

    def go(seed=2_147_483_659, trace=0):
        cell = spec.cell(CELL)._replace(config=TINY_CONFIG, traffic=TINY_MIX)
        args = argparse.Namespace(seed=seed, seconds=0.5, trace=trace)
        return run.execute(args, jax.devices()[:1], cell, LIMITS)

    return go


def test_sound_run_is_correct(tiny):
    line = tiny()
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks" and set(line["checks"]) == {"score_gap"}
    assert line["attempted"] > 50 and line["failed"] == 0
    assert set(line["metrics"]) == {"serve_p50_ms", "serve_candidates_per_s",
                                    "setup_s"}


def test_traced_run_reports_the_full_windows_tail_and_checks_both_windows(tiny):
    """A traced run offers the untraced window of ``--seconds`` and then
    the traced slice: the tail is the window's, the scores of both are
    compared."""
    line = tiny(seed=7, trace=1)
    assert line["correct"], line["checks"]
    assert "serve_p99_ms" in line["metrics"] and "serve_occupancy" in line["metrics"]
    window = len(DRIVER.pageviews.schedule(TINY_MIX, 0.5, 7)[0])
    sliced = len(DRIVER.pageviews.schedule(TINY_MIX, TINY_MIX["trace_seconds"], 9)[0])
    assert line["attempted"] == window + sliced and line["failed"] == 0
    assert line["device"]["window_s"] > 0


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_control_fails(seed):
    """The reference's scores in bfloat16 against its float32 scores."""
    from repro.serve import BundleRequest

    d, m2 = TINY_CONFIG["num_features"], 2 * TINY_CONFIG["regions"]
    win = DRIVER.daystream.window(TINY_TRAIN, d)
    theta0 = np.asarray(DRIVER.init_theta(TINY_MIX["model"]["seed"], d, m2))
    _, _, theta = reference.run(TINY_CONFIG, win, theta0, TINY_MIX["model"]["iters"])
    _, sizes = DRIVER.pageviews.schedule(TINY_MIX, 0.5, seed)
    reqs = [BundleRequest(*pv) for pv in DRIVER.pageviews.requests(TINY_MIX, d, sizes, seed)]
    p = DRIVER.reference_scores(theta, reqs, block=256)
    p_low = DRIVER.reference_scores(theta, reqs, block=256, dtype=jnp.bfloat16)
    gap = max(float(np.max(np.abs(a - b))) for a, b in zip(p_low, p))
    assert gap > LIMITS["score_gap"]


def test_an_altered_answer_is_not_correct(tiny, monkeypatch):
    from repro.serve.engine import ScoringEngine

    score = ScoringEngine._score_chunk

    def altered(self, requests, env):
        out = score(self, requests, env)
        out[0] = out[0].copy()
        out[0][0] += 1e-3
        return out

    monkeypatch.setattr(ScoringEngine, "_score_chunk", altered)
    line = tiny()
    assert not line["correct"]
    assert line["checks"]["score_gap"]["value"] == pytest.approx(1e-3, rel=0.01)
