"""The benchmark's own traffic generators."""
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402
from bench.traffic import daystream, pageviews  # noqa: E402

MIX = dict(spec.mix("daystream-2d-4096s"), sessions_per_day=32)
SERVE = dict(spec.mix("pageviews-steady"), rate_per_s=500.0)


def test_daystream_copy_matches_the_programs_stream():
    from repro.stream import DayStream

    d = 5_000
    prog = DayStream(MIX["days"], sessions_per_day=MIX["sessions_per_day"],
                     num_features=d, active_user=MIX["k_user"],
                     active_ad=MIX["k_ad"], seed=MIX["data_seed"]).window(
                         MIX["days"] - 1, MIX["days"])
    ours = daystream.window(MIX, d)
    for field in ("user_ids", "user_vals", "ad_ids", "ad_vals", "session_id", "y"):
        np.testing.assert_array_equal(np.asarray(getattr(prog, field)),
                                      getattr(ours, field), err_msg=field)


def test_daystream_window_shape_and_id_ranges():
    d = 10_000
    w = daystream.window(MIX, d)
    g = MIX["days"] * MIX["sessions_per_day"]
    assert w.user_ids.shape == (g, MIX["k_user"])
    assert w.ad_ids.shape == (g * MIX["ads_per_session"], MIX["k_ad"])
    user_lo = int(MIX["user_frac"] * d)
    assert w.user_ids.min() >= user_lo and w.user_ids.max() < d
    assert w.ad_ids.min() >= 0 and w.ad_ids.max() < user_lo
    assert np.all(np.diff(w.session_id) >= 0)


def test_pageviews_same_sizes_and_span_for_every_seed():
    due_a, sizes_a = pageviews.schedule(SERVE, 2.0, seed=1)
    due_b, sizes_b = pageviews.schedule(SERVE, 2.0, seed=2_147_483_659)
    assert len(sizes_a) == len(sizes_b) > 0.8 * 500 * 2.0
    assert sorted(sizes_a) == sorted(sizes_b) and sizes_a != sizes_b
    assert abs(due_a[-1] - due_b[-1]) < 1e-9  # the same gaps, summed in another order
    assert np.all(np.diff(due_a) > 0) and due_a[-1] < 2.0
    for ku, ka, n in sizes_a:
        assert 12 <= ku <= 24 and 6 <= ka <= 12 and 10 <= n <= 30


def test_pageviews_are_deterministic_in_the_seed():
    _, sizes = pageviews.schedule(SERVE, 0.5, seed=7)
    a = pageviews.requests(SERVE, 20_000, sizes, seed=7)
    b = pageviews.requests(SERVE, 20_000, sizes, seed=7)
    c = pageviews.requests(SERVE, 20_000, sizes, seed=8)
    assert all(np.array_equal(x.ad_ids, y.ad_ids) for x, y in zip(a, b))
    assert not all(np.array_equal(x.ad_ids, y.ad_ids) for x, y in zip(a, c))
    user_lo = int(SERVE["user_frac"] * 20_000)
    assert all(r.user_ids.min() >= user_lo and r.ad_ids.max() < user_lo for r in a)
