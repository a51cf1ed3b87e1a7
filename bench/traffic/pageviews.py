"""Serving traffic: open-loop page views with Poisson arrivals.

Each page view is one user's id list and N candidate ads, with the
shapes of ``repro.serve.engine.synthetic_requests``: K_user, K_ad and N
drawn uniformly from the mix's inclusive ranges. The features are those
the served model was trained on (``daystream``): binary multi-hot
values, ``1 / sqrt(K)`` on each side, and ids from the hot-head model of
``daystream._drifted_ids`` on the mix's ``day`` (user ids from
``[user_lo, d)``, ad ids from ``[0, user_lo)``).

Steadiness: the multiset of request sizes and of inter-arrival gaps is
drawn once from the mix's ``shape_seed`` for the whole window, and the
run's seed only permutes them and draws the ids. So every
seed offers the same number of requests, of the same sizes, over the
same span, in another order.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from bench.traffic.daystream import _drifted_ids


class PageView(NamedTuple):
    user_ids: np.ndarray  # (Ku,) int32
    user_vals: np.ndarray  # (Ku,) float32
    ad_ids: np.ndarray  # (N, Ka) int32
    ad_vals: np.ndarray  # (N, Ka) float32


def schedule(mix: dict, seconds: float, seed: int) -> tuple[np.ndarray, list]:
    """(due offsets in seconds, sizes [(ku, ka, n)]) of every request due
    in a window of ``seconds`` at the mix's offered rate."""
    rng = np.random.default_rng(mix["shape_seed"])
    rate = float(mix["rate_per_s"])
    gaps = rng.exponential(1.0 / rate, size=int(rate * seconds * 1.5) + 16)
    gaps = gaps[: int(np.searchsorted(np.cumsum(gaps), seconds))]
    n = gaps.size
    (k0, k1), (a0, a1), (n0, n1) = mix["k_user"], mix["k_ad"], mix["n_ads"]
    sizes = np.stack([rng.integers(k0, k1 + 1, n), rng.integers(a0, a1 + 1, n),
                      rng.integers(n0, n1 + 1, n)], axis=1)
    perm = np.random.default_rng(seed)
    due = np.cumsum(gaps[perm.permutation(n)])
    sizes = sizes[perm.permutation(n)]
    return due, [tuple(int(x) for x in s) for s in sizes]


def requests(mix: dict, d: int, sizes: list, seed: int) -> list[PageView]:
    """The page views for ``sizes``, ids drawn from ``seed``."""
    rng = np.random.default_rng([seed, 1])
    user_lo = max(1, int(mix["user_frac"] * d))
    t = mix["day"]
    out = []
    for ku, ka, n in sizes:
        out.append(PageView(
            user_ids=_drifted_ids(rng, user_lo, d, (ku,), t, mix).astype(np.int32),
            user_vals=np.full((ku,), 1.0 / np.sqrt(ku), np.float32),
            ad_ids=_drifted_ids(rng, 0, user_lo, (n, ka), t, mix).astype(np.int32),
            ad_vals=np.full((n, ka), 1.0 / np.sqrt(ka), np.float32)))
    return out
