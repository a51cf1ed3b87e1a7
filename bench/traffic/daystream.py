"""Training traffic: a multi-day window of sparse CTR impressions.

A copy of the id and label model of ``repro.stream.source.DayStream``
(``_drifted_ids``, ``_day_locked`` with binary values) and of
``repro.data.sparse.planted_ctr_labels``, kept here so that a change to
the program cannot move the benchmark's traffic. Given the same
parameters and seed it produces the same arrays as ``DayStream``
(checked by ``tests/bench/test_perfbench_traffic.py``).

Day t: G sessions, each with K_user user ids from ``[user_lo, d)`` and
A ads with K_ad ad ids from ``[0, user_lo)``. ``head_frac`` of the ids
come from an exponential hot head of width ``head_width * span`` whose
centre moves by ``drift * span`` ids a day; the rest are uniform.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Window(NamedTuple):
    """One planned training window, as numpy arrays (pad id == d)."""

    user_ids: np.ndarray  # (G, Ku) int32
    user_vals: np.ndarray  # (G, Ku) float32
    ad_ids: np.ndarray  # (B, Ka) int32
    ad_vals: np.ndarray  # (B, Ka) float32
    session_id: np.ndarray  # (B,) int32
    y: np.ndarray  # (B,) float32
    num_features: int


def _planted_id_weight(ids: np.ndarray, salt: int) -> np.ndarray:
    h = (np.asarray(ids).astype(np.uint64) * np.uint64(2654435761)
         + np.uint64(salt))
    return (((h % np.uint64(10007)).astype(np.float64) / 10007.0) * 4.0
            - 2.0).astype(np.float32)


def _planted_labels(user_ids, user_vals, ad_ids, ad_vals, session_id, rng):
    regions = 4
    region_score = np.stack([
        (user_vals * _planted_id_weight(user_ids, 31 * (r + 1))).sum(-1)
        for r in range(regions)], axis=-1)
    region = np.argmax(region_score, axis=-1)[session_id]
    gains = np.asarray([2.5, -2.5, 1.0, -1.0], np.float32)[region]
    base = (ad_vals * _planted_id_weight(ad_ids, 7)).sum(-1) \
        + 0.5 * (user_vals * _planted_id_weight(user_ids, 13)).sum(-1)[session_id]
    p = 1 / (1 + np.exp(-(gains * base)))
    return (rng.random(session_id.shape[0]) < p).astype(np.float32)


def _drifted_ids(rng, lo: int, hi: int, shape, day: int, mix: dict):
    span = hi - lo
    scale = max(1.0, mix["head_width"] * span)
    offset = int(round(mix["drift"] * day * span))
    r = (-scale * np.log1p(-rng.random(shape))).astype(np.int64)
    head = (offset + r) % span
    tail = rng.integers(0, span, shape)
    ids = np.where(rng.random(shape) < mix["head_frac"], head, tail)
    return lo + ids


def day(mix: dict, d: int, t: int, seed: int) -> Window:
    """Day t of the stream with ``seed`` (binary multi-hot values)."""
    rng = np.random.default_rng(seed * 1_000_003 + t)
    g, a = mix["sessions_per_day"], mix["ads_per_session"]
    ku, ka = mix["k_user"], mix["k_ad"]
    user_lo = max(1, int(mix["user_frac"] * d))
    user_ids = _drifted_ids(rng, user_lo, d, (g, ku), t, mix)
    ad_ids = _drifted_ids(rng, 0, user_lo, (g * a, ka), t, mix)
    user_vals = np.full((g, ku), 1.0 / np.sqrt(ku), np.float32)
    ad_vals = np.full((g * a, ka), 1.0 / np.sqrt(ka), np.float32)
    session_id = np.repeat(np.arange(g, dtype=np.int32), a)
    y = _planted_labels(user_ids, user_vals, ad_ids, ad_vals, session_id, rng)
    return Window(user_ids.astype(np.int32), user_vals,
                  ad_ids.astype(np.int32), ad_vals, session_id, y, d)


def window(mix: dict, d: int) -> Window:
    """The last ``mix["days"]`` days of the stream, concatenated with
    session ids rebased (the traffic's own ``data_seed``)."""
    days = [day(mix, d, t, mix["data_seed"]) for t in range(mix["days"])]
    off = np.cumsum([0] + [w.user_ids.shape[0] for w in days[:-1]])
    cat = lambda f: np.concatenate([getattr(w, f) for w in days])
    return Window(cat("user_ids"), cat("user_vals"), cat("ad_ids"),
                  cat("ad_vals"),
                  np.concatenate([w.session_id + o for w, o in zip(days, off)]
                                 ).astype(np.int32),
                  cat("y"), d)
