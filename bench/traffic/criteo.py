"""Training traffic on the public Criteo schema: sessionless rows of 39
one-hot fields.

A copy of ``repro.data.criteo`` (field layout, Zipf ranks, planted
labels) and of ``repro.data.sparse.planted_id_weight``, kept here so
that a change to the program cannot move the benchmark's traffic. Given
the same parameters and seed it produces the same arrays as
``repro.data.criteo.generate_criteo`` (checked by
``tests/bench/test_perfbench_criteo.py``).

Each field owns a contiguous range of rows, the integer fields first
(``int_buckets`` rows each), then the categorical fields, each of
``min(count, cap)`` rows; the rows still missing from the
configuration's ``num_features`` go one each to the largest raw fields.
Within a field, rank r is drawn with probability proportional to
(r + 1)^-zipf_s; rank 0 is the field's unknown id.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Rows(NamedTuple):
    """One planned training window of sessionless rows (pad id == d)."""

    ids: np.ndarray  # (B, K) int32
    vals: np.ndarray  # (B, K) float32
    y: np.ndarray  # (B,) float32
    num_features: int


def _planted_id_weight(ids: np.ndarray, salt: int) -> np.ndarray:
    h = (np.asarray(ids).astype(np.uint64) * np.uint64(2654435761)
         + np.uint64(salt))
    return (((h % np.uint64(10007)).astype(np.float64) / 10007.0) * 4.0
            - 2.0).astype(np.float32)


def field_sizes(mix: dict, d: int) -> np.ndarray:
    """Rows of each field, in layout order, summing to ``d``."""
    raw = np.asarray(mix["cat_counts"], np.int64)
    cat = np.minimum(raw, mix["cap"])
    short = d - mix["int_fields"] * mix["int_buckets"] - int(cat.sum())
    capped = np.flatnonzero(raw > mix["cap"])
    if not 0 <= short <= capped.size:
        raise ValueError(f"{d} rows cannot be laid out from the mix's fields "
                         f"(short by {short}, {capped.size} capped)")
    cat[capped[np.argsort(-raw[capped], kind="stable")][:short]] += 1
    return np.concatenate(
        [np.full(mix["int_fields"], mix["int_buckets"], np.int64), cat])


def _zipf_ranks(rng, size: int, n: int, s: float) -> np.ndarray:
    cdf = np.cumsum(np.arange(1, size + 1, dtype=np.float64) ** -s)
    return np.minimum(np.searchsorted(cdf, rng.random(n) * cdf[-1],
                                      side="right"), size - 1)


def _planted_labels(ids, vals, rng, offset: float) -> np.ndarray:
    regions = 4
    region = np.argmax(np.stack([
        (vals * _planted_id_weight(ids, 31 * (r + 1))).sum(-1)
        for r in range(regions)], axis=-1), axis=-1)
    gains = np.asarray([2.5, -2.5, 1.0, -1.0], np.float32)[region]
    logits = gains * (vals * _planted_id_weight(ids, 7)).sum(-1) + offset
    p = 1 / (1 + np.exp(-logits))
    return (rng.random(ids.shape[0]) < p).astype(np.float32)


def window(mix: dict, d: int) -> Rows:
    """The mix's ``impressions`` rows from its own ``data_seed``."""
    rng = np.random.default_rng(mix["data_seed"])
    sizes = field_sizes(mix, d)
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    ids = np.stack([o + _zipf_ranks(rng, int(n), mix["impressions"],
                                    mix["zipf_s"])
                    for o, n in zip(offsets, sizes)], axis=1).astype(np.int32)
    vals = np.full(ids.shape, 1.0 / np.sqrt(ids.shape[1]), np.float32)
    y = _planted_labels(ids, vals, rng, mix["label_offset"])
    return Rows(ids, vals, y, d)
