"""The public Criteo display-ad schema as a sessionless training source.

The Criteo Display Advertising Challenge dataset (Criteo Labs, Kaggle
2014), preprocessed as AutoInt does (Song et al., arXiv:1810.11921,
Table 1): 39 fields per impression, 13 integer (I1-I13) and 26
categorical (C1-C26), 998,960 features once infrequent values fold into
one unknown id per field. Every impression has exactly one id in every
field and nothing is shared between impressions: a
:class:`~repro.data.sparse.FlatCTRBatch` with K = 39.

Layout: each field owns a contiguous range of Theta rows, the 13
integer fields first, then C1-C26. Sizes:

  * integer fields: ``INT_BUCKETS`` = 100 buckets each (assumed; the
    ln(v)^2 bucketing AutoInt uses gives vocabularies of this order);
  * categorical fields: the raw per-field value counts that the DLRM
    reference's Kaggle preprocessing prints (facebookresearch/dlrm),
    each capped at ``CAP`` = 122,445 (standing in for the frequency
    threshold). The cap hits the 7 largest fields; the rows still
    missing from ``num_features`` (2 at 998,960) go one each to the
    largest raw fields.

Ids: within a field, rank r is drawn with probability proportional to
(r + 1)^-s (Zipf, s = 1.05, assumed); rank 0 is the field's
unknown/missing id. Values are binary, 1/sqrt(39) a slot. Labels come
from the planted piecewise-linear truth of the session sources (hashed
per-id weights, ``sparse.planted_id_weight``; four regions selected by
the ids) with a logit offset that makes about a quarter of impressions
clicks, as in the dataset.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.data.sparse import FlatCTRBatch, build_batch_plans, planted_id_weight

NUM_FEATURES = 998_960
INT_FIELDS = 13
INT_BUCKETS = 100
CAT_COUNTS = (1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3, 93145,
              5683, 8351593, 3194, 27, 14992, 5461306, 10, 5652, 2173, 4,
              7046547, 18, 15, 286181, 105, 142572)
CAP = 122_445
ZIPF_S = 1.05
LABEL_OFFSET = -2.0


def field_sizes() -> np.ndarray:
    """Rows of each of the fields, in layout order; they sum to
    ``NUM_FEATURES``."""
    raw = np.asarray(CAT_COUNTS, np.int64)
    cat = np.minimum(raw, CAP)
    short = NUM_FEATURES - INT_FIELDS * INT_BUCKETS - int(cat.sum())
    capped = np.flatnonzero(raw > CAP)
    cat[capped[np.argsort(-raw[capped], kind="stable")][:short]] += 1
    return np.concatenate([np.full(INT_FIELDS, INT_BUCKETS, np.int64), cat])


def field_offsets(sizes: np.ndarray) -> np.ndarray:
    """First row of each field."""
    return np.concatenate([[0], np.cumsum(sizes)[:-1]])


def _zipf_ranks(rng: np.random.Generator, size: int, n: int) -> np.ndarray:
    """``n`` draws of a rank in [0, size) with P(r) ~ (r + 1)^-ZIPF_S."""
    cdf = np.cumsum(np.arange(1, size + 1, dtype=np.float64) ** -ZIPF_S)
    return np.minimum(np.searchsorted(cdf, rng.random(n) * cdf[-1],
                                      side="right"), size - 1)


def _planted_labels(ids: np.ndarray, vals: np.ndarray,
                    rng: np.random.Generator) -> np.ndarray:
    """Clicks from the planted truth: the ids' hashed weights pick one of
    four regions, whose gain scales the hashed-weight sum;
    ``LABEL_OFFSET`` shifts every logit."""
    regions = 4
    region = np.argmax(np.stack([
        (vals * planted_id_weight(ids, 31 * (r + 1))).sum(-1)
        for r in range(regions)], axis=-1), axis=-1)
    gains = np.asarray([2.5, -2.5, 1.0, -1.0], np.float32)[region]
    logits = gains * (vals * planted_id_weight(ids, 7)).sum(-1) + LABEL_OFFSET
    p = 1 / (1 + np.exp(-logits))
    return (rng.random(ids.shape[0]) < p).astype(np.float32)


def generate_criteo(impressions: int, *, seed: int = 0,
                    with_plans: bool = True) -> FlatCTRBatch:
    """``impressions`` rows of the Criteo schema from ``seed``; planned
    with ``build_batch_plans`` unless ``with_plans=False``."""
    rng = np.random.default_rng(seed)
    sizes = field_sizes()
    ids = np.stack([o + _zipf_ranks(rng, int(n), impressions)
                    for o, n in zip(field_offsets(sizes), sizes)],
                   axis=1).astype(np.int32)
    vals = np.full(ids.shape, 1.0 / np.sqrt(ids.shape[1]), np.float32)
    y = _planted_labels(ids, vals, rng)
    batch = FlatCTRBatch(ids=jnp.asarray(ids), vals=jnp.asarray(vals),
                         y=jnp.asarray(y), num_features=NUM_FEATURES)
    return build_batch_plans(batch) if with_plans else batch
