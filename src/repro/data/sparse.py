"""Sparse feature substrate — the paper's actual input format.

Production CTR features are one-hot/multi-hot IDs: each sample has a
small set of active feature ids (tens) out of millions of columns. Dense
(B, d) matrices waste d/active memory and FLOPs. We store padded COO per
sample:

    ids  (B, K) int32   active column ids (pad with id = d, weight 0)
    vals (B, K) float32 feature values

and compute z = x @ Theta as a gather + weighted segment-sum:
    z[b] = sum_k vals[b,k] * Theta[ids[b,k], :]

This is TPU-native (dense gather + reductions — no hash maps, DESIGN.md
§3), exactly how embedding lookups work in production CTR systems.

Execution path: everything here rides the FUSED sparse kernel package
(``repro.kernels.lsplm_sparse_fused``) — a pipelined block-DMA Pallas
gather-matmul on TPU (per-tile SMEM ids, double-buffered K-row
blocks), a K-chunked ``lax.scan`` accumulation elsewhere, and a
``jax.custom_vjp`` whose backward is the transposed scatter. The old
``take``+einsum formulation, which materialises the (N, K, 2m) gather
intermediate in HBM, lives on as the oracle in that package's ``ref.py``.

Transpose plans: the backward's id->entries transposition (a sort) is
data-dependent but BATCH-constant, so it is precomputed here, once per
batch, as a :class:`TransposePlan` (``build_transpose_plan`` /
``build_batch_plans``) and carried on the batch. With a plan attached
the per-step backward is pure gathers + segment sums — no sort, no
scatter — on every backend (``repro.kernels.lsplm_sparse_scatter``).
Batches without plans still work (scan-chunked scatter fallback).

Two batch layouts train on this path:

  * :class:`SparseCTRBatch`, the session layout (the common-feature
    trick): user ids stored once per session (G, Ku) and gathered per
    sample, ad ids per sample (B, Ka); two gathers an evaluation.
  * :class:`FlatCTRBatch`, sessionless rows such as the public Criteo
    schema's (``repro.data.criteo``): every impression carries all of
    its K ids, nothing is shared; one gather an evaluation.

On one device a side with more samples than one scatter call holds
(``max_slice_samples``) is planned in slices of samples
(``SlicedPlan``); the backward makes one call per slice.

Distribution composes too: ``build_batch_plans(shards=...)`` /
``generate_sparse(shards=...)`` route the batch for a (data x model)
mesh — ids bucketed per id-range Theta shard, plans sliced per shard
from the one sort already paid — returning a
``repro.shard.ShardedSparseBatch`` for the ``shard_map`` training step
(``repro.shard.step``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.objective import nll_sparse
from repro.kernels.lsplm_sparse_fused.ops import (  # noqa: F401 (pad_theta re-exported)
    pad_theta,
    sparse_gather_matmul,
)
from repro.kernels.lsplm_sparse_scatter.lsplm_sparse_scatter import (
    MAX_DZ_VMEM_BYTES,
    max_slice_samples,
)
from repro.kernels.lsplm_sparse_scatter.ops import (  # noqa: F401 (re-export)
    SlicedPlan,
    TransposePlan,
    build_transpose_plan,
    plan_slices,
)


class SparseCTRBatch(NamedTuple):
    """Sparse analogue of CommonFeatureBatch (padded COO)."""

    user_ids: jax.Array  # (G, Ku) int32, pad = num_features
    user_vals: jax.Array  # (G, Ku)
    ad_ids: jax.Array  # (B, Ka)
    ad_vals: jax.Array  # (B, Ka)
    session_id: jax.Array  # (B,)
    y: jax.Array  # (B,)
    num_features: int = 0  # d (static)
    # precomputed backward transpose plans (None -> scan-chunked fallback)
    user_plan: TransposePlan | None = None
    ad_plan: TransposePlan | None = None


class FlatCTRBatch(NamedTuple):
    """Sessionless padded COO: each impression carries all of its ids."""

    ids: jax.Array   # (B, K) int32, pad = num_features
    vals: jax.Array  # (B, K)
    y: jax.Array     # (B,)
    num_features: int = 0  # d (static)
    plan: TransposePlan | SlicedPlan | None = None


def _plan_fields(batch) -> tuple[tuple[str, str, str], ...]:
    """(side, ids field, plan field) of each id tensor the batch plans."""
    if isinstance(batch, FlatCTRBatch):
        return (("ids", "ids", "plan"),)
    return (("user", "user_ids", "user_plan"), ("ad", "ad_ids", "ad_plan"))


def plan_sides(batch) -> tuple[tuple[str, jax.Array, object], ...]:
    """(side, ids, plan or None) of each id tensor of a session or a
    sessionless batch: the one place outside the objective that knows
    the two layouts."""
    return tuple((side, getattr(batch, f), getattr(batch, p))
                 for side, f, p in _plan_fields(batch))


def _route(batch: "SparseCTRBatch", shards, data_shards: int):
    """Coerce ``shards`` (count or Partition) and route the batch for a
    (data x model) mesh — the one place the shards= paths share."""
    # local import: repro.shard builds on this module
    from repro.shard.partition import Partition, make_partition, route_batch

    part = shards if isinstance(shards, Partition) else make_partition(
        batch.num_features, int(shards))
    return route_batch(batch, part, data_shards=data_shards)


def build_batch_plans(batch, *, shards=None, data_shards: int = 1,
                      max_dz_bytes: int = MAX_DZ_VMEM_BYTES):
    """Attach per-batch transpose plans (one argsort per id tensor, on
    the host, once) so every optimizer step's backward is sort-free.
    Plans address the PADDED Theta (d + 1 rows, pad id == d). Takes a
    :class:`SparseCTRBatch` or a :class:`FlatCTRBatch`.

    On one device, an id tensor of more samples than one scatter call's
    dz fits in ``max_dz_bytes`` of VMEM (the scatter kernel's ceiling)
    is planned in slices of samples (:class:`SlicedPlan`, one scatter
    call each); smaller ones get one plan, as before.

    With ``shards`` (a shard count or a ``repro.shard.Partition``) the
    planned batch is additionally ROUTED for a (data x model) mesh and a
    ``repro.shard.ShardedSparseBatch`` is returned instead: ids bucketed
    per id-range shard, the freshly built plans sliced per (data block,
    id range) — the argsort is NOT redone per shard — and stacked for
    ``shard_map`` (see ``repro.shard``). Session batches only.

    Recorded in ``repro.obs``: a ``train/plan`` span with args
    ``impressions``, ``entries`` (non-pad id slots), ``distinct_rows``
    and ``slices`` (scatter calls one backward makes), and the
    ``train_plan_*`` counters of the same four, summed over batches.
    """
    if shards is not None and isinstance(batch, FlatCTRBatch):
        raise ValueError("a sessionless FlatCTRBatch trains on one device; "
                         "the sharded path takes session batches only")
    d = batch.num_features
    max_samples = None if shards is not None else max_slice_samples(max_dz_bytes)
    with obs.get_tracer().span("train/plan") as span:
        ids = {f: np.asarray(getattr(batch, f))
               for _, f, _ in _plan_fields(batch)}
        batch = batch._replace(**{
            p: build_transpose_plan(ids[f], d + 1, pad_id=d,
                                    max_samples=max_samples)
            for _, f, p in _plan_fields(batch)})
        flat = np.concatenate([a.reshape(-1) for a in ids.values()])
        flat = flat[flat != d]
        counts = dict(
            impressions=int(np.asarray(batch.y).shape[0]),
            entries=int(flat.size),
            distinct_rows=int(np.count_nonzero(np.bincount(flat, minlength=d))),
            slices=sum(len(plan_slices(plan))
                       for _, _, plan in plan_sides(batch)))
        span.set(**counts)
    reg = obs.get_registry()
    for name, value in counts.items():
        reg.counter(f"train_plan_{name}").inc(value)
    if shards is None:
        return batch
    return _route(batch, shards, data_shards)


def sparse_matmul(ids: jax.Array, vals: jax.Array, theta: jax.Array,
                  *, mode: str = "auto",
                  plan: TransposePlan | None = None) -> jax.Array:
    """(N, K) ids/vals  x  Theta (d+1, 2m) -> (N, 2m), FUSED.

    Theta must carry ONE trailing pad row (all zeros) so pad ids hit it
    (``pad_theta``). Dispatches to the pipelined Pallas kernel on TPU
    and the chunked jnp path elsewhere; differentiable via the
    transposed-scatter custom VJP either way (plan-driven when ``plan``
    is given).
    """
    return sparse_gather_matmul(ids, vals, theta, mode=mode, plan=plan)


def sparse_nll(theta: jax.Array, batch: SparseCTRBatch) -> jax.Array:
    """Eq. 5 on sparse features with the common-feature trick (Eq. 13):
    user dot-products computed ONCE per session, gathered per sample.
    Delegates to the fused-kernel path in ``repro.core.objective``."""
    return nll_sparse(theta, batch)


def sparse_loss_and_grad(theta: jax.Array, batch: SparseCTRBatch):
    return jax.value_and_grad(sparse_nll)(theta, batch)


def sparse_predict(theta: jax.Array, batch) -> jax.Array:
    """p(y=1|x) for a session-structured sparse batch — delegates to the
    unified inference layer's session-shared path (``repro.serve``), the
    same code that serves online traffic (model polymorphic: pass a
    pruned ``ServingArtifact`` instead of Theta and it still works). A
    :class:`FlatCTRBatch` takes the flat path."""
    from repro.serve.score import predict

    if isinstance(batch, FlatCTRBatch):
        return sparse_predict_flat(theta, batch.ids, batch.vals)
    return predict(theta, batch)


def sparse_predict_flat(theta: jax.Array, ids: jax.Array, vals: jax.Array,
                        *, mode: str = "auto") -> jax.Array:
    """p(y=1|x) for flat (sessionless) padded-COO rows — the serving hot
    path (``repro.serve.score.score_sparse``), fully fused down to the
    (N,) probabilities."""
    from repro.serve.score import score_sparse

    return score_sparse(theta, ids, vals, mode=mode)


# ----------------------------------------------------------------- generator
def planted_id_weight(ids: np.ndarray, salt: int) -> np.ndarray:
    """Deterministic latent weight per feature id (hash of the id), so a
    hot id keeps stable semantics across batches, splits and DAYS — the
    invariant that makes drifted multi-day streams learnable."""
    h = (np.asarray(ids).astype(np.uint64) * np.uint64(2654435761)
         + np.uint64(salt))
    return (((h % np.uint64(10007)).astype(np.float64) / 10007.0) * 4.0
            - 2.0).astype(np.float32)


def planted_ctr_labels(user_ids, user_vals, ad_ids, ad_vals, session_id,
                       rng: np.random.Generator) -> np.ndarray:
    """Sample click labels from the shared piecewise-linear ground truth
    (Eq. 2 family): every id carries a latent hashed weight
    (:func:`planted_id_weight`); the USER side selects one of 4 latent
    regions which modulates the ad-side weights. Used by both the
    full-batch generator (``generate_sparse``) and the day-sliced stream
    (``repro.stream.source.DayStream``) so their labels agree wherever
    their id draws do."""
    regions = 4
    session_id = np.asarray(session_id)
    region_score = np.stack([
        (user_vals * planted_id_weight(user_ids, 31 * (r + 1))).sum(-1)
        for r in range(regions)], axis=-1)  # (G, regions)
    region = np.argmax(region_score, axis=-1)[session_id]  # (B,)
    gains = np.asarray([2.5, -2.5, 1.0, -1.0], np.float32)[region]
    base = (ad_vals * planted_id_weight(ad_ids, 7)).sum(-1) \
        + 0.5 * (user_vals * planted_id_weight(user_ids, 13)).sum(-1)[session_id]
    logits = gains * base
    p = 1 / (1 + np.exp(-logits))
    return (rng.random(session_id.shape[0]) < p).astype(np.float32)


def generate_sparse(
    num_features: int = 1_000_000,
    num_user_features_range: tuple[int, int] = (600_000, 1_000_000),
    sessions: int = 512,
    ads_per_session: int = 4,
    active_user: int = 24,
    active_ad: int = 12,
    seed: int = 0,
    with_plans: bool = True,
    shards=None,
    data_shards: int = 1,
) -> SparseCTRBatch:
    """Million-column sparse CTR batch with session structure. Ground
    truth: piecewise-linear over a planted low-dim projection of the
    active ids (so LS-PLM has signal without densifying anything).

    ``shards`` (a model-shard count or ``repro.shard.Partition``) routes
    the batch for a (data x model) mesh and returns a
    ``repro.shard.ShardedSparseBatch`` — see ``build_batch_plans``.
    """
    rng = np.random.default_rng(seed)
    d = num_features
    G, A = sessions, ads_per_session
    B = G * A
    user_lo = num_user_features_range[0]

    def zipf_ids(lo, hi, shape):
        """Power-law id draws: hot ids recur across splits (real CTR
        feature traffic is Zipf — uniform draws over millions of columns
        would make train/test supports disjoint and learning impossible)."""
        u = rng.random(shape)
        r = (hi - lo) * (u ** 10.0)  # very hot head at lo (CTR id traffic)
        return (lo + r).astype(np.int64)

    user_ids = zipf_ids(user_lo, d, (G, active_user))
    ad_ids = zipf_ids(0, user_lo, (B, active_ad))
    user_vals = rng.normal(size=(G, active_user)).astype(np.float32) / np.sqrt(active_user)
    ad_vals = rng.normal(size=(B, active_ad)).astype(np.float32) / np.sqrt(active_ad)
    session_id = np.repeat(np.arange(G, dtype=np.int32), A)

    # planted truth shared with the streaming generator (see
    # planted_ctr_labels): hashed per-id weights + 4 user-selected regions
    y = planted_ctr_labels(user_ids, user_vals, ad_ids, ad_vals,
                           session_id, rng)

    batch = SparseCTRBatch(
        user_ids=jnp.asarray(user_ids, jnp.int32),
        user_vals=jnp.asarray(user_vals),
        ad_ids=jnp.asarray(ad_ids, jnp.int32),
        ad_vals=jnp.asarray(ad_vals),
        session_id=jnp.asarray(session_id),
        y=jnp.asarray(y),
        num_features=d,
    )
    if with_plans:
        return build_batch_plans(batch, shards=shards,
                                 data_shards=data_shards)
    if shards is not None:  # routed, scan-chunked fallback backward
        return _route(batch, shards, data_shards)
    return batch


def to_dense(batch: SparseCTRBatch) -> np.ndarray:
    """Densify (tests only — production never does this)."""
    d = batch.num_features
    G = np.asarray(batch.user_ids).shape[0]
    B = np.asarray(batch.ad_ids).shape[0]
    x = np.zeros((B, d), np.float32)
    uid = np.asarray(batch.user_ids)[np.asarray(batch.session_id)]
    uval = np.asarray(batch.user_vals)[np.asarray(batch.session_id)]
    np.add.at(x, (np.arange(B)[:, None], uid), uval)
    np.add.at(x, (np.arange(B)[:, None], np.asarray(batch.ad_ids)),
              np.asarray(batch.ad_vals))
    return x
