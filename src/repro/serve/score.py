"""The ONE inference layer: every LS-PLM prediction goes through here.

Training-eval (``repro.data.sparse.sparse_predict``), the core model
predictors (``repro.core.lsplm.predict_proba_sparse``), the examples and
the batched :class:`~repro.serve.engine.ScoringEngine` all call these
functions — the Eq. 2 softmax-dot-sigmoid head lives in exactly one
place (``repro.kernels.lsplm_sparse_fused.ops.finalize_p``) and the
model argument is polymorphic:

  * a raw UNPADDED Theta ``(d, 2m)`` array,
  * ``repro.core.lsplm.LSPLMParams``,
  * a pruned :class:`~repro.serve.compress.ServingArtifact`,
  * an int8 :class:`~repro.serve.compress.QuantizedArtifact` — served
    INT8-NATIVE: the codes/scales are kept as-is and the sparse paths
    run the int8 gather ops (``lsplm_sparse_forward_int8`` /
    ``sparse_gather_matmul_int8``), which gather packed int8 code rows
    and fold the per-row fp32 scale into each slot's value — fp32 rows
    are never materialised, the row gather moves ~4x fewer bytes, and
    the scores are the dequantise-then-score numbers up to fp32 rounding
    (bounded-error vs the unquantised fp32 model, see
    ``serve.compress``). The one exception is the DENSE path,
    which has no gather to fuse into: it dequantises on the fly (a
    (R, 2m) multiply per call — fine off the hot path, wasteful on it).

Request formats:

  * :func:`score_dense`    — dense ``x (..., d)`` rows;
  * :func:`score_sparse`   — flat padded-COO ``(ids, vals)`` rows, the
    production wire format, on the fused sparse kernel;
  * :func:`score_bundles`  — SESSION-SHARED sparse scoring (the serving
    side of Eq. 13, §3.2): each page view is one user id list + N ad
    candidates; the user half of Theta^T x is gathered and contracted
    ONCE per bundle and broadcast over its candidates. Versus the naive
    per-ad path (:func:`score_bundles_naive` — user ids concatenated
    into every candidate's id list) this removes the (N-1)/N redundant
    user gathers, which is where bundle throughput comes from
    (``benchmarks/bench_serve.py``).

Artifact requests stay in the ORIGINAL id space: ids are remapped to
compact rows by one gather through ``artifact.remap`` before hitting the
kernel, so pruned scoring is bit-identical on the sparse paths (same
gathered row values, same per-sample contraction shapes). The DENSE path
on an artifact contracts over the R alive columns instead of all d —
a shorter, reassociated reduction — so parity there is <= 1e-6, not
bitwise (documented acceptance carve-out).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.lsplm import LSPLMParams
from repro.kernels.lsplm_sparse_fused.ops import (
    finalize_p,
    logps_from_z,
    lsplm_sparse_forward,
    lsplm_sparse_forward_int8,
    pad_theta,
    sparse_gather_matmul,
    sparse_gather_matmul_int8,
)
from repro.serve.compress import QuantizedArtifact, ServingArtifact


class ScoreBundle(NamedTuple):
    """A batch of page-view bundles: G user rows, B = sum of candidates.

    Ids address the ORIGINAL feature space (pad id == d) regardless of
    whether the model is pruned — remapping is the scorer's job.
    """

    user_ids: jax.Array  # (G, Ku) int32
    user_vals: jax.Array  # (G, Ku)
    ad_ids: jax.Array  # (B, Ka) int32
    ad_vals: jax.Array  # (B, Ka)
    session_id: jax.Array  # (B,) int32 in [0, G)


class ServingModel(NamedTuple):
    """Normalised model: kernel-ready rows + optional id remap.

    Exactly one of ``theta`` (fp32 models) or ``codes``/``scales``
    (int8-native models) is set; :attr:`is_int8` is the dispatch bit the
    scoring paths branch on."""

    theta: jax.Array | None  # (D, 2m) with the trailing zero pad row
    remap: jax.Array | None  # (d+1,) int32, None for full models
    alive_ids: jax.Array | None  # (R,) int32, None for full models
    num_features: int  # original d
    codes: jax.Array | None = None  # (D, 2m) int8, int8-native models only
    scales: jax.Array | None = None  # (D,) fp32 row scales (pad row == 0)

    @property
    def is_int8(self) -> bool:
        return self.codes is not None

    def dense_theta(self) -> jax.Array:
        """The padded fp32 row matrix — int8 models dequantise ON THE
        FLY here (the dense path's documented carve-out; the sparse
        paths never call this)."""
        if self.codes is not None:
            return self.codes.astype(jnp.float32) * self.scales[:, None]
        return self.theta


def as_model(model) -> ServingModel:
    """Coerce any accepted model form (see module docstring); idempotent."""
    if isinstance(model, ServingModel):
        return model
    if isinstance(model, QuantizedArtifact):
        # int8-native: keep the codes/scales — the sparse scorers fuse
        # the scale into the gather instead of rebuilding fp32 rows
        return ServingModel(theta=None, remap=model.remap,
                            alive_ids=model.alive_ids,
                            num_features=model.num_features,
                            codes=model.codes, scales=model.scales)
    if isinstance(model, ServingArtifact):
        return ServingModel(theta=model.theta, remap=model.remap,
                            alive_ids=model.alive_ids,
                            num_features=model.num_features)
    if isinstance(model, LSPLMParams):
        model = model.theta
    theta = jnp.asarray(model)
    if theta.ndim != 2 or theta.shape[1] % 2:
        raise ValueError(f"expected an unpadded (d, 2m) Theta, got {theta.shape}")
    return ServingModel(theta=pad_theta(theta), remap=None, alive_ids=None,
                        num_features=theta.shape[0])


def _request_ids(model: ServingModel, ids: jax.Array) -> jax.Array:
    """Original-space ids -> kernel ids (compact for pruned models)."""
    if model.remap is None:
        return ids
    return jnp.take(model.remap, ids, axis=-1)


def _z_sparse(model: ServingModel, ids, vals, *, mode, dedup, plan):
    """Region logits for flat padded-COO rows, routed by model dtype:
    int8-native models run the scale-fused int8 gather (plans never
    apply — quantised models are always remapped artifacts, and plans
    are rejected on those before this is reached)."""
    if model.is_int8:
        return sparse_gather_matmul_int8(ids, vals, model.codes,
                                         model.scales, mode=mode,
                                         dedup=dedup)
    return sparse_gather_matmul(ids, vals, model.theta, mode=mode,
                                dedup=dedup, plan=plan)


def score_dense(model, x: jax.Array) -> jax.Array:
    """p(y=1|x) for dense rows x (..., d). Pruned models contract over
    the alive columns only (<= 1e-6 vs full — see module docstring);
    int8 models dequantise on the fly (no gather to fuse the scale
    into — the dense path's carve-out)."""
    model = as_model(model)
    if model.alive_ids is not None:
        x = jnp.take(x, model.alive_ids, axis=-1)
    return finalize_p(x @ model.dense_theta()[:-1])


def score_sparse(model, ids: jax.Array, vals: jax.Array, *,
                 mode: str = "auto", dedup: bool = True,
                 plan=None) -> jax.Array:
    """p(y=1|x) for flat padded-COO rows (N, K) on the fused kernel.

    ``plan`` (a full-model :class:`TransposePlan`) keeps a differentiated
    call's backward sort-free; plans address the full padded Theta, so
    they cannot be combined with a pruned model."""
    model = as_model(model)
    if plan is not None and model.remap is not None:
        raise ValueError("transpose plans address the full Theta layout; "
                         "rebuild the plan in compact space or score the "
                         "full model")
    if model.is_int8:
        return lsplm_sparse_forward_int8(_request_ids(model, ids), vals,
                                         model.codes, model.scales,
                                         mode=mode, dedup=dedup)
    return lsplm_sparse_forward(_request_ids(model, ids), vals, model.theta,
                                mode=mode, dedup=dedup, plan=plan)


def score_sparse_logps(model, ids: jax.Array, vals: jax.Array, *,
                       mode: str = "auto", dedup: bool = True,
                       plan=None) -> tuple[jax.Array, jax.Array]:
    """Stable (log_p1, log_p0) for flat padded-COO rows (the Eq. 5 eval
    head on the serving layer)."""
    model = as_model(model)
    if plan is not None and model.remap is not None:
        raise ValueError("transpose plans address the full Theta layout")
    z = _z_sparse(model, _request_ids(model, ids), vals, mode=mode,
                  dedup=dedup, plan=plan)
    return logps_from_z(z)


def bundle_logits(model, bundle: ScoreBundle, *, mode: str = "auto",
                  dedup: bool = True, user_plan=None,
                  ad_plan=None) -> jax.Array:
    """Session-shared region logits z (B, 2m): the user contraction runs
    once per bundle (G rows), then broadcasts over candidates (Eq. 13).

    ``user_plan``/``ad_plan`` (full-model transpose plans for the bundle's
    id tensors) keep a DIFFERENTIATED call's backward sort-free — the
    training-eval path passes a ``SparseCTRBatch``'s plans through here."""
    model = as_model(model)
    if (user_plan is not None or ad_plan is not None) \
            and model.remap is not None:
        raise ValueError("transpose plans address the full Theta layout; "
                         "they cannot be combined with a pruned artifact")
    z_user = _z_sparse(model, _request_ids(model, bundle.user_ids),
                       bundle.user_vals, mode=mode, dedup=dedup,
                       plan=user_plan)
    z_ad = _z_sparse(model, _request_ids(model, bundle.ad_ids),
                     bundle.ad_vals, mode=mode, dedup=dedup, plan=ad_plan)
    return z_user[bundle.session_id] + z_ad


def score_bundles(model, bundle: ScoreBundle, *, mode: str = "auto",
                  dedup: bool = True, user_plan=None,
                  ad_plan=None) -> jax.Array:
    """p(y=1|x) (B,) for session-grouped bundles — the serving hot path."""
    return finalize_p(bundle_logits(model, bundle, mode=mode, dedup=dedup,
                                    user_plan=user_plan, ad_plan=ad_plan))


def score_bundles_naive(model, bundle: ScoreBundle, *, mode: str = "auto",
                        dedup: bool = True) -> jax.Array:
    """The un-shared baseline: every candidate re-carries its bundle's
    user ids, so the user gathers/contractions run N times per page view
    instead of once. Identical scores; bench_serve measures the gap."""
    ids = jnp.concatenate(
        [bundle.user_ids[bundle.session_id], bundle.ad_ids], axis=-1)
    vals = jnp.concatenate(
        [bundle.user_vals[bundle.session_id], bundle.ad_vals], axis=-1)
    return score_sparse(model, ids, vals, mode=mode, dedup=dedup)


def predict(model, request, *, mode: str = "auto") -> jax.Array:
    """Unified entry: dispatch on the request's structure.

    * session-grouped sparse (has ``user_ids``/``ad_ids``/``session_id``,
      e.g. :class:`ScoreBundle` or a ``SparseCTRBatch``) -> shared path;
    * a ``(ids, vals)`` pair -> flat sparse;
    * a dense array ``(..., d)`` -> dense.
    """
    if hasattr(request, "user_ids") and hasattr(request, "session_id"):
        # a SparseCTRBatch carries transpose plans; thread them through so
        # differentiated full-model calls keep the sort-free backward
        # (score_bundles rejects plans on pruned models)
        model_n = as_model(model)
        user_plan = getattr(request, "user_plan", None)
        ad_plan = getattr(request, "ad_plan", None)
        if model_n.remap is not None:
            user_plan = ad_plan = None  # inference-only on artifacts
        return score_bundles(model_n, ScoreBundle(
            user_ids=request.user_ids, user_vals=request.user_vals,
            ad_ids=request.ad_ids, ad_vals=request.ad_vals,
            session_id=request.session_id), mode=mode,
            user_plan=user_plan, ad_plan=ad_plan)
    if isinstance(request, (tuple, list)) and len(request) == 2:
        ids, vals = request
        return score_sparse(model, ids, vals, mode=mode)
    return score_dense(model, jnp.asarray(request))
