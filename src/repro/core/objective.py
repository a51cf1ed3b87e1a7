"""Objective Eq. 4/5: f(Theta) = NLL + lambda*||Theta||_{2,1} + beta*||Theta||_1.

All functions operate on Theta as a single (d, 2m) array (the paper's
parameter layout; feature rows are L2,1 groups). The smooth part (NLL) is
differentiable everywhere; the regularisers are handled by the optimizer via
directional derivatives (Eq. 9), so ``smooth_loss_and_grad`` is what the
optimizer consumes.

Supports the common-feature trick (§3.2): when a batch carries
(x_common [G,d_c], session_id [B]) alongside x_noncommon [B,d_nc], the
common part of the dot products is computed once per session group and
gathered per sample (Eq. 13).

Sparse dispatch: batches shaped like ``repro.data.sparse.SparseCTRBatch``
(padded-COO ``user_ids``/``ad_ids`` id lists instead of dense x) or
``FlatCTRBatch`` (sessionless ``ids``) are detected structurally and
routed to ``nll_sparse``, which runs on the
fused sparse kernel (``repro.kernels.lsplm_sparse_fused``) — Pallas
gather-matmul on TPU, chunked jnp elsewhere, scatter-add custom VJP.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import regularizers
from repro.core.lsplm import params_from_theta, predict_logits_stable
from repro.kernels.lsplm_sparse_fused.ops import (
    logps_from_z,
    pad_theta,
    sparse_gather_matmul,
)


class CTRBatch(NamedTuple):
    """A plain (uncompressed) batch."""

    x: jax.Array  # (B, d) dense or pre-embedded sparse features
    y: jax.Array  # (B,) in {0, 1}
    weight: jax.Array | None = None  # (B,) optional sample weights


class CommonFeatureBatch(NamedTuple):
    """Compressed batch per §3.2 (Eq. 13).

    Feature space is split: the first ``d_c`` feature columns are "common"
    (user features shared within one page-view session), the remaining
    ``d_nc`` are per-sample (ad features). x = [x_common ; x_noncommon].
    """

    x_common: jax.Array  # (G, d_c)   one row per session group
    x_noncommon: jax.Array  # (B, d_nc)
    session_id: jax.Array  # (B,) int in [0, G)
    y: jax.Array  # (B,)
    weight: jax.Array | None = None


def _nll_from_logps(log_p1, log_p0, y, weight):
    per = -(y * log_p1 + (1.0 - y) * log_p0)
    if weight is not None:
        per = per * weight
    return jnp.sum(per)


def nll(theta: jax.Array, batch: CTRBatch) -> jax.Array:
    """Eq. 5 — total (summed) negative log-likelihood."""
    params = params_from_theta(theta)
    log_p1, log_p0 = predict_logits_stable(params, batch.x)
    return _nll_from_logps(log_p1, log_p0, batch.y.astype(log_p1.dtype), batch.weight)


def nll_common_feature(theta: jax.Array, batch: CommonFeatureBatch) -> jax.Array:
    """Eq. 5 evaluated with the common-feature decomposition (Eq. 13).

    z = x @ Theta = x_c @ Theta_c  (once per group, gathered) + x_nc @ Theta_nc
    """
    d_c = batch.x_common.shape[-1]
    theta_c, theta_nc = theta[:d_c], theta[d_c:]
    z_c = batch.x_common @ theta_c  # (G, 2m) — computed ONCE per session
    z = z_c[batch.session_id] + batch.x_noncommon @ theta_nc  # (B, 2m)
    m = theta.shape[-1] // 2
    zu, zw = z[..., :m], z[..., m:]
    log_gate = jax.nn.log_softmax(zu, axis=-1)
    log_p1 = jax.nn.logsumexp(log_gate + jax.nn.log_sigmoid(zw), axis=-1)
    log_p0 = jax.nn.logsumexp(log_gate + jax.nn.log_sigmoid(-zw), axis=-1)
    return _nll_from_logps(log_p1, log_p0, batch.y.astype(log_p1.dtype), batch.weight)


def is_flat_batch(batch) -> bool:
    """Structural check for sessionless padded COO (FlatCTRBatch)."""
    return hasattr(batch, "ids") and hasattr(batch, "plan")


def is_sparse_batch(batch) -> bool:
    """Structural check for a padded-COO sparse batch (SparseCTRBatch or
    FlatCTRBatch)."""
    return (hasattr(batch, "ad_ids") and hasattr(batch, "user_ids")) \
        or is_flat_batch(batch)


def nll_sparse(theta: jax.Array, batch, *, mode: str = "auto") -> jax.Array:
    """Eq. 5 on padded-COO sparse features with the common-feature trick
    (Eq. 13): user region-logits once per session group, gathered per
    sample. Both gather-matmuls run on the fused sparse kernel, so the
    backward is the transposed scatter into active Theta rows only —
    sort-free when the batch carries precomputed transpose plans
    (``repro.data.sparse.build_batch_plans``), scan-chunked otherwise.
    A sessionless batch (``FlatCTRBatch``) runs one gather-matmul over
    its rows, with no user side and no session gather.
    """
    tp = pad_theta(theta)
    if is_flat_batch(batch):
        z = sparse_gather_matmul(batch.ids, batch.vals, tp, mode=mode,
                                 plan=batch.plan)
        log_p1, log_p0 = logps_from_z(z)
        return _nll_from_logps(log_p1, log_p0,
                               batch.y.astype(log_p1.dtype), None)
    z_user = sparse_gather_matmul(batch.user_ids, batch.user_vals, tp,
                                  mode=mode,
                                  plan=getattr(batch, "user_plan", None))
    z_ad = sparse_gather_matmul(batch.ad_ids, batch.ad_vals, tp, mode=mode,
                                plan=getattr(batch, "ad_plan", None))
    z = z_user[batch.session_id] + z_ad
    log_p1, log_p0 = logps_from_z(z)
    return _nll_from_logps(log_p1, log_p0, batch.y.astype(log_p1.dtype), None)


def _nll_fn(batch, common_feature: bool):
    if is_sparse_batch(batch):
        return nll_sparse
    return nll_common_feature if common_feature else nll


def objective(
    theta: jax.Array, batch, lam: float, beta: float, *, common_feature: bool = False
) -> jax.Array:
    """f(Theta), Eq. 4. Used by tests and the line search. Dense,
    common-feature and sparse (padded-COO) batches all dispatch here."""
    loss = _nll_fn(batch, common_feature)(theta, batch)
    return loss + lam * regularizers.l21_norm(theta) + beta * regularizers.l1_norm(theta)


def smooth_loss_and_grad(theta: jax.Array, batch, *, common_feature: bool = False):
    """(loss(Theta), grad loss(Theta)) for the smooth NLL part only."""
    return jax.value_and_grad(_nll_fn(batch, common_feature))(theta, batch)
