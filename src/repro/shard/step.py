"""Distributed sparse loss/grad — the paper's worker/server split run as
one ``shard_map`` over a (data, model) mesh.

Per device, the program is exactly the single-device fused path on its
own block: Theta row block (its id range, padded), its data block's
routed (ids, vals) and plan cell. The only cross-device traffic is

  * one ``psum`` of the (B_local, 2m) region-logit PARTIALS over 'model'
    (each server shard contributes the rows it owns — Fig. 5's
    pull/push collapsed into a single reduction), and
  * one scalar ``psum`` of the per-block NLL over the data axis.

The backward needs nothing extra: the transpose of the 'model' psum
broadcasts dz to every server shard, whose plan-driven scatter then
produces exactly its own rows of dTheta — the row-sharded gradient the
sharded OWLQN+ step (``repro.dist``) consumes in place. The fused
forward kernels are the SAME ones the single-device path runs
(``lsplm_sparse_fused``), invoked per shard on local ids.

Composition: ``make_sharded_sparse_loss`` is a drop-in
``loss_and_grad`` for :class:`~repro.optim.owlqn_plus.OWLQNPlus`;
``dist.make_distributed_step`` then keeps the whole optimizer state
row-sharded across iterations, orthant algebra and all (Theta rows are
the L2,1 groups — they never straddle shards).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import sparse_batch_specs
from repro.kernels.lsplm_sparse_fused.ops import (
    logps_from_z,
    pad_theta,
    sparse_gather_matmul,
)
from repro.launch.mesh import data_axes
from repro.shard.partition import ShardedSparseBatch
from repro.shard.plan_slicing import cell_plan


def _check_mesh(mesh, sbatch: ShardedSparseBatch) -> None:
    """The routed batch's (data, model) factorisation must equal the
    mesh's: a mismatch would make shard_map silently split the routed
    leading axes across the wrong number of devices (e.g. two id-range
    shards landing on one device, whose local pad id then aliases a real
    Theta row)."""
    model = mesh.shape["model"]
    data = 1
    for a in data_axes(mesh):
        data *= mesh.shape[a]
    if sbatch.num_shards != model or sbatch.data_shards != data:
        raise ValueError(
            f"batch routed for (data={sbatch.data_shards}, "
            f"model={sbatch.num_shards}) but mesh is (data={data}, "
            f"model={model}) — re-route with matching shard counts")


def sharded_sparse_nll(theta: jax.Array, sbatch: ShardedSparseBatch,
                       mesh, *, mode: str = "auto") -> jax.Array:
    """Eq. 5 NLL of the padded row-sharded Theta over the routed batch.

    ``theta`` is the (num_shards * rows_per_shard, 2m) PADDED layout
    (``Partition.pad_rows``), sharded — or shardable — as
    ``P('model', None)``: GSPMD's equal split of the leading axis IS the
    id-range split. Differentiable: ``jax.grad`` of this function yields
    the row-sharded dTheta with every scatter shard-local.
    """
    S, R = sbatch.num_shards, sbatch.rows_per_shard
    if theta.shape[0] != S * R:
        raise ValueError(
            f"theta has {theta.shape[0]} rows; routed batch expects the "
            f"padded layout {S} * {R} (Partition.pad_rows)")
    _check_mesh(mesh, sbatch)
    # ONE statement of the batch layout: the same specs shard_sparse_batch
    # placed the data with
    specs = sparse_batch_specs(mesh, sbatch)
    reduce_axes = data_axes(mesh)
    has_user_plan = sbatch.user_plan is not None
    has_ad_plan = sbatch.ad_plan is not None

    def local(theta_l, u_ids, u_vals, a_ids, a_vals, sid, y, *plans):
        it = iter(plans)
        u_plan = cell_plan(next(it)) if has_user_plan else None
        a_plan = cell_plan(next(it)) if has_ad_plan else None
        # theta_l varies over 'model' only; the routed batch also varies
        # over the data axes. Cast Theta varying there too, so its
        # cotangent (a per-data-block scatter) types-checks; the
        # transpose of this cast is the data-axis psum of dTheta.
        theta_l = jax.lax.pcast(theta_l, reduce_axes, to="varying")
        tp = pad_theta(theta_l)  # local zero pad row at index R
        z_u = sparse_gather_matmul(u_ids[0], u_vals[0], tp, mode=mode,
                                   plan=u_plan)
        z_a = sparse_gather_matmul(a_ids[0], a_vals[0], tp, mode=mode,
                                   plan=a_plan)
        # one reduction: every server shard's partial logits for the
        # local data block
        z = jax.lax.psum(z_u[sid] + z_a, "model")
        log_p1, log_p0 = logps_from_z(z)
        yf = y.astype(log_p1.dtype)
        nll = -jnp.sum(yf * log_p1 + (1.0 - yf) * log_p0)
        return jax.lax.psum(nll, reduce_axes)

    args = [sbatch.user_ids, sbatch.user_vals, sbatch.ad_ids, sbatch.ad_vals,
            sbatch.session_id, sbatch.y]
    in_specs = [P("model", None), specs.user_ids, specs.user_vals,
                specs.ad_ids, specs.ad_vals, specs.session_id, specs.y]
    if has_user_plan:
        args.append(sbatch.user_plan)
        in_specs.append(specs.user_plan)
    if has_ad_plan:
        args.append(sbatch.ad_plan)
        in_specs.append(specs.ad_plan)
    return jax.shard_map(local, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=P())(theta, *args)


def sharded_sparse_loss_and_grad(theta: jax.Array,
                                 sbatch: ShardedSparseBatch, mesh, *,
                                 mode: str = "auto"):
    """(NLL, row-sharded dTheta) — the smooth part OWLQN+ consumes."""
    return jax.value_and_grad(sharded_sparse_nll)(theta, sbatch, mesh,
                                                  mode=mode)


def make_sharded_sparse_loss(sbatch: ShardedSparseBatch, mesh, *,
                             mode: str = "auto"):
    """Bind batch + mesh into the ``loss_and_grad(theta)`` callable
    :class:`~repro.optim.owlqn_plus.OWLQNPlus` expects; compose with
    ``dist.make_distributed_step`` to keep the optimizer state sharded
    across iterations."""
    def loss_and_grad(theta):
        return sharded_sparse_loss_and_grad(theta, sbatch, mesh, mode=mode)

    return loss_and_grad
