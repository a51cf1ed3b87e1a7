"""Plan-driven scatter-add ops — the sparse LS-PLM backward engine.

Public surface:

  * ``scatter_add_planned(plan, vals, dz, *, mode)`` -> dTheta (D, 2m):
    the transposed scatter as pure gathers + segment reductions driven by
    a precomputed :class:`~.plan.TransposePlan`. No sort, no XLA scatter,
    no data-dependent work inside the step.
  * ``dvals_planned(plan, theta, dz, shape)`` -> dvals (N, K): the gather
    half of the backward, read through the plan's sorted layout so the
    Theta row reads are id-ordered (cache/DMA friendly: duplicate ids
    are adjacent instead of strewn across the batch).
  * ``plan`` may be a :class:`~.plan.SlicedPlan` (a batch split by
    samples where one call's dz would not fit the kernel's VMEM): both
    ops then run once per slice.
  * ``scatter_add_ref(ids, vals, dz, num_rows)``: the direct ``.at[].add``
    oracle the tests and benchmarks compare against.

``mode`` mirrors the fused-forward dispatch:
    "auto"      Pallas run-length kernel on TPU, class-gather jnp elsewhere
    "kernel"    force the compiled Pallas kernel
    "interpret" force the Pallas kernel in interpret mode (tests/CI)
    "jnp"       force the class-gather jnp path

jnp path mechanics: for each popularity class the plan provides a dense
(uc*c,) gather table into the batch entries; the class's per-id sums are

    (vals[src] * mask)[:, None] * dz[samp]  ->  reshape(uc, c, 2m).sum(1)

— one fused gather-multiply-reduce per class, every index known to be in
bounds (``promise_in_bounds``), so XLA emits no clamps, no sorts and no
serial scatter loop. The class results concatenate into a compact
(U+1, 2m) table (trailing zero row) and densify with one plain gather
through ``plan.inv_compact``. This is what makes the planned backward
>=2x faster than the chunked ``.at[].add`` scatter on CPU at production
sparsity (see ``benchmarks/bench_sparse_fused.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.lsplm_sparse_scatter.lsplm_sparse_scatter import (
    lsplm_sparse_scatter_compact,
)
from repro.kernels.lsplm_sparse_scatter.plan import (  # noqa: F401  (re-export)
    SlicedPlan,
    TransposePlan,
    build_transpose_plan,
    plan_slices,
)
from repro.tune import table as tune

_SCATTER_BLOCK_E = 1024  # builtin default entry block (autotune table wins)


def _take(a: jax.Array, idx: jax.Array, *, unique: bool = False) -> jax.Array:
    """Gather with plan-guaranteed in-bounds indices (no clamp codegen)."""
    return a.at[idx].get(mode="promise_in_bounds", unique_indices=unique)


def _use_kernel(mode: str) -> bool:
    if mode == "auto":
        return jax.default_backend() == "tpu"
    if mode in ("kernel", "interpret"):
        return True
    if mode == "jnp":
        return False
    raise ValueError(f"unknown mode {mode!r}")


def scatter_add_ref(ids: jax.Array, vals: jax.Array, dz: jax.Array,
                    num_rows: int) -> jax.Array:
    """Oracle: dTheta[r] = sum_{ids[n,k]=r} vals[n,k] * dz[n] (direct)."""
    m2 = dz.shape[-1]
    data = (vals.astype(jnp.float32)[..., None]
            * dz.astype(jnp.float32)[:, None, :]).reshape(-1, m2)
    return jnp.zeros((num_rows, m2), jnp.float32).at[ids.reshape(-1)].add(data)


def _compact_classes(plan: TransposePlan, vals: jax.Array,
                     dz: jax.Array) -> jax.Array:
    """Class-gather segment sums -> compact (U+1, 2m), class-major order."""
    m2 = dz.shape[-1]
    vflat = vals.reshape(-1).astype(jnp.float32)
    dz = dz.astype(jnp.float32)
    outs = []
    for src, samp, mask, width in zip(plan.class_src, plan.class_samp,
                                      plan.class_mask, plan.class_width):
        v = _take(vflat, src) * mask.astype(jnp.float32)
        rows = (v[:, None] * _take(dz, samp)).reshape(-1, width, m2)
        outs.append(rows.sum(axis=1))
    outs.append(jnp.zeros((1, m2), jnp.float32))
    return jnp.concatenate(outs, axis=0)


def scatter_add_planned(
    plan: TransposePlan | SlicedPlan,
    vals: jax.Array,   # (N, K)
    dz: jax.Array,     # (N, 2m)
    *,
    mode: str = "auto",
    block_e: int | None = None,
) -> jax.Array:
    """dTheta (D, 2m) from the precomputed transpose plan. Race-free by
    construction: every output row is produced by exactly one segment.
    ``block_e=None`` resolves from the autotune table (``repro.tune``)
    by the (entry-count, 2m) envelope; an explicit value wins. A
    :class:`SlicedPlan` runs once per slice of samples, each result
    densified and summed."""
    if isinstance(plan, SlicedPlan):
        parts = [scatter_add_planned(p, vals[lo:hi], dz[lo:hi], mode=mode,
                                     block_e=block_e)
                 for lo, hi, p in plan.slices()]
        return functools.reduce(jnp.add, parts)
    if _use_kernel(mode):
        if block_e is None:
            env = tune.scatter_envelope(plan.num_kept, dz.shape[-1])
            block_e = tune.resolve("scatter", env, mode=mode)["block_e"]
        row_ids, sample_sorted, vals_sorted = pad_plan_entries(
            plan, vals, block_e=block_e)
        compact = lsplm_sparse_scatter_compact(
            row_ids, sample_sorted, vals_sorted, dz,
            num_unique=plan.num_unique, num_kept=plan.num_kept,
            block_e=block_e, interpret=mode == "interpret")
        return _take(compact, plan.inv_sorted, unique=False)
    compact = _compact_classes(plan, vals, dz)
    return _take(compact, plan.inv_compact, unique=False)


def dvals_planned(
    plan: TransposePlan | SlicedPlan,
    theta: jax.Array,  # (D, 2m)
    dz: jax.Array,     # (N, 2m)
    shape: tuple[int, int],
) -> jax.Array:
    """dvals[n,k] = theta[ids[n,k]] . dz[n] via the sorted layout.

    The Theta gather runs in id order (duplicates adjacent — the hot-id
    rows are read once per cache line instead of once per occurrence)
    and the result is permuted back to (N, K) with one gather; dropped
    pad entries land on the appended zero slot. A :class:`SlicedPlan`
    does this per slice and concatenates the slices' rows.
    """
    if isinstance(plan, SlicedPlan):
        return jnp.concatenate(
            [dvals_planned(p, theta, dz[lo:hi], (hi - lo, shape[1]))
             for lo, hi, p in plan.slices()], axis=0)
    rows = _take(theta.astype(jnp.float32), plan.row_ids)
    dv_sorted = (rows * _take(dz.astype(jnp.float32),
                              plan.sample_sorted)).sum(axis=-1)
    dv_sorted = jnp.concatenate([dv_sorted, jnp.zeros((1,), jnp.float32)])
    return _take(dv_sorted, plan.rank).reshape(shape)


def pad_plan_entries(
    plan: TransposePlan,
    vals: jax.Array,
    *,
    block_e: int = _SCATTER_BLOCK_E,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sentinel-pad the plan's sorted entries for the Pallas kernel.

    Appends >=1 sentinel entry (id == num_rows — larger than any real id,
    so it terminates the last run) and rounds up to a ``block_e``
    multiple. Returns (row_ids, sample_sorted, vals_sorted), each
    (E_pad,); sentinel slots carry sample 0 and value 0.
    """
    e = plan.num_kept
    e_pad = ((e + 1 + block_e - 1) // block_e) * block_e
    n_sent = e_pad - e
    sentinel_id = jnp.full((n_sent,), plan.num_rows, jnp.int32)
    sentinel_n = jnp.zeros((n_sent,), jnp.int32)
    vals_sorted = _take(vals.reshape(-1).astype(jnp.float32), plan.order)
    return (
        jnp.concatenate([plan.row_ids, sentinel_id]),
        jnp.concatenate([plan.sample_sorted, sentinel_n]),
        jnp.concatenate([vals_sorted, jnp.zeros((n_sent,), jnp.float32)]),
    )
