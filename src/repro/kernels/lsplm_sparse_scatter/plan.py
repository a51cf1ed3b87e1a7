"""Transpose plan — the precomputed CSR-style layout for the sparse backward.

The backward of z = x @ Theta on padded COO is the transposed scatter

    dTheta[r] = sum_{(n,k): ids[n,k]=r} vals[n,k] * dz[n]

Scattering E = N*K rows into a (D, 2m) table is the training hot spot:
XLA lowers ``.at[].add`` to a serial per-update loop (CPU) or a sorted
scatter (TPU), and it re-derives the id->entries mapping EVERY step even
though full-batch OWLQN+ feeds the same batch every iteration. The
transpose plan hoists all data-dependent index computation out of the
step: it is built ONCE per batch on the host (numpy) and the step then
runs only dense gathers, reshapes and reductions — no sort, no scatter.

Layout (all device leaves int32; static sizes in the pytree aux data):

  * ``order``/``row_ids``/``sample_sorted``/``slot_sorted`` — the E' kept
    entries (pad-id entries dropped) sorted by column id: a COO->CSC
    transposition recorded as a permutation.
  * ``classes`` — the segment-sum schedule. Unique ids are bucketed by
    popularity: class c holds ids whose entry count is in (c/2, c]
    (power-of-two widths), each padded to exactly c slots. A class is a
    dense (uc, c) gather table into the sorted entries, so its segment
    sums are one gather + reshape + ``sum(axis=1)`` — vectorisable
    everywhere, race-free by construction, and ≤2x padding waste even
    for Zipf-hot traffic (real CTR id distributions).
  * ``inv_compact`` — (D,) map from column id to its row in the compact
    per-unique-id result (U for untouched ids, which points at an
    appended zero row), turning the final densification into one plain
    gather instead of a scatter. ``inv_sorted`` is the same map for
    results in sorted-unique order — the layout the Pallas run-length
    kernel emits (classes reorder ids by popularity; the kernel walks
    them in id order).
  * ``rank`` — original entry -> sorted position (E'-pointing for
    dropped pad entries), so dvals comes back in (N, K) order with a
    gather as well.

The same plan drives the jnp segment-sum path (`ops.scatter_add_planned`),
the Pallas run-accumulate kernel (`lsplm_sparse_scatter.py`) and the
fused forward/backward custom VJPs in ``lsplm_sparse_fused.ops``.

A batch whose backward would not fit one scatter call is planned as a
:class:`SlicedPlan`: the samples split into contiguous slices, each with
its own plan over the same Theta rows (``build_transpose_plan(...,
max_samples=)``). Each consumer runs once per slice and sums (dTheta) or
concatenates (dvals) the results.

Shapes in the plan are data-dependent (U, E' and the class split change
with the batch), so jitted consumers recompile when the batch changes.
That is the intended trade: the paper's OWLQN+ is full-batch — one batch,
hundreds of iterations — and streaming variants re-plan per day, not per
step.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
class TransposePlan:
    """Precomputed id->entries transposition of a padded-COO batch.

    Device arrays are all int32 (so custom-VJP cotangents are uniformly
    ``float0``); every size that determines an output shape is static
    python metadata carried in the pytree aux data.
    """

    def __init__(self, *, class_src, class_samp, class_mask, class_width,
                 row_ids, sample_sorted, slot_sorted, order, rank,
                 inv_compact, inv_sorted, num_rows: int, num_entries: int,
                 num_kept: int, num_unique: int):
        self.class_src = tuple(class_src)     # per class: (uc*c,) into entries
        self.class_samp = tuple(class_samp)   # per class: (uc*c,) sample index
        self.class_mask = tuple(class_mask)   # per class: (uc*c,) 0/1 pad mask
        self.class_width = tuple(int(c) for c in class_width)
        self.row_ids = row_ids                # (E',) sorted column ids
        self.sample_sorted = sample_sorted    # (E',) entry -> sample n
        self.slot_sorted = slot_sorted        # (E',) entry -> slot k
        self.order = order                    # (E',) sorted pos -> flat entry
        self.rank = rank                      # (N*K,) flat entry -> sorted pos
        self.inv_compact = inv_compact        # (D,) id -> compact row (U: zero)
        self.inv_sorted = inv_sorted          # (D,) id -> sorted-unique row
        self.num_rows = int(num_rows)         # D (padded Theta rows)
        self.num_entries = int(num_entries)   # N*K
        self.num_kept = int(num_kept)         # E' after pad-id drop
        self.num_unique = int(num_unique)     # U distinct non-pad ids

    def tree_flatten(self):
        children = (self.class_src, self.class_samp, self.class_mask,
                    self.row_ids, self.sample_sorted, self.slot_sorted,
                    self.order, self.rank, self.inv_compact, self.inv_sorted)
        aux = (self.class_width, self.num_rows, self.num_entries,
               self.num_kept, self.num_unique)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        (class_src, class_samp, class_mask, row_ids, sample_sorted,
         slot_sorted, order, rank, inv_compact, inv_sorted) = children
        class_width, num_rows, num_entries, num_kept, num_unique = aux
        return cls(class_src=class_src, class_samp=class_samp,
                   class_mask=class_mask, class_width=class_width,
                   row_ids=row_ids, sample_sorted=sample_sorted,
                   slot_sorted=slot_sorted, order=order, rank=rank,
                   inv_compact=inv_compact, inv_sorted=inv_sorted,
                   num_rows=num_rows, num_entries=num_entries,
                   num_kept=num_kept, num_unique=num_unique)

    def validate(self, ids_shape: tuple, theta_rows: int) -> None:
        n, k = ids_shape
        if n * k != self.num_entries:
            raise ValueError(
                f"plan was built for {self.num_entries} entries, batch has "
                f"{n}x{k}={n * k}")
        if theta_rows != self.num_rows:
            raise ValueError(
                f"plan was built for {self.num_rows} Theta rows, got "
                f"{theta_rows}")


@jax.tree_util.register_pytree_node_class
class SlicedPlan:
    """A batch's transpose plan split by samples: ``plans[i]`` is the
    :class:`TransposePlan` of samples ``[bounds[i], bounds[i + 1])``,
    each addressing the same ``num_rows`` Theta rows. The bounds are
    static; each slice's backward is one scatter call."""

    def __init__(self, plans, bounds):
        self.plans = tuple(plans)
        self.bounds = tuple(int(b) for b in bounds)
        if len(self.bounds) != len(self.plans) + 1:
            raise ValueError(f"{len(self.plans)} slices need "
                             f"{len(self.plans) + 1} bounds, got {self.bounds}")

    def tree_flatten(self):
        return self.plans, self.bounds

    @classmethod
    def tree_unflatten(cls, bounds, plans):
        return cls(plans, bounds)

    def slices(self):
        """(lo, hi, plan) per slice, in sample order."""
        return [(lo, hi, p) for lo, hi, p
                in zip(self.bounds[:-1], self.bounds[1:], self.plans)]

    def validate(self, ids_shape: tuple, theta_rows: int) -> None:
        n, k = ids_shape
        if self.bounds[0] != 0 or self.bounds[-1] != n:
            raise ValueError(f"plan slices cover samples [{self.bounds[0]}, "
                             f"{self.bounds[-1]}), batch has {n}")
        for lo, hi, p in self.slices():
            p.validate((hi - lo, k), theta_rows)


def plan_slices(plan) -> tuple:
    """The per-call plans of ``plan``: its slices, or the plan itself."""
    return plan.plans if isinstance(plan, SlicedPlan) else (plan,)


def assemble_plan_from_sorted(
    srt: np.ndarray,
    order: np.ndarray,
    *,
    num_rows: int,
    num_entries: int,
    num_cols: int,
) -> TransposePlan:
    """Assemble a :class:`TransposePlan` from already-sorted entries.

    The data-dependent SORT is the only part of plan construction that is
    expensive; everything after it (popularity classes, inverse maps,
    rank) is determined by the sorted layout alone. Factoring it out lets
    ``repro.shard.plan_slicing`` slice a full-batch plan at id-range /
    sample-range boundaries and rebuild shard-local plans that are
    bit-identical to ``build_transpose_plan`` on the shard-local ids —
    WITHOUT re-sorting them.

    Args:
      srt: (E',) kept column ids sorted ascending (stable w.r.t. flat
        entry order within equal ids).
      order: (E',) sorted position -> flat entry index in the (N, K)
        grid the plan addresses (``num_entries == N * num_cols``).
      num_rows: D, rows of the padded Theta the plan addresses.
      num_entries: N * K of the addressed ids grid.
      num_cols: K of the addressed ids grid (recovers n = order // K).
    """
    srt = np.asarray(srt, np.int64)
    order = np.asarray(order, np.int64)
    E_kept = int(srt.size)
    K = int(num_cols)
    E = int(num_entries)

    uniq, counts = np.unique(srt, return_counts=True)
    U = int(uniq.size)
    ptr = np.concatenate([[0], np.cumsum(counts)]) if U else np.zeros(1, np.int64)

    # popularity classes: width c = 2^ceil(log2(count)), ids padded to c
    cls = np.ones_like(counts)
    if U:
        cls = np.where(
            counts <= 1, 1,
            1 << np.ceil(np.log2(counts)).astype(np.int64))
    class_src, class_samp, class_mask, class_width = [], [], [], []
    dest_parts = []
    for c in np.unique(cls):
        sel = np.nonzero(cls == c)[0]
        cnts = counts[sel]
        js = np.arange(int(c))
        pos = ptr[sel][:, None] + js[None, :]          # sorted positions
        valid = js[None, :] < cnts[:, None]
        pos = np.where(valid, pos, 0)
        src = order[pos]                               # original entries
        class_src.append(jnp.asarray(src.reshape(-1).astype(np.int32)))
        class_samp.append(jnp.asarray((src.reshape(-1) // K).astype(np.int32)))
        class_mask.append(jnp.asarray(valid.reshape(-1).astype(np.int32)))
        class_width.append(int(c))
        dest_parts.append(sel)

    # compact row order == class-major order of unique ids
    inv_compact = np.full(num_rows, U, np.int64)       # U -> appended zero row
    if dest_parts:
        dest = np.concatenate(dest_parts)          # compact row -> unique idx
        compact_pos = np.empty(U, np.int64)
        compact_pos[dest] = np.arange(U)           # unique idx -> compact row
        inv_compact[uniq] = compact_pos

    inv_sorted = np.full(num_rows, U, np.int64)        # U -> appended zero row
    inv_sorted[uniq] = np.arange(U)

    rank = np.full(E, E_kept, np.int64)                # dropped -> zero slot
    rank[order] = np.arange(E_kept)

    return TransposePlan(
        class_src=class_src, class_samp=class_samp, class_mask=class_mask,
        class_width=class_width,
        row_ids=jnp.asarray(srt.astype(np.int32)),
        sample_sorted=jnp.asarray((order // K).astype(np.int32)),
        slot_sorted=jnp.asarray((order % K).astype(np.int32)),
        order=jnp.asarray(order.astype(np.int32)),
        rank=jnp.asarray(rank.astype(np.int32)),
        inv_compact=jnp.asarray(inv_compact.astype(np.int32)),
        inv_sorted=jnp.asarray(inv_sorted.astype(np.int32)),
        num_rows=int(num_rows), num_entries=E, num_kept=E_kept,
        num_unique=U)


def build_transpose_plan(
    ids: Any,
    num_rows: int,
    *,
    pad_id: int | None = None,
    max_samples: int | None = None,
) -> TransposePlan | SlicedPlan:
    """Build the per-batch transpose plan on the host (numpy, no jit).

    Args:
      ids: (N, K) int column ids of the padded-COO batch.
      num_rows: D, the number of rows of the PADDED Theta the batch will
        be contracted against (``d + 1`` with the zero pad row appended).
      pad_id: if given, entries with this id are dropped from the plan —
        their values are 0 by the padded-COO convention, so they
        contribute nothing and hot pad slots stop costing segment work.
        The pad row's cotangent is exactly 0 either way.
      max_samples: if N exceeds it, the samples are split into the
        fewest contiguous slices of at most ``max_samples`` (their sizes
        differ by at most one) and a :class:`SlicedPlan` of one plan per
        slice is returned.

    Cost: one argsort + unique over N*K int32 — tens of ms at production
    batch sizes, paid once per batch (not per optimizer step).
    """
    ids = np.asarray(ids)
    if ids.ndim != 2:
        raise ValueError(f"ids must be (N, K), got {ids.shape}")
    N, K = ids.shape
    if max_samples is not None and N > max_samples:
        n = -(-N // max(1, int(max_samples)))
        bounds = [i * N // n for i in range(n + 1)]
        return SlicedPlan([build_transpose_plan(ids[lo:hi], num_rows,
                                                pad_id=pad_id)
                           for lo, hi in zip(bounds[:-1], bounds[1:])], bounds)
    E = N * K
    flat = ids.reshape(-1).astype(np.int64)
    if flat.size and (flat.min() < 0 or flat.max() >= num_rows):
        raise ValueError(
            f"ids out of range [0, {num_rows}): [{flat.min()}, {flat.max()}]")

    keep_flat = np.arange(E, dtype=np.int64)
    if pad_id is not None:
        keep_flat = keep_flat[flat != pad_id]
    kept_ids = flat[keep_flat]
    order_kept = np.argsort(kept_ids, kind="stable")
    order = keep_flat[order_kept]            # sorted pos -> original entry
    srt = kept_ids[order_kept]               # sorted column ids

    return assemble_plan_from_sorted(
        srt, order, num_rows=num_rows, num_entries=E, num_cols=K)
