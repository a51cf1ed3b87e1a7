"""Id-range partitioning + host-side batch routing for the sharded
sparse subsystem — the paper's parameter-server split (§4, Fig. 5) made
concrete for padded-COO batches.

Each model shard owns one CONTIGUOUS id range ``[bounds[s], bounds[s+1])``
of the d feature columns. Contiguity is the load-bearing choice:

  * Theta rows are the L2,1 groups, so a feature row never straddles
    shards and OWLQN+'s orthant/direction algebra stays shard-local.
  * The backward :class:`~repro.kernels.lsplm_sparse_scatter.plan.
    TransposePlan` is sorted by id, so per-shard plans are contiguous
    SLICES of the full plan (``repro.shard.plan_slicing``) — no
    re-sorting at routing time.
  * Local ids are global ids minus the range start — routing is a
    subtract, not a hash map.

``make_partition`` cuts equal ranges; ``balanced_partition`` cuts at
quantiles of the batch's id histogram so Zipf-hot heads (real CTR id
traffic concentrates on low ids) don't overload shard 0 — unequal range
WIDTHS, near-equal entry COUNTS. Unequal ranges still present a uniform
(S * rows_per_shard, 2m) device layout: each shard's rows are padded to
the widest range (``Partition.pad_rows`` / ``unpad_rows``); pad rows
receive no ids, so their gradient is exactly zero and OWLQN+ keeps them
at exact zero — padding is free in math, only bytes.

``route_batch`` buckets each sample's (ids, vals) per shard into
per-shard padded-COO tensors with ONE uniform per-shard K (the max
in-shard count over all samples and shards, optionally rounded up) —
uniform because the sharded step stacks them on a leading 'model' axis
for ``shard_map``. Entry order within a sample is preserved (k-ascending),
which is what makes the sliced plans bit-identical to plans built
directly on the routed local ids.
"""
from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.sparse import FlatCTRBatch, SlicedPlan, SparseCTRBatch
from repro.kernels.lsplm_sparse_scatter.plan import TransposePlan


class Partition:
    """Contiguous id-range partition of ``num_rows`` feature columns.

    ``bounds`` is (S+1,) non-decreasing with ``bounds[0] == 0`` and
    ``bounds[-1] == num_rows``; shard s owns ids in
    ``[bounds[s], bounds[s+1])``.
    """

    def __init__(self, bounds: Sequence[int]):
        b = np.asarray(bounds, np.int64)
        if b.ndim != 1 or b.size < 2:
            raise ValueError(f"bounds must be (S+1,) with S >= 1, got {b.shape}")
        if b[0] != 0:
            raise ValueError(f"bounds[0] must be 0, got {b[0]}")
        if np.any(np.diff(b) < 0):
            raise ValueError(f"bounds must be non-decreasing: {b}")
        self.bounds = b

    # ------------------------------------------------------------ properties
    @property
    def num_shards(self) -> int:
        return int(self.bounds.size - 1)

    @property
    def num_rows(self) -> int:
        return int(self.bounds[-1])

    @property
    def sizes(self) -> np.ndarray:
        return np.diff(self.bounds)

    @property
    def rows_per_shard(self) -> int:
        """Uniform per-shard row count of the padded device layout."""
        return int(max(1, self.sizes.max()))

    @property
    def is_uniform(self) -> bool:
        """True iff every range already has ``rows_per_shard`` rows (the
        padded layout is then the identity)."""
        return bool(np.all(self.sizes == self.rows_per_shard))

    def ranges(self) -> list[tuple[int, int]]:
        return [(int(self.bounds[s]), int(self.bounds[s + 1]))
                for s in range(self.num_shards)]

    def __repr__(self) -> str:
        return (f"Partition(num_rows={self.num_rows}, "
                f"num_shards={self.num_shards}, sizes={self.sizes.tolist()})")

    def __eq__(self, other) -> bool:
        return (isinstance(other, Partition)
                and np.array_equal(self.bounds, other.bounds))

    # ------------------------------------------------------------- id algebra
    def shard_of(self, ids) -> np.ndarray:
        """Owning shard per id (host numpy). Ids >= num_rows (e.g. the
        global pad id) map to ``num_shards`` — owned by nobody."""
        return np.searchsorted(self.bounds[1:], np.asarray(ids), side="right")

    # ---------------------------------------------------- padded Theta layout
    def pad_rows(self, theta: jax.Array) -> jax.Array:
        """(d, 2m) -> (S * rows_per_shard, 2m): shard s's rows at
        ``[s * rows_per_shard, s * rows_per_shard + sizes[s])``, zero
        padding after. Identity (no copy) for uniform partitions."""
        if theta.shape[0] != self.num_rows:
            raise ValueError(
                f"theta has {theta.shape[0]} rows, partition covers "
                f"{self.num_rows}")
        if self.is_uniform:
            return theta
        R = self.rows_per_shard
        parts = []
        for (lo, hi) in self.ranges():
            parts.append(theta[lo:hi])
            if hi - lo < R:
                parts.append(jnp.zeros((R - (hi - lo),) + theta.shape[1:],
                                       theta.dtype))
        return jnp.concatenate(parts, axis=0)

    def unpad_rows(self, theta_padded: jax.Array) -> jax.Array:
        """Inverse of :meth:`pad_rows` — drops the per-shard pad rows."""
        R = self.rows_per_shard
        if theta_padded.shape[0] != self.num_shards * R:
            raise ValueError(
                f"padded theta has {theta_padded.shape[0]} rows, expected "
                f"{self.num_shards * R}")
        if self.is_uniform:
            return theta_padded
        parts = [theta_padded[s * R: s * R + (hi - lo)]
                 for s, (lo, hi) in enumerate(self.ranges())]
        return jnp.concatenate(parts, axis=0)


def make_partition(num_rows: int, num_shards: int) -> Partition:
    """Equal contiguous ranges (first ``num_rows % num_shards`` shards get
    one extra row). With ``num_rows % num_shards == 0`` the padded device
    layout is the identity — this is the partition the trainer uses so
    GSPMD's equal axis split IS the id-range split."""
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if num_rows < num_shards:
        raise ValueError(
            f"cannot cut {num_rows} rows into {num_shards} non-empty ranges")
    base, rem = divmod(num_rows, num_shards)
    sizes = np.full(num_shards, base, np.int64)
    sizes[:rem] += 1
    return Partition(np.concatenate([[0], np.cumsum(sizes)]))


def balanced_partition(num_rows: int, num_shards: int, *id_arrays,
                       pad_id: int | None = None) -> Partition:
    """Frequency-balanced contiguous ranges from the batch's id histogram.

    Cuts at quantiles of the cumulative entry count so each shard serves
    ~1/S of the batch's gather/scatter traffic even when the id
    distribution is Zipf-hot (CTR reality: without this, equal ranges
    put nearly every entry on shard 0). A single id's mass cannot be
    split — pathological heads still bound the imbalance from below.
    """
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    counts = np.zeros(num_rows, np.int64)
    for arr in id_arrays:
        flat = np.asarray(arr).reshape(-1)
        if pad_id is not None:
            flat = flat[flat != pad_id]
        if flat.size:
            counts += np.bincount(flat, minlength=num_rows)[:num_rows]
    cum = np.cumsum(counts)
    total = int(cum[-1]) if num_rows else 0
    if total == 0:  # no signal — fall back to equal ranges
        return make_partition(num_rows, num_shards)
    targets = (np.arange(1, num_shards) * total) / num_shards
    cuts = np.searchsorted(cum, targets, side="left") + 1
    bounds = np.concatenate([[0], cuts, [num_rows]])
    return Partition(np.maximum.accumulate(np.clip(bounds, 0, num_rows)))


def shard_slot_width(part: Partition, ids, *, pad_id: int,
                     num_samples: int | None = None,
                     k_multiple: int = 1) -> int:
    """The uniform per-shard K: max in-shard entry count over all
    (sample, shard) cells, rounded up to ``k_multiple``, at least 1.
    ``route_ids`` and ``plan_slicing.slice_plan`` both use this rule, so
    routed tensors and sliced plans agree without coordination."""
    ids = np.asarray(ids)
    N = ids.shape[0] if num_samples is None else num_samples
    flat = ids.reshape(-1)
    keep = flat != pad_id
    if not np.any(keep):
        return max(1, k_multiple)
    sh = part.shard_of(flat[keep])
    n = np.nonzero(keep)[0] // ids.shape[1]
    per_cell = np.bincount(sh * N + n, minlength=(part.num_shards + 1) * N)
    k = int(per_cell[: part.num_shards * N].max())
    return max(1, -(-k // k_multiple) * k_multiple)


def route_ids(part: Partition, ids, vals, *, pad_id: int,
              shard_k: int | None = None,
              k_multiple: int = 1) -> tuple[np.ndarray, np.ndarray, int]:
    """Bucket a padded-COO (N, K) tensor per model shard.

    Returns ``(ids_r, vals_r, Ks)`` with ``ids_r``/``vals_r`` of shape
    (S, N, Ks): shard s's slice holds, per sample, the entries whose
    global id falls in shard s's range — LOCAL ids (global minus range
    start), k-order preserved, tail padded with the local pad id
    ``part.rows_per_shard`` (the zero row ``pad_theta`` appends to each
    shard's padded row block) and value 0. Entries carrying the global
    ``pad_id`` are dropped (they are pads by the COO convention).
    """
    ids = np.asarray(ids)
    vals = np.asarray(vals)
    if ids.shape != vals.shape or ids.ndim != 2:
        raise ValueError(f"ids/vals must share (N, K): {ids.shape} vs "
                         f"{vals.shape}")
    N, K = ids.shape
    S = part.num_shards
    Ks = shard_slot_width(part, ids, pad_id=pad_id, k_multiple=k_multiple) \
        if shard_k is None else int(shard_k)

    flat = ids.reshape(-1)
    keep = np.nonzero(flat != pad_id)[0]
    sh = part.shard_of(flat[keep])
    if keep.size and sh.max() >= S:
        bad = flat[keep][sh >= S].max()
        raise ValueError(f"id {bad} outside partition range "
                         f"[0, {part.num_rows}) and != pad_id {pad_id}")
    n = keep // K

    ids_r = np.full((S, N, Ks), part.rows_per_shard, np.int32)
    vals_r = np.zeros((S, N, Ks), vals.dtype)
    if keep.size:
        # lexsort by (shard, sample); ties keep flat (= k) order, so the
        # within-sample entry order survives routing
        perm = np.argsort(sh * np.int64(N) + n, kind="stable")
        sh_s, n_s, e_s = sh[perm], n[perm], keep[perm]
        cell = sh_s * np.int64(N) + n_s
        starts = np.nonzero(np.diff(np.concatenate([[-1], cell])))[0]
        lens = np.diff(np.concatenate([starts, [cell.size]]))
        if lens.max() > Ks:
            raise ValueError(
                f"shard_k={Ks} too small: a (sample, shard) cell holds "
                f"{lens.max()} entries")
        offs = np.arange(cell.size) - np.repeat(starts, lens)
        ids_r[sh_s, n_s, offs] = (flat[e_s] - part.bounds[sh_s]).astype(np.int32)
        vals_r[sh_s, n_s, offs] = vals.reshape(-1)[e_s]
    return ids_r, vals_r, Ks


class ShardedSparseBatch(NamedTuple):
    """A :class:`~repro.data.sparse.SparseCTRBatch` routed for a
    (data x model) mesh.

    Id/val tensors carry a leading 'model' axis (S shards, LOCAL ids,
    local pad id = ``rows_per_shard``); ``session_id`` is rebased per
    data block (each data shard sees sessions [0, G / data_shards)).
    Plans, when present, are STACKED :class:`TransposePlan`s — every
    leaf has leading (data_shards, num_shards) axes and uniform padded
    shapes (``plan_slicing.stack_plans``) so ``shard_map`` can hand each
    device its own (data-block, id-range) plan cell.
    """

    user_ids: jax.Array   # (S, G, Ku') int32 local ids
    user_vals: jax.Array  # (S, G, Ku')
    ad_ids: jax.Array     # (S, B, Ka') int32 local ids
    ad_vals: jax.Array    # (S, B, Ka')
    session_id: jax.Array  # (B,) block-local session index
    y: jax.Array          # (B,)
    num_features: int = 0           # d (static, global columns)
    rows_per_shard: int = 0         # padded rows per model shard (static)
    data_shards: int = 1            # leading plan axis / batch blocks
    bounds: tuple[int, ...] = ()    # partition bounds (static, hashable)
    user_plan: TransposePlan | None = None  # stacked (Dd, S, ...) leaves
    ad_plan: TransposePlan | None = None

    @property
    def num_shards(self) -> int:
        return len(self.bounds) - 1

    @property
    def partition(self) -> Partition:
        return Partition(np.asarray(self.bounds, np.int64))


def route_batch(batch: SparseCTRBatch, part: Partition, *,
                data_shards: int = 1,
                k_multiple: int = 1) -> ShardedSparseBatch:
    """Route a session-structured sparse batch onto a (data x model) mesh.

    Ids/vals are bucketed per model shard (``route_ids``); the batch's
    transpose plans, when attached, are restricted per data block and
    sliced per id range (``plan_slicing``) — the id sort is NOT redone —
    then stacked into uniform (data_shards, num_shards, ...) leaves.

    Sessions must be contiguous and divisible: each data shard takes
    G / data_shards whole sessions (and their A ads each), mirroring the
    dense path's ``pad_to_multiple`` requirement.
    """
    from repro.shard.plan_slicing import shard_plan_grid, stack_plans

    if isinstance(batch, FlatCTRBatch):
        raise ValueError("route_batch takes a session SparseCTRBatch: "
                         "sessionless FlatCTRBatch rows train on one device")
    if isinstance(batch.user_plan, SlicedPlan) or \
            isinstance(batch.ad_plan, SlicedPlan):
        raise ValueError("plans split by samples address one device's "
                         "scatter; plan the batch with build_batch_plans("
                         "batch, shards=...) instead")
    d = batch.num_features
    if part.num_rows != d:
        raise ValueError(f"partition covers {part.num_rows} rows, batch has "
                         f"{d} feature columns")
    uid = np.asarray(batch.user_ids)
    aid = np.asarray(batch.ad_ids)
    sid = np.asarray(batch.session_id)
    G, B = uid.shape[0], aid.shape[0]
    Dd = int(data_shards)
    if Dd < 1 or G % Dd or B % Dd:
        raise ValueError(
            f"data_shards={Dd} must divide sessions ({G}) and samples ({B})")
    G_l, B_l = G // Dd, B // Dd
    blocks = sid.reshape(Dd, B_l) // G_l
    if not np.all(blocks == np.arange(Dd)[:, None]):
        raise ValueError(
            "sessions must be contiguous: data block b must hold exactly "
            f"sessions [b*{G_l}, (b+1)*{G_l})")

    user_r, user_v, Ku = route_ids(part, uid, np.asarray(batch.user_vals),
                                   pad_id=d, k_multiple=k_multiple)
    ad_r, ad_v, Ka = route_ids(part, aid, np.asarray(batch.ad_vals),
                               pad_id=d, k_multiple=k_multiple)

    user_plan = ad_plan = None
    if batch.user_plan is not None:
        user_plan = stack_plans(shard_plan_grid(
            batch.user_plan, part, num_cols=uid.shape[1],
            data_shards=Dd, shard_k=Ku))
    if batch.ad_plan is not None:
        ad_plan = stack_plans(shard_plan_grid(
            batch.ad_plan, part, num_cols=aid.shape[1],
            data_shards=Dd, shard_k=Ka))

    return ShardedSparseBatch(
        user_ids=jnp.asarray(user_r), user_vals=jnp.asarray(user_v),
        ad_ids=jnp.asarray(ad_r), ad_vals=jnp.asarray(ad_v),
        session_id=jnp.asarray((sid % G_l).astype(np.int32)),
        y=jnp.asarray(batch.y),
        num_features=d, rows_per_shard=part.rows_per_shard,
        data_shards=Dd, bounds=tuple(int(b) for b in part.bounds),
        user_plan=user_plan, ad_plan=ad_plan)
