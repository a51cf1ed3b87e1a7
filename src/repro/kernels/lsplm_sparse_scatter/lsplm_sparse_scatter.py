"""Pallas scatter kernel for the sparse LS-PLM backward (dTheta).

Consumes the transpose plan (`plan.py`): entries pre-sorted by column id,
so the scatter degenerates into a RUN-LENGTH SEGMENT SUM — walk the
sorted entries once, accumulate ``vals[e] * dz[sample[e]]`` into a VMEM
accumulator while the id stays the same, and flush the accumulator to
the next compact output row when it changes. No sort inside the step, no
read-modify-write on HBM (each compact row is written exactly once), and
no cross-program races: the grid is sequential on TPU and the
accumulator/cursor live in scratch, which persists across grid steps.

Flushes are PIPELINED: the accumulator is double-buffered (two VMEM
slots, one DMA semaphore each). A flush starts the active slot's copy to
its compact row and immediately switches accumulation to the other slot
— so flush t's HBM write overlaps run t+1's accumulate stream instead of
stalling it (the old kernel start()+wait()ed every flush inline). A
slot's outstanding copy is drained only when that slot is about to be
reused (the NEXT flush), or at the sentinel tail; the in-flight flag and
destination row ride in the SMEM cursor so the matching copy descriptor
can be rebuilt for the deferred wait.

The kernel emits the COMPACT (U+1, 2m) result — one row per distinct id
in plan order plus a trailing zero row — and the caller densifies it
with the plan's ``inv_compact`` gather. That keeps the kernel free of
(D, 2m) traffic entirely: HBM cost is O(U) writes, not O(D).

The sorted entries (``row_ids``, ``sample_sorted``, ``vals_sorted``) arrive
as 1-D SMEM blocks, so the run id, the dz row index and the entry value
are scalars. dz is copied once into a VMEM scratch, each row right-padded
to whole 128-lane tiles (the accumulator, the flush DMAs and the compact
output use the same lane-aligned rows — the DMA engine cannot slice a
2m-wide row). dz costs N * 512 B of VMEM at 2m <= 128: 8 MiB at N=16k;
the scoped-VMEM limit is raised to fit it. A call takes at most
``MAX_DZ_VMEM_BYTES`` of dz (131,072 samples at 2m <= 128): the batch
planner (``data.sparse.build_batch_plans``) splits larger batches by
samples into a ``plan.SlicedPlan`` of at most ``max_slice_samples()``
each, and ``ops.scatter_add_planned`` makes one call per slice.

The plan pads the sorted entries with at least one trailing sentinel
(id == num_rows, never a real id): the sentinel both triggers the final
flush of the last real run and absorbs the tail of the last grid block.

The CPU test suite runs this kernel in interpret mode;
``tests/test_tpu_compile.py`` compiles it for a described TPU v5e, and
``chip_smoke.py`` runs it on the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lsplm_sparse_fused.lsplm_sparse_fused import (
    LANES,
    lane_rows,
    varying_axes,
)

MAX_DZ_VMEM_BYTES = 64 << 20
DZ_ROW_BYTES = LANES * 4  # one lane-aligned float32 dz row at 2m <= 128


def max_slice_samples(max_dz_bytes: int = MAX_DZ_VMEM_BYTES) -> int:
    """Samples whose dz one call holds in VMEM, at 2m <= 128."""
    return max(1, max_dz_bytes // DZ_ROW_BYTES)


def _kernel(row_ids_ref, sample_ref, vals_ref, dz_hbm, out_ref,
            dz, acc, cursor, sems, dz_sem, *, block_e: int, num_kept: int,
            total: int):
    # SMEM cursor layout (persists across sequential grid steps):
    #   [0] id of the current run          [1] next compact row to write
    #   [2] active accumulator slot        [3+s] slot s copy in flight?
    #   [5+s] slot s in-flight destination row
    pid = pl.program_id(0)

    @pl.when(pid == 0)
    def _init():
        copy = pltpu.make_async_copy(dz_hbm, dz, dz_sem)
        copy.start()
        acc[...] = jnp.zeros_like(acc)
        cursor[0] = row_ids_ref[0]   # id of the first run
        for i in range(1, 7):
            cursor[i] = 0
        copy.wait()

    def flush_copy(slot, row):
        return pltpu.make_async_copy(acc.at[slot], out_ref.at[pl.ds(row, 1)],
                                     sems.at[slot])

    def drain(slot):
        # deferred wait: rebuild slot's outstanding copy descriptor from
        # the tracked destination row and settle its semaphore
        @pl.when(cursor[3 + slot] == 1)
        def _():
            flush_copy(slot, cursor[5 + slot]).wait()
            cursor[3 + slot] = 0

    def entry(e, carry):
        gid = pid * block_e + e
        rid = row_ids_ref[e]

        @pl.when(rid != cursor[0])
        def _flush():
            slot = cursor[2]
            other = 1 - slot
            drain(other)  # the slot we are about to accumulate into
            flush_copy(slot, cursor[1]).start()  # overlaps the next run
            cursor[3 + slot] = 1
            cursor[5 + slot] = cursor[1]
            acc[other] = jnp.zeros(acc.shape[1:], jnp.float32)
            cursor[0] = rid
            cursor[1] = cursor[1] + 1
            cursor[2] = other

        @pl.when(gid < num_kept)
        def _accumulate():
            s = cursor[2]
            acc[s] = acc[s] + vals_ref[e] * dz[pl.ds(sample_ref[e], 1), :]

        # last entry overall: the sentinel tail flushed the final real run
        # above and accumulated nothing since, so the active slot is zero —
        # write it to the trailing zero row that inv_sorted points untouched
        # ids at, after draining the other slot (nothing may stay in flight
        # past kernel end).
        @pl.when(gid == total - 1)
        def _zero_row():
            slot = cursor[2]
            drain(1 - slot)
            copy = flush_copy(slot, cursor[1])
            copy.start()
            copy.wait()

        return carry

    jax.lax.fori_loop(0, block_e, entry, 0)


@functools.partial(jax.jit, static_argnames=("num_unique", "num_kept",
                                             "block_e", "interpret"))
def lsplm_sparse_scatter_compact(
    row_ids: jax.Array,        # (E_pad,) int32 sorted ids + sentinel tail
    sample_sorted: jax.Array,  # (E_pad,) int32 entry -> sample
    vals_sorted: jax.Array,    # (E_pad,) f32 entry values (0 on sentinels)
    dz: jax.Array,             # (N, 2m) f32 upstream cotangent
    *,
    num_unique: int,
    num_kept: int,
    block_e: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """Segment-sum sorted entries into the compact (U+1, 2m) result.

    The inputs must come from ``ops.pad_plan_entries`` (sentinel-padded to
    a block multiple). Returns compact rows in plan order with a trailing
    zero row; densify with ``compact[plan.inv_compact]``.
    """
    E_pad = row_ids.shape[0]
    if E_pad % block_e:
        raise ValueError(f"E_pad={E_pad} not a multiple of block_e={block_e}")
    m2 = dz.shape[1]
    dz_rows = lane_rows(dz.astype(jnp.float32))
    dz_bytes = dz_rows.size * 4
    if dz_bytes > MAX_DZ_VMEM_BYTES:
        raise ValueError(
            f"dz needs {dz_bytes:,} B of VMEM (> {MAX_DZ_VMEM_BYTES:,}); "
            f"plan the batch in slices of at most max_slice_samples() "
            f"samples (build_transpose_plan(..., max_samples=))")
    smem_block = pl.BlockSpec((block_e,), lambda i: (i,),
                              memory_space=pltpu.SMEM)
    compact = pl.pallas_call(
        functools.partial(_kernel, block_e=block_e, num_kept=num_kept,
                          total=E_pad),
        grid=(E_pad // block_e,),
        in_specs=[smem_block, smem_block, smem_block,
                  pl.BlockSpec(memory_space=pl.ANY)],  # dz: copied once
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(
            (num_unique + 1, dz_rows.shape[1]), jnp.float32,
            vma=varying_axes(row_ids, sample_sorted, vals_sorted, dz)),
        scratch_shapes=[
            pltpu.VMEM(dz_rows.shape, jnp.float32),  # dz, lane-aligned rows
            pltpu.VMEM((2, 1, dz_rows.shape[1]), jnp.float32),  # accumulator
            pltpu.SMEM((7,), jnp.int32),        # run/row/slot/in-flight cursor
            pltpu.SemaphoreType.DMA((2,)),      # one per accumulator slot
            pltpu.SemaphoreType.DMA(()),        # the one dz copy
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=dz_bytes + (16 << 20)),
        interpret=interpret,
    )(row_ids, sample_sorted, vals_sorted.astype(jnp.float32), dz_rows)
    return compact[:, :m2]
