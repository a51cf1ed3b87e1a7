"""Fused sparse LS-PLM forward kernel — pipelined row-DMA gather + Eq. 2.

The paper's production inputs are one-hot/multi-hot id lists over millions
of columns (§2, §3.2); a dense (B, d) batch never exists. This kernel
computes p(y=1|x) straight from padded-COO (ids, vals) in one pass per
batch tile, with the row gathers organised as a DMA pipeline:

  * each tile's ids and vals arrive as 1-D SMEM blocks (the grid
    pipeline copies tile i+1's while tile i runs), so every DMA's source
    row and every slot's value are scalars the kernel reads without
    touching VMEM. SMEM holds two tiles at a time, never the batch.
  * the gathered table stays in HBM in a LANE-ALIGNED ROW LAYOUT: one
    128-lane row per id (``lane_rows``), because the TPU's DMA engine
    moves whole lane-tiles and cannot slice a 2m-wide row out of a
    (D, 2m) array. The K id slots of each sample are processed in K-ROW
    BLOCKS of ``block_k`` rows; two (block_k, 128) VMEM buffers
    double-buffer the stream: while block t is contracted, the row
    copies of block t+1 are already in flight, across sample boundaries
    too (the flat pipeline index runs over the whole tile).
  * the contraction is ``block_k`` scalar-times-row multiply-adds on the
    vector unit into a (1, 128) register carry; a sample's finished
    carry is stored as one row of a (block_n, 128) VMEM tile.
  * pad-id rows (id == D-1) are SKIPPED: no HBM DMA is issued; the
    buffer row is zeroed in place instead, so a pad slot contracts
    exactly like the zero pad row it aliases (even if its val is not 0,
    matching the jnp path and the oracle). Combined with the runtime
    dedup pre-pass in ``ops.dedup_tile_ids`` (duplicate ids within a
    sample collapse onto their first slot with summed values, freed
    slots become pad), hot features are fetched once per sample and
    ragged tails cost nothing.
  * the softmax-dot-sigmoid fusion (Eq. 2) runs on the finished z tile;
    only (BT,) probabilities and the (BT, 2m) region logits are written
    back (z is the residual the custom VJP needs).

The int8-native variant gathers from a table of int8 codes packed four
rows to a 128-lane int32 row (``int8_plane_rows``: byte b of every lane
of packed row r holds row 4r+b), unpacks the plane with two shifts, and
takes the per-row scale folded into the slot's value — fp32 rows never
exist in HBM, and each row DMA serves four codes rows' worth of bytes.

Grid: (N/block_n,) over batch tiles. Theta must carry the zero pad row
(id == D-1); ``ops.pad_theta`` provides it.

VMEM/SMEM sizing rule (what bounds the block sizes):

    VMEM  ~=  2 * block_k * 128 * 4        (double buffers)
            + block_n * 128 * 4            (z accumulator tile)
            + 2 * block_n * (2m + 1) * 4   (z + p output tiles)
    SMEM  ~=  2 * 2 * block_n * K_pad * 4  (ids + vals, double-buffered)

Theta itself never enters VMEM (d is HBM-bounded).

(block_n, block_k) are RESOLVED FROM THE AUTOTUNE TABLE (``repro.tune``,
kernel key ``"fused_fwd"``) when the public ops are called with the
knobs left at None — the sizing rule above bounds the sweep grid, the
sweep (``python -m repro.tune.sweep``) picks within it, parity-gated
against the ref oracle per config. Explicit kwargs always win.

Coverage: the CPU test suite runs this kernel in INTERPRET mode;
``tests/test_tpu_compile.py`` compiles it for a described TPU v5e at
production width, and ``chip_smoke.py`` runs it on the chip against the
``ref.py`` oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


LANES = 128


def lane_width(m2: int) -> int:
    """Lanes per gathered row: 2m rounded up to whole 128-lane tiles."""
    return -(-m2 // LANES) * LANES


def lane_rows(theta: jax.Array) -> jax.Array:
    """(D, 2m) -> (D, lane_width(2m)): each row right-padded with zeros to
    whole lane tiles, the layout the row DMAs can address."""
    m2 = theta.shape[1]
    return jnp.pad(theta, ((0, 0), (0, lane_width(m2) - m2)))


def int8_plane_rows(codes: jax.Array) -> jax.Array:
    """(D, 2m) int8 -> (ceil(D/4), lane_width(2m)) int32: byte b of every
    lane of packed row r holds codes row 4r+b (little-endian planes)."""
    D, m2 = codes.shape
    d4 = -(-D // 4)
    c = jnp.pad(codes.astype(jnp.int32) & 0xFF, ((0, 4 * d4 - D), (0, 0)))
    # shifts, not a bitcast: XLA's constant folder packs bitcast bytes in
    # another order than its runtime does when the codes are constants
    shifts = jnp.arange(0, 32, 8, dtype=jnp.int32)[None, :, None]
    word = jnp.sum(jnp.left_shift(c.reshape(d4, 4, m2), shifts), axis=1)
    return jnp.pad(word, ((0, 0), (0, lane_width(m2) - m2)))


def varying_axes(*xs) -> frozenset:
    """The manual mesh axes any of ``xs`` varies over (empty outside a
    ``shard_map``): a kernel's outputs inside one vary like its inputs."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _kernel(ids_ref, vals_ref, table_ref, p_ref, z_ref, acc_ref, bufs, sems,
            *, m: int, block_n: int, block_k: int, nkb: int, skip_id: int,
            planes: int):
    """One batch tile: T = block_n * nkb pipelined K-row blocks."""
    T = block_n * nkb
    width = bufs.shape[-1]

    def row_dma(slot, j, rid):
        src = rid // planes if planes > 1 else rid
        return pltpu.make_async_copy(table_ref.at[pl.ds(src, 1)],
                                     bufs.at[slot, pl.ds(j, 1)],
                                     sems.at[slot, j])

    def start(t, slot):
        for j in range(block_k):
            rid = ids_ref[t * block_k + j]

            @pl.when(rid != skip_id)
            def _():
                row_dma(slot, j, rid).start()

            # skipped slots must still contract like the zero pad row —
            # zero the buffer row (VMEM-only store into the slot the
            # current step is not reading, so it never races the contraction)
            @pl.when(rid == skip_id)
            def _():
                bufs[slot, pl.ds(j, 1), :] = jnp.zeros((1, width), bufs.dtype)

    def wait(t, slot):
        for j in range(block_k):
            rid = ids_ref[t * block_k + j]

            @pl.when(rid != skip_id)
            def _():
                row_dma(slot, j, rid).wait()

    start(0, 0)

    def pipeline_step(t, acc):
        slot = jax.lax.rem(t, 2)

        @pl.when(t + 1 < T)
        def _prefetch_next():  # overlaps the contraction below
            start(t + 1, 1 - slot)

        wait(t, slot)
        b = jax.lax.rem(t, nkb)
        acc = jnp.where(b == 0, jnp.zeros_like(acc), acc)
        for j in range(block_k):
            e = t * block_k + j
            row = bufs[slot, pl.ds(j, 1), :]
            if planes > 1:  # int8 plane (id % 4) of the packed int32 row
                shift = 8 * (planes - 1 - jax.lax.rem(ids_ref[e], planes))
                row = jax.lax.shift_right_arithmetic(
                    jax.lax.shift_left(row, jnp.full_like(row, shift)),
                    jnp.full_like(row, 8 * (planes - 1)))
            acc = acc + vals_ref[e] * row.astype(jnp.float32)

        @pl.when(b == nkb - 1)
        def _():
            acc_ref[pl.ds(t // nkb, 1), :] = acc

        return acc

    jax.lax.fori_loop(0, T, pipeline_step,
                      jnp.zeros((1, width), jnp.float32))

    z = acc_ref[:, :2 * m]
    z_ref[...] = z
    gate = jax.nn.softmax(z[:, :m], axis=-1)
    fit = jax.nn.sigmoid(z[:, m:])
    p_ref[...] = jnp.sum(gate * fit, axis=-1, keepdims=True).astype(p_ref.dtype)


def _pipelined_gather(ids, vals, table, *, m2: int, skip_id: int,
                      planes: int, block_n: int, block_k: int,
                      interpret: bool):
    """Pad (N, K) to block multiples with pad-id slots, run the kernel over
    ``table`` (a ``lane_rows`` / ``int8_plane_rows`` layout), slice back."""
    N, K = ids.shape
    block_n = max(1, min(block_n, N))
    block_k = max(1, min(block_k, K))
    n_pad = pl.cdiv(N, block_n) * block_n
    k_pad = pl.cdiv(K, block_k) * block_k
    ids = jnp.pad(ids, ((0, n_pad - N), (0, k_pad - K)),
                  constant_values=skip_id)
    vals = jnp.pad(vals.astype(jnp.float32), ((0, n_pad - N), (0, k_pad - K)))
    tile = block_n * k_pad
    vma = varying_axes(ids, vals, table)
    smem_block = pl.BlockSpec((tile,), lambda i: (i,),
                              memory_space=pltpu.SMEM)
    p, z = pl.pallas_call(
        functools.partial(_kernel, m=m2 // 2, block_n=block_n,
                          block_k=block_k, nkb=k_pad // block_k,
                          skip_id=skip_id, planes=planes),
        grid=(n_pad // block_n,),
        in_specs=[smem_block, smem_block,
                  pl.BlockSpec(memory_space=pl.ANY)],  # table stays in HBM
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_n, m2), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((n_pad, m2), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, table.shape[1]), jnp.float32),
            pltpu.VMEM((2, block_k, table.shape[1]), table.dtype),
            pltpu.SemaphoreType.DMA((2, block_k)),
        ],
        interpret=interpret,
    )(ids.reshape(-1), vals.reshape(-1), table)
    return p[:N, 0], z[:N]


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def lsplm_sparse_fused_int8_forward(
    ids: jax.Array,  # (N, K) int32, pad id == codes.shape[0] - 1
    vals: jax.Array,  # (N, K)
    codes: jax.Array,  # (D, 2m) int8; row i fp32 == codes[i] * scales[i]
    scales: jax.Array,  # (D,) fp32 per-row scales; pad row scale == 0
    *,
    block_n: int = 256,
    block_k: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Int8-native pipelined fused sparse forward: serve a quantised
    model WITHOUT materialising fp32 rows. Returns (p (N,), z (N, 2m)).

    Same gather/contraction pipeline as :func:`lsplm_sparse_fused_forward`,
    over the four-rows-per-lane-row ``int8_plane_rows`` table: each row
    DMA moves a quarter of the fp32 table's bytes per row, and each
    slot's value carries its row's scale (``vals * scales[ids]``), so the
    contraction sums ``(v * s) * code`` — the dequantise-then-score
    numbers up to fp32 rounding. (block_n, block_k) resolve from the
    autotune table under kernel key ``"fused_fwd_int8"``.
    """
    if ids.shape != vals.shape or ids.ndim != 2:
        raise ValueError(f"ids/vals must be (N, K), got {ids.shape}/{vals.shape}")
    if codes.ndim != 2 or codes.shape[1] % 2:
        raise ValueError(f"codes must be (D, 2m), got {codes.shape}")
    if codes.dtype != jnp.int8:
        raise ValueError(f"codes must be int8, got {codes.dtype}")
    if scales.shape != (codes.shape[0],):
        raise ValueError(
            f"scales must be ({codes.shape[0]},), got {scales.shape}")
    D, m2 = codes.shape
    vals = vals.astype(jnp.float32) * jnp.take(scales.astype(jnp.float32), ids)
    return _pipelined_gather(ids, vals, int8_plane_rows(codes), m2=m2,
                             skip_id=D - 1, planes=4, block_n=block_n,
                             block_k=block_k, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_k", "interpret"))
def lsplm_sparse_fused_forward(
    ids: jax.Array,  # (N, K) int32, pad id == theta.shape[0] - 1
    vals: jax.Array,  # (N, K)
    theta: jax.Array,  # (D, 2m) with zero pad row at D-1
    *,
    block_n: int = 256,
    block_k: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Pipelined fused sparse forward. Returns (p (N,), z (N, 2m)).

    Ragged N and K are handled by padding with pad-id slots up to block
    multiples (skipped by the pipeline, zero-valued in the contraction)
    and slicing the outputs back — loaders never round their shapes.
    """
    if ids.shape != vals.shape or ids.ndim != 2:
        raise ValueError(f"ids/vals must be (N, K), got {ids.shape}/{vals.shape}")
    if theta.ndim != 2 or theta.shape[1] % 2:
        raise ValueError(f"theta must be (D, 2m), got {theta.shape}")
    D, m2 = theta.shape
    p, z = _pipelined_gather(ids, vals, lane_rows(theta.astype(jnp.float32)),
                             m2=m2, skip_id=D - 1, planes=1, block_n=block_n,
                             block_k=block_k, interpret=interpret)
    return p.astype(theta.dtype), z
