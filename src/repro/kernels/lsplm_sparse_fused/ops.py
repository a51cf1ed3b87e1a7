"""Public fused sparse LS-PLM ops: dispatch + ``jax.custom_vjp``.

Two differentiable entry points, both backed by the pipelined Pallas
kernel on TPU (or in interpret mode) and by a K-chunked ``lax.scan``
accumulation elsewhere — the chunked path keeps the live intermediate at
(N, chunk, 2m) instead of the (N, K, 2m) HBM blob the ``take``+einsum
oracle materialises, which is what makes it win at production sparsity
(K << d; see ``benchmarks/bench_sparse_fused.py``):

  * ``sparse_gather_matmul(ids, vals, theta) -> z (N, 2m)`` — the region
    logits. The stable-NLL training path (log-space Eq. 5) builds on this,
    so OWLQN+ line searches differentiate through the custom VJP.
  * ``lsplm_sparse_forward(ids, vals, theta) -> p (N,)`` — fully fused
    probabilities (softmax-dot-sigmoid in-register on the kernel path).

Plus the INFERENCE-ONLY int8-native pair — ``sparse_gather_matmul_int8``
and ``lsplm_sparse_forward_int8`` — which score a quantised model
(int8 ``codes`` + per-row fp32 ``scales``) without ever materialising
fp32 rows: the kernel gathers from int8 codes packed four rows per
lane-aligned row (a quarter of the fp32 table's bytes) with each row's
scale folded into its slot value, the jnp fallback fuses the scale
multiply into its gather chunks. No VJP: training stays fp32,
quantisation is a deploy-time transform (``repro.serve.compress``).

Both VJPs share one backward: the transposed scatter

    dTheta[r] = sum_{(n,k): ids[n,k]=r} vals[n,k] * dz[n]     (segment-sum)
    dvals[n,k] = theta[ids[n,k]] . dz[n]                      (gather-dot)

With a precomputed :class:`TransposePlan` (``plan=`` — built once per
batch by ``repro.data.sparse.build_transpose_plan``) the dTheta half runs
on ``repro.kernels.lsplm_sparse_scatter``: race-free segment sums with NO
sort and NO scatter inside the step — the Pallas run-length kernel on
TPU, plan-scheduled class gathers elsewhere. Without a plan it falls back
to a ``lax.scan`` of K-chunked ``.at[].add`` scatters (constant trace
size in K). The dvals half reuses the forward-gathered Theta rows when
they were small enough to keep as residuals (``ROWS_REUSE_LIMIT``), else
re-gathers through the plan's id-sorted layout (duplicates adjacent). ids
are integer primals and get float0 cotangents; so does every plan leaf.

``mode`` selects the implementation on both sides of the VJP:
    "auto"      Pallas kernels on TPU, chunked/plan jnp elsewhere (default)
    "kernel"    force the compiled Pallas kernels
    "interpret" force the Pallas kernels in interpret mode (tests/CI)
    "jnp"       force the jnp paths

Tunables: ``block_n``/``block_k`` (kernel tiles) and ``chunk`` (scan
fallbacks) default to None = RESOLVED FROM THE AUTOTUNE TABLE
(``repro.tune``) by the ``(backend, kernel, shape-envelope)`` key —
explicit kwargs always win, then ``repro.tune.set_overrides``, then the
committed table, then the builtin defaults. The forward and backward
scans resolve their chunks independently (``chunk_fwd``/``chunk_bwd``
table kernels); an explicit ``chunk=`` kwarg pins both. Resolution is
trace-time dict lookups — zero steady-state sweeps.
``ROWS_REUSE_LIMIT`` caps ids.size * 2m kept as (N, K, 2m) residual rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.lsplm_sparse_fused.lsplm_sparse_fused import (
    lsplm_sparse_fused_forward,
    lsplm_sparse_fused_int8_forward,
)
from repro.kernels.lsplm_sparse_scatter.ops import (
    SlicedPlan,
    TransposePlan,
    dvals_planned,
    scatter_add_planned,
)
from repro.tune import table as tune

DEFAULT_CHUNK = 8     # K-chunk for the scan fallbacks (builtin default)
ROWS_REUSE_LIMIT = 1 << 22  # save fwd rows as residuals up to this many floats


def pad_theta(theta: jax.Array) -> jax.Array:
    """Append the zero pad row (pad id == d == theta.shape[0]).

    The trailing row is RESERVED: every consumer in this package treats
    id D-1 as the pad slot (skipped by the kernel pipeline, dropped by
    transpose plans); its values must be 0.
    """
    return jnp.concatenate(
        [theta, jnp.zeros((1, theta.shape[1]), theta.dtype)], axis=0)


def finalize_p(z: jax.Array) -> jax.Array:
    """Eq. 2 head: region logits z (..., 2m) -> p(y=1|x) (...,). The ONE
    softmax-dot-sigmoid used by every inference consumer (``repro.serve``,
    the dense predictors, the jnp fallbacks here)."""
    m = z.shape[-1] // 2
    gate = jax.nn.softmax(z[..., :m], axis=-1)
    fit = jax.nn.sigmoid(z[..., m:])
    return jnp.sum(gate * fit, axis=-1)


def logps_from_z(z: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Stable (log_p1, log_p0) from region logits z (..., 2m) — the one
    log-space Eq. 5 head shared by every fused-path consumer."""
    m = z.shape[-1] // 2
    log_gate = jax.nn.log_softmax(z[..., :m], axis=-1)
    log_p1 = jax.nn.logsumexp(log_gate + jax.nn.log_sigmoid(z[..., m:]), axis=-1)
    log_p0 = jax.nn.logsumexp(log_gate + jax.nn.log_sigmoid(-z[..., m:]), axis=-1)
    return log_p1, log_p0


def dedup_tile_ids(ids: jax.Array, vals: jax.Array,
                   pad_id: int) -> tuple[jax.Array, jax.Array]:
    """Collapse duplicate ids within each sample onto one slot.

    Repeated ids (hot features, multi-valued slots) are merged: the
    shared slot carries the SUM of their values, freed slots become
    (pad_id, 0). z is unchanged (sum_k v_k * theta[i_k] groups by id);
    the kernel pipeline then fetches each hot row once per sample and
    skips the freed slots entirely.

    This is a RUNTIME pre-pass on the kernel path (an (N, K) per-row
    argsort + two small scatters per call), worth it when id traffic is
    hot/duplicated; pass ``dedup=False`` to the public ops for batches
    known to be duplicate-free (e.g. pre-coalesced serving traffic).
    """
    N, K = ids.shape
    order = jnp.argsort(ids, axis=1)
    ids_s = jnp.take_along_axis(ids, order, axis=1)
    vals_s = jnp.take_along_axis(vals, order, axis=1)
    first = jnp.concatenate(
        [jnp.ones((N, 1), bool), ids_s[:, 1:] != ids_s[:, :-1]], axis=1)
    seg = jnp.cumsum(first.astype(jnp.int32), axis=1) - 1
    row = jnp.broadcast_to(jnp.arange(N)[:, None], (N, K))
    vals_d = jnp.zeros_like(vals).at[row, seg].add(vals_s)
    ids_d = jnp.full_like(ids, pad_id).at[row, seg].min(ids_s)
    return ids_d, vals_d


def _pad_k(ids, vals, pad_id, multiple):
    """Right-pad the K axis with (pad_id, 0) slots to a block multiple."""
    N, K = ids.shape
    k_pad = -(-K // multiple) * multiple
    if k_pad == K:
        return ids, vals
    return (
        jnp.concatenate(
            [ids, jnp.full((N, k_pad - K), pad_id, ids.dtype)], axis=1),
        jnp.concatenate(
            [vals, jnp.zeros((N, k_pad - K), vals.dtype)], axis=1),
    )


def _chunk_blocks(ids, vals, pad_id, chunk):
    """Shared K-blocking for the scan paths: clamp chunk, pad K, reshape
    to (kb, N, chunk) scan order."""
    K = ids.shape[1]
    chunk = DEFAULT_CHUNK if chunk is None else chunk
    chunk = max(1, min(chunk, K))
    ids_p, vals_p = _pad_k(ids, vals, pad_id, chunk)
    kb = ids_p.shape[1] // chunk
    N = ids.shape[0]
    return (ids_p.reshape(N, kb, chunk).transpose(1, 0, 2),
            vals_p.reshape(N, kb, chunk).transpose(1, 0, 2), chunk, kb)


def _chunked_zmap(ids, vals, theta, chunk: int | None = None) -> jax.Array:
    """Fused-style jnp forward: ``lax.scan`` over K-chunks so the live
    gather intermediate is (N, chunk, 2m) and the TRACE is constant in K
    (a python loop would grow the program linearly with K)."""
    N = ids.shape[0]
    ids_r, vals_r, _, _ = _chunk_blocks(ids, vals, theta.shape[0] - 1, chunk)

    def body(z, xs):
        i, v = xs
        rows = jnp.take(theta, i, axis=0)
        return z + jnp.einsum("nk,nkm->nm", v.astype(rows.dtype), rows), None

    z0 = jnp.zeros((N, theta.shape[1]), jnp.float32)
    z, _ = jax.lax.scan(body, z0, (ids_r, vals_r))
    return z


def _chunk_pair(chunk) -> tuple[int | None, int | None]:
    """Normalise the VJP's nondiff chunk arg to (chunk_fwd, chunk_bwd).

    The public ops thread a resolved (fwd, bwd) tuple; direct private
    callers (benchmarks) may still pass a single int or None."""
    return chunk if isinstance(chunk, tuple) else (chunk, chunk)


def _resolve_fused(ids, theta, mode, block_n, block_k, chunk):
    """Fill None knobs from the autotune table (explicit kwargs win).

    Trace-time python on static shapes — a jitted caller pays this once
    per shape, never per step."""
    env = tune.fused_envelope(ids.shape[0], ids.shape[1], theta.shape[-1])
    if block_n is None or block_k is None:
        cfg = tune.resolve("fused_fwd", env, mode=mode)
        block_n = cfg["block_n"] if block_n is None else block_n
        block_k = cfg["block_k"] if block_k is None else block_k
    if chunk is None:
        chunk = (tune.resolve("chunk_fwd", env, mode=mode)["chunk"],
                 tune.resolve("chunk_bwd", env, mode=mode)["chunk"])
    else:
        chunk = (chunk, chunk)
    return block_n, block_k, chunk


def _use_kernel(mode: str) -> bool:
    if mode == "auto":
        return jax.default_backend() == "tpu"
    if mode in ("kernel", "interpret"):
        return True
    if mode == "jnp":
        return False
    raise ValueError(f"unknown mode {mode!r}")


def _save_rows(ids, theta) -> bool:
    return ids.size * theta.shape[-1] <= ROWS_REUSE_LIMIT


def _kernel_forward(mode, block_n, block_k, dedup, ids, vals, theta):
    if dedup:
        ids, vals = dedup_tile_ids(ids, vals, theta.shape[0] - 1)
    return lsplm_sparse_fused_forward(
        ids, vals, theta, block_n=block_n, block_k=block_k,
        interpret=mode == "interpret")


def _zmap(mode, block_n, block_k, chunk, dedup, ids, vals, theta):
    """Primal forward z — NEVER materialises the (N, K, 2m) rows."""
    if _use_kernel(mode):
        _, z = _kernel_forward(mode, block_n, block_k, dedup, ids, vals, theta)
        return z
    return _chunked_zmap(ids, vals, theta, _chunk_pair(chunk)[0])


def _zmap_with_rows(mode, block_n, block_k, chunk, dedup, ids, vals, theta):
    """VJP-forward z plus (optionally) the gathered rows kept as the
    residual. Only DIFFERENTIATED calls come through here: when the
    batch is small enough (``ROWS_REUSE_LIMIT``) the (N, K, 2m) rows are
    gathered once, reused for z now and for dvals in the backward —
    inference calls take ``_zmap`` and never build the blob."""
    if _use_kernel(mode):
        _, z = _kernel_forward(mode, block_n, block_k, dedup, ids, vals, theta)
        return z, None
    if _save_rows(ids, theta):
        rows = jnp.take(theta, ids, axis=0)
        z = jnp.einsum("nk,nkm->nm", vals.astype(rows.dtype), rows)
        return z.astype(jnp.float32), rows
    return _chunked_zmap(ids, vals, theta, _chunk_pair(chunk)[0]), None


def _dtheta_chunked(ids, vals, theta, dz, chunk):
    """``lax.scan`` of K-chunked scatter-adds (constant trace size in K)."""
    m2 = theta.shape[1]
    ids_r, vals_r, _, _ = _chunk_blocks(ids, vals, theta.shape[0] - 1, chunk)

    def body(dtheta, xs):
        i, v = xs
        data = (v.astype(jnp.float32)[..., None] * dz[:, None, :]).reshape(-1, m2)
        # scatter straight into the one accumulator (duplicate ids sum) —
        # a per-chunk segment_sum would build a full (D, 2m) temp each time
        return dtheta.at[i.reshape(-1)].add(data), None

    dtheta, _ = jax.lax.scan(
        body, jnp.zeros(theta.shape, jnp.float32), (ids_r, vals_r))
    return dtheta


def _dvals_chunked(ids, vals, theta, dz, chunk):
    """``lax.scan`` of K-chunked gather-dots (the no-plan/no-rows case)."""
    N, K = ids.shape
    ids_r, vals_r, chunk, kb = _chunk_blocks(ids, vals, theta.shape[0] - 1, chunk)

    def body(_, xs):
        i, _v = xs
        rows = jnp.take(theta, i, axis=0).astype(jnp.float32)
        return 0, jnp.einsum("nkm,nm->nk", rows, dz)

    _, dv = jax.lax.scan(body, 0, (ids_r, vals_r))
    return dv.transpose(1, 0, 2).reshape(N, kb * chunk)[:, :K]


def _scatter_bwd(mode, chunk, ids, vals, theta, dz, plan, rows):
    """Shared VJP tail: dz (N, 2m) -> (dvals, dtheta)."""
    dz = dz.astype(jnp.float32)
    chunk = _chunk_pair(chunk)[1]
    if plan is not None:
        plan.validate(ids.shape, theta.shape[0])
        dtheta = scatter_add_planned(plan, vals, dz, mode=mode)
    else:
        dtheta = _dtheta_chunked(ids, vals, theta, dz, chunk)
    if rows is not None:  # reuse the forward's gathered rows (no re-gather)
        dvals = jnp.einsum("nkm,nm->nk", rows.astype(jnp.float32), dz)
    elif plan is not None:
        dvals = dvals_planned(plan, theta, dz, ids.shape)
    else:
        dvals = _dvals_chunked(ids, vals, theta, dz, chunk)
    return dvals.astype(vals.dtype), dtheta.astype(theta.dtype)


def _float0_like(x):
    return jax.tree.map(
        lambda a: np.zeros(a.shape, dtype=jax.dtypes.float0), x)


# ------------------------------------------------------- z-level custom VJP
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _gather_matmul(mode, block_n, block_k, chunk, dedup, ids, vals, theta,
                   plan):
    return _zmap(mode, block_n, block_k, chunk, dedup, ids, vals, theta)


def _gather_matmul_fwd(mode, block_n, block_k, chunk, dedup, ids, vals, theta,
                       plan):
    z, rows = _zmap_with_rows(mode, block_n, block_k, chunk, dedup, ids, vals,
                              theta)
    return z, (ids, vals, theta, plan, rows)


def _gather_matmul_bwd(mode, block_n, block_k, chunk, dedup, res, dz):
    ids, vals, theta, plan, rows = res
    dvals, dtheta = _scatter_bwd(mode, chunk, ids, vals, theta, dz, plan, rows)
    return _float0_like(ids), dvals, dtheta, _float0_like(plan)


_gather_matmul.defvjp(_gather_matmul_fwd, _gather_matmul_bwd)


# ------------------------------------------------------- p-level custom VJP
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _forward_p(mode, block_n, block_k, chunk, dedup, ids, vals, theta, plan):
    if _use_kernel(mode):
        p, _ = _kernel_forward(mode, block_n, block_k, dedup, ids, vals, theta)
        return p
    return finalize_p(_zmap(mode, block_n, block_k, chunk, dedup, ids, vals,
                            theta))


def _forward_p_fwd(mode, block_n, block_k, chunk, dedup, ids, vals, theta,
                   plan):
    if _use_kernel(mode):
        p, z = _kernel_forward(mode, block_n, block_k, dedup, ids, vals, theta)
        rows = None
    else:
        z, rows = _zmap_with_rows(mode, block_n, block_k, chunk, dedup, ids,
                                  vals, theta)
        p = finalize_p(z)
    return p, (ids, vals, theta, z, p, plan, rows)


def _forward_p_bwd(mode, block_n, block_k, chunk, dedup, res, dp):
    ids, vals, theta, z, p, plan, rows = res
    m = z.shape[-1] // 2
    gate = jax.nn.softmax(z[:, :m], axis=-1)
    fit = jax.nn.sigmoid(z[:, m:])
    dp = dp.astype(jnp.float32)[:, None]
    dzu = dp * gate * (fit - p.astype(jnp.float32)[:, None])
    dzw = dp * gate * fit * (1.0 - fit)
    dvals, dtheta = _scatter_bwd(
        mode, chunk, ids, vals, theta,
        jnp.concatenate([dzu, dzw], axis=-1), plan, rows)
    return _float0_like(ids), dvals, dtheta, _float0_like(plan)


_forward_p.defvjp(_forward_p_fwd, _forward_p_bwd)


# ------------------------------------------------------------- public API
def sparse_gather_matmul(ids, vals, theta, *, mode: str = "auto",
                         block_n: int | None = None,
                         block_k: int | None = None,
                         chunk: int | None = None, dedup: bool = True,
                         plan: TransposePlan | SlicedPlan | None = None
                         ) -> jax.Array:
    """z = x @ Theta from padded COO, fused, custom-VJP'd. (N, K) -> (N, 2m).

    Pass ``plan`` (one ``build_transpose_plan`` per batch) to run the
    backward on the precomputed transpose layout — no sort/scatter in
    the step; a ``SlicedPlan`` runs it once per slice of samples. Without it the backward scans K-chunked scatter-adds.
    ``dedup=False`` skips the kernel path's per-call duplicate-id
    collapse for batches known to be duplicate-free. block_n/block_k/
    chunk left at None resolve from the autotune table (``repro.tune``).
    """
    if plan is not None:
        plan.validate(ids.shape, theta.shape[0])
    block_n, block_k, chunk = _resolve_fused(ids, theta, mode, block_n,
                                             block_k, chunk)
    return _gather_matmul(mode, block_n, block_k, chunk, dedup, ids, vals,
                          theta, plan)


def lsplm_sparse_forward(ids, vals, theta, *, mode: str = "auto",
                         block_n: int | None = None,
                         block_k: int | None = None,
                         chunk: int | None = None, dedup: bool = True,
                         plan: TransposePlan | None = None) -> jax.Array:
    """p(y=1|x) per Eq. 2 from padded COO, fully fused. Returns (N,)."""
    if plan is not None:
        plan.validate(ids.shape, theta.shape[0])
    block_n, block_k, chunk = _resolve_fused(ids, theta, mode, block_n,
                                             block_k, chunk)
    return _forward_p(mode, block_n, block_k, chunk, dedup, ids, vals, theta,
                      plan)


def _resolve_fused_int8(ids, codes, mode, block_n, block_k, chunk):
    """Knob resolution for the int8-native path: same envelope rule as
    :func:`_resolve_fused`, but block sizes key on ``"fused_fwd_int8"``
    (the int8 pipeline's DMA:compute balance differs, so it tunes
    independently); the jnp fallback chunk shares ``chunk_fwd``."""
    env = tune.fused_envelope(ids.shape[0], ids.shape[1], codes.shape[-1])
    if block_n is None or block_k is None:
        cfg = tune.resolve("fused_fwd_int8", env, mode=mode)
        block_n = cfg["block_n"] if block_n is None else block_n
        block_k = cfg["block_k"] if block_k is None else block_k
    if chunk is None:
        chunk = tune.resolve("chunk_fwd", env, mode=mode)["chunk"]
    return block_n, block_k, chunk


def _chunked_zmap_int8(ids, vals, codes, scales,
                       chunk: int | None = None) -> jax.Array:
    """Int8-native jnp forward: the ``lax.scan`` K-chunk structure of
    :func:`_chunked_zmap` with the scale epilogue fused into each chunk
    — gathered int8 code rows become fp32 via one multiply by their
    per-row scale, so the fp32 row values (and therefore the einsum and
    the accumulation order) are IDENTICAL to running :func:`_chunked_zmap`
    on the dequantised ``codes * scales`` Theta; only the gather moves
    int8 bytes. Pad rows stay exact zero (pad scale == 0)."""
    N = ids.shape[0]
    ids_r, vals_r, _, _ = _chunk_blocks(ids, vals, codes.shape[0] - 1, chunk)

    def body(z, xs):
        i, v = xs
        rows = (jnp.take(codes, i, axis=0).astype(jnp.float32)
                * jnp.take(scales, i, axis=0)[..., None])
        return z + jnp.einsum("nk,nkm->nm", v.astype(rows.dtype), rows), None

    z0 = jnp.zeros((N, codes.shape[1]), jnp.float32)
    z, _ = jax.lax.scan(body, z0, (ids_r, vals_r))
    return z


def _check_int8_model(codes, scales):
    if codes.ndim != 2 or codes.shape[1] % 2:
        raise ValueError(f"codes must be (D, 2m), got {codes.shape}")
    if codes.dtype != jnp.int8:
        raise ValueError(f"codes must be int8, got {codes.dtype}")
    if scales.shape != (codes.shape[0],):
        raise ValueError(
            f"scales must be ({codes.shape[0]},), got {scales.shape}")


def sparse_gather_matmul_int8(ids, vals, codes, scales, *, mode: str = "auto",
                              block_n: int | None = None,
                              block_k: int | None = None,
                              chunk: int | None = None,
                              dedup: bool = True) -> jax.Array:
    """z = x @ (codes * scales) from padded COO WITHOUT materialising the
    fp32 rows — the int8-native serving path. (N, K) -> (N, 2m).

    ``codes`` is the (D, 2m) int8 matrix with the zero pad row at D-1;
    ``scales`` the (D,) per-row fp32 scales (pad row scale 0). On the
    kernel path the row DMAs move packed int8 codes (a quarter of the
    fp32 table's bytes per row) and each slot's value carries its row's
    scale; the jnp fallback fuses the scale multiply into its gather
    chunks. INFERENCE-ONLY: no custom VJP —
    training differentiates the fp32 ops, quantisation is a deploy-time
    transform. Knobs resolve from the autotune table under
    ``"fused_fwd_int8"``.
    """
    _check_int8_model(codes, scales)
    block_n, block_k, chunk = _resolve_fused_int8(ids, codes, mode, block_n,
                                                  block_k, chunk)
    if _use_kernel(mode):
        if dedup:
            ids, vals = dedup_tile_ids(ids, vals, codes.shape[0] - 1)
        _, z = lsplm_sparse_fused_int8_forward(
            ids, vals, codes, scales, block_n=block_n, block_k=block_k,
            interpret=mode == "interpret")
        return z
    return _chunked_zmap_int8(ids, vals, codes, scales, chunk)


def lsplm_sparse_forward_int8(ids, vals, codes, scales, *, mode: str = "auto",
                              block_n: int | None = None,
                              block_k: int | None = None,
                              chunk: int | None = None,
                              dedup: bool = True) -> jax.Array:
    """p(y=1|x) per Eq. 2 from padded COO on int8 codes, fully fused
    (softmax-dot-sigmoid in-register on the kernel path). Returns (N,).
    Inference-only; see :func:`sparse_gather_matmul_int8`."""
    _check_int8_model(codes, scales)
    block_n, block_k, chunk = _resolve_fused_int8(ids, codes, mode, block_n,
                                                  block_k, chunk)
    if _use_kernel(mode):
        if dedup:
            ids, vals = dedup_tile_ids(ids, vals, codes.shape[0] - 1)
        p, _ = lsplm_sparse_fused_int8_forward(
            ids, vals, codes, scales, block_n=block_n, block_k=block_k,
            interpret=mode == "interpret")
        return p
    return finalize_p(_chunked_zmap_int8(ids, vals, codes, scales, chunk))


def lsplm_sparse_logps(ids, vals, theta, *, mode: str = "auto",
                       block_n: int | None = None,
                       block_k: int | None = None,
                       chunk: int | None = None, dedup: bool = True,
                       plan: TransposePlan | None = None
                       ) -> tuple[jax.Array, jax.Array]:
    """Stable (log_p1, log_p0) for Eq. 5 on padded COO — the training path."""
    z = sparse_gather_matmul(ids, vals, theta, mode=mode, block_n=block_n,
                             block_k=block_k, chunk=chunk, dedup=dedup,
                             plan=plan)
    return logps_from_z(z)
