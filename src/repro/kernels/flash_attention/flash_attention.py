"""Causal flash attention (forward) — pl.pallas_call + BlockSpec.

Online-softmax blocked attention: never materialises the (S, S) score
matrix, the requirement for prefill_32k (DESIGN.md §4). TPU mapping:

  * grid (B, H, S/BQ, S/BK); the KV axis is the minor (sequential) axis so
    the fp32 accumulator, running max m and running sum l persist in VMEM
    scratch across KV steps of one (b, h, q-block).
  * q/k/v tiles are (BQ, hd)/(BK, hd) VMEM blocks; matmuls hit the MXU
    with hd and BK multiples of 128 in production (tests sweep smaller
    shapes in interpret mode).
  * causal masking: KV blocks strictly above the diagonal contribute
    nothing; the diagonal block is masked elementwise. (A production
    variant would skip dead blocks via a skewed grid; on the straight
    grid they early-out on the mask.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc, m_i, l_i, *,
            scale: float, block_q: int, block_k: int, n_k: int, causal: bool):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_i[...] = jnp.full_like(m_i, NEG_INF)
        l_i[...] = jnp.zeros_like(l_i)

    def _step():
        q = q_ref[0, 0].astype(jnp.float32)  # (BQ, hd)
        k = k_ref[0, 0].astype(jnp.float32)  # (BK, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_new = jnp.maximum(m_i[...], jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_i[...] - m_new)
        l_i[...] = l_i[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * alpha + jnp.dot(p, v, preferred_element_type=jnp.float32)
        m_i[...] = m_new

    if causal:
        # KV blocks strictly above the diagonal have no valid (q, k) pair
        pl.when(qi * block_q + block_q - 1 >= ki * block_k)(_step)
    else:
        _step()

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.where(l_i[...] == 0.0, 1.0, l_i[...])
        o_ref[0, 0] = (acc[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(
    q: jax.Array,  # (B, S, H, hd)
    k: jax.Array,  # (B, S, H, hd)  (GQA-repeated)
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, hd = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    n_q, n_k = S // block_q, S // block_k
    scale = hd ** -0.5

    # layout (B, H, S, hd) so S tiles are contiguous per (b, h)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, n_k=n_k, causal=causal),
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
