"""Mixture-of-Experts FFN — the paper's divide-and-conquer gating scaled up.

LS-PLM's softmax-dividing / per-region-fitting structure (Eq. 2) is exactly
a token-level MoE router + experts; this module is where the paper's idea
lives inside the transformer zoo (DESIGN.md §5).

Implementation: sort-based token dispatch with capacity truncation
(drop-on-overflow), replicated-activation expert parallelism:

  * activations (B,S,d) are sharded over `data` and replicated over `model`;
  * experts are sharded over `model` (E_loc = E / model_size per device);
  * each device routes its local tokens to ITS experts only (no all-to-all
    needed with replicated activations), computes them, and the partial
    outputs are `psum`ed over `model`.

The same local routine runs unsharded (mesh=None) for CPU smoke tests, so
the shard_map path is testably identical to the reference path.

Router load-balance auxiliary loss follows Switch Transformer:
  aux = E * sum_e( frac_tokens_e * mean_prob_e ).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig


def init_moe(key, cfg: ArchConfig, dtype):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    ks = jax.random.split(key, 4)
    return {
        "router": d ** -0.5 * jax.random.normal(ks[0], (d, E), dtype),
        "w1": d ** -0.5 * jax.random.normal(ks[1], (E, d, f), dtype),
        "w3": d ** -0.5 * jax.random.normal(ks[2], (E, d, f), dtype),
        "w2": f ** -0.5 * jax.random.normal(ks[3], (E, f, d), dtype),
    }


def _route(x_flat: jax.Array, router_w: jax.Array, k: int):
    """x (T,d) -> (gate (T,k) fp32, idx (T,k) int, probs (T,E) fp32)."""
    logits = (x_flat @ router_w.astype(x_flat.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)  # renormalise top-k
    return gate, idx, probs


def _aux_loss(probs: jax.Array, idx: jax.Array, num_experts: int) -> jax.Array:
    """Switch-style load-balance loss over the local token set."""
    T = probs.shape[0]
    assign = jax.nn.one_hot(idx[:, 0], num_experts, dtype=jnp.float32)
    frac_tokens = jnp.mean(assign, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(frac_tokens * mean_prob)


def _dispatch_compute(
    x_flat: jax.Array,  # (T, d)
    gate: jax.Array,  # (T, k) fp32
    idx: jax.Array,  # (T, k)
    w1: jax.Array,  # (E_loc, d, f)
    w3: jax.Array,
    w2: jax.Array,
    *,
    expert_lo: int,
    capacity: int,
) -> jax.Array:
    """Sort-based dispatch of local tokens to the local expert slice.

    Returns the partial output (T, d): tokens not routed to a local expert
    (or dropped by capacity) contribute zero.
    """
    T, d = x_flat.shape
    E_loc = w1.shape[0]
    k = idx.shape[1]

    flat_e = idx.reshape(-1) - expert_lo  # (T*k,) local expert id or OOR
    mine = (flat_e >= 0) & (flat_e < E_loc)
    sort_key = jnp.where(mine, flat_e, E_loc)  # foreign tokens sort last
    order = jnp.argsort(sort_key, stable=True)  # (T*k,)
    sorted_e = sort_key[order]
    # position within expert group = rank - first rank of that expert
    ranks = jnp.arange(T * k)
    counts = jnp.bincount(sorted_e, length=E_loc + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)])[:-1]
    pos = ranks - starts[sorted_e]
    keep = (sorted_e < E_loc) & (pos < capacity)
    slot = jnp.where(keep, sorted_e * capacity + pos, E_loc * capacity)  # drop slot

    token_of = order // k  # original token per assignment
    # scatter tokens into (E_loc*capacity + 1, d) buffer (last row = dropped)
    buf = jnp.zeros((E_loc * capacity + 1, d), x_flat.dtype)
    buf = buf.at[slot].set(x_flat[token_of])
    eb = buf[: E_loc * capacity].reshape(E_loc, capacity, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", eb, w1.astype(eb.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", eb, w3.astype(eb.dtype))
    eo = jnp.einsum("ecf,efd->ecd", h, w2.astype(eb.dtype))
    eo = jnp.concatenate([eo.reshape(E_loc * capacity, d),
                          jnp.zeros((1, d), eo.dtype)], axis=0)

    out_per_assign = eo[slot] * gate.reshape(-1, 1)[order].astype(eo.dtype)
    out = jnp.zeros_like(x_flat).at[token_of].add(
        jnp.where(keep[:, None], out_per_assign, 0.0)
    )
    return out


def capacity_for(tokens: int, num_experts: int, top_k: int, factor: float = 1.25) -> int:
    cap = int(tokens * top_k / num_experts * factor)
    return max(8, -(-cap // 8) * 8)  # round up to 8


def moe_ffn(
    x: jax.Array,  # (B, S, d)
    params: dict,
    cfg: ArchConfig,
    mesh: jax.sharding.Mesh | None = None,
    capacity_factor: float = 1.25,
    serving_mode: str = "weight_gather",
) -> tuple[jax.Array, jax.Array]:
    """Returns (out (B,S,d), aux_loss scalar).

    Two expert-parallel communication plans (EXPERIMENTS.md §Perf):
      * "weight_gather" (training default): expert weights are FSDP-
        sharded over `data` on the d_ff axis and all-gathered at the
        shard_map boundary. Amortised over B*S train tokens this is
        cheap and keeps per-chip parameter memory minimal.
      * "token_gather" (serving): weights stay fully local (E over
        `model`, d_ff over `data`); the (tiny) token activations are
        all-gathered over `data` instead, every device computes its
        d_ff-slice of its experts, and partial outputs psum over both
        axes. For decode (few tokens, huge weights) this moves orders of
        magnitude fewer bytes.
    """
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.top_k

    if mesh is None or "model" not in mesh.axis_names:
        x_flat = x.reshape(-1, d)
        gate, idx, probs = _route(x_flat, params["router"], k)
        cap = capacity_for(x_flat.shape[0], E, k, capacity_factor)
        out = _dispatch_compute(
            x_flat, gate, idx, params["w1"], params["w3"], params["w2"],
            expert_lo=0, capacity=cap,
        )
        return out.reshape(B, S, d), _aux_loss(probs, idx, E)

    model_size = mesh.shape["model"]
    assert E % model_size == 0, (E, model_size)
    E_loc = E // model_size
    import math

    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = math.prod(mesh.shape[a] for a in dp) if dp else 1
    B_loc = B // dp_size

    if serving_mode == "token_gather" and dp:
        cap = capacity_for(B * S, E, k, capacity_factor)

        def local_tg(xl, router_w, w1, w3, w2):
            # xl (B_loc,S,d); w* (E_loc, d, f_loc) stay LOCAL (no gather)
            xg = jax.lax.all_gather(xl, dp, axis=0, tiled=True)  # (B,S,d)
            T = xg.shape[0] * xg.shape[1]
            x_flat = xg.reshape(T, d)
            gate, idx, probs = _route(x_flat, router_w, k)
            midx = jax.lax.axis_index("model")
            out = _dispatch_compute(
                x_flat, gate, idx, w1, w3, w2,
                expert_lo=midx * E_loc, capacity=cap,
            )
            # partial over experts (model) AND d_ff slices (data):
            # psum_scatter back to this device's batch shard.
            out = jax.lax.psum(out.reshape((dp_size,) + xl.shape), "model")
            out = jax.lax.psum_scatter(out, dp, scatter_dimension=0,
                                       tiled=False)
            aux = _aux_loss(probs, idx, E)
            return out.reshape(xl.shape), aux

        out, aux = jax.shard_map(
            local_tg,
            mesh=mesh,
            in_specs=(P(dp, None, None), P(), P("model", None, dp),
                      P("model", None, dp), P("model", dp, None)),
            out_specs=(P(dp, None, None), P()),
            check_vma=False,
        )(x, params["router"], params["w1"], params["w3"], params["w2"])
        return out, aux

    cap = capacity_for(B_loc * S, E, k, capacity_factor)

    def local(xl, router_w, w1, w3, w2):
        # xl (B_loc, S, d) — replicated over model; w* hold local experts
        # (the data-axis d_ff shards were all-gathered at the boundary)
        T = xl.shape[0] * xl.shape[1]
        x_flat = xl.reshape(T, d)
        gate, idx, probs = _route(x_flat, router_w, k)
        midx = jax.lax.axis_index("model")
        out = _dispatch_compute(
            x_flat, gate, idx, w1, w3, w2,
            expert_lo=midx * E_loc, capacity=cap,
        )
        out = jax.lax.psum(out, "model")
        aux = _aux_loss(probs, idx, E)  # identical on every model shard
        aux = jax.lax.pmean(aux, dp) if dp else aux
        return out.reshape(xl.shape), aux

    out, aux = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(dp, None, None), P(), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(x, params["router"], params["w1"], params["w3"], params["w2"])
    return out, aux


def moe_ffn_dense_reference(x: jax.Array, params: dict, cfg: ArchConfig):
    """O(T·E) dense oracle (no capacity drops) for tests: every token is
    processed by its top-k experts exactly."""
    B, S, d = x.shape
    x_flat = x.reshape(-1, d)
    gate, idx, probs = _route(x_flat, params["router"], cfg.top_k)
    all_out = jnp.stack([
        (jax.nn.silu(x_flat @ params["w1"][e].astype(x_flat.dtype))
         * (x_flat @ params["w3"][e].astype(x_flat.dtype)))
        @ params["w2"][e].astype(x_flat.dtype)
        for e in range(cfg.num_experts)
    ], axis=1)  # (T, E, d)
    sel = jnp.take_along_axis(all_out, idx[..., None], axis=1)  # (T,k,d)
    out = jnp.sum(sel * gate[..., None].astype(sel.dtype), axis=1)
    return out.reshape(B, S, d), _aux_loss(probs, idx, cfg.num_experts)
