"""Plain LS-PLM (Gai et al. 2017, arXiv:1704.05194): Eq. 2 and Eq. 5.

Theta is the (d, 2m) parameter matrix: columns [0, m) are the gating
weights u_i, columns [m, 2m) the fitting weights w_i. For a sample with
sparse features x,

    p(y=1|x) = sum_i softmax(u^T x)_i * sigmoid(w_i^T x)     (Eq. 2)

and the smooth loss is the summed negative log-likelihood (Eq. 5).
Sessions share the user half of x (Eq. 13): z = Theta^T x_user[session]
+ Theta^T x_ad. Sparse features are padded COO with pad id == d and
value 0; the reference appends one zero row so pad ids gather zeros.

Straightforward ``jax.numpy`` with gathers and einsums, no kernels, no
plans; ``dtype`` float32 under ``highest`` matmul precision is the
reference, bfloat16 is the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _z(theta, ids, vals, dtype):
    table = jnp.concatenate([theta, jnp.zeros((1, theta.shape[1]), theta.dtype)])
    rows = jnp.take(table.astype(dtype), ids, axis=0)
    return jnp.einsum("nk,nkm->nm", vals.astype(dtype), rows)


def region_logits(theta, user_ids, user_vals, ad_ids, ad_vals, session_id,
                  dtype=jnp.float32):
    """z (B, 2m) = Theta^T x for session-shared sparse samples."""
    return (_z(theta, user_ids, user_vals, dtype)[session_id]
            + _z(theta, ad_ids, ad_vals, dtype))


def nll(theta, user_ids, user_vals, ad_ids, ad_vals, session_id, y,
        dtype=jnp.float32):
    """Eq. 5: -sum_b [y log p + (1 - y) log (1 - p)], in log space."""
    z = region_logits(theta, user_ids, user_vals, ad_ids, ad_vals,
                      session_id, dtype)
    m = z.shape[-1] // 2
    log_gate = jax.nn.log_softmax(z[:, :m], axis=-1)
    log_p1 = jax.nn.logsumexp(log_gate + jax.nn.log_sigmoid(z[:, m:]), axis=-1)
    log_p0 = jax.nn.logsumexp(log_gate + jax.nn.log_sigmoid(-z[:, m:]), axis=-1)
    y = y.astype(log_p1.dtype)
    return -jnp.sum(y * log_p1 + (1.0 - y) * log_p0).astype(jnp.float32)


def probability(z):
    """Eq. 2 from region logits z (..., 2m)."""
    m = z.shape[-1] // 2
    gate = jax.nn.softmax(z[..., :m], axis=-1)
    return jnp.sum(gate * jax.nn.sigmoid(z[..., m:]), axis=-1)
