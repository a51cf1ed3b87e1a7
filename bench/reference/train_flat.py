"""The reference's training on sessionless rows: plain LS-PLM (Eq. 2,
Eq. 5) over padded-COO rows with no shared side, and plain OWLQN+
(``owlqn``, Algorithm 1), float32 under ``highest`` matmul precision,
or bfloat16 for the control.

z = Theta^T x for each row from its own K ids; the summed negative
log-likelihood is computed in blocks of ``BLOCK`` rows (a scan, so the
(rows, K, 2m) gather never exceeds one block), rows past the batch's
end padded with the zero row and weight 0. Straightforward
``jax.numpy``: gathers and einsums, no kernels, no plans.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.owlqn import OWLQN, Hyper

BLOCK = 16384


def nll(theta, ids, vals, y, dtype=jnp.float32):
    """Eq. 5 over (B, K) rows: -sum_b [y log p + (1 - y) log (1 - p)]."""
    d, m2 = theta.shape
    n = ids.shape[0]
    block = min(BLOCK, n)
    nb = -(-n // block)
    pad = nb * block - n
    w = jnp.concatenate([jnp.ones((n,), jnp.float32), jnp.zeros((pad,), jnp.float32)])
    ids = jnp.concatenate([ids, jnp.full((pad, ids.shape[1]), d, ids.dtype)])
    vals = jnp.concatenate([vals, jnp.zeros((pad, vals.shape[1]), vals.dtype)])
    y = jnp.concatenate([y, jnp.zeros((pad,), y.dtype)])
    table = jnp.concatenate([theta, jnp.zeros((1, m2), theta.dtype)]).astype(dtype)

    def one(total, xs):
        i, v, yb, wb = xs
        z = jnp.einsum("nk,nkm->nm", v.astype(dtype), jnp.take(table, i, axis=0))
        m = m2 // 2
        log_gate = jax.nn.log_softmax(z[:, :m], axis=-1)
        log_p1 = jax.nn.logsumexp(log_gate + jax.nn.log_sigmoid(z[:, m:]), axis=-1)
        log_p0 = jax.nn.logsumexp(log_gate + jax.nn.log_sigmoid(-z[:, m:]), axis=-1)
        yb = yb.astype(log_p1.dtype)
        per = (yb * log_p1 + (1.0 - yb) * log_p0).astype(jnp.float32)
        return total - jnp.sum(wb * per), None

    xs = tuple(a.reshape(nb, block, *a.shape[1:]) for a in (ids, vals, y, w))
    total, _ = jax.lax.scan(one, jnp.float32(0.0), xs)
    return total


def run(cfg: dict, rows, theta0: np.ndarray, steps: int, dtype=jnp.float32):
    """The first ``steps`` iterations from Theta0 on ``rows``
    (``bench.traffic.criteo.Rows``): (f at Theta0..Theta_steps, the first
    gradient, Theta_steps) as numpy arrays."""
    args = tuple(jnp.asarray(a) for a in (rows.ids, rows.vals, rows.y))
    loss = jax.jit(lambda t, *a: nll(t, *a, dtype=dtype))
    loss_grad = jax.jit(jax.value_and_grad(lambda t, *a: nll(t, *a, dtype=dtype)))
    o = cfg["optimizer"]
    opt = OWLQN(lambda t: loss_grad(t, *args), lambda t: loss(t, *args),
                Hyper(cfg["lam"], cfg["beta"], o["memory"], o["c1"],
                      o["ls_shrink"], o["max_ls"]))
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        theta = jnp.asarray(theta0)
        fs, grad0 = [], None
        for _ in range(steps):
            theta, st, grad = opt.step(theta)
            fs += [st.f_new] if fs else [st.f, st.f_new]
            grad0 = np.asarray(grad) if grad0 is None else grad0
        return np.asarray(fs), grad0, np.asarray(theta)
