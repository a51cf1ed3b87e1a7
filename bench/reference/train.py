"""The reference's training: plain OWLQN+ (``owlqn``) on the plain
LS-PLM loss (``lsplm``) over a window's arrays, float32 under ``highest``
matmul precision, or bfloat16 for the control."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import lsplm as ref_model
from bench.reference.owlqn import OWLQN, Hyper


def run(cfg: dict, win, theta0: np.ndarray, steps: int, dtype=jnp.float32):
    """The first ``steps`` iterations from Theta0 on ``win``
    (``bench.traffic.daystream.Window``): (f at Theta0..Theta_steps, the
    first gradient, Theta_steps) as numpy arrays."""
    args = tuple(jnp.asarray(a) for a in (win.user_ids, win.user_vals,
                                           win.ad_ids, win.ad_vals,
                                           win.session_id, win.y))
    loss = jax.jit(lambda t, *a: ref_model.nll(t, *a, dtype=dtype))
    loss_grad = jax.jit(jax.value_and_grad(
        lambda t, *a: ref_model.nll(t, *a, dtype=dtype)))
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
