"""Plain OWLQN+ (Algorithm 1 of Gai et al. 2017) on one (d, 2m) Theta.

    f(Theta) = loss(Theta) + lam * sum_i ||Theta_i.||_2 + beta * ||Theta||_1

Per iteration k:
  1. d = the Eq. 9 direction at Theta from grad loss (Proposition 2);
  2. push the pair s = Theta^k - Theta^{k-1}, y = d^{k-1} - d^k, kept
     only when y.s > 1e-10 (the positive-definite safeguard), at most
     ``memory`` pushes remembered;
  3. p = pi(H d; d): the L-BFGS two-loop over the kept pairs with
     H0 = gamma I (gamma = s.y / y.y of the newest kept pair, else 1),
     projected onto d's orthant; p = d where the projection is all zero;
  4. xi = sign(Theta), or sign(d) where Theta is 0 (Eq. 10);
  5. backtracking line search on Theta(alpha) = pi(Theta + alpha p; xi)
     (Eq. 12), alpha = alpha0 * shrink^j, alpha0 = 1 / ||p|| on the
     first iteration and 1 after, accepted when
     f(Theta(alpha)) <= f(Theta) + c1 * <-d, Theta(alpha) - Theta>;
     Theta is kept when no trial of ``max_ls`` is accepted.

Every decision of the line search is taken on the host from plain
float32 values: no while loops, no fused kernels, no plans.
"""
from __future__ import annotations

from collections import deque
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp


class Hyper(NamedTuple):
    lam: float
    beta: float
    memory: int = 10
    c1: float = 1e-4
    ls_shrink: float = 0.5
    max_ls: int = 30


class Step(NamedTuple):
    f: float  # objective before the step
    f_new: float
    alpha: float
    ls_evals: int


def _rownorm(x):
    return jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))


@jax.jit
def _direction(theta, grad, lam, beta):
    g = -grad
    rn = _rownorm(theta)
    d_a = g - lam * theta / jnp.where(rn > 0, rn, 1.0) - beta * jnp.sign(theta)
    d_b = jnp.maximum(jnp.abs(g) - beta, 0.0) * jnp.sign(g)
    v = jnp.maximum(jnp.abs(g) - beta, 0.0) * jnp.sign(g)
    vn = _rownorm(v)
    d_c = jnp.maximum(vn - lam, 0.0) / jnp.where(vn > 0, vn, 1.0) * v
    return jnp.where(rn > 0, jnp.where(theta != 0, d_a, d_b), d_c)


@jax.jit
def _project(x, omega):
    return jnp.where(jnp.sign(x) == jnp.sign(omega), x, 0.0)


@jax.jit
def _regularizer(theta, lam, beta):
    return lam * jnp.sum(_rownorm(theta)) + beta * jnp.sum(jnp.abs(theta))


@jax.jit
def _trial(theta, p, xi, alpha):
    return _project(theta + alpha * p, xi)


@jax.jit
def _vdot(a, b):
    return jnp.sum(a * b)  # elementwise: no matmul precision to choose


def _dot(a, b) -> float:
    return float(_vdot(a, b))


class OWLQN:
    """Algorithm 1 driven from the host; ``loss_and_grad(theta)`` returns
    the smooth loss (Eq. 5) and its gradient."""

    def __init__(self, loss_and_grad: Callable, loss: Callable, hyper: Hyper):
        self.loss_and_grad = loss_and_grad
        self.loss = loss
        self.h = hyper
        self.pairs: deque = deque(maxlen=hyper.memory)  # (s, y, rho) or None
        self.gamma = 1.0
        self.k = 0
        self.prev = None  # (theta, d) of the previous iteration

    def objective(self, theta) -> float:
        return float(self.loss(theta)) + float(
            _regularizer(theta, self.h.lam, self.h.beta))

    def _two_loop(self, d):
        kept = [pr for pr in self.pairs if pr is not None]
        q, alphas = d, []
        for s, y, rho in reversed(kept):
            a = rho * _dot(s, q)
            alphas.append(a)
            q = q - a * y
        q = self.gamma * q
        for (s, y, rho), a in zip(kept, reversed(alphas)):
            b = rho * _dot(y, q)
            q = q + (a - b) * s
        return q

    def step(self, theta):
        """One iteration from ``theta``; returns (theta_new, Step, grad)."""
        h = self.h
        loss, grad = self.loss_and_grad(theta)
        f0 = float(loss) + float(_regularizer(theta, h.lam, h.beta))
        d = _direction(theta, grad, h.lam, h.beta)
        if self.prev is not None:
            s = theta - self.prev[0]
            y = self.prev[1] - d
            ys, yy = _dot(y, s), _dot(y, y)
            if ys > 1e-10:
                self.pairs.append((s, y, 1.0 / ys))
                self.gamma = ys / (yy if yy > 0 else 1.0)
            else:
                self.pairs.append(None)
        p = _project(self._two_loop(d), d)
        if _dot(p, p) <= 0:
            p = d
        xi = jnp.where(theta != 0, jnp.sign(theta), jnp.sign(d))
        alpha = 1.0 / max(_dot(p, p) ** 0.5, 1e-12) if self.k == 0 else 1.0
        theta_new, f_new, accepted, evals = theta, f0, 0.0, 0
        for j in range(h.max_ls):
            a = alpha * h.ls_shrink ** j
            cand = _trial(theta, p, xi, jnp.float32(a))
            f_t = self.objective(cand)
            evals += 1
            if f_t <= f0 + h.c1 * _dot(-d, cand - theta):
                theta_new, f_new, accepted = cand, f_t, a
                break
        self.prev = (theta, d)
        self.k += 1
        return theta_new, Step(f0, f_new, accepted, evals), grad
