"""The comparisons that decide ``correct``, and how they are printed.

Training compares numbers of the timed path against the plain reference
(``bench/reference``): the objective after each of the first steps, the
norm of the first gradient and the norm of Theta's change after three
steps. Norms are taken per leaf: Theta's four blocks of ad-id rows and
user-id rows, gating and fitting columns. A leaf's gap is the
difference of the two norms over the larger of the reference leaf's
norm and the median reference leaf's norm; the number compared is the
worst leaf's gap. Leaves whose reference gradient is under a thousandth
of the median leaf's are left out of the change: only round-off and
the regularisers move them, so their change says nothing of the loss.

Serving compares every score that the window produced with the
reference's, by the largest absolute difference.
"""
from __future__ import annotations

import sys
from typing import NamedTuple

import numpy as np

QUIET_LEAF = 1e-3  # leaves under this share of the median gradient leaf


class Check(NamedTuple):
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def leaves(theta: np.ndarray, user_lo: int) -> dict:
    """Theta's blocks: ad-id rows / user-id rows x gating / fitting."""
    m = theta.shape[1] // 2
    return {"ad.gate": theta[:user_lo, :m], "ad.fit": theta[:user_lo, m:],
            "user.gate": theta[user_lo:, :m], "user.fit": theta[user_lo:, m:]}


def _norms(x: np.ndarray, user_lo: int) -> dict:
    return {k: float(np.linalg.norm(v.astype(np.float64)))
            for k, v in leaves(x, user_lo).items()}


def leaf_gap(prog: np.ndarray, ref: np.ndarray, user_lo: int,
             skip: frozenset = frozenset()) -> float:
    """Worst leaf's |norm(prog) - norm(ref)| / max(norm(ref), median)."""
    p, r = _norms(prog, user_lo), _norms(ref, user_lo)
    med = float(np.median(list(r.values())))
    gaps = [abs(p[k] - r[k]) / max(r[k], med, 1e-30) for k in r if k not in skip]
    return max(gaps) if gaps else 0.0


def quiet_leaves(ref_grad: np.ndarray, user_lo: int) -> frozenset:
    r = _norms(ref_grad, user_lo)
    med = float(np.median(list(r.values())))
    return frozenset(k for k, v in r.items() if v < QUIET_LEAF * med)


def rel_gap(prog, ref) -> float:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(prog - ref) / np.maximum(np.abs(ref), 1e-30)))


def report(checks: list[Check]) -> dict:
    """Print each number beside its limit as the last lines on standard
    error; return the result line's entry for them."""
    for c in checks:
        print(f"check {c.name} = {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    return {c.name: {"value": c.value, "limit": c.limit} for c in checks}
