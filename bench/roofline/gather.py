"""Required work of one sparse gather-contraction, z = x @ Theta.

For ids and values of shape (N, K) against a (d, 2m) float32 Theta:

* flops: 2 * 2m per real slot (one multiply and one add per column);
* bytes: ids and values read once (4 + 4 per real slot), each distinct
  real id's Theta row read once (4 * 2m), z written once (4 * 2m per
  row of the batch).

A real slot is one whose id is not the pad id and, for a served model,
whose row survived pruning (``keep``): a pruned row is zero and adds
nothing. Eq. 2's head, which the fused kernel also computes, is left
out, so the count is a lower bound for the kernel's work.
"""
from __future__ import annotations

import numpy as np

from bench.roofline import F32, Work, real_slots


def work(ids: np.ndarray, pad_id: int, m2: int, keep=None,
         rows: int | None = None) -> Work:
    """``rows``: rows of z written (default: rows of ``ids``)."""
    rows = np.asarray(ids).shape[0] if rows is None else rows
    real = real_slots(ids, pad_id, keep)
    distinct = np.unique(real).size
    return Work(flops=2.0 * m2 * real.size,
                bytes=float(8 * real.size + F32 * m2 * (distinct + rows)))
