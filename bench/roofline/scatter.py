"""Required work of one sparse transposed scatter, the backward of
z = x @ Theta:

    dTheta[r] = sum over slots (n, k) with ids[n, k] = r of vals[n, k] * dz[n]

For ids and values of shape (N, K) and dz of shape (N, 2m), float32:

* flops: 2 * 2m per real slot;
* bytes: ids and values read once (4 + 4 per real slot), dz read once
  (4 * 2m per row), each distinct real id's gradient row written once
  (4 * 2m).

Rows of dTheta that no slot touches are zero and need no work; the
program's final densification into a (d, 2m) array is not counted.
"""
from __future__ import annotations

import numpy as np

from bench.roofline import F32, Work, real_slots


def work(ids: np.ndarray, pad_id: int, m2: int) -> Work:
    rows = np.asarray(ids).shape[0]
    real = real_slots(ids, pad_id)
    distinct = np.unique(real).size
    return Work(flops=2.0 * m2 * real.size,
                bytes=float(8 * real.size + F32 * m2 * (rows + distinct)))
