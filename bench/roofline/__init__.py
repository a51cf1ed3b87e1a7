"""Operations and bytes that the mathematics requires, and the least
time the chip could take for them.

A share of a roofline is ``least_seconds(work) / measured seconds``. The
least time is the larger of operations over the chip's peak rate and
bytes over its peak memory bandwidth, with the peaks of
``bench/peaks.json``. Work is counted from the actual batch: real (non
pad) slots, distinct rows, each input read once and each output written
once. Padding, lane-width rows, duplicated row reads and recomputation
are not required work, so no implementation of the same work can read
above 100 %.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import NamedTuple

import numpy as np

F32 = 4
PEAKS_FILE = Path(__file__).resolve().parents[1] / "peaks.json"


class Work(NamedTuple):
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":  # type: ignore[override]
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def scale(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)


ZERO = Work(0.0, 0.0)


def peaks(device_kind: str) -> dict:
    """The peak rates of ``device_kind``; an unknown device is an error."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name} (have {sorted(table)})")
    return table[device_kind]


def least_seconds(work: Work, peak: dict, chips: int = 1) -> tuple[float, str]:
    """(least seconds, which bound) for ``work`` spread over ``chips``.

    The flop bound uses the chip's highest rate (bf16), so it is a lower
    bound for work done in any precision."""
    t_flops = work.flops / (chips * peak["flops_bf16"])
    t_bytes = work.bytes / (chips * peak["hbm_bytes_per_s"])
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")


def real_slots(ids: np.ndarray, pad_id: int, keep=None) -> np.ndarray:
    """The ids of the real slots: not the pad id, and (``keep``, a bool
    table over ids) present in the served model."""
    ids = np.asarray(ids).reshape(-1)
    ids = ids[ids != pad_id]
    if keep is not None:
        ids = ids[keep[ids]]
    return ids


def share(x: dict, seconds: float, works, times: float, name: str):
    """100 x least time of ``works`` (one Work per call, all done
    ``times`` over) / ``seconds`` measured; None when nothing was
    measured. ``x`` is the reader's input (device kind, chips). Logs
    which bound sets the least time."""
    if not seconds or seconds <= 0:
        return None
    least, bound = 0.0, set()
    for work in works:
        t, b = least_seconds(work, peaks(x["kind"]), chips=x["chips"])
        least += t
        bound.add(b)
    print(f"[bench] {name}: least time bound by {'/'.join(sorted(bound))}",
          file=sys.stderr)
    return 100.0 * least * times / seconds
