"""Device traces: take one with ``jax.profiler`` and reduce it.

``capture`` runs a slice of the workload inside one host annotation,
``bench/window``, with the profiler on, reads the ``.xplane.pb`` back
with ``jax.profiler.ProfileData`` and deletes it. ``reduce`` works on
plain ``Event`` tuples, so it can be checked on a small hand-made trace:

* the window is the ``bench/window`` annotation on the host;
* device busy time is the union of the intervals of the operations on
  a device's ``XLA Ops`` line, clipped to the window, averaged over the
  devices;
* a kernel's time is the sum of its operations' device durations
  (``classify`` names the kernels by their HLO instruction names, which
  carry the jitted function's name: ``..._lsplm_sparse_fused_forward..``
  for the gather, ``..._lsplm_sparse_scatter_compact..`` for the
  scatter), averaged over the devices;
* a collective (found by its HLO opcode or name) is exposed where it
  runs and no other operation does;
* each idle gap inside the window goes to the innermost host span of
  the benchmark or the program (``SPAN_PREFIXES``) that covers its
  middle (``(no span)`` when none does).

A device operation's name is its HLO instruction, ``%name.N = <text>``;
the breakdown lists the instruction names with the most device time,
leaving out ``while``, ``conditional`` and ``call`` operations, whose
time is that of the operations inside them.
"""
from __future__ import annotations

import collections
import glob
import re
import shutil
import tempfile
from typing import NamedTuple

WINDOW = "bench/window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIXES = ("bench/", "serve/", "train/", "stream/")
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
# a collective's opcode in the instruction's text: its name may be the JAX
# primitive's (``%psum_invariant.45 = f32[..] all-reduce(...)``)
COLLECTIVE_OP = re.compile(r" (?:%s)(?:-start|-done)?\(" % "|".join(COLLECTIVES))
TOP = 10


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict


class Reduced(NamedTuple):
    window_s: float
    busy_s: float
    kernel_s: dict  # kernel class -> seconds a device
    collective_exposed_s: float
    ops: list  # [(operation, seconds a device)] most time first
    gaps: list  # [(host span, idle seconds a device)] most time first

    def breakdown(self) -> dict:
        return {"device_ops": [[n, s] for n, s in self.ops[:TOP]],
                "idle_gaps": [[n, s] for n, s in self.gaps[:TOP]]}


def op_name(ev: Event) -> str:
    """The HLO instruction name of a device operation, without ``%``."""
    return ev.name.split(" = ", 1)[0].lstrip("%")


def classify(ev: Event) -> str | None:
    """The kernel class of a device operation: ``gather`` (the fused
    sparse gather kernel), ``scatter`` (the planned scatter kernel),
    ``collective``, or None."""
    name = op_name(ev)
    if "lsplm_sparse_scatter" in name:
        return "scatter"
    if "lsplm_sparse_fused_forward" in name:
        return "gather"
    if name.startswith(COLLECTIVES) or COLLECTIVE_OP.search(ev.name):
        return "collective"
    return None


def load(logdir: str) -> list[Event]:
    """Every event of the newest trace under ``logdir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name, e.start_ns,
                                 e.duration_ns, dict(e.stats)))
    return out


def capture(ctx, fn):
    """Run ``fn`` traced; returns (``Reduced``, what ``fn`` returned)."""
    import jax

    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # annotations only, no Python calls
    try:
        jax.profiler.start_trace(logdir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                result = fn()
        finally:
            jax.profiler.stop_trace()
        red = reduce(load(logdir), len(ctx.devices))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    ctx.log(f"trace: window {red.window_s:.4f} s, busy {red.busy_s:.4f} s, "
            f"kernels {red.kernel_s}")
    return red, result


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def _minus(a: list, b: list) -> list:
    """Intervals of union ``a`` not covered by union ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def reduce(events: list[Event], devices: int) -> Reduced:
    host = [e for e in events if not e.plane.startswith(DEVICE_PREFIX)]
    win = [e for e in host if e.name == WINDOW]
    if not win:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    w0, w1 = win[0].start_ns, win[0].start_ns + win[0].dur_ns
    spans = [e for e in host if e.name != WINDOW and e.dur_ns > 0
             and e.name.startswith(SPAN_PREFIXES)
             and e.start_ns < w1 and e.start_ns + e.dur_ns > w0]

    per_dev = collections.defaultdict(list)
    for e in events:
        if e.plane.startswith(DEVICE_PREFIX) and e.line == OPS_LINE:
            s, t = max(e.start_ns, w0), min(e.start_ns + e.dur_ns, w1)
            if t > s:
                per_dev[e.plane].append((s, t, e))
    ndev = max(devices, 1)
    busy = 0.0
    kernel = collections.Counter()
    ops = collections.Counter()
    exposed = 0.0
    gaps = collections.Counter()
    for plane, evs in per_dev.items():
        union = _union([[s, t] for s, t, _ in evs])
        busy += _length(union)
        coll = []
        other = []
        for s, t, e in evs:
            cls = classify(e)
            name = op_name(e)
            if not name.startswith(CONTAINERS):
                ops[name] += t - s
            if cls is not None:
                kernel[cls] += t - s
            if cls == "collective":
                coll.append([s, t])
            elif not name.startswith(CONTAINERS):
                other.append([s, t])
        exposed += _length(_minus(_union(coll), _union(other)))
        for s, t in _minus([[w0, w1]], union):
            mid = (s + t) / 2
            cover = [e for e in spans if e.start_ns <= mid <= e.start_ns + e.dur_ns]
            name = min(cover, key=lambda e: e.dur_ns).name if cover else "(no span)"
            gaps[name] += t - s
    ns = 1e-9 / ndev
    return Reduced(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy * ns,
        kernel_s={k: v * ns for k, v in kernel.items()},
        collective_exposed_s=exposed * ns,
        ops=[(n, v * ns) for n, v in ops.most_common()],
        gaps=[(n, v * ns) for n, v in gaps.most_common()])
