"""Context-manager span tracing with Chrome-trace/Perfetto export.

A :class:`Tracer` records host-side spans — nested, thread-safe (the
:class:`~repro.stream.planner.WindowPlanner` background thread and the
trainer's main thread interleave into one timeline, separated by their
``tid``) — and exports the Chrome trace event format that
``chrome://tracing`` and https://ui.perfetto.dev load directly: one
``"ph": "X"`` complete event per span with microsecond ``ts``/``dur``
relative to the tracer's epoch, plus one ``"M"`` metadata event naming
each thread.

When ``annotate=True`` every span additionally enters a
``jax.profiler.TraceAnnotation`` (and :meth:`Tracer.step_span` a
``jax.profiler.StepTraceAnnotation``), so when a jax profiler trace is
active the host spans line up with the device timeline in the same
Perfetto view. Annotation is off by default — it costs a couple of
microseconds per span even with no profiler attached.

Disabled fast path: ``Tracer(enabled=False)`` (and the module's default
tracer until a launch driver configures ``--trace-out``) hands out one
shared no-op context manager — a span in cold code costs a method call
and nothing else.
"""
from __future__ import annotations

import json
import os
import threading
import time


class _NullSpan:
    """Shared no-op context manager for disabled tracers."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def set(self, **args) -> None:
        return None


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tracer", "name", "args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = 0
        self._ann = None

    def __enter__(self) -> "_Span":
        if self._tracer.annotate:
            import jax

            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def set(self, **args) -> None:
        """Add args known only inside the span (recorded on exit)."""
        self.args.update(args)

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer._record(self.name, self._t0, t1, self.args)


class _StepSpan(_Span):
    """A span that also enters ``jax.profiler.StepTraceAnnotation`` so
    device work launched inside it is attributed to ``step_num``."""

    def __enter__(self) -> "_StepSpan":
        if self._tracer.annotate:
            import jax

            self._ann = jax.profiler.StepTraceAnnotation(
                self.name, step_num=self.args.get("step", 0))
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self


class Tracer:
    """Span recorder; see the module docstring."""

    def __init__(self, *, enabled: bool = True, annotate: bool = False):
        self.enabled = enabled
        self.annotate = annotate
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._epoch_ns = time.perf_counter_ns()
        self._pid = os.getpid()
        self._named_tids: set[int] = set()

    # ------------------------------------------------------------- recording
    def span(self, name: str, **args):
        """``with tracer.span("stream/plan", day=3): ...`` — records one
        complete event on exit. No-op (shared null span) when disabled."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, args)

    def step_span(self, name: str, step: int, **args):
        """A span for one optimizer/train step; with ``annotate=True``
        it uses ``StepTraceAnnotation`` so the profiler's device timeline
        groups the step's kernels under ``step``."""
        if not self.enabled:
            return NULL_SPAN
        return _StepSpan(self, name, {"step": step, **args})

    def _record(self, name: str, t0_ns: int, t1_ns: int, args: dict) -> None:
        tid = threading.get_ident()
        ev = {
            "name": name,
            "ph": "X",
            "ts": (t0_ns - self._epoch_ns) / 1e3,  # us
            "dur": (t1_ns - t0_ns) / 1e3,
            "pid": self._pid,
            "tid": tid,
        }
        if args:
            ev["args"] = args
        with self._lock:
            if tid not in self._named_tids:
                self._named_tids.add(tid)
                self._events.append({
                    "name": "thread_name", "ph": "M", "pid": self._pid,
                    "tid": tid,
                    "args": {"name": threading.current_thread().name},
                })
            self._events.append(ev)

    # --------------------------------------------------------------- export
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def to_chrome_trace(self) -> dict:
        """The Chrome trace event JSON document (Perfetto-loadable)."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def write(self, path: str) -> str:
        """Atomic (temp + ``os.replace``): a crash mid-export leaves the
        previous trace intact, never a truncated JSON document."""
        from repro.obs.fileio import atomic_write

        with atomic_write(path) as f:
            json.dump(self.to_chrome_trace(), f)
            f.write("\n")
        return path

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._named_tids.clear()


NULL_TRACER = Tracer(enabled=False)
_DEFAULT = NULL_TRACER


def get_tracer() -> Tracer:
    """The process default tracer — disabled until a driver configures
    ``--trace-out`` (see ``repro.obs.configure``)."""
    return _DEFAULT


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process default tracer; returns the previous one."""
    global _DEFAULT
    prev, _DEFAULT = _DEFAULT, tracer
    return prev
