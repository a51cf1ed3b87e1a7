"""dispatch_host_idle.serve: share of the traced serving window in which
the device is idle while the engine's host path of a dispatch is the
innermost host span: ``serve/pad`` + ``serve/launch`` +
``serve/readback``. Overlapping the host's preparation of one round with
the device's previous round removes it. Moves ``serve_p50_ms``.

``idle_share`` is shared with the other readers of the serving spans.
A program without those spans books no gap to any of them, and then
every reader returns None."""

SPANS = ("serve/pad", "serve/launch", "serve/readback")
# the spans inside a dispatch and around it; none is in an older program
SERVE_SPANS = ("serve/pad", "serve/launch", "serve/sync", "serve/readback",
               "serve/flush", "serve/admit")


def idle_share(x, spans):
    """Idle seconds booked to ``spans``, as % of the traced window."""
    red = x["reduced"]
    if red is None or red.window_s <= 0:
        return None
    gaps = dict(red.gaps)
    if not any(s in gaps for s in SERVE_SPANS):
        return None
    return 100.0 * sum(gaps.get(s, 0.0) for s in spans) / red.window_s


def read(x):
    return idle_share(x, SPANS)
