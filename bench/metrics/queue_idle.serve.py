"""queue_idle.serve: share of the traced serving window in which the
device is idle while the micro-batching queue or its front door is the
innermost host span (``serve/flush`` + ``serve/admit``): the queue's
bookkeeping around a dispatch, and the generator's wait for the lock
while the pump flushes. Moves ``serve_p50_ms``."""
from bench import spec


def read(x):
    return spec.load_module("metrics", "dispatch_host_idle.serve").idle_share(
        x, ("serve/flush", "serve/admit"))
