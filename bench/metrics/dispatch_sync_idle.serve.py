"""dispatch_sync_idle.serve: share of the traced serving window in which
the device is idle while the host waits on a dispatch's result
(``serve/sync``, ``block_until_ready``): the device has finished, or has
not started, and the host still waits. Moves ``serve_p50_ms``."""
from bench import spec


def read(x):
    return spec.load_module("metrics", "dispatch_host_idle.serve").idle_share(
        x, ("serve/sync",))
