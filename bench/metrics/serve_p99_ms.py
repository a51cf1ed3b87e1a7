"""serve_p99_ms: the 99th percentile of request latency over every
request due in the run's full, untraced window, each from when it was
due to when its scores were on the host, as ``serve_p50_ms`` times them.
It shows the engine's rare dispatch stalls, which the median does not.
Moves ``serve_p50_ms``."""
import numpy as np


def read(x):
    lat = x["counters"].get("window_latency_s")
    return 1e3 * float(np.percentile(lat, 99)) if lat else None
