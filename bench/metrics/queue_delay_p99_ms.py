"""queue_delay_p99_ms: the 99th percentile, over every served request of
the traced window, of the queue's dispatch start minus the request's
arrival (``serve.traffic.Completion``). Moves ``serve_p50_ms``."""
import numpy as np


def read(x):
    q = x["counters"].get("queue_delay_s")
    return 1e3 * float(np.percentile(q, 99)) if q else None
