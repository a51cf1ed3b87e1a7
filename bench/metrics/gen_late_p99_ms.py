"""gen_late_p99_ms: the 99th percentile, over every request of the
traced window, of how late the benchmark's load generator submitted it
after it was due. Moves ``serve_p50_ms``."""
import numpy as np


def read(x):
    late = x["counters"].get("gen_late_s")
    return 1e3 * float(np.percentile(late, 99)) if late else None
