"""gather_roofline.serve: the fused gather kernel's share of its
roofline in serving: the user-side and ad-side gathers of the real
requests of every traced dispatch (``bench/roofline/serve_step.py``)
over the kernel's traced device time. Moves ``serve_p50_ms``."""
from bench.roofline import share


def read(x):
    red, w = x["reduced"], x["work"]
    if red is None or not w or not w["dispatches"]:
        return None
    calls = [c for d in w["dispatches"] for c in d[:2]]
    return share(x, red.kernel_s.get("gather"), calls, 1,
                 "gather_roofline.serve")
