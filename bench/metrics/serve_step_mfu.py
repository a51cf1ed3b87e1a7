"""serve_step_mfu: the scoring executables' share of the chip's peak:
the least time of the real requests' required work in every traced
dispatch (``bench/roofline/serve_step.py``) over the device's busy time
in the traced window, where only scoring runs. Moves ``serve_p50_ms``."""
from bench.roofline import share


def read(x):
    red, w = x["reduced"], x["work"]
    if red is None or not w or not w["dispatches"]:
        return None
    return share(x, red.busy_s, [d[2] for d in w["dispatches"]], 1,
                 "serve_step_mfu")
