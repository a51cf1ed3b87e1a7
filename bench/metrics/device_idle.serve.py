"""device_idle.serve: share of the traced serving window in which no
operation ran on the device (averaged over the chips). Moves
``serve_p50_ms``."""


def read(x):
    red = x["reduced"]
    if red is None or red.window_s <= 0 or red.busy_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
