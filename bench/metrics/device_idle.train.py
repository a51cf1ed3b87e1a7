"""device_idle.train: share of the traced training window in which no
operation ran on the device (averaged over the chips). Moves
``train_impressions_per_s``."""


def read(x):
    red = x["reduced"]
    if red is None or red.window_s <= 0 or red.busy_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
