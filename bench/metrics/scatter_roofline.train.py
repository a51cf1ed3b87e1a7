"""scatter_roofline.train: the planned scatter kernel's share of its
roofline: one user-side and one ad-side call per iteration (the
backward), required work from ``bench/roofline/scatter.py``, over the
kernel's traced device time. Moves ``train_impressions_per_s``."""
from bench.roofline import share


def read(x):
    red, c = x["reduced"], x["counters"]
    if red is None or not c.get("ls_evals"):
        return None
    return share(x, red.kernel_s.get("scatter"), x["work"]["scatter"],
                 len(c["ls_evals"]), "scatter_roofline.train")
