"""gather_roofline.train: the fused gather kernel's share of its
roofline in training. Per iteration it runs (1 + line-search
evaluations) forwards of one user-side and one ad-side call; their
required work (``bench/roofline/gather.py``) over the kernel's traced
device time. Moves ``train_impressions_per_s``."""
from bench.roofline import share


def read(x):
    red, c = x["reduced"], x["counters"]
    if red is None or not c.get("ls_evals"):
        return None
    forwards = sum(1 + e for e in c["ls_evals"])
    return share(x, red.kernel_s.get("gather"), x["work"]["gather"], forwards,
                 "gather_roofline.train")
