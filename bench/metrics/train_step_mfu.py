"""train_step_mfu: the whole OWLQN+ iteration's share of the chip's peak:
the least time of every traced iteration's required work
(``bench/roofline/train_step.py``, with that iteration's line-search
evaluations) over the traced window. Moves ``train_impressions_per_s``."""
from bench.roofline import share, train_step


def read(x):
    red, c, w = x["reduced"], x["counters"], x["work"]
    if red is None or not c.get("ls_evals"):
        return None
    works = [train_step.iteration(
        forward=w["gather"][0] + w["gather"][1],
        backward=w["scatter"][0] + w["scatter"][1],
        impressions=c["impressions"], d=w["d"], m2=w["m2"],
        memory=w["memory"], evals=evals) for evals in c["ls_evals"]]
    return share(x, red.window_s, works, 1, "train_step_mfu")
