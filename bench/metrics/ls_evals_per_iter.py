"""ls_evals_per_iter: objective evaluations of OWLQN+'s line search per
iteration (``StepStats.ls_iters``) over the traced iterations. Each is a
whole sparse forward. Moves ``train_impressions_per_s``."""


def read(x):
    evals = x["counters"].get("ls_evals")
    return sum(evals) / len(evals) if evals else None
