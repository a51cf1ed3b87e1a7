"""collective_exposed.train: share of the traced training window in
which a collective (the z psum over ``model``, the gradient psum over
``data`` and the optimizer's scalar reductions) runs on a device and no
other operation does, averaged over the chips. Moves
``train_impressions_per_s``."""


def read(x):
    red = x["reduced"]
    if red is None or not red.kernel_s.get("collective"):
        return None
    return 100.0 * red.collective_exposed_s / red.window_s
