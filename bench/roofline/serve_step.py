"""Required work of scoring one dispatch of page views (Eq. 2, Eq. 13).

For the real requests of a dispatch (no pad bundles, no pad candidates,
no pad slots): one user-side gather over their user id lists and one
ad-side gather over their candidates' id lists (``gather.work``, with
``keep`` the rows alive in the served model), and one probability
written per real candidate (4 bytes). The id remap of a pruned model
and the head's transcendental functions are not counted.
"""
from __future__ import annotations

import numpy as np

from bench.roofline import F32, Work
from bench.roofline import gather


def dispatch(requests, pad_id: int, m2: int, keep) -> tuple[Work, Work, Work]:
    """(user-side gather, ad-side gather, whole dispatch) work of the real
    requests of one dispatch; each request has ``user_ids`` (Ku,) and
    ``ad_ids`` (N, Ka)."""
    users = np.concatenate([r.user_ids for r in requests])
    ads = np.concatenate([r.ad_ids.reshape(-1) for r in requests])
    candidates = sum(r.ad_ids.shape[0] for r in requests)
    user = gather.work(users, pad_id, m2, keep, rows=len(requests))
    ad = gather.work(ads, pad_id, m2, keep, rows=candidates)
    return user, ad, user + ad + Work(0.0, float(F32 * candidates))
