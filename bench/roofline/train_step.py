"""Required work of one OWLQN+ iteration (Algorithm 1) on LS-PLM.

Per iteration, with ``evals`` line-search trials and L-BFGS memory M:

* (1 + evals) forwards, each one user-side and one ad-side gather
  (``gather.work``) and the labels read once (4 bytes an impression);
* one backward: one user-side and one ad-side scatter (``scatter.work``);
* dense passes over Theta-sized float32 vectors (4 * d * 2m bytes
  each), each counted once:
    - the Eq. 9 direction: read Theta, write d                   2
    - push the newest (s, y) pair                                2
    - the two-loop recursion: read each of the 2M history vectors 2M
    - write the projected direction p                             1
    - each line-search trial: read Theta and p                    2 * evals
    - write the accepted Theta                                    1
  with 2 flops an element per pass.

The count leaves out what an implementation may avoid: re-reading a
history vector in the second loop, materialising the dense gradient,
the Eq. 2 head's transcendental functions. So it is a lower bound on
the iteration's work, and the share it gives cannot pass 100 %.
"""
from __future__ import annotations

from bench.roofline import F32, Work


def dense_passes(memory: int, evals: float) -> float:
    return 2 + 2 + 2 * memory + 1 + 2 * evals + 1


def iteration(*, forward: Work, backward: Work, impressions: int, d: int,
              m2: int, memory: int, evals: float) -> Work:
    """``forward`` is the two gathers of one evaluation, ``backward`` the
    two scatters of the gradient."""
    fwd = forward + Work(0.0, float(F32 * impressions))
    passes = dense_passes(memory, evals)
    dense = Work(flops=2.0 * passes * d * m2, bytes=float(passes * F32 * d * m2))
    return fwd.scale(1 + evals) + backward + dense
