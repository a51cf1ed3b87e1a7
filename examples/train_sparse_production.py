"""Production-regime LS-PLM: 1M sparse feature columns, 8M parameters.

    PYTHONPATH=src python examples/train_sparse_production.py

Dense (B, d) features are impossible at this width (a 2048-sample batch
would be 8 TB); the padded-COO sparse path (`repro.data.sparse`) stores
only active ids — exactly the paper's one-hot regime — and OWLQN+ trains
Theta (1e6 x 8) with L1+L2,1 sparsity.

Execution: the whole job rides the FUSED sparse kernel package
(`repro.kernels.lsplm_sparse_fused`) — a pipelined block-DMA Pallas
gather-matmul on TPU (per-tile SMEM ids, double-buffered K-row
blocks, Theta in HBM), K-chunked `lax.scan` accumulation on CPU/GPU, and
a custom-VJP backward scheduled by per-batch TRANSPOSE PLANS
(`generate_sparse` attaches them): the id->entries sort happens once on
the host, every optimizer step then runs sort-free, scatter-free segment
sums into active Theta rows only. No (B, d) batch is ever built, and the
(N, K, 2m) gather blob exists only below ``ROWS_REUSE_LIMIT`` — where it
is deliberately kept as a VJP residual so the backward skips re-gathering
— never at production batch sizes like this one.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.sparse import (
    generate_sparse,
    sparse_loss_and_grad,
    sparse_predict,
)
from repro.eval import report
from repro.optim import OWLQNPlus

D = 1_000_000
M = 4


def main():
    train = generate_sparse(num_features=D, sessions=2048, seed=1)
    test = generate_sparse(num_features=D, sessions=128, seed=2)
    theta0 = jnp.asarray(
        0.01 * np.random.default_rng(0).normal(size=(D, 2 * M)), jnp.float32)
    n_samples = np.asarray(train.ad_ids).shape[0]
    backend = jax.default_backend()
    print(f"sparse execution path: fused kernel "
          f"({'pipelined Pallas' if backend == 'tpu' else 'scan-jnp fallback'}, "
          f"backend={backend}), transpose-plan custom VJP "
          f"({train.ad_plan.num_unique:,} unique ad ids, "
          f"{train.user_plan.num_unique:,} unique user ids)")
    print(f"features d = {D:,}; params = {theta0.size:,} "
          f"(this batch dense: {n_samples * D * 4 / 2**30:.1f} GiB; one of "
          f"the paper's 1.4e9-sample days dense: "
          f"{1.4e9 * D * 4 / 2**50:.1f} PiB — sparse batch here: "
          f"{np.asarray(train.ad_ids).nbytes / 2**20:.1f} MB)")

    opt = OWLQNPlus(lambda t: sparse_loss_and_grad(t, train), lam=0.05, beta=0.05)
    t0 = time.perf_counter()
    theta, trace = opt.run(theta0, max_iters=40)
    dt = time.perf_counter() - t0

    p = np.asarray(sparse_predict(theta, test))
    r = report(np.asarray(test.y), p)
    nnz_rows = int((np.abs(np.asarray(theta)).sum(1) > 0).sum())
    print(f"trained {len(trace)} iters in {dt:.1f}s  "
          f"f {float(trace[0].f):.1f} -> {float(trace[-1].f_new):.1f}")
    print(f"test: AUC={r['auc']:.4f} NE={r['normalized_entropy']:.4f} "
          f"calibration={r['calibration']:.3f}")
    print(f"sparsity: {nnz_rows:,}/{D:,} feature rows non-zero "
          "(only ids seen in training can survive)")
    print("note: test AUC is bounded by cold-id coverage (ids never seen "
          "in training score 0.5 by construction) — the paper's billions "
          "of samples make coverage a non-issue; this example demonstrates "
          "the SPARSE SUBSTRATE at production width, whose exactness vs "
          "the dense path is proven in tests/test_sparse.py")


if __name__ == "__main__":
    main()
