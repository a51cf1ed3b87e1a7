"""The program's objects, built the way its entry points build them."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def train_step(cfg: dict, win, theta0):
    """``launch.train --sparse``'s OWLQN+ step over the window ``win``
    (``bench.traffic.daystream.Window``) from ``theta0``, compiled ahead
    of time: (compiled step, ``init(theta0) -> state``, a map from the
    state's Theta layout to the global (d, 2m) numpy array). ``init`` is
    ``OWLQNPlus.init`` jitted, with the state's shardings on a mesh, so a
    fresh history is made on the chips that hold it. A config mesh of
    (data, model) > (1, 1) takes the sharded path of ``launch.train
    --mesh-data --mesh-model``."""
    from repro.core.objective import smooth_loss_and_grad
    from repro.data.sparse import SparseCTRBatch, build_batch_plans
    from repro.optim import OWLQNPlus

    opt_cfg = cfg["optimizer"]
    kw = dict(lam=cfg["lam"], beta=cfg["beta"], memory=opt_cfg["memory"],
              c1=opt_cfg["c1"], max_ls=opt_cfg["max_ls"],
              ls_shrink=opt_cfg["ls_shrink"])
    batch = SparseCTRBatch(
        user_ids=jnp.asarray(win.user_ids), user_vals=jnp.asarray(win.user_vals),
        ad_ids=jnp.asarray(win.ad_ids), ad_vals=jnp.asarray(win.ad_vals),
        session_id=jnp.asarray(win.session_id), y=jnp.asarray(win.y),
        num_features=win.num_features)
    data, model = cfg["mesh"]["data"], cfg["mesh"]["model"]
    if data * model == 1:
        batch = build_batch_plans(batch)
        opt = OWLQNPlus(lambda t: smooth_loss_and_grad(t, batch), **kw)
        init = jax.jit(opt.init)
        return jax.jit(opt.step).lower(init(theta0)).compile(), init, np.asarray
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.dist import make_distributed_step, shard_sparse_batch, state_specs
    from repro.launch.mesh import make_debug_mesh
    from repro.shard import make_partition, make_sharded_sparse_loss

    mesh = make_debug_mesh(data=data, model=model)
    part = make_partition(win.num_features, model)
    sbatch = shard_sparse_batch(
        mesh, build_batch_plans(batch, shards=part, data_shards=data))
    opt = OWLQNPlus(make_sharded_sparse_loss(sbatch, mesh), **kw)
    shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp), state_specs(mesh),
                             is_leaf=lambda x: isinstance(x, PartitionSpec))
    init = jax.jit(lambda th: opt.init(part.pad_rows(th)), out_shardings=shardings)
    step = make_distributed_step(opt, mesh)
    return (step.lower(init(theta0)).compile(), init,
            lambda th: np.asarray(part.unpad_rows(jnp.asarray(th))))
