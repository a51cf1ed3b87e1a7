"""The main path's kernels compile for a TPU v5e at production width.

Nothing here runs: each test lowers a kernel (or the sharded
loss-and-grad) for a DESCRIBED v5e:2x2 topology and asks the TPU's own
compiler for an executable, which refuses what interpret mode accepts —
unaligned slices, oversized VMEM/SMEM, unsupported vector shapes. The
width is the production one the chip smoke runs: d = 1,000,000 columns,
m = 12 (2m = 24), sessions = 4096 (G = 4096 user rows at K = 24,
B = 16384 ad rows at K = 12).

The topology is described inside a module fixture, never at import: only
the process that loads the TPU library may use it, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.lsplm_sparse_fused.lsplm_sparse_fused import (
    lsplm_sparse_fused_forward,
    lsplm_sparse_fused_int8_forward,
)
from repro.kernels.lsplm_sparse_scatter.lsplm_sparse_scatter import (
    lsplm_sparse_scatter_compact,
)
from repro.tune import table as tune

D = 1_000_001          # d columns + the pad row
M2 = 24                # 2m at the paper's production m = 12
G, KU = 4096, 24       # user rows per batch, user ids per session
B, KA = 16384, 12      # ad rows per batch, ad ids per impression


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the
    # persistent cache, and kernel blocks must resolve as on the chip
    # (no TPU table: builtin defaults), not from the CPU table
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    tune.set_active_table(tune.AutotuneTable())
    yield topo
    tune.set_active_table(None)
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("n,k", [(G, KU), (B, KA)])
def test_fused_forward_compiles(one_chip, n, k):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    txt = _compiled_text(
        lambda i, v, t: lsplm_sparse_fused_forward(i, v, t),
        s((n, k), jnp.int32), s((n, k), jnp.float32), s((D, M2), jnp.float32))
    assert "tpu_custom_call" in txt


def test_int8_fused_forward_compiles(one_chip):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    txt = _compiled_text(
        lambda i, v, c, sc: lsplm_sparse_fused_int8_forward(i, v, c, sc),
        s((G, KU), jnp.int32), s((G, KU), jnp.float32),
        s((D, M2), jnp.int8), s((D,), jnp.float32))
    assert "tpu_custom_call" in txt


def test_planned_scatter_compiles(one_chip):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    block_e = tune.BUILTIN_DEFAULTS["scatter"]["block_e"]
    e_pad = -(-(B * KA + 1) // block_e) * block_e
    txt = _compiled_text(
        lambda r, smp, v, dz: lsplm_sparse_scatter_compact(
            r, smp, v, dz, num_unique=B * KA // 2, num_kept=B * KA,
            block_e=block_e),
        s((e_pad,), jnp.int32), s((e_pad,), jnp.int32),
        s((e_pad,), jnp.float32), s((B, M2), jnp.float32))
    assert "tpu_custom_call" in txt


def test_sharded_loss_and_grad_compiles(topo):
    """The worker/server step on a (data=2, model=2) mesh of the
    described chips, with the Pallas kernels inside the shard_map."""
    import numpy as np
    from jax.sharding import Mesh

    from repro.data.sparse import generate_sparse
    from repro.dist import sparse_batch_specs
    from repro.shard import sharded_sparse_loss_and_grad

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    d = D - 1
    sbatch = generate_sparse(num_features=d,
                             num_user_features_range=(int(0.6 * d), d),
                             sessions=G, seed=1, shards=2, data_shards=2)
    specs = sparse_batch_specs(mesh, sbatch)
    fields = [f for f, sp in zip(sbatch._fields, specs) if sp is not None]
    shapes = {
        f: jax.tree.map(
            lambda x, sp: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, sp)),
            getattr(sbatch, f), getattr(specs, f))
        for f in fields}
    rows = sbatch.num_shards * sbatch.rows_per_shard
    theta = jax.ShapeDtypeStruct((rows, M2), jnp.float32,
                                 sharding=NamedSharding(mesh, P("model", None)))

    def loss_and_grad(th, arrays):
        return sharded_sparse_loss_and_grad(th, sbatch._replace(**arrays),
                                            mesh, mode="kernel")

    txt = _compiled_text(loss_and_grad, theta, shapes)
    assert "tpu_custom_call" in txt
    assert "all-reduce" in txt  # the z psum over 'model'



def test_sliced_planned_scatter_compiles(one_chip):
    """The Criteo-schema cell's backward: 196,608 rows planned in two
    slices of samples, each slice's dz within the scatter kernel's VMEM
    ceiling (the whole batch's is not). dz's VMEM depends on the rows
    alone, so two ids a row stand in for the cell's 39 (XLA's compile of
    the entry gathers grows with the entries, not the kernel's)."""
    from repro.data.sparse import SlicedPlan, TransposePlan
    from repro.kernels.lsplm_sparse_scatter.lsplm_sparse_scatter import (
        max_slice_samples,
    )
    from repro.kernels.lsplm_sparse_scatter.ops import scatter_add_planned

    d, n, k = 998_960, 196_608, 2
    half = n // 2
    assert half <= max_slice_samples() < n
    s = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(shape, dt,
                                                         sharding=one_chip)

    def plan(rows):  # the leaves' shapes; the kernel path reads no classes
        e = rows * k
        return TransposePlan(
            class_src=(), class_samp=(), class_mask=(), class_width=(),
            row_ids=s((e,)), sample_sorted=s((e,)), slot_sorted=s((e,)),
            order=s((e,)), rank=s((e,)), inv_compact=s((d + 1,)),
            inv_sorted=s((d + 1,)), num_rows=d + 1, num_entries=e,
            num_kept=e, num_unique=200_000)

    txt = _compiled_text(
        lambda p, v, dz: scatter_add_planned(p, v, dz, mode="kernel"),
        SlicedPlan([plan(half), plan(half)], (0, half, n)),
        s((n, k), jnp.float32), s((n, M2), jnp.float32))
    assert txt.count("tpu_custom_call") >= 2
