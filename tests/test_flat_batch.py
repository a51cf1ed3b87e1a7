"""Sessionless training batches (``FlatCTRBatch``), the planned backward
split by samples (``SlicedPlan``), and the Criteo-schema source through
``launch.train --sparse --schema criteo39``."""
import functools
import importlib
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import CTRBatch
from repro.data import criteo
from repro.data.sparse import (
    FlatCTRBatch,
    SlicedPlan,
    SparseCTRBatch,
    build_batch_plans,
    build_transpose_plan,
    plan_slices,
)
from repro.kernels.lsplm_sparse_scatter.lsplm_sparse_scatter import (
    DZ_ROW_BYTES,
    MAX_DZ_VMEM_BYTES,
    max_slice_samples,
)
from repro.kernels.lsplm_sparse_scatter.ops import (
    dvals_planned,
    scatter_add_planned,
)

# repro.core's own ``objective`` function hides the module attribute
objective = importlib.import_module("repro.core.objective")


def _flat(n=24, k=7, d=300, m=3, seed=0, pad=True):
    rng = np.random.default_rng(seed)
    ids = (d * rng.random((n, k)) ** 3).astype(np.int32)  # hot low ids
    vals = rng.normal(size=(n, k)).astype(np.float32) / np.sqrt(k)
    if pad:
        ids[::5, -2:], vals[::5, -2:] = d, 0.0
    y = (rng.random(n) < 0.3).astype(np.float32)
    theta = jnp.asarray(rng.normal(size=(d, 2 * m)) * 0.3, jnp.float32)
    batch = FlatCTRBatch(ids=jnp.asarray(ids), vals=jnp.asarray(vals),
                         y=jnp.asarray(y), num_features=d)
    return batch, theta


def _dense_x(batch):
    n, d = batch.ids.shape[0], batch.num_features
    x = np.zeros((n, d + 1), np.float32)
    np.add.at(x, (np.arange(n)[:, None], np.asarray(batch.ids)),
              np.asarray(batch.vals))
    return jnp.asarray(x[:, :d])


@pytest.mark.parametrize("mode", ["jnp", "interpret"])
def test_flat_nll_and_gradient_match_the_plain_reference(mode):
    batch, theta = _flat()
    planned = build_batch_plans(batch)
    f = lambda t: objective.nll_sparse(t, planned, mode=mode)
    val, grad = jax.jit(jax.value_and_grad(f))(theta)
    ref = lambda t: objective.nll(t, CTRBatch(x=_dense_x(batch), y=batch.y))
    with jax.default_matmul_precision("highest"):
        rval, rgrad = jax.value_and_grad(ref)(theta)
    np.testing.assert_allclose(float(val), float(rval), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(rgrad),
                               rtol=1e-4, atol=1e-6)


def test_flat_rows_equal_the_same_rows_as_sessions_of_one_ad():
    batch, theta = _flat(seed=1)
    split = 3
    session = SparseCTRBatch(
        user_ids=batch.ids[:, :split], user_vals=batch.vals[:, :split],
        ad_ids=batch.ids[:, split:], ad_vals=batch.vals[:, split:],
        session_id=jnp.arange(batch.ids.shape[0], dtype=jnp.int32),
        y=batch.y, num_features=batch.num_features)
    loss_and_grad = jax.jit(objective.smooth_loss_and_grad)
    val, grad = loss_and_grad(theta, build_batch_plans(batch))
    sval, sgrad = loss_and_grad(theta, build_batch_plans(session))
    np.testing.assert_allclose(float(val), float(sval), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(sgrad),
                               rtol=1e-5, atol=1e-7)


def test_flat_nll_runs_one_gather_and_no_session_gather(monkeypatch):
    batch, theta = _flat(seed=2)
    calls = []
    real = objective.sparse_gather_matmul

    def counted(ids, *a, **kw):
        calls.append(ids.shape)
        return real(ids, *a, **kw)

    monkeypatch.setattr(objective, "sparse_gather_matmul", counted)
    objective.smooth_loss_and_grad(theta, build_batch_plans(batch))
    assert calls == [batch.ids.shape]


def test_slices_are_the_fewest_that_fit_and_differ_by_at_most_one():
    assert max_slice_samples() == MAX_DZ_VMEM_BYTES // DZ_ROW_BYTES == 131_072
    ids = np.zeros((20, 3), np.int32)
    plan = build_transpose_plan(ids, 5, max_samples=7)
    assert isinstance(plan, SlicedPlan)
    assert plan.bounds == (0, 6, 13, 20)
    assert [p.num_entries for p in plan.plans] == [18, 21, 21]
    assert isinstance(build_transpose_plan(ids, 5, max_samples=20),
                      type(plan.plans[0]))


@pytest.mark.parametrize("n,ceiling_samples", [(20, 7), (33, 8), (9, 4)])
def test_sliced_planned_backward_matches_the_unsliced_one(n, ceiling_samples):
    """Uneven slices, a small ceiling passed as an argument, the Pallas
    kernel in interpret mode: the same dTheta and dvals within float32
    rounding."""
    batch, theta = _flat(n=n, seed=n)
    d, k = batch.num_features, batch.ids.shape[1]
    whole = build_batch_plans(batch).plan
    sliced = build_batch_plans(
        batch, max_dz_bytes=ceiling_samples * DZ_ROW_BYTES).plan
    assert isinstance(sliced, SlicedPlan)
    assert len(plan_slices(sliced)) == -(-n // ceiling_samples)
    tp = objective.pad_theta(theta)
    dz = jax.random.normal(jax.random.key(n), (n, tp.shape[1]), jnp.float32)
    scatter = jax.jit(functools.partial(scatter_add_planned, mode="interpret",
                                        block_e=16))
    a, b = (scatter(p, batch.vals, dz) for p in (whole, sliced))
    np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                               rtol=1e-6, atol=1e-6)
    dvals = jax.jit(dvals_planned, static_argnums=3)
    np.testing.assert_allclose(np.asarray(dvals(sliced, tp, dz, (n, k))),
                               np.asarray(dvals(whole, tp, dz, (n, k))),
                               rtol=1e-6, atol=1e-6)
    # and through the loss's custom VJP (the jnp class-gather path)
    loss = jax.jit(jax.value_and_grad(
        lambda t, b: objective.nll_sparse(t, b, mode="jnp")))
    va, ga = loss(theta, batch._replace(plan=whole))
    vb, gb = loss(theta, batch._replace(plan=sliced))
    np.testing.assert_allclose(float(vb), float(va), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(ga),
                               rtol=1e-5, atol=1e-6)
    assert all(p.num_rows == d + 1 for p in sliced.plans)


def test_session_batches_below_the_ceiling_keep_one_plan_a_side():
    batch, _ = _flat(n=16, seed=3)
    session = SparseCTRBatch(
        user_ids=batch.ids[:4, :3], user_vals=batch.vals[:4, :3],
        ad_ids=batch.ids[:, 3:], ad_vals=batch.vals[:, 3:],
        session_id=jnp.repeat(jnp.arange(4, dtype=jnp.int32), 4),
        y=batch.y, num_features=batch.num_features)
    planned = build_batch_plans(session)
    for plan in (planned.user_plan, planned.ad_plan):
        assert len(plan_slices(plan)) == 1 and not isinstance(plan, SlicedPlan)


def test_planning_records_its_span_and_counters():
    batch, _ = _flat(n=20, seed=4)
    reg = obs.MetricsRegistry()
    prev_reg, prev_tr = obs.set_registry(reg), obs.set_tracer(obs.Tracer())
    try:
        build_batch_plans(batch, max_dz_bytes=8 * DZ_ROW_BYTES)
        spans = [e for e in obs.get_tracer().events()
                 if e.get("name") == "train/plan"]
    finally:
        obs.set_registry(prev_reg)
        obs.set_tracer(prev_tr)
    ids = np.asarray(batch.ids)
    real = ids[ids != batch.num_features]
    want = {"impressions": 20, "entries": real.size,
            "distinct_rows": np.unique(real).size, "slices": 3}
    assert len(spans) == 1 and spans[0]["args"] == want
    got = {s.name: s.value for s in reg.series()}
    assert got == {f"train_plan_{k}": float(v) for k, v in want.items()}


def test_sharded_paths_refuse_a_sessionless_batch():
    from repro.shard import make_partition, route_batch

    batch, _ = _flat(seed=5)
    with pytest.raises(ValueError, match="one device"):
        build_batch_plans(batch, shards=2)
    with pytest.raises(ValueError, match="one device"):
        route_batch(batch, make_partition(batch.num_features, 2))


def test_criteo_layout_one_id_per_field_in_its_range():
    sizes = criteo.field_sizes()
    assert sizes.size == 39 and int(sizes.sum()) == criteo.NUM_FEATURES == 998_960
    assert (sizes[:13] == 100).all()
    raw = np.asarray(criteo.CAT_COUNTS)
    capped = raw > criteo.CAP
    assert capped.sum() == 7
    # the two largest raw fields take the two rows the cap leaves over
    assert sorted(np.flatnonzero(sizes[13:] == criteo.CAP + 1)) == \
        sorted(np.argsort(-raw)[:2])
    np.testing.assert_array_equal(sizes[13:][~capped], raw[~capped])
    b = criteo.generate_criteo(500, seed=3, with_plans=False)
    ids = np.asarray(b.ids)
    lo = criteo.field_offsets(sizes)
    assert ids.shape == (500, 39)
    assert ((ids >= lo) & (ids < lo + sizes)).all()
    # rank 0 (the field's unknown id) is each field's most frequent row
    assert (np.asarray([np.bincount(ids[:, f] - lo[f]).argmax()
                        for f in range(39)]) == 0).all()
    np.testing.assert_allclose(np.asarray(b.vals), 1 / np.sqrt(39))
    assert 0.2 < float(np.asarray(b.y).mean()) < 0.32


def test_launch_train_criteo39_end_to_end(tmp_path, monkeypatch, capsys):
    from repro.launch import train

    trace, metrics = tmp_path / "trace.json", tmp_path / "metrics.json"
    monkeypatch.setattr(sys, "argv", [
        "train", "--sparse", "--schema", "criteo39", "--impressions", "256",
        "--regions", "2", "--lam", "0.05", "--beta", "0.05", "--iters", "3",
        "--trace-out", str(trace), "--metrics-out", str(metrics)])
    prev = obs.set_registry(obs.MetricsRegistry())
    try:
        assert train.main() == 0
    finally:
        obs.set_registry(prev)
    out = capsys.readouterr().out
    assert "criteo39 schema): d=998,960" in out
    assert "ids transpose plan: 9,984 entries" in out
    fs = [float(line.split("f=")[1].split()[0]) for line in out.splitlines()
          if line.startswith("iter")]
    assert len(fs) == 2 and fs[-1] < fs[0]
    plan = [e for e in json.loads(trace.read_text())["traceEvents"]
            if e.get("name") == "train/plan"]
    # the training batch is planned once; the held-out batch, only scored,
    # is never planned
    assert [e["args"]["impressions"] for e in plan] == [256]
    assert all(e["args"]["slices"] == 1 for e in plan)
    snap = json.loads(metrics.read_text())
    assert "train_plan_slices" in json.dumps(snap)


def test_launch_train_refuses_criteo39_off_the_sparse_path(monkeypatch):
    from repro.launch import train

    monkeypatch.setattr(sys, "argv", ["train", "--stream", "--schema",
                                      "criteo39"])
    with pytest.raises(SystemExit, match="--sparse"):
        train.main()


def test_launch_train_sharded_plans_the_batch_once(tmp_path):
    """On a (data x model) mesh the session batch is planned once, whole,
    and routed: one ``train/plan`` span of the training batch's
    impressions. Run in a subprocess for four host devices."""
    root = Path(__file__).resolve().parents[1]
    trace = tmp_path / "trace.json"
    env = dict(os.environ, PYTHONPATH=str(root / "src"), REPRO_DEVICES="4",
               JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--sparse",
         "--sparse-features", "20000", "--sessions", "32", "--regions", "2",
         "--iters", "2", "--mesh-data", "2", "--mesh-model", "2",
         "--trace-out", str(trace)],
        env=env, cwd=tmp_path, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "mesh: data=2 x model=2" in proc.stdout
    plan = [e for e in json.loads(trace.read_text())["traceEvents"]
            if e.get("name") == "train/plan"]
    assert [e["args"]["impressions"] for e in plan] == [32 * 4]
    assert plan[0]["args"]["slices"] == 2  # one whole plan a side
