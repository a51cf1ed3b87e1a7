#!/usr/bin/env python3
"""Chip smoke: LS-PLM kernels, training, streaming and serving on a TPU,
through the normal entry points, at the paper's production width.

    python chip_smoke.py             # one chip: kernels, train, stream, serve
    python chip_smoke.py --chips 4   # four chips: sharded step vs one device

Width: d = 1,000,000 feature columns and m = 12 regions (2m = 24), the
paper's production division number; batches of 4096 sessions (B = 16,384
impressions, K_user = 24, K_ad = 12). Weights start random from --seed.
Depth is cut: 4 OWLQN+ iterations, a 3-day stream with 2 inner iterations
per window, 64 serving requests.

Every check raises on failure (non-zero exit). The script refuses to run
unless JAX's first device is a TPU. The last line of standard output is
one JSON object naming the device. Compiles go to JAX's persistent cache
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``),
so a second run in the same checkout starts warm.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

D_FEATURES = 1_000_000
REGIONS = 12
SESSIONS = 4096


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _phase(name: str, fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    _log(f"phase {name}: ok in {time.perf_counter() - t0:.1f}s")
    return out


def _train_args(seed: int, *extra: str):
    from repro.launch import train as train_cli

    return train_cli.build_parser().parse_args([
        "--sparse", "--sparse-features", str(D_FEATURES),
        "--regions", str(REGIONS), "--sessions", str(SESSIONS),
        "--lam", "0.05", "--beta", "0.05", "--seed", str(seed), *extra])


def _f_trajectory(run) -> tuple[list[float], list[float]]:
    import jax
    import numpy as np

    stats = jax.device_get(run.stats)
    f = [float(s.f) for s in stats]
    f_new = [float(s.f_new) for s in stats]
    if not np.all(np.isfinite(f + f_new)):
        raise AssertionError(f"non-finite objective: f={f} f_new={f_new}")
    return f, f_new


def phase_kernels(seed: int) -> None:
    """Compiled Pallas kernels (mode="kernel") against the fp32 gather +
    einsum / direct scatter-add oracles, small shapes up to production."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.lsplm_sparse_fused.ops import (
        pad_theta,
        sparse_gather_matmul,
        sparse_gather_matmul_int8,
    )
    from repro.kernels.lsplm_sparse_fused.ref import sparse_matmul_ref
    from repro.kernels.lsplm_sparse_scatter.ops import (
        build_transpose_plan,
        scatter_add_planned,
        scatter_add_ref,
    )

    shapes = [  # (N, K, d, m)
        (64, 8, 512, 2),
        (512, 8, 4_096, 4),
        (4096, 16, 16_384, 12),
        (4 * SESSIONS, 12, D_FEATURES, REGIONS),
    ]
    for n, k, d, m in shapes:
        rng = np.random.default_rng(seed + n)
        ids = rng.integers(0, d, (n, k))
        ids[:, -1] = d  # keep a pad column in play
        vals = rng.normal(size=(n, k)).astype(np.float32)
        vals[:, -1] = 0.0
        theta = (0.1 * rng.normal(size=(d, 2 * m))).astype(np.float32)
        dz = rng.normal(size=(n, 2 * m)).astype(np.float32)
        idsj, valsj = jnp.asarray(ids, jnp.int32), jnp.asarray(vals)
        tp = pad_theta(jnp.asarray(theta))

        z = sparse_gather_matmul(idsj, valsj, tp, mode="kernel")
        with jax.default_matmul_precision("highest"):
            z_ref = sparse_matmul_ref(idsj, valsj, tp)
        np.testing.assert_allclose(np.asarray(z), np.asarray(z_ref),
                                   rtol=1e-5, atol=1e-5)

        th = np.asarray(tp)
        scales = (np.abs(th).max(axis=1) / 127.0).astype(np.float32)
        codes = np.rint(th / np.where(scales > 0, scales, 1.0)[:, None])
        codes = codes.astype(np.int8)
        z8 = sparse_gather_matmul_int8(idsj, valsj, jnp.asarray(codes),
                                       jnp.asarray(scales), mode="kernel")
        with jax.default_matmul_precision("highest"):
            z8_ref = sparse_matmul_ref(
                idsj, valsj, jnp.asarray(codes * scales[:, None]))
        np.testing.assert_allclose(np.asarray(z8), np.asarray(z8_ref),
                                   rtol=1e-5, atol=1e-5)

        plan = build_transpose_plan(ids, d + 1, pad_id=d)
        dt = scatter_add_planned(plan, valsj, jnp.asarray(dz), mode="kernel")
        dt_ref = scatter_add_ref(idsj, valsj, jnp.asarray(dz), d + 1)
        np.testing.assert_allclose(np.asarray(dt), np.asarray(dt_ref),
                                   rtol=1e-4, atol=1e-5)
        _log(f"kernels N={n} K={k} d={d:,} m={m}: fp32 gather, int8 "
             f"gather and planned scatter match the oracles")


def phase_train(seed: int):
    """``launch.train --sparse``: 4 OWLQN+ iterations at full width."""
    from repro.launch import train as train_cli

    run = train_cli.train_sparse(_train_args(seed, "--iters", "4"))
    f, f_new = _f_trajectory(run)
    falling = f_new[-1] < f[0] and all(
        b <= a for a, b in zip([f[0]] + f_new, f_new))
    if not falling:
        raise AssertionError(f"objective not falling: f={f} f_new={f_new}")
    _log(f"train: f {f[0]:.2f} -> " + " -> ".join(f"{x:.2f}" for x in f_new)
         + " (finite, non-increasing, below the start)")
    hlo = run.step.lower(run.state).compile().as_text()
    calls = hlo.count("tpu_custom_call")
    if not calls:
        raise AssertionError("training step holds no Pallas kernel")
    _log(f"train: compiled OWLQN+ step holds {calls} tpu_custom_call sites")
    return run.theta


def phase_stream(seed: int) -> None:
    """``launch.train --stream``: 3 days, window 2, 2 inner iterations."""
    import numpy as np

    from repro.launch import train as train_cli

    args = train_cli.build_parser().parse_args([
        "--stream", "--days", "3", "--window", "2", "--inner-iters", "2",
        "--sparse-features", str(D_FEATURES), "--regions", str(REGIONS),
        "--sessions", str(SESSIONS), "--lam", "0.05", "--beta", "0.05",
        "--seed", str(seed)])
    trace = train_cli.train_stream(args)
    if len(trace) != 3:
        raise AssertionError(f"{len(trace)} of 3 stream windows completed")
    for ws in trace:
        if not np.all(np.isfinite(ws.fs)):
            raise AssertionError(f"day {ws.day}: non-finite f {ws.fs}")
    _log(f"stream: {len(trace)} windows completed, f per window "
         + ", ".join(f"{ws.fs[-1]:.2f}" for ws in trace))


def _oracle_scores(theta, requests):
    """fp32 gather+einsum oracle on the full Theta, one padded batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.lsplm_sparse_fused.ops import finalize_p, pad_theta
    from repro.kernels.lsplm_sparse_fused.ref import sparse_matmul_ref

    d = theta.shape[0]
    ku = max(r.user_ids.shape[0] for r in requests)
    ka = max(r.ad_ids.shape[1] for r in requests)
    ui = np.full((len(requests), ku), d, np.int32)
    uv = np.zeros((len(requests), ku), np.float32)
    ai, av, sess = [], [], []
    for s, r in enumerate(requests):
        ui[s, :r.user_ids.shape[0]] = r.user_ids
        uv[s, :r.user_vals.shape[0]] = r.user_vals
        n, k = r.ad_ids.shape
        ai.append(np.pad(r.ad_ids, ((0, 0), (0, ka - k)), constant_values=d))
        av.append(np.pad(r.ad_vals, ((0, 0), (0, ka - k))))
        sess.append(np.full(n, s, np.int32))
    tp = pad_theta(theta)
    with jax.default_matmul_precision("highest"):
        z_u = sparse_matmul_ref(jnp.asarray(ui), jnp.asarray(uv), tp)
        z_a = sparse_matmul_ref(jnp.asarray(np.concatenate(ai)),
                                jnp.asarray(np.concatenate(av)), tp)
        p = np.asarray(finalize_p(z_u[np.concatenate(sess)] + z_a))
    bounds = np.cumsum([0] + [r.ad_ids.shape[0] for r in requests])
    return [p[a:b] for a, b in zip(bounds[:-1], bounds[1:])]


def phase_serve(seed: int, theta) -> None:
    """``launch.serve``'s prune -> engine path on the trained Theta, fp32
    and int8-native, each scored against the fp32 oracle on the rows it
    serves (int8: the dequantised codes; their drift from the fp32 model
    is the serve driver's own bounded check)."""
    import numpy as np

    from repro.launch import serve as serve_cli
    from repro.serve import compress, dequantize, quantize

    deq = dequantize(quantize(compress(theta)))
    served = {"fp32": theta, "int8": deq.theta[deq.remap[:theta.shape[0]]]}
    for dtype, extra in (("fp32", []), ("int8", ["--int8"])):
        args = serve_cli.build_parser().parse_args(
            ["--requests", "64", "--seed", str(seed), *extra])
        run = serve_cli.serve(args, theta=theta)
        p_ref = _oracle_scores(served[dtype], run.requests)
        for p, r in zip(run.scores, p_ref):
            np.testing.assert_allclose(p, r, rtol=1e-5, atol=1e-6)
        worst = max(float(np.abs(p - r).max())
                    for p, r in zip(run.scores, p_ref))
        compiles = run.engine.stats.compiles
        run.engine.score_batch(run.requests)
        recompiles = run.engine.stats.compiles - compiles
        if recompiles:
            raise AssertionError(f"{dtype} engine recompiled {recompiles}x")
        _log(f"serve {dtype}: {len(run.requests)} ragged requests, max |p - "
             f"oracle| = {worst:.2e}, {compiles} compiles in warm-up, "
             f"{recompiles} recompiles after")


def phase_sharded(seed: int) -> None:
    """The sharded worker/server step on a (data=2, model=2) mesh next to
    the single-device run: f trajectories agree to rtol 2e-3."""
    import numpy as np

    from repro.launch import train as train_cli

    single = train_cli.train_sparse(_train_args(seed, "--iters", "3"))
    sharded = train_cli.train_sparse(_train_args(
        seed, "--iters", "3", "--mesh-data", "2", "--mesh-model", "2"))
    f1, f1_new = _f_trajectory(single)
    f4, f4_new = _f_trajectory(sharded)
    np.testing.assert_allclose(f4 + f4_new, f1 + f1_new, rtol=2e-3)
    hlo = sharded.step.lower(sharded.state).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise AssertionError("sharded step holds no Pallas kernel")
    worst = max(abs(a - b) / abs(b) for a, b in zip(f4_new, f1_new))
    _log("sharded (data=2, model=2) f: "
         + " -> ".join(f"{x:.4f}" for x in f4_new) + "; single device: "
         + " -> ".join(f"{x:.4f}" for x in f1_new)
         + f"; max rel diff {worst:.2e} (<= 2e-3)")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded step vs one device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    _log(f"device {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
         f"compile cache {cache}")
    cut = ("3 OWLQN+ iters on a (data=2, model=2) mesh and on one device"
           if args.chips == 4 else
           "4 OWLQN+ iters, 3 stream days x 2 inner iters, 64 requests")
    _log(f"width d={D_FEATURES:,} m={REGIONS} sessions={SESSIONS}; cut: {cut}")

    t0 = time.perf_counter()
    if args.chips == 4:
        _phase("sharded", phase_sharded, args.seed)
    else:
        _phase("kernels", phase_kernels, args.seed)
        theta = _phase("train", phase_train, args.seed)
        _phase("stream", phase_stream, args.seed)
        _phase("serve", phase_serve, args.seed, theta)
    _log(f"all phases ok in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
