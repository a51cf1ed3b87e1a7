"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  * bench_division        — Fig. 4 (division number m sweep)
  * bench_regularization  — Table 2 (L1 / L2,1 sparsity + AUC)
  * bench_common_feature  — Table 3 (common-feature trick cost)
  * bench_lr_vs_lsplm     — Fig. 5 (LS-PLM vs LR over 7 datasets)
  * bench_sparse_fused    — fused sparse kernel fwd/bwd vs oracles
  * bench_tune            — autotuned configs vs the hand-picked defaults
  * bench_stream          — streaming trainer: overlapped re-planner
  * bench_serve           — serving: pruned artifacts, shared bundles, engine
  * bench_obs             — observability overhead: instrumented train step
  * roofline_report       — §Roofline rows from the dry-run artifacts

Usage:
  PYTHONPATH=src python -m benchmarks.run [--only NAME[,NAME...]] \
      [--smoke] [--json]

``--only`` selects suites by name — an exact module name (with or
without the ``bench_`` prefix) or a substring; comma-separate to run
several — so CI jobs can run a single suite without paying for the
rest. ``--smoke`` asks modules that support it for tiny shapes;
``--json`` additionally writes the machine-readable perf trajectories
CI archives as artifacts: ``BENCH_sparse_fused.json`` (kernel
fwd/bwd timings + speedups), ``BENCH_stream.json`` (streaming
steps/sec, overlap ratio, overlapped-vs-sync speedup, per-day decay
table), ``BENCH_serve.json`` (pruned-vs-full, shared-vs-naive,
engine latency) and ``BENCH_obs.json`` (instrumentation overhead
ratio). The CI smoke steps run ``--only sparse_fused``, ``--only
stream``, ``--only serve`` and ``--only obs`` with ``--smoke --json``
on CPU.

Every ``--json`` artifact also carries a ``meta`` block — git rev,
backend, device/cpu counts and the module's wall seconds — so an
archived trajectory is self-describing. ``check_regression.py`` treats
``meta.*`` as info-only: provenance drift never fails the gate.
"""
from __future__ import annotations

import os

if "REPRO_DEVICES" in os.environ:  # must precede any jax import: the
    # sharded sparse rows need forced host devices (same knob as
    # repro.launch.train and the CI shard job)
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS"),
        f"--xla_force_host_platform_device_count={os.environ['REPRO_DEVICES']}",
    ]))

import argparse
import inspect
import json
import subprocess
import sys
import time
import traceback

SPARSE_FUSED_JSON = "BENCH_sparse_fused.json"
TUNE_JSON = "BENCH_tune.json"
STREAM_JSON = "BENCH_stream.json"
SERVE_JSON = "BENCH_serve.json"
OBS_JSON = "BENCH_obs.json"


def _git_rev() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10, check=True).stdout.strip()
    except Exception:  # noqa: BLE001 — provenance only, never fail a bench
        return "unknown"


def _meta(wall_seconds: float) -> dict:
    """Provenance stamped into every BENCH_*.json. Info-only for the
    regression gate (``check_regression.py`` matches ``meta.*``)."""
    import jax

    return {
        "git_rev": _git_rev(),
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "cpu_count": os.cpu_count(),
        "wall_seconds": wall_seconds,
    }


def _select(mods, only: str):
    """--only: comma-separated names; each matches a module exactly
    (``bench_stream`` / ``stream``) or as a substring. An unmatched name
    is a hard error LISTING the valid modules — a typo must not silently
    run nothing (CI would archive an empty artifact and call it green).
    """
    picked = []
    for name in (s.strip() for s in only.split(",") if s.strip()):
        short = {m.__name__.split(".")[-1]: m for m in mods}
        hits = [short[name]] if name in short else (
            [short[f"bench_{name}"]] if f"bench_{name}" in short
            else [m for m in mods if name in m.__name__])
        if not hits:
            raise SystemExit(
                f"--only {name!r} matched no benchmark module; valid names: "
                + ", ".join(sorted(short)))
        picked += [m for m in hits if m not in picked]
    return picked


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="run only these suites: exact module names (with or "
                         "without the bench_ prefix) or substrings, "
                         "comma-separated")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes where supported (CI)")
    ap.add_argument("--json", action="store_true",
                    help=f"write {SPARSE_FUSED_JSON} / {TUNE_JSON} / "
                         f"{STREAM_JSON} / {SERVE_JSON} / {OBS_JSON} with "
                         "the machine-readable timings (CI artifacts)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_common_feature,
        bench_division,
        bench_lr_vs_lsplm,
        bench_obs,
        bench_regularization,
        bench_router_balance,
        bench_serve,
        bench_sparse_fused,
        bench_stream,
        bench_tune,
        roofline_report,
    )

    mods = [bench_division, bench_regularization, bench_common_feature,
            bench_lr_vs_lsplm, bench_router_balance, bench_sparse_fused,
            bench_tune, bench_stream, bench_serve, bench_obs,
            roofline_report]
    json_paths = {bench_sparse_fused: SPARSE_FUSED_JSON,
                  bench_tune: TUNE_JSON,
                  bench_stream: STREAM_JSON,
                  bench_serve: SERVE_JSON,
                  bench_obs: OBS_JSON}
    if args.only:
        mods = _select(mods, args.only)

    ok = True
    for mod in mods:
        kwargs = {}
        params = inspect.signature(mod.run).parameters
        if args.smoke and "smoke" in params:
            kwargs["smoke"] = True
        collect: dict = {}
        if args.json and mod in json_paths:
            kwargs["collect"] = collect
        t0 = time.perf_counter()
        try:
            mod.run(**kwargs)
        except Exception:  # noqa: BLE001
            ok = False
            print(f"{mod.__name__},0,ERROR", file=sys.stderr)
            traceback.print_exc()
            if "collect" in kwargs:
                collect["error"] = traceback.format_exc()
        if "collect" in kwargs:
            collect["meta"] = _meta(time.perf_counter() - t0)
            # written even when a gate raised (possibly partial, plus the
            # "error" traceback): CI archives the trajectory either way
            # and the regression gate reports WHAT was missing instead of
            # diffing against a file that does not exist
            with open(json_paths[mod], "w") as f:
                json.dump(collect, f, indent=2, sort_keys=True)
            print(f"wrote {json_paths[mod]}", file=sys.stderr)
    if not ok:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
