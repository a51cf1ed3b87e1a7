#!/usr/bin/env python3
"""The LS-PLM chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix; the mix names the
driver (``bench/drivers/<kind>.py``) that builds the program's objects,
warms them, runs the measured window and compares what the timed path
produced with the plain reference. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces a short steady slice and
reports its per-layer metrics, each read by ``bench/metrics/<name>.py``.

The run needs a TPU with at least the cell's number of chips: without
one it exits 2 and prints no result. The last line of standard output
is one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, ``breakdown`` when traced, and last ``checks``: each number
compared with its limit, also printed as the last lines of standard
error). JAX's persistent compilation cache is ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Context:
    """What a driver is given, and what it hands back through."""

    def __init__(self, cell, args, devices, limits):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.devices = devices
        self.limits = limits
        self.setup_end = None
        self.log = log

    def mark_setup_end(self, t: float) -> None:
        """The first measured step starts at ``t`` (perf_counter)."""
        self.setup_end = t


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices_for(chips: int):
    """The first ``chips`` TPU devices, or None (with the reason logged)."""
    import jax

    devs = jax.devices()
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    if devs[0].platform != "tpu":
        log(f"needs a TPU; JAX found {devs[0].platform}")
        return None
    if len(devs) < chips:
        log(f"needs {chips} chips; JAX found {len(devs)}")
        return None
    return devs[:chips]


def execute(args, devices, cell, limits: dict | None = None) -> dict:
    """Run the cell on ``devices`` and build the result line; ``limits``
    default to ``bench/limits/<cell>.json``."""
    from bench import check, spec

    if limits is None:
        limits = spec.load_json(ROOT / "bench" / "limits" / f"{cell.name}.json")
    ctx = Context(cell, args, devices, limits)
    driver = spec.load_module("drivers", cell.traffic["kind"])
    out = driver.run(ctx)

    metrics = {}
    if not ctx.trace:
        values = dict(out["metrics"], setup_s=ctx.setup_end - T_START)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
        if out.get("compiles_in_window"):
            log(f"{out['compiles_in_window']} compiles inside the window")
    else:
        inputs = dict(out, kind=devices[0].device_kind, chips=len(devices))
        for m in cell.per_layer:
            value = spec.load_module("metrics", m["name"]).read(inputs)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(out["memory_peak_bytes"])}
    line = {"correct": all(c.ok for c in out["checks"]),
            "attempted": int(out["attempted"]), "failed": int(out["failed"]),
            "metrics": metrics, "device": device}
    if ctx.trace:
        red = out["reduced"]
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        line["breakdown"] = red.breakdown()
    line["checks"] = check.report(out["checks"])
    return line


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from bench import spec

    try:
        cell = spec.cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        log(f"cannot load workload {args.workload!r}: {e}")
        return 2
    try:
        import jax
    except ImportError as e:
        log(f"cannot import jax: {e}")
        return 2
    devices = devices_for(cell.chips)
    if devices is None:
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache {enable_compile_cache()}")
    line = execute(args, devices, cell)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
