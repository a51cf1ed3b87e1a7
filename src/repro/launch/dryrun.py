import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

"""Multi-pod dry-run: prove every (architecture x input shape) lowers and
compiles on the production meshes, and extract roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single --out results.json
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi

For each combo we jit with explicit in/out shardings, .lower() on
ShapeDtypeStructs (no allocation), .compile(), then record
memory_analysis() / cost_analysis() / collective bytes parsed from the
compiled HLO.
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (
    INPUT_SHAPES,
    decode_cache_len,
    get_config,
    input_specs,
    list_archs,
    uses_sliding_window,
)
from repro.launch.mesh import data_axes, make_production_mesh
from repro.models import (
    init_caches,
    init_model,
    make_serve_step,
    make_train_step,
    param_specs,
    prefill,
)
from repro.models.transformer import cache_specs
from repro.utils.hlo import collective_bytes
from repro.utils.roofline import Roofline, model_flops_per_chip


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _cast_tree(tree, dtype):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, dtype if jnp.issubdtype(s.dtype, jnp.floating) else s.dtype),
        tree,
    )


def _batch_spec_tree(batch, dp):
    """Batch-dim sharding for every input leaf."""
    def spec(s):
        if s.shape and s.shape[0] > 1:
            return P(dp, *([None] * (len(s.shape) - 1)))
        return P(*([None] * len(s.shape)))

    return jax.tree.map(spec, batch)


def lower_combo(cfg, shape_name: str, mesh, serve_dtype=jnp.bfloat16,
                moe_serving_mode: str = "weight_gather"):
    """Build + lower + compile one (cfg x shape x mesh) combo.

    Returns (lowered, compiled, meta) — meta has tokens processed.
    """
    spec = INPUT_SHAPES[shape_name]
    kind = spec["kind"]
    B, S = spec["global_batch"], spec["seq_len"]
    dp = data_axes(mesh)
    pspec = param_specs(cfg)
    batch = input_specs(cfg, shape_name)

    if kind == "train":
        params_s = jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0)))
        opt, train_step = make_train_step(cfg, mesh=mesh)
        opt_s = jax.eval_shape(opt.init, params_s)
        from repro.optim.adamw import AdamWState
        ospec = AdamWState(mu=pspec, nu=pspec, count=P())
        bspec = _batch_spec_tree(batch, dp)
        jitted = jax.jit(
            train_step,
            in_shardings=(_ns(mesh, pspec), _ns(mesh, ospec), _ns(mesh, bspec)),
            out_shardings=(_ns(mesh, pspec), _ns(mesh, ospec), None),
        )
        lowered = jitted.lower(params_s, opt_s, batch)
        tokens = B * S
    elif kind == "prefill":
        params_s = _cast_tree(
            jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0))),
            serve_dtype,
        )
        bspec = _batch_spec_tree(batch, dp)

        def prefill_step(params, batch):
            return prefill(params, cfg, mesh=mesh, **batch)

        cspec = cache_specs(cfg, batch_sharded=True, dp=dp, model_size=mesh.shape["model"])
        jitted = jax.jit(
            prefill_step,
            in_shardings=(_ns(mesh, pspec), _ns(mesh, bspec)),
            out_shardings=(None, _ns(mesh, cspec)),
        )
        lowered = jitted.lower(params_s, batch)
        tokens = B * S
    else:  # decode
        params_s = _cast_tree(
            jax.eval_shape(lambda: init_model(cfg, jax.random.PRNGKey(0))),
            serve_dtype,
        )
        window = uses_sliding_window(cfg, shape_name)
        cache_len = decode_cache_len(cfg, shape_name)
        batch_sharded = B > 1
        caches_s = jax.eval_shape(
            lambda: init_caches(cfg, B, cache_len, dtype=serve_dtype))
        cspec = cache_specs(cfg, batch_sharded=batch_sharded, dp=dp, model_size=mesh.shape["model"])
        serve_step = make_serve_step(cfg, mesh=mesh, window=window,
                                     batch_sharded=batch_sharded,
                                     moe_serving_mode=moe_serving_mode)
        tok = batch.get("token", batch.get("embed"))
        tok_spec = P(dp) if (batch_sharded and tok.ndim >= 1) else P(
            *([None] * tok.ndim))
        jitted = jax.jit(
            serve_step,
            in_shardings=(_ns(mesh, pspec), _ns(mesh, cspec),
                          NamedSharding(mesh, tok_spec), None),
            out_shardings=(None, _ns(mesh, cspec)),
        )
        lowered = jitted.lower(params_s, caches_s, tok,
                               jax.ShapeDtypeStruct((), jnp.int32))
        tokens = B  # one new token per sequence
    compiled = lowered.compile()
    return lowered, compiled, {"tokens": tokens, "kind": kind,
                               "window": kind == "decode" and
                               uses_sliding_window(cfg, shape_name)}


def _costs(compiled):
    ca = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)),
            float(coll["total_bytes"]), coll)


def _ssm_scan_corrections(cfg, shape_name, chips):
    """Mamba1's per-timestep selective scan is a while loop whose body
    cost_analysis counts once; no matmul factorisation exists (DESIGN.md
    §4), so we model it analytically with the Pallas-kernel streaming
    model: state lives in VMEM, inputs/outputs stream from HBM once.

      flops  ~= 8 * B*S*di*N   per layer (exp, h update, C reduction)
      bytes  ~= 4 * B*S*di * 4 per layer (dt,x in + y out + misc, fp32)

    Mamba2's SSD path is matmul-form (honest under unrolling) except the
    tiny inter-chunk state pass, corrected the same way."""
    if cfg.family not in ("ssm", "hybrid"):
        return 0.0, 0.0
    spec = INPUT_SHAPES[shape_name]
    if spec["kind"] == "decode":
        return 0.0, 0.0  # decode steps are loop-free (honest)
    B, S = spec["global_batch"], spec["seq_len"]
    di, N = cfg.d_inner, cfg.ssm_state
    L = cfg.num_layers
    mult = 3 if spec["kind"] == "train" else 1  # fwd+bwd ~ 3x fwd
    if cfg.family == "ssm":  # mamba1 per-step scan
        flops = 8.0 * B * S * di * N * L * mult
        bytes_ = 4.0 * B * S * di * 4 * L * mult
    else:  # mamba2: only inter-chunk state pass (nc steps)
        nh, p = di // cfg.ssm_headdim, cfg.ssm_headdim
        nc = S // cfg.ssd_chunk
        flops = 3.0 * B * nc * nh * p * N * L * mult
        bytes_ = 2.0 * B * nc * nh * p * N * 4 * L * mult
    return flops / chips, bytes_ / chips


def extrapolated_costs(cfg, shape_name, mesh, chips, **lower_kwargs):
    """XLA's cost_analysis counts while-loop (scan) bodies ONCE regardless
    of trip count. We recover true totals by compiling shallow variants
    with every layer/attention-chunk scan UNROLLED (cost_analysis then sees
    each iteration), and extrapolating linearly in depth:
        X(L) = X(l1) + (L - l1) * (X(l2) - X(l1)) / (l2 - l1),
    exact for uniform stacked layers. Mamba1's per-timestep scan cannot be
    unrolled (S up to 512k); it gets an analytic streaming correction."""
    import dataclasses

    spec = INPUT_SHAPES[shape_name]
    if spec["kind"] == "decode":
        # decode bodies are small (no chunk scans): unroll the FULL depth
        # and read exact costs — depth extrapolation is unreliable here
        # (GSPMD re-plans reshardings per depth).
        full = dataclasses.replace(cfg, unroll_layers=True)
        _, c_full, _ = lower_combo(full, shape_name, mesh, **lower_kwargs)
        f, b, cb, _ = _costs(c_full)
        df, db = _ssm_scan_corrections(cfg, shape_name, chips)
        return f + df, b + db, cb

    if cfg.family == "hybrid":
        k = cfg.shared_attn_every
        l1, l2 = k, 2 * k
    else:
        l1, l2 = 1, 2
    # keep probe compile time bounded: <= 16 attention chunks / 8 ssd chunks
    attn_chunk = max(cfg.attn_chunk, spec["seq_len"] // 16)
    ssd_chunk = max(cfg.ssd_chunk, min(spec["seq_len"] // 8, 512))
    probe = dict(unroll_layers=True, attn_chunk=attn_chunk, ssd_chunk=ssd_chunk)
    cfg1 = dataclasses.replace(cfg, num_layers=l1, **probe)
    cfg2 = dataclasses.replace(cfg, num_layers=l2, **probe)
    _, c1, _ = lower_combo(cfg1, shape_name, mesh, **lower_kwargs)
    f1, b1, cb1, _ = _costs(c1)
    _, c2, _ = lower_combo(cfg2, shape_name, mesh, **lower_kwargs)
    f2, b2, cb2, _ = _costs(c2)
    scale = (cfg.num_layers - l1) / (l2 - l1)
    df, db = _ssm_scan_corrections(cfg, shape_name, chips)
    return (f1 + scale * (f2 - f1) + df,
            b1 + scale * (b2 - b1) + db,
            max(cb1 + scale * (cb2 - cb1), 0.0))


def analyse(arch, shape_name, mesh_name, compiled, cfg, meta, mesh,
            probes: bool = True, lower_kwargs: dict | None = None) -> dict:
    lower_kwargs = lower_kwargs or {}
    chips = 512 if mesh_name == "multi" else 256
    ma = compiled.memory_analysis()
    f_raw, b_raw, cb_raw, coll = _costs(compiled)
    if probes:
        flops, hbm_bytes, coll_bytes = extrapolated_costs(
            cfg, shape_name, mesh, chips, **lower_kwargs)
    else:  # multi-pod pass proves lowering/sharding; roofline is single-pod
        flops, hbm_bytes, coll_bytes = f_raw, b_raw, cb_raw
    rl = Roofline(
        flops=flops,
        hbm_bytes=hbm_bytes,
        coll_bytes=coll_bytes,
        model_flops=model_flops_per_chip(cfg, meta["kind"], meta["tokens"], chips),
    )
    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "kind": meta["kind"],
        "sliding_window": bool(meta.get("window")),
        "chips": chips,
        "memory": {
            "argument_bytes_per_chip": ma.argument_size_in_bytes,
            "output_bytes_per_chip": ma.output_size_in_bytes,
            "temp_bytes_per_chip": ma.temp_size_in_bytes,
            "total_bytes_per_chip": (ma.argument_size_in_bytes
                                     + ma.temp_size_in_bytes),
        },
        "collectives": coll,
        "raw_body_once": {"flops": f_raw, "hbm_bytes": b_raw,
                          "collective_bytes": cb_raw},
        "roofline": rl.to_dict(),
    }


def run_one(arch, shape_name, mesh_name, verbose=True, probes=True):
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    cfg = get_config(arch)
    t0 = time.time()
    _lowered, compiled, meta = lower_combo(cfg, shape_name, mesh)
    rec = analyse(arch, shape_name, mesh_name, compiled, cfg, meta, mesh,
                  probes=probes)
    rec["compile_seconds"] = round(time.time() - t0, 1)
    if verbose:
        r = rec["roofline"]
        mem_gb = rec["memory"]["total_bytes_per_chip"] / 2**30
        print(f"[OK] {arch:22s} {shape_name:12s} {mesh_name:6s} "
              f"compile={rec['compile_seconds']:6.1f}s mem/chip={mem_gb:7.2f}GiB "
              f"t_comp={r['t_compute_s']:.3e} t_mem={r['t_memory_s']:.3e} "
              f"t_coll={r['t_collective_s']:.3e} bound={r['bottleneck']:10s} "
              f"useful={r['useful_flops_ratio']:.2f}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--no-probes", action="store_true",
                    help="skip depth-probe compiles (multi-pod pass)")
    args = ap.parse_args()

    combos = []
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) else [args.shape]
    for a in archs:
        for s in shapes:
            combos.append((a, s))

    results = []
    if args.out and args.skip_existing and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results if "roofline" in r}

    for arch, shape_name in combos:
        if (arch, shape_name, args.mesh) in done:
            continue
        try:
            rec = run_one(arch, shape_name, args.mesh,
                          probes=not args.no_probes)
        except Exception as e:  # noqa: BLE001 — record the failure, keep going
            rec = {"arch": arch, "shape": shape_name, "mesh": args.mesh,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            print(f"[FAIL] {arch} {shape_name} {args.mesh}: {rec['error']}",
                  flush=True)
        results.append(rec)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)

    n_ok = sum(1 for r in results if "roofline" in r)
    print(f"\n{n_ok}/{len(results)} combos compiled successfully")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
