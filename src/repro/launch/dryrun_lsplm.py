import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import: jax locks the device count on first init.

"""Dry-run of the paper's own production job: one Algorithm-1 iteration of
LS-PLM on the (16,16) single-pod and (2,16,16) multi-pod meshes.

Production scale stand-in: d = 2^19 features (12.6M parameters at m=12 —
the paper's 'tens of millions' regime), common-feature batch of 2^14
samples / 2^12 sessions per iteration. The paper's sparse-hash feature
store is simulated by dense columns (DESIGN.md §8).

  PYTHONPATH=src python -m repro.launch.dryrun_lsplm [--multi] [--out f.json]
"""
import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.objective import CommonFeatureBatch, smooth_loss_and_grad
from repro.dist import batch_specs, state_specs
from repro.launch.mesh import data_axes, make_production_mesh
from repro.optim import OWLQNPlus
from repro.utils.hlo import collective_bytes
from repro.utils.roofline import Roofline

D_FEATURES = 2**19
D_COMMON = 2**18
M_REGIONS = 12
BATCH = 2**14
SESSIONS = 2**12


def run(mesh_name: str, variant: str = "baseline"):
    """variants (§Perf): 'baseline' (fp32 features, LBFGS memory 10),
    'bf16_features' (feature matrices in bf16 — CTR indicators/counts
    tolerate it), 'bf16+m5_history' (also halve the LBFGS memory)."""
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    chips = 512 if mesh_name == "multi" else 256
    dp = data_axes(mesh)
    sds = jax.ShapeDtypeStruct
    feat_dtype = jnp.bfloat16 if "bf16" in variant else jnp.float32
    memory = 5 if "m5" in variant else 10
    sessions = SESSIONS // 2 if variant == "cf8_sessions" else SESSIONS
    batch = CommonFeatureBatch(
        x_common=sds((sessions, D_COMMON), feat_dtype),
        x_noncommon=sds((BATCH, D_FEATURES - D_COMMON), feat_dtype),
        session_id=sds((BATCH,), jnp.int32),
        y=sds((BATCH,), jnp.float32),
        weight=sds((BATCH,), jnp.float32),
    )
    bspec = batch_specs(mesh, common_feature=True)
    ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))

    def step(state, batch):
        opt = OWLQNPlus(
            lambda t: smooth_loss_and_grad(t, batch, common_feature=True),
            lam=1.0, beta=1.0, memory=memory)
        return opt.step(state)

    opt0 = OWLQNPlus(lambda t: (jnp.zeros(()), t), lam=1.0, beta=1.0,
                     memory=memory)
    theta_s = sds((D_FEATURES, 2 * M_REGIONS), jnp.float32)
    state_s = jax.eval_shape(opt0.init, theta_s)
    sspec = state_specs(mesh)

    t0 = time.time()
    jitted = jax.jit(step, in_shardings=(ns(sspec), ns(bspec)),
                     out_shardings=(ns(sspec), None))
    lowered = jitted.lower(state_s, batch)
    compiled = lowered.compile()
    dt = time.time() - t0
    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    coll = collective_bytes(compiled.as_text())
    # Algorithm 1's line search is a while loop (body counted once); its
    # trip count is data dependent (typically 1-3 accepted quickly) —
    # report body-once numbers and note the multiplier.
    params = D_FEATURES * 2 * M_REGIONS
    # model flops: ls-plm fwd+bwd ~ 6 * params * batch eqv (common-feature
    # compressed: common rows count once per session)
    eff_rows = SESSIONS * D_COMMON + BATCH * (D_FEATURES - D_COMMON)
    model_flops = 6.0 * 2 * M_REGIONS * eff_rows / chips
    rl = Roofline(
        flops=float(ca.get("flops", 0.0)),
        hbm_bytes=float(ca.get("bytes accessed", 0.0)),
        coll_bytes=float(coll["total_bytes"]),
        model_flops=model_flops,
    )
    rec = {
        "arch": "lsplm-production", "shape": "ctr_iteration", "mesh": mesh_name,
        "variant": variant,
        "chips": chips, "params": params,
        "compile_seconds": round(dt, 1),
        "memory": {
            "argument_bytes_per_chip": ma.argument_size_in_bytes,
            "temp_bytes_per_chip": ma.temp_size_in_bytes,
            "total_bytes_per_chip": ma.argument_size_in_bytes + ma.temp_size_in_bytes,
        },
        "collectives": coll,
        "roofline": rl.to_dict(),
    }
    r = rec["roofline"]
    print(f"[OK] lsplm-production {mesh_name} [{variant}]: "
          f"params={params / 1e6:.1f}M "
          f"mem/chip={rec['memory']['total_bytes_per_chip'] / 2**30:.2f}GiB "
          f"t_comp={r['t_compute_s']:.3e} t_mem={r['t_memory_s']:.3e} "
          f"t_coll={r['t_collective_s']:.3e} bound={r['bottleneck']}",
          flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    args = ap.parse_args()
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    recs = [run(m) for m in meshes]
    if args.out:
        with open(args.out, "w") as f:
            json.dump(recs, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
