"""The dry-run machinery must work end-to-end at CI scale: reduced archs,
tiny shape variants, 2x2 device mesh, in a subprocess with 8 host devices.
(The production 512-device sweep runs via repro.launch.dryrun --all.)"""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
import repro.configs as C
from repro.launch import dryrun
from repro.launch.mesh import make_debug_mesh
from repro.utils.hlo import collective_bytes

# shrink the workload shapes for CI
C.INPUT_SHAPES.clear()
C.INPUT_SHAPES.update({
    "train_4k": dict(kind="train", seq_len=64, global_batch=4),
    "prefill_32k": dict(kind="prefill", seq_len=64, global_batch=4),
    "decode_32k": dict(kind="decode", seq_len=64, global_batch=4),
    "long_500k": dict(kind="decode", seq_len=256, global_batch=1),
})
mesh = make_debug_mesh(data=2, model=2)

archs = ["llama3.2-1b", "granite-moe-1b-a400m", "falcon-mamba-7b",
         "zamba2-2.7b", "musicgen-medium", "internvl2-2b"]
shapes = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
for arch in archs:
    cfg = C.get_config(arch).reduced()
    cfg = dataclasses.replace(cfg, num_prefix_embeds=min(cfg.num_prefix_embeds, 8),
                              attn_chunk=32, sliding_window=32)
    for shape in shapes:
        lowered, compiled, meta = dryrun.lower_combo(cfg, shape, mesh)
        ca = compiled.cost_analysis()
        assert ca.get("flops", 0) > 0, (arch, shape)
        txt = compiled.as_text()
        coll = collective_bytes(txt)
        assert compiled.memory_analysis().argument_size_in_bytes > 0
        print(f"ok {arch} {shape} coll_bytes={coll['total_bytes']}")
print("DRYRUN-SMALL-OK")
"""


@pytest.mark.slow
def test_dryrun_machinery_small():
    env = os.environ.copy()
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       timeout=1500)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout[-3000:]}\nSTDERR:\n{r.stderr[-3000:]}"
    assert "DRYRUN-SMALL-OK" in r.stdout
