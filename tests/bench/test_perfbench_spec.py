"""BENCHMARK.json's shape, files found by name, and the refusal to run
without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [c["name"] for c in BENCH["configs"]] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (ROOT / "bench" / "traffic" / "mixes" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").is_file()
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25 and m["bound"] >= 0.01
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["workloads"] and set(m["workloads"]) <= cells
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    c = spec.cell(cell)
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    assert all(m["moves"] in names for m in c.per_layer)
    spec.load_module("drivers", c.traffic["kind"])


def test_new_files_are_found_by_name_without_editing_any(tmp_path):
    """A later PR adds a config, a mix, a driver, a metric and a cell:
    files and entries only. The harness finds each by its name."""
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}

    cfg = json.loads((root / "bench/configs/lsplm-d1m-m12.json").read_text())
    (root / "bench/configs/lsplm-new.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/mixes/new-mix.json").write_text(
        json.dumps({"kind": "new_kind", "rate_per_s": 1}))
    (root / "bench/drivers/new_kind.py").write_text("def run(ctx):\n    return 'ran'\n")
    (root / "bench/metrics/new_metric.serve.py").write_text(
        "def read(x):\n    return x['counters']['n'] / 2\n")
    (root / "bench/limits/new-cell.json").write_text("{}")
    bench["configs"].append({"name": "lsplm-new", "source": "s", "why": "w",
                             "file": "bench/configs/lsplm-new.json", "reduced": []})
    bench["workloads"].append({"name": "new-cell", "config": "lsplm-new",
                               "traffic": "new-mix", "chips": 1, "why": "w"})
    bench["per_layer"].append({"name": "new_metric.serve", "unit": "%",
                               "better": "higher", "source": "program_counter",
                               "layer": "l", "moves": "setup_s",
                               "workloads": ["new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.cell("new-cell", root)
    assert cell.config == cfg and cell.traffic["kind"] == "new_kind"
    assert [m["name"] for m in cell.per_layer] == ["new_metric.serve"]
    assert spec.load_module("drivers", "new_kind", root / "bench").run(None) == "ran"
    reader = spec.load_module("metrics", "new_metric.serve", root / "bench")
    assert reader.read({"counters": {"n": 3}}) == 1.5
    # every file that was there is byte for byte what it was
    assert all(p.read_bytes() == b for p, b in before.items())
    # and the cells that were there did not change
    for w in BENCH["workloads"]:
        assert spec.cell(w["name"], root) == spec.cell(w["name"])


def _bench_env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def test_refuses_to_run_without_a_tpu():
    cell = BENCH["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_bench_env(), timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr and "platform=cpu" in proc.stderr


def test_unknown_workload_exits_nonzero_without_a_result():
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", "nope",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=_bench_env(), timeout=300)
    assert proc.returncode == 2 and proc.stdout.strip() == ""
