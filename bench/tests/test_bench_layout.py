"""``BENCHMARK.json`` resolves to its files, and a configuration, a mix,
a cell and a metric added as new files are found with no other edit."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import harness
from conftest import BENCH, ROOT


def test_every_cell_resolves_to_its_files(spec):
    import run
    metrics = spec["end_to_end"] + spec["per_layer"]
    e2e = {m["name"] for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    for w in spec["workloads"]:
        cell, cfg, mix = run.resolve(spec, w["name"])
        assert cell["chips"] == 1 and cell["why"]
        harness.load_module("families", cfg["family"])
        harness.load_module("graphs", cfg["generator"]["kind"])
        assert mix["loop"] in ("closed", "open")
        assert set(cfg["check"]["limits"]) and cfg["check"]["sample"] > 0
    for c in spec["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("bench/")
    for m in metrics:
        assert callable(harness.load_module("metrics", m["name"]).read)
        assert set(m.get("workloads", cells)) <= cells
    for m in spec["per_layer"]:
        assert m["layer"] and m["moves"] in e2e and m["workloads"]
    assert "setup_s" in e2e


DUMMY = """
import json, pathlib, sys, time
t = time.perf_counter()
bench = pathlib.Path(sys.argv[1])
sys.path[:0] = [str(bench), str(bench.parent / "src")]
import harness, run
spec = json.loads((bench.parent / "BENCHMARK.json").read_text())
cell, cfg, mix = run.resolve(spec, "dummy-cell")
r = harness.run_cell("dummy-cell", cfg, mix, seed=5, seconds=1.0,
                     trace=False, out_dir=bench / "_out", t_start=t,
                     device_kind=None)
out = run.report(r, spec["per_layer"], {"platform": "cpu"})
print(json.dumps(run.finite(out)))
"""


def test_new_config_mix_cell_and_metric_need_only_new_files(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("_out", "__pycache__",
                                                  "data"))
    os.symlink(ROOT / "src", tmp_path / "src")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "configs" / "g500-s20-bfs.json").read_text())
    cfg.update(name="dummy-config", capacity=2 * 16 << 8)
    cfg["generator"]["scale"] = 8
    cfg["server"]["host_kernels"] = False
    (bench / "configs" / "dummy-config.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"name": "dummy-mix", "loop": "open", "rate_per_s": 200,
         "sources": {"kind": "zipf", "s": 1.0},
         "warm_queries_per_slot": 1, "warmup_deadline_s": 30,
         "updates": None, "stream_seed": 1}))
    (bench / "metrics" / "dummy_metric.py").write_text(
        "def read(run):\n    return float(run.answered)\n")
    spec["configs"].append({"name": "dummy-config", "source": "x",
                            "file": "bench/configs/dummy-config.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "dummy-cell", "config": "dummy-config",
                              "traffic": "dummy-mix", "chips": 1,
                              "why": "x"})
    spec["per_layer"].append({"name": "dummy_metric", "unit": "queries",
                              "better": "higher", "source": "host_clock",
                              "layer": "x", "moves": "queries_per_s",
                              "workloads": ["dummy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", DUMMY, str(bench)], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out
    assert out["metrics"]["dummy_metric"]["value"] > 0
    assert "queries_per_chunk" not in out["metrics"]   # names other cells
