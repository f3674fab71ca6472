"""Whole runs of tiny cells on the CPU: discovery by name, the result
line's contract, and the exits without a chip."""

import json
import os
import shutil
import subprocess
import sys
import time

from benchmark import run

from conftest import ROOT, make_copy

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


def _run(root, cell, trace=0, seconds=1.0, seed=2**31 + 3):
    import contextlib
    import io

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)], root=root,
                      require_tpu=False, t0=time.perf_counter())
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]) if lines else None


def test_result_line_has_the_contract_keys(tiny_root):
    cell = "tiny-batch.batch"
    rc, line = _run(tiny_root, cell)
    assert rc == 0
    assert list(line) == KEYS  # checks last
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    spec = json.load(open(os.path.join(tiny_root, "BENCHMARK.json")))
    e2e = {m["name"] for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == e2e
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert all(set(v) == {"value", "limit"} for v in line["checks"].values())


def test_added_files_are_found_by_name(tmp_path):
    """A new traffic mix, a new per-layer metric and a new cell, added
    as files and entries only, run without editing any file."""
    root = make_copy(tmp_path)
    before = {p: open(os.path.join(root, "benchmark", p), "rb").read()
              for p in ("harness.py", "run.py", "drivers/batch.py")}
    with open(os.path.join(root, "benchmark/traffic/batch-cold.json"),
              "w") as f:
        json.dump({"driver": "batch", "why": "one warm-up",
                   "seed_relabels": True,
                   "warmup_partitions": 1,
                   "partition_options": {"comm_volume": False}}, f)
    with open(os.path.join(root, "benchmark/metrics/partitions.batch.py"),
              "w") as f:
        f.write("def read(layer):\n    return len(layer['partitions'])\n")
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["workloads"].append({"name": "tiny-batch.batch-cold",
                              "config": "tiny-batch", "traffic": "batch-cold",
                              "chips": 1, "why": "added by a test"})
    for m in spec["end_to_end"]:
        if "workloads" in m and "tiny-batch.batch" in m["workloads"]:
            m["workloads"].append("tiny-batch.batch-cold")
    spec["per_layer"].append({"name": "partitions.batch", "unit": "count",
                              "better": "higher", "source": "program_counter",
                              "layer": "backend driver",
                              "moves": "edges_per_s",
                              "workloads": ["tiny-batch.batch-cold"]})
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))
    rc, line = _run(root, "tiny-batch.batch-cold", trace=1)
    assert rc == 0 and line["correct"]
    assert line["metrics"]["partitions.batch"]["value"] == line["attempted"]
    assert "build_s.batch" not in line["metrics"]  # listed for another cell
    for p, body in before.items():
        assert open(os.path.join(root, "benchmark", p), "rb").read() == body


def _cli(cwd, cell, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "5", "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=600)


def test_no_tpu_exits_without_a_result():
    r = _cli(ROOT, "graph500-s20-k64.batch")
    assert r.returncode != 0
    assert "{" not in r.stdout
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_exit_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(tmp_path, "benchmark"))
    r = _cli(str(tmp_path), "graph500-s20-k64.batch",
             {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert "{" not in r.stdout
