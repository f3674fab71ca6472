"""Small cells on the CPU: a copy of the benchmark with tiny
configurations added as new files, the program linked in beside it."""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY = {"graph500-s20-k64": ("tiny-batch", 12, 8)}


def make_copy(dst) -> str:
    """A checkout at ``dst``: BENCHMARK.json, benchmark/ and the
    program, plus one tiny configuration and cell per configuration of
    BENCHMARK.json, added as new files and entries."""
    dst = str(dst)
    shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "sheep_tpu"),
               os.path.join(dst, "sheep_tpu"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for cfg in list(spec["configs"]):
        name, scale, k = TINY[cfg["name"]]
        with open(os.path.join(ROOT, cfg["file"])) as f:
            body = json.load(f)
        body["name"] = name
        body["scale"] = scale
        body["vertices"], body["edges"] = 1 << scale, 16 << scale
        body["k"] = k
        path = f"benchmark/configs/{name}.json"
        with open(os.path.join(dst, path), "w") as f:
            json.dump(body, f)
        spec["configs"].append(dict(cfg, name=name, file=path))
        for w in [w for w in spec["workloads"] if w["config"] == cfg["name"]]:
            cell = dict(w, name=f"{name}.{w['traffic']}", config=name)
            spec["workloads"].append(cell)
            for m in spec["end_to_end"] + spec["per_layer"]:
                if w["name"] in m.get("workloads", []):
                    m["workloads"].append(cell["name"])
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return dst


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_copy(tmp_path_factory.mktemp("checkout"))
