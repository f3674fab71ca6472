"""What every cell shares: finding its files by name, the compile meter,
the device check, and the result line.

Files are found by the names in ``BENCHMARK.json``:

- a configuration: the ``file`` its entry names (``configs/<name>.json``);
- a traffic mix: ``traffic/<name>.json``, whose ``driver`` names the
  general driver that reads it (``drivers/<driver>.py``);
- a per-layer metric: ``metrics/<name>.py``, a reader with
  ``read(layer) -> float | None`` over what the driver recorded.

So a later change adds a configuration, a mix or a metric as new files
and entries, and edits none that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def by_name(items: list, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of BENCHMARK.json with everything found for it."""

    def __init__(self, spec: dict, name: str, root: str = ROOT):
        self.root = root
        self.bench = os.path.join(root, "benchmark")
        self.entry = by_name(spec["workloads"], name, "workload")
        self.name = name
        cfg = by_name(spec["configs"], self.entry["config"], "config")
        self.config = load_json(os.path.join(root, cfg["file"]))
        self.traffic = load_json(os.path.join(
            self.bench, "traffic", self.entry["traffic"] + ".json"))
        self.chips = int(self.entry["chips"])
        self.end_to_end = [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])]
        e2e_names = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])
                          and m["moves"] in e2e_names]

    def driver(self):
        d = self.traffic["driver"]
        return load_module(os.path.join(self.bench, "drivers", d + ".py"),
                           f"benchmark_driver_{d}")

    def read_layer(self, layer: dict) -> dict:
        """Every per-layer metric of this cell that finds something."""
        out = {}
        for m in self.per_layer:
            mod = load_module(os.path.join(self.bench, "metrics",
                                           m["name"] + ".py"),
                              "benchmark_metric_" + m["name"].replace(
                                  ".", "_").replace("-", "_"))
            v = mod.read(layer)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out


class CompileMeter:
    """Counts backend compiles (persistent-cache loads included) from
    JAX's own monitoring events; register before the first compile."""

    def __init__(self):
        from jax import monitoring

        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def device_info(chips: int, require_tpu: bool = True) -> dict:
    """The devices JAX sees; raises :class:`NoChip` when they are not
    TPUs (unless a test lifts that) or fewer than the cell asks for."""
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def result_line(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: dict, breakdown=None) -> str:
    """The last line of standard output. ``checks`` (name -> [value,
    limit]) comes last."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return json.dumps(out)


def judge(checks: dict) -> bool:
    return all(v is not None and v <= lim for v, lim in checks.values())


def print_checks(checks: dict) -> None:
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v} (limit {lim})")
