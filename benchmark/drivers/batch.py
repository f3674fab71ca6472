"""Batch traffic: whole partitions back to back, in this process.

The configuration's graph is made once from the seed and handed to the
program as a host array (``EdgeStream.from_array``), as a library user
holds a snapshot, so every partition pays the host-to-device staging.
Set-up runs ``warmup_partitions`` partitions of that same array, which
compiles every program the window's partitions use. The window then
starts partitions until ``--seconds`` have passed; the one running at
that moment finishes and counts, so the window holds whole partitions
and its length is their sum. Afterwards each partition of the window is
compared with the plain reference: forest, parts, cut and total.
"""

from __future__ import annotations

import shutil
import tempfile
import time

import numpy as np

from benchmark import graph500, harness, reference, tracereduce


def check(results: list, ref) -> dict:
    """Worst reading over the window's partitions; all limits are 0:
    the forest is unique and the split and score are exact."""
    checks = {"parent_diff": 0, "part_diff": 0, "cut_gap": 0,
              "total_gap": 0}
    for parent, part, cut, total in results:
        checks["parent_diff"] = max(checks["parent_diff"], int(
            np.count_nonzero(parent != ref.parent)))
        checks["part_diff"] = max(checks["part_diff"], int(
            np.count_nonzero(part != ref.part)))
        checks["cut_gap"] = max(checks["cut_gap"], abs(cut - ref.cut))
        checks["total_gap"] = max(checks["total_gap"],
                                  abs(total - ref.total))
    if not results:
        return {k: (None, 0) for k in checks}
    return {k: (v, 0) for k, v in checks.items()}


def run(cell, seed: int, seconds: float, trace: bool, t0: float,
        require_tpu: bool = True) -> dict:
    cfg, traffic = cell.config, cell.traffic
    meter = harness.CompileMeter()
    import jax

    device = harness.device_info(cell.chips, require_tpu)
    from sheep_tpu import get_backend
    from sheep_tpu.io.edgestream import EdgeStream

    t_dev = time.perf_counter()
    g = graph500.Graph500(cfg, seed, traffic["seed_relabels"])
    edges = g.base()
    k = int(cfg["k"])
    backend = get_backend(cfg["backend"])
    opts = dict(traffic.get("partition_options", {}))

    def one():
        return backend.partition(EdgeStream.from_array(edges, n_vertices=g.n),
                                 k, keep_tree=True, **opts)

    t_gen = time.perf_counter()
    for _ in range(int(traffic["warmup_partitions"])):
        one()
    t_warm = time.perf_counter()
    harness.log(f"[{cell.name}] set-up {t_warm - t0:.3f} s: imports and "
                f"devices {t_dev - t0:.3f}, generation {t_gen - t_dev:.3f}, "
                f"warm-up {t_warm - t_gen:.3f}")

    tdir = tempfile.mkdtemp(prefix="sheep_bench_trace_") if trace else None
    records, answers = [], []
    failed = 0
    c0 = meter.count
    if trace:
        jax.profiler.start_trace(tdir)
    w0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("window"):
            while True:
                with jax.profiler.TraceAnnotation("partition"):
                    try:
                        res = one()
                    except Exception as e:  # noqa: BLE001 — counted
                        harness.log(f"[{cell.name}] partition failed: "
                                    f"{type(e).__name__}: {e}")
                        failed += 1
                        break
                records.append({"phase_times": dict(res.phase_times),
                                "diagnostics": dict(res.diagnostics)})
                harness.log(f"[{cell.name}] partition {len(records)} ends "
                            f"at {time.perf_counter() - w0:.3f} s, "
                            f"{res.diagnostics.get('fixpoint_rounds')} "
                            f"rounds, phases {res.phase_times}")
                answers.append((np.asarray(res.tree["parent"]),
                                np.asarray(res.assignment),
                                int(res.edge_cut), int(res.total_edges)))
                del res
                if time.perf_counter() - w0 >= seconds:
                    break
        window_s = time.perf_counter() - w0
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_compiles = meter.count - c0
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    del backend
    harness.log(f"[{cell.name}] {len(records)} partitions in "
                f"{window_s:.3f} s, {window_compiles} compiles")

    layer = {"partitions": records, "window_compiles": window_compiles,
             "trace": None}
    breakdown = None
    if trace:
        try:
            red = tracereduce.reduce(tracereduce.extract(
                tracereduce.newest_xplane(tdir)))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        layer["trace"] = red
        if red is None:
            harness.log(f"[{cell.name}] the trace has no device plane")
        else:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}

    t_ref = time.perf_counter()
    ref = reference.partition(edges, g.n, k)
    harness.log(f"[{cell.name}] reference {time.perf_counter() - t_ref:.3f}"
                f" s")
    return {
        "e2e": {"edges_per_s": len(records) * len(edges) / window_s,
                "setup_s": w0 - t0},
        "layer": layer, "breakdown": breakdown, "device": device,
        "attempted": len(records) + failed, "failed": failed,
        "checks": check(answers, ref),
    }
