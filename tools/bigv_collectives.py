#!/usr/bin/env python
"""Measure tpu-bigv's per-round collective cost on the virtual mesh
(VERDICT r2 item 5): rounds x (all_gather + all_to_all) counts and bytes
per run, on hub-heavy graphs (star = the routed worst case: every
request climbs to one owner; RMAT = the power-law production shape),
with the in-shard request dedup compaction A/B'd.

Usage:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/bigv_collectives.py [--scale 16] [--ef 8]

One JSON line per configuration; cross-config assert that the forest is
identical with and without dedup (the dedup is exact).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = os.environ.get("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8").strip()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=16)
    ap.add_argument("--ef", type=int, default=8)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--devices", type=int, default=8)
    args = ap.parse_args()

    import numpy as np

    from sheep_tpu.io import generators
    from sheep_tpu.io.edgestream import EdgeStream
    from sheep_tpu.parallel.bigv import BigVPipeline
    from sheep_tpu.parallel.mesh import shards_mesh

    n = 1 << args.scale
    graphs = {
        f"rmat{args.scale}": (generators.rmat(args.scale, args.ef, seed=9), n),
        f"star{args.scale}": (generators.star_graph(n), n),
    }
    mesh = shards_mesh(args.devices)
    out = {}
    for gname, (e, nv) in graphs.items():
        per_dedup = {}
        for dedup in (True, False):
            es = EdgeStream.from_array(e, n_vertices=nv)
            pipe = BigVPipeline(nv, max(1024, len(e) // args.devices), mesh,
                                dedup_compact=dedup)
            t0 = time.perf_counter()
            r = pipe.run(es, args.k, comm_volume=False)
            wall = time.perf_counter() - t0
            st = r["build_stats"]
            rec = {
                "graph": gname, "dedup_compact": dedup,
                "rounds": r["fixpoint_rounds"],
                "collective_ops": st.get("collective_ops", 0),
                "collective_MB": round(
                    st.get("collective_bytes", 0) / 1e6, 2),
                "q_rounds": st.get("q_rounds", 0),
                "compactions": st.get("compactions", 0),
                "edge_cut": r["edge_cut"], "wall_s": round(wall, 2),
            }
            per_dedup[dedup] = (r["parent"], rec)
            print(json.dumps(rec), flush=True)
        # the dedup must be exact: identical forest either way
        a, b = per_dedup[True][0], per_dedup[False][0]
        assert np.array_equal(a, b), f"{gname}: dedup changed the forest!"
        ra, rb = per_dedup[True][1], per_dedup[False][1]
        out[gname] = {
            "bytes_ratio": round(
                ra["collective_MB"] / max(rb["collective_MB"], 1e-9), 3),
            "rounds_ratio": round(
                ra["rounds"] / max(rb["rounds"], 1e-9), 3),
        }
    print(json.dumps({"summary": out}), flush=True)


if __name__ == "__main__":
    main()
