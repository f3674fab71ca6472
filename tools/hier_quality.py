#!/usr/bin/env python
"""Hierarchical k=64 quality driver (VERDICT r4 item 3 continuation).

Runs partition_hierarchical on the planted-partition stream and writes
the artifact JSON keyed by every quality-relevant knob. Round-5 history
at s22 k=64 (planted optimum 0.050):

    flat refine-30            0.847   (sbm_s22_r30.json)
    hier [8,8] refine-10      0.431   (hier_s22.json)
    + final_refine=10         0.336   (hier_s22_fr.json — stopped at
                                       the round cap, NOT at rollback)

The refine loop stops on its own at the first non-improving round, so
generous --refine/--final-refine caps cost nothing once converged.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# quality runs are platform-invariant (cut/balance bit-identical cpu vs
# tpu — balance_frontier.json), so they run on the CPU and leave the
# chip free; SHEEP_QUALITY_PLATFORM overrides deliberately.
from sheep_tpu.utils.platform import pin_platform  # noqa: E402

pin_platform(os.environ.get("SHEEP_QUALITY_PLATFORM") or "cpu")


def _num(v):
    """Diagnostics values are floats in the common case but can be
    status strings (e.g. the refine pass's 'refine_skipped' fallback) —
    coerce defensively so a completed multi-hour partition always writes
    its artifact instead of dying on float('refine_skipped')."""
    try:
        return float(v)
    except (TypeError, ValueError):
        return str(v)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=22)
    ap.add_argument("--blocks", type=int, default=64)
    ap.add_argument("--p-out", type=float, default=0.05)
    ap.add_argument("--edge-factor", type=int, default=16)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--k-levels", default="8,8")
    ap.add_argument("--refine", type=int, default=30)
    ap.add_argument("--final-refine", type=int, default=60)
    ap.add_argument("--balance", type=float, default=None)
    ap.add_argument("--refine-budget-gb", type=float, default=6.0,
                    help="histogram budget for the final refine; the "
                         "4 GB library default misses s22/k=256 by 1 KB "
                         "and quintuples its passes")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    from sheep_tpu.hierarchy import partition_hierarchical
    from sheep_tpu.io.edgestream import open_input

    spec = (f"sbm-hash:{args.scale}:{args.blocks}:{args.p_out}"
            f":{args.edge_factor}:{args.seed}")
    k_levels = [int(x) for x in args.k_levels.split(",")]

    t0 = time.perf_counter()
    res = partition_hierarchical(
        spec, k_levels, refine=args.refine,
        final_refine=args.final_refine, balance=args.balance,
        refine_budget_bytes=int(args.refine_budget_gb * (1 << 30)))
    wall = time.perf_counter() - t0

    with open_input(spec) as es:
        planted = es.planted_cut_ratio()
        # the cut ledger's residual attribution (ISSUE 13): per-level
        # achieved-vs-planted excess, naming which level owns the
        # residual — the diagnosis ROADMAP item 4's follow-up attacks
        from sheep_tpu.utils.metrics import ledger_residual

        residual = ledger_residual(res.diagnostics or {}, k_levels,
                                   es.planted_cut_ratio,
                                   res.total_edges)

    out = {
        "spec": spec,
        "k_levels": k_levels,
        "refine": args.refine,
        "final_refine": args.final_refine,
        "balance_budget": args.balance,
        "cut_ratio": round(res.cut_ratio, 6),
        "edge_cut": int(res.edge_cut),
        "total_edges": int(res.total_edges),
        "balance": round(res.balance, 4),
        "comm_volume": None if res.comm_volume is None
                       else int(res.comm_volume),
        "wall_s_contended": round(wall, 1),
        "phase_times": res.phase_times,
        "diagnostics": {k: _num(v) for k, v in
                        (res.diagnostics or {}).items()},
        "planted_optimum": round(planted, 4),
        "residual": residual,
        "history": {"flat_r30": 0.8467, "hier_r4": 0.4313,
                    "hier_fr10": 0.3364},
    }
    # every quality-relevant knob keys the filename (ADVICE r4's clobber
    # lesson, re-learned once: a balance run overwrote its unbalanced
    # twin before the budget joined the name)
    tag = f"_{args.tag}" if args.tag else ""
    bal = f"_b{args.balance}".replace(".", "") if args.balance else ""
    lv = "x".join(str(k) for k in k_levels)
    path = os.path.join(
        os.path.dirname(__file__), "out", "soak",
        f"hier_s{args.scale}_k{args.blocks}_L{lv}"
        f"_r{args.refine}_fr{args.final_refine}{bal}{tag}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
