"""The control of ``correct``: the reference with one stated guarantee
broken, put in the program's place and judged by the cell's own
comparison. It must come out as not correct.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3

- batch (guarantee: the forest holds every edge): the forest, split,
  cut and total of the stream without its last 1/256 (at scale 20 the
  last 65,536 edges), as a fold that loses the tail of the stream would
  give.

It runs at the cell's own sizes and needs no chip; the benchmark's runs
never run it. For each seed it prints the readings beside their limits.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import graph500, harness, reference  # noqa: E402

DROPPED = 256  # the batch control loses the last 1/DROPPED edges


def batch_control(cell, seed: int) -> dict:
    g = graph500.Graph500(cell.config, seed,
                          cell.traffic["seed_relabels"])
    edges = g.base()
    k = int(cell.config["k"])
    ref = reference.partition(edges, g.n, k)
    bad = reference.partition(edges[:-(len(edges) // DROPPED)], g.n, k)
    return cell.driver().check([(bad.parent, bad.part, bad.cut,
                                  bad.total)], ref)


CONTROLS = {"batch": batch_control}


def main(argv=None, root: str = harness.ROOT) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True,
                   help="comma-separated seeds")
    a = p.parse_args(argv)
    cell = harness.Cell(harness.load_spec(root), a.workload, root)
    control = CONTROLS[cell.traffic["driver"]]
    failed_all = True
    for seed in (int(s) for s in a.seeds.split(",")):
        checks = control(cell, seed)
        ok = harness.judge(checks)
        failed_all &= not ok
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "correct": ok,
                          "checks": {k: {"value": v, "limit": lim}
                                     for k, (v, lim) in checks.items()}}),
              flush=True)
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
