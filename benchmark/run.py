"""Run one cell of the benchmark and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``workloads`` in BENCHMARK.json; its
configuration, traffic mix and per-layer metric readers are found by
name (``benchmark/harness.py``). The run makes its inputs from
``--seed``, warms up every program the window uses (set-up), measures
for ``--seconds`` and lets the last partition or epoch finish, then
checks every answer of the window against the plain reference
(``benchmark/reference.py``). With ``--trace 0`` the metrics are the
cell's end-to-end metrics; with ``--trace 1`` the window runs under the
profiler and the metrics are its per-layer ones.

Stdout's last line is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``, each compared number beside its limit (also the
last lines of stderr). Without a TPU, or with fewer chips than the cell
asks for, it exits 3 and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up starts with the process

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None, root: str = harness.ROOT, require_tpu: bool = True,
         t0: float = T0) -> int:
    """``require_tpu=False`` is for the CPU tests, which drive a whole
    run on small cells; the command line always requires the chip."""
    args = parse(argv)
    # the checkout's own cache at a fixed path (a child inherits it)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root,
                                                           ".jax_cache")
    cell = harness.Cell(harness.load_spec(root), args.workload, root)
    try:
        rec = cell.driver().run(cell, seed=args.seed, seconds=args.seconds,
                                trace=bool(args.trace), t0=t0,
                                require_tpu=require_tpu)
    except harness.NoChip as e:
        harness.log(f"benchmark: {e}")
        return 3
    if args.trace:
        metrics = cell.read_layer(rec["layer"])
    else:
        metrics = {m["name"]: {"value": rec["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    checks = rec["checks"]
    correct = harness.judge(checks) and rec["failed"] == 0
    harness.print_checks(checks)
    print(harness.result_line(correct, rec["attempted"], rec["failed"],
                              metrics, rec["device"], checks,
                              rec.get("breakdown") if args.trace else None),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
