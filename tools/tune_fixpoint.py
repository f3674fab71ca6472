#!/usr/bin/env python
"""Sweep the adaptive-fixpoint schedule on the real chip (or cpu-jax).

The build phase dominates the headline bench, and its cost is
~lift_levels x active-buffer-width gathers per round — so the schedule
knobs (cheap low-lift warm rounds, compaction cadence, rounds per
segment, chunk size) are where single-chip throughput lives. This tool
folds the same RMAT stream under each candidate schedule and reports
build-phase seconds + round/segment counts as JSON lines; every
candidate produces the identical forest (asserted), so the fastest line
wins outright.

Usage:
    python tools/tune_fixpoint.py [--scale 20] [--ef 16]
        [--chunk-logs 24] [--platform cpu] [--quick]
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


WARM_SCHEDULES = {
    "none": (),
    "w1": ((1, 1),),     # near-pure retire round (scatter + 1-step climb)
    "w11": ((2, 1),),
    "w4": ((1, 4),),
    "w44": ((2, 4),),
    "w48": ((1, 4), (1, 8)),
    "w248": ((1, 2), (1, 4), (1, 8)),
    "w8": ((1, 8),),
    "w88": ((2, 8),),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=20)
    ap.add_argument("--ef", type=int, default=16)
    ap.add_argument("--chunk-logs", default="24")
    ap.add_argument("--platform", default=None)
    ap.add_argument("--warm", default=None,
                    help="comma list of warm-schedule names "
                         f"(default: all of {list(WARM_SCHEDULES)})")
    ap.add_argument("--segment-rounds", default="2")
    ap.add_argument("--lift-levels", default="0",
                    help="comma list; 0 = full depth ceil(log2 V)")
    ap.add_argument("--tail-divisors", default="8",
                    help="comma list d: host_tail_threshold = C/d "
                         "(0 = keep the auto default)")
    ap.add_argument("--reps", type=int, default=1)
    args = ap.parse_args()

    if args.platform:
        from sheep_tpu.utils.platform import pin_platform

        pin_platform(args.platform)

    import numpy as np
    import jax
    import jax.numpy as jnp

    from sheep_tpu.backends.tpu_backend import pad_chunk
    from sheep_tpu.io import generators
    from sheep_tpu.ops import degrees as degrees_ops
    from sheep_tpu.ops import elim as elim_ops
    from sheep_tpu.ops import order as order_ops

    plat = jax.default_backend()
    n = 1 << args.scale
    t0 = time.perf_counter()
    edges = generators.rmat(args.scale, args.ef, seed=42)
    log(f"platform={plat} RMAT-{args.scale} ef={args.ef} "
        f"E={len(edges):,} (gen {time.perf_counter() - t0:.0f}s)")

    # degrees + order once (identical for every candidate)
    deg = degrees_ops.init_degrees(n)
    for i in range(0, len(edges), 1 << 24):
        deg = degrees_ops.degree_chunk(
            deg, jnp.asarray(pad_chunk(edges[i:i + (1 << 24)],  # sheeplint: h2d-ok, spill-ok (one-shot sweep-tool pass)
                                       1 << 24, n)),
            n)
    pos, order = order_ops.elimination_order(deg[:n], n)
    pos_host = np.asarray(pos[:n])  # sheeplint: sync-ok

    def run(chunk_log, warm_name, seg_rounds, lift, tail_div):
        cs = 1 << chunk_log
        # pre-pad + pre-upload all chunks so only fold time is measured
        dev_chunks = [jnp.asarray(pad_chunk(edges[i:i + cs], cs, n))
                      for i in range(0, len(edges), cs)]
        np.asarray(dev_chunks[-1][:2])  # settle uploads
        stats: dict = {}
        P = jnp.full(n + 1, n, dtype=jnp.int32)
        total = 0
        t0 = time.perf_counter()
        for d in dev_chunks:
            P, rounds = elim_ops.build_chunk_step_adaptive_pos(
                P, d, pos, pos_host, n,
                lift_levels=lift,
                segment_rounds=seg_rounds,
                warm_schedule=WARM_SCHEDULES[warm_name], stats=stats,
                host_tail_threshold=(cs // tail_div if tail_div else 0))
            total += int(rounds)
        np.asarray(P[:8])  # force completion (a host pull)
        dt = time.perf_counter() - t0
        return P, dt, total, stats

    warm_names = (args.warm.split(",") if args.warm
                  else list(WARM_SCHEDULES))
    chunk_logs = [int(x) for x in args.chunk_logs.split(",")]
    seg_rounds_list = [int(x) for x in args.segment_rounds.split(",")]
    lifts = [int(x) for x in args.lift_levels.split(",")]
    tail_divs = [int(x) for x in args.tail_divisors.split(",")]

    reference = None
    best = None
    for cl, wn, sr, lv, td in itertools.product(
            chunk_logs, warm_names, seg_rounds_list, lifts, tail_divs):
        dts = []
        for rep in range(args.reps):
            P, dt, total, stats = run(cl, wn, sr, lv, td)
            dts.append(dt)
        dt = min(dts)
        P_np = np.asarray(P)
        if reference is None:
            reference = P_np
        else:
            assert np.array_equal(reference, P_np), \
                (f"config warm={wn} seg={sr} L={lv} td={td} "
                 f"changed the forest!")
        line = {"chunk_log": cl, "warm": wn, "segment_rounds": sr,
                "lift_levels": lv, "tail_div": td,
                "build_s": round(dt, 2), "rounds": total,
                "platform": plat, **{k: int(v) for k, v in stats.items()}}
        print(json.dumps(line), flush=True)
        log(f"chunk=2^{cl} warm={wn:5s} seg={sr} L={lv} td={td}: "
            f"{dt:7.2f}s rounds={total} {stats}")
        if best is None or dt < best[0]:
            best = (dt, line)
    log(f"best: {best[1]}")


if __name__ == "__main__":
    main()
