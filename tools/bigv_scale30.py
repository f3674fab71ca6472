"""RMAT-30-class capability run: V = 2^30 through tpu-bigv
(BASELINE.json eval config 5's vertex scale).

The single-chip streaming build caps at V = 2^29 on a 16 GiB chip and
the tpu-sharded pipeline replicates tables per device, so neither can
hold the RMAT-30 class (BASELINE.md HBM table). tpu-bigv exists to
remove that ceiling: pos/P/deg block-sharded across the mesh (B =
(V+1)/D rows per device), ONE distributed forest via routed
collectives. This driver proves it at the real vertex scale on the
virtual CPU mesh (--devices sizes the mesh; see that flag's help for
why the virtual-mesh default is 2):

- graph: a PREFIX of the rmat_stream(30, ef=1) edge stream (Graph500
  R-MAT parameters, so the hub skew of the scale-30 class is real),
  edge count bounded so the run fits CI-hours on one host core;
- tpu-bigv partitions it at k=1024 (the config-5 part count);
- the native cpu backend partitions the same stream; the parent
  forests and scores must agree EXACTLY.

Results -> tools/out/soak/bigv_s30.json.

Usage:
    python tools/bigv_scale30.py [--edge-chunks 16] [--k 1024]
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=30)
    ap.add_argument("--edge-chunks", type=int, default=16,
                    help="number of 2^22-edge rmat_stream chunks to take "
                         "(16 -> 67M edges over 1.07B vertices)")
    ap.add_argument("--k", type=int, default=1024)
    ap.add_argument("--chunk-edges", type=int, default=1 << 22)
    ap.add_argument("--lift-levels", type=int, default=4,
                    help="stream-descent lifting depth for bulk rounds. "
                         "At V=2^30 each level is a (D, B)-shaped routed "
                         "lookup (~4.3 GB of collective intermediates on "
                         "the single-host virtual mesh), and the auto "
                         "depth of 31 levels OOM-killed a 125 GB host — "
                         "small depth trades more rounds for a bounded "
                         "per-program footprint")
    ap.add_argument("--segment-rounds", type=int, default=1,
                    help="fixpoint rounds per device execution (same "
                         "memory trade as --lift-levels)")
    ap.add_argument("--jumps", type=int, default=16)
    ap.add_argument("--devices", type=int, default=2,
                    help="mesh size for the run. On the VIRTUAL mesh "
                         "every all_gather of a B-width buffer "
                         "replicates all D shards into ONE host RAM "
                         "(D * V words per live gathered buffer — 34 GB "
                         "at D=8/V=2^30, several live at once: the "
                         "observed 130 GB OOMs), where real chips hold "
                         "their own copy in their own HBM. D=2 proves "
                         "the identical block-sharded/routed design at "
                         "full vertex scale within 125 GB; per-device "
                         "collective counts for D=8 come from "
                         "build_stats at smaller V (BASELINE.md)")
    ap.add_argument("--hoist-bytes", type=int, default=None,
                    help="per-device budget for the per-segment stale "
                         "lifting stack. The s28+ class is the "
                         "V-dominant regime (B >> Q) BASELINE.md "
                         "reserves hoisting for: squarings are paid "
                         "once per segment instead of every round")
    ap.add_argument("--balance", type=float, default=None, metavar="BETA",
                    help="guaranteed balance bound, threaded like the "
                         "CLI's flat path: the host split runs at alpha "
                         "= BETA - 1, delivering max part load <= BETA "
                         "* total/k + max vertex weight. The committed "
                         "k=1024 artifacts shipped balance ~1.97 from "
                         "the alpha=1.0 default this flag replaces "
                         "(ROADMAP item 5); the oracle leg runs at the "
                         "same alpha so exact-equality checking holds")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="per-batch checkpointing via utils/checkpoint "
                         "(VERDICT r4 item 2: the s28 run needs to span "
                         "sessions); pass with --resume to continue")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-every", type=int, default=1,
                    help="checkpoint cadence in CHUNKS (a D-device batch "
                         "consumes D chunks; 1 = every batch)")
    ap.add_argument("--skip-oracle", action="store_true")
    args = ap.parse_args()
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir (without it the "
                 "run would silently restart from scratch)")
    alpha = 1.0
    if args.balance is not None:
        if args.balance <= 1.0:
            ap.error("--balance must be > 1 (it bounds max part load "
                     "at BETA * total/k)")
        alpha = min(args.balance - 1.0, 1.0)

    # artifact path up front (also the auto-resume idempotency key)
    tag = "" if args.devices == 2 else f"_d{args.devices}"
    if args.balance is not None:
        # a balance-budgeted run is a different experiment; keep the
        # default-alpha artifact (same ADVICE-r4 no-clobber rule as D)
        tag += f"_b{args.balance:g}"
    out = os.path.join(REPO, "tools", "out", "soak",
                       f"bigv_s{args.scale}{tag}.json")
    if args.resume and os.path.exists(out):
        # unattended re-entry (tools/run_paused_aware.sh auto-resume,
        # ISSUE 9 satellite): a completed artifact means the previous
        # attempt finished AFTER the supervisor decided to retry (e.g.
        # killed between the final write and exit) — converge instead
        # of re-burning hours re-proving the same verdict
        try:
            with open(out) as f:
                prior = json.load(f)
        except (OSError, json.JSONDecodeError):
            prior = None
        if prior and "bigv" in prior and (
                prior.get("oracle_equal") is True
                or ("native_oracle" not in prior
                    and "oracle_equal" not in prior)):
            print(f"auto-resume: completed artifact already at {out} "
                  f"(oracle_equal={prior.get('oracle_equal')}); "
                  f"nothing to do")
            return

    nd = max(8, args.devices)
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={nd}").strip()
    from sheep_tpu.utils.platform import pin_platform

    pin_platform("cpu")
    import jax

    assert jax.device_count() >= args.devices, jax.devices()

    from sheep_tpu.backends.base import get_backend
    from sheep_tpu.io import generators
    from sheep_tpu.io.edgestream import EdgeStream

    n = 1 << args.scale
    gen_chunk = 1 << 22
    m = args.edge_chunks * gen_chunk

    def prefix():
        from itertools import islice

        yield from islice(
            generators.rmat_stream(args.scale, 1, seed=42, chunk=gen_chunk),
            args.edge_chunks)

    def stream():
        return EdgeStream.from_generator(prefix, n_vertices=n, num_edges=m)

    result = {"scale": args.scale, "n_vertices": n, "n_edges": m,
              "k": args.k, "devices": args.devices,
              "chunk_edges": args.chunk_edges}
    print(f"V=2^{args.scale} = {n:,}  E={m:,}  k={args.k}  "
          f"devices={args.devices} (virtual mesh of {jax.device_count()})", flush=True)

    result["lift_levels"] = args.lift_levels
    result["segment_rounds"] = args.segment_rounds
    result["jumps"] = args.jumps
    result["hoist_bytes"] = args.hoist_bytes
    result["balance_budget"] = args.balance
    result["alpha"] = alpha
    ckpt = None
    if args.checkpoint_dir:
        from sheep_tpu.utils.checkpoint import Checkpointer

        ckpt = Checkpointer(args.checkpoint_dir, every=args.ckpt_every)
    t0 = time.perf_counter()
    # through the REGISTERED backend (vertex-range check, chunk clamping,
    # PartitionResult packaging), not a hand-wired pipeline
    big = get_backend(
        "tpu-bigv", chunk_edges=args.chunk_edges, jumps=args.jumps,
        segment_rounds=args.segment_rounds, n_devices=args.devices,
        lift_levels=args.lift_levels, alpha=alpha,
        hoist_bytes=args.hoist_bytes).partition(
            stream(), args.k, comm_volume=False,
            checkpointer=ckpt, resume=args.resume)
    # the backend clamps chunk_edges for small streams; its diagnostics
    # carry the value actually run, so cross-round artifact comparisons
    # don't attribute a hidden chunk-size change to code changes
    result["chunk_edges_effective"] = int(
        big.diagnostics.get("chunk_edges_effective", args.chunk_edges))
    result["bigv"] = {
        "wall_s": round(time.perf_counter() - t0, 1),
        "edge_cut": int(big.edge_cut),
        "total_edges": int(big.total_edges),
        "balance": round(float(big.balance), 4),
        "phases": {p: round(s, 1) for p, s in big.phase_times.items()},
        "diagnostics": {k: (int(v) if isinstance(v, (int, float)) else v)
                        for k, v in big.diagnostics.items()},
        "fixpoint_rounds": int(big.diagnostics["fixpoint_rounds"]),
        "peak_rss_gb": round(resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss / 1e6, 1),
    }
    print("bigv:", json.dumps(result["bigv"]), flush=True)

    if not args.skip_oracle:
        from sheep_tpu.core import native

        assert native.available(), "native core needed for the oracle"
        t0 = time.perf_counter()
        ref = get_backend("cpu", chunk_edges=args.chunk_edges,
                          alpha=alpha).partition(
            stream(), args.k, comm_volume=False)
        result["native_oracle"] = {
            "wall_s": round(time.perf_counter() - t0, 1),
            "edge_cut": int(ref.edge_cut),
            "balance": round(float(ref.balance), 4),
        }
        print("oracle:", json.dumps(result["native_oracle"]), flush=True)
        result["oracle_equal"] = bool(
            big.edge_cut == ref.edge_cut
            and np.array_equal(big.assignment, ref.assignment))

    # write the artifact BEFORE any equality verdicting exits: a
    # multi-hour disagreeing run must still leave its evidence on disk
    # (oracle_equal: false), not vanish into an AssertionError. The
    # path is keyed by mesh size / balance up top (ADVICE r4: a rerun
    # at another D or BETA is a semantically different run and must not
    # clobber committed evidence).
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    print(f"written to {out}")
    if result.get("oracle_equal") is False:
        print("ORACLE MISMATCH: bigv != native at this scale",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
