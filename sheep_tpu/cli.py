"""CLI driver (SURVEY.md §2 #12, §3.1).

The reference's entry point, rebuilt:

    python -m sheep_tpu.cli --input g.edges --k 64 --backend tpu \
        --output parts.bin

Prints per-phase timing and scores (edge cut, cut ratio, balance, comm
volume) as human-readable lines plus one machine-readable JSON line, and
writes the vertex->part map. ``--backend`` selects the execution strategy
via the Partitioner plugin registry [NORTH-STAR].
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="sheep",
        description="TPU-native distributed graph partitioner "
                    "(SHEEP elimination-tree algorithm)",
        epilog="server mode: `sheep serve --socket PATH` runs the "
               "resident sheepd daemon (warm compiled programs, "
               "multi-tenant job queue); `sheep submit --server PATH "
               "--input G --k N` submits to one (--watch for live "
               "progress); `sheep top --server PATH` is the live "
               "telemetry console. See README 'Server mode' and "
               "'Live telemetry'.",
    )
    p.add_argument("--input",
                   help="edge list (.edges/.txt text, .bin32/.bin64 "
                        "binary), or a synthetic stream spec: "
                        "rmat-hash:SCALE[:EF[:SEED]] (device-generated "
                        "chunks on TPU backends) or rmat:SCALE[:EF[:SEED]]")
    p.add_argument("--k", help="number of parts; a comma list (e.g. "
                               "--k 8,64,256) splits ONE elimination-tree "
                               "build for every k (the tree is "
                               "k-independent), one result line each")
    p.add_argument("--backend", default=None,
                   help="execution backend (default: best available; see --list-backends)")
    p.add_argument("--k-levels", default=None, metavar="K1,K2",
                   help="hierarchical partitioning into K1*K2*... parts: "
                        "partition + refine at K1, recurse into each "
                        "part's induced subgraph for the remaining "
                        "levels. --refine rounds apply at EVERY level "
                        "(default 8 when --refine is 0). Recovers "
                        "community structure where flat k stalls below "
                        "the LP signal threshold (BASELINE.md 'SBM "
                        "quality'); replaces --k. Combines with "
                        "--checkpoint-dir/--resume (chunk-level inside "
                        "level 0, level-boundary for the recursion) and "
                        "with multi-host flags (level 0 is an ordinary "
                        "flat partition)")
    p.add_argument("--final-refine", type=int, default=None, metavar="N",
                   help="with --k-levels (or --auto-recipe): N "
                        "warm-start LP rounds at the FULL k after "
                        "hierarchical assembly (level-1 leakage repair; "
                        "the LP signal objection applies to cold starts "
                        "only)")
    p.add_argument("--auto-recipe", action="store_true",
                   help="let the quality advisor pick the hierarchy "
                        "recipe when the intra-degree/k signal says flat "
                        "label propagation will stall at --k (below the "
                        "measured threshold a naive --k 64 --refine 30 "
                        "silently lands ~0.85 cut on community graphs "
                        "where the recipe lands ~0.13). Without this "
                        "flag the advisor only prints its "
                        "recommendation; with it, the run becomes the "
                        "exact --k-levels/--final-refine/--balance "
                        "invocation it prints — bit-identical to "
                        "passing those flags by hand")
    p.add_argument("--spill-dir", default=None, metavar="DIR",
                   help="with --k-levels: where per-part intra-edge "
                        "shards spill (default: system temp). Disk "
                        "high-water mark is 8 bytes per intra edge of "
                        "the current level")
    p.add_argument("--deltas", default=None, metavar="LOG",
                   help="incremental replay (ISSUE 15): build --input, "
                        "then fold the delta log's epochs "
                        "(io/deltalog.py add/tombstone batches) into "
                        "the converged table in O(Δ) each — "
                        "bit-identical to a one-shot build of the "
                        "delta: input at the final epoch; deletions "
                        "tombstone and compact (see README "
                        "'Incremental updates'). Single k, flat path, "
                        "single-device backends (tpu/cpu/pure)")
    p.add_argument("--score-only", default=None, metavar="PARTS",
                   help="skip partitioning: score this existing partition "
                        "map (.parts/.pbin) against --input — the "
                        "standalone edge_cut_score() use case; --k is "
                        "inferred from the map if omitted")
    p.add_argument("--output", default=None,
                   help="partition map output (.parts text or .pbin binary)")
    p.add_argument("--weights", choices=["unit", "degree"], default="unit",
                   help="vertex weights for balance (default unit)")
    p.add_argument("--alpha", type=float, default=1.0,
                   help="bag capacity factor for the tree split (default "
                        "1.0; delivered balance is bounded by 1 + alpha "
                        "+ k*max_weight/total — see --balance for the "
                        "contract form)")
    p.add_argument("--balance", type=float, default=None, metavar="BETA",
                   help="guaranteed balance bound: deliver max part load "
                        "<= BETA * (total/k) + max vertex weight (+ one "
                        "weight unit on tiny parts, total/k < "
                        "1/(BETA-1), where the bag capacity floors at a "
                        "single unit), by running the split at alpha = "
                        "BETA - 1 (measured cut cost ~1-2.5%% at BETA "
                        "1.1-1.3, BASELINE.md balance table); BETA > 1, "
                        "mutually exclusive with --alpha")
    p.add_argument("--segment-rounds", type=int, default=None,
                   help="fixpoint rounds per device execution (tpu "
                        "backend; default 2 — tuned on the v5e)")
    p.add_argument("--warm-schedule", default=None, metavar="R:L[,R:L...]",
                   help="low-lift warm rounds before full-depth rounds, "
                        "e.g. '1:8' (the tpu backend's tuned default) or "
                        "'' to disable")
    p.add_argument("--host-tail-threshold", type=int, default=None,
                   help="hand the fixpoint tail to the native host core "
                        "at this live-constraint count (tpu backend; "
                        "default: chunk/2 on accelerators, auto on cpu)")
    p.add_argument("--no-cache-chunks", action="store_true",
                   help="disable the device-resident edge-chunk cache "
                        "(tpu backend re-streams each pass)")
    p.add_argument("--dispatch-batch", type=int, default=None, metavar="N",
                   help="tpu/tpu-sharded: stage N streamed chunks as one "
                        "padded [N, C] block and fold them in single "
                        "bounded device programs — one packed stats sync "
                        "per execution instead of per fixpoint segment "
                        "(default 1 = the adaptive per-segment driver; "
                        "2 is refused on a TPU; the forest is "
                        "bit-identical either way)")
    p.add_argument("--inflight", type=int, default=None, metavar="D",
                   help="tpu/tpu-sharded: depth of the asynchronous "
                        "dispatch pipeline — keep up to D batched device "
                        "executions in flight with their packed stats "
                        "words read one-behind, so host staging, H2D "
                        "transfer and the device fixpoint overlap "
                        "instead of alternating (default 1 = synchronous "
                        "dispatch; the forest is bit-identical at every "
                        "depth)")
    p.add_argument("--h2d-ring", type=int, default=None, metavar="D",
                   help="tpu backend: staged host->device ring depth — "
                        "keep up to D pre-padded chunk blocks' "
                        "device_put transfers issued ahead of the "
                        "dispatch chain, so the upload of block i+D "
                        "overlaps the fold of block i (0 = auto: 2 on "
                        "accelerators, 1 on cpu-jax; bit-identical at "
                        "every depth). Device-generated synthetic "
                        "streams (rmat-hash:/sbm-hash:) synthesize "
                        "chunks in accelerator memory and skip staging "
                        "entirely")
    p.add_argument("--lift-levels", type=int, default=None,
                   help="binary-lifting depth of the fixpoint climb "
                        "(0 = auto; tpu and tpu-bigv backends)")
    p.add_argument("--jumps", type=int, default=None,
                   help="tpu-bigv: single-step climbs per tail round")
    p.add_argument("--hoist-bytes", type=int, default=None,
                   help="tpu-bigv: per-device HBM budget for the "
                        "per-segment stale lifting stack (0 = per-round "
                        "squaring, the measured default; see BASELINE.md)")
    p.add_argument("--chunk-edges", type=int, default=None,
                   help="edges per streamed chunk (default backend-specific)")
    p.add_argument("--refine", type=int, default=None, metavar="N",
                   help="post-pass: up to N rounds of capacity-constrained "
                        "label propagation (cut never regresses; extension "
                        "beyond the reference). Default 0 for flat runs; "
                        "--k-levels defaults to 8 per level (pass an "
                        "explicit 0 for unrefined levels)")
    p.add_argument("--refine-alpha", type=float, default=1.10,
                   help="refinement balance cap (x ceil(V/k) per part)")
    p.add_argument("--refine-budget-gb", type=float, default=4.0,
                   metavar="GB",
                   help="histogram budget for refinement: above "
                        "(V+1)*k*4 bytes it switches to multi-pass "
                        "blocked mode (bit-identical, ~(2B+1)/2x the "
                        "stream passes at B blocks). s22/k=256 misses "
                        "the 4 GB default by 1 KB — raise on big-RAM "
                        "hosts")
    p.add_argument("--no-comm-volume", action="store_true",
                   help="skip communication-volume computation (saves a pass of memory)")
    p.add_argument("--num-vertices", type=int, default=None,
                   help="vertex count if known (skips a counting pass)")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax profiler trace (tpu backend) to this dir")
    p.add_argument("--metrics-out", default=None,
                   help="append structured JSONL metrics (phases, scores, "
                        "part loads, device memory) to this file")
    p.add_argument("--trace", default=None, metavar="FILE",
                   help="append a structured trace (JSONL: run manifest, "
                        "hierarchical span tree with counter deltas, "
                        "heartbeats, scores) to FILE; render with "
                        "tools/trace_report.py. Multi-host runs trace on "
                        "process 0 only")
    p.add_argument("--heartbeat-secs", type=float, default=None,
                   metavar="S",
                   help="with --trace: emit a progress heartbeat record "
                        "(phase, chunks done, edges/sec, ETA, dispatch "
                        "counts, device memory) every S seconds, plus one "
                        "final flush — a dead run stops heartbeating, a "
                        "slow one doesn't")
    p.add_argument("--checkpoint-dir", default=None,
                   help="save O(V) chunk-level checkpoints to this dir")
    p.add_argument("--checkpoint-every", type=int, default=64,
                   help="checkpoint cadence in chunks (default 64)")
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest checkpoint in --checkpoint-dir")
    p.add_argument("--json", action="store_true", help="print only the JSON result line")
    p.add_argument("--list-backends", action="store_true", help="list backends and exit")
    from sheep_tpu import __version__

    p.add_argument("--version", action="version",
                   version=f"sheep_tpu {__version__}")
    mh = p.add_argument_group("multi-host (the reference's mpirun equivalent)")
    mh.add_argument("--coordinator", default=None,
                    help="coordinator address host:port; launch one process "
                         "per host with the same value")
    mh.add_argument("--num-processes", type=int, default=None,
                    help="total number of processes in the multi-host run")
    mh.add_argument("--process-id", type=int, default=None,
                    help="this process's rank in [0, num_processes)")
    return p


def _parse_warm_schedule(spec: str, parser) -> tuple:
    """'R:L[,R:L...]' -> ((R, L), ...); '' -> (); malformed input is an
    argparse error at parse time, not a mid-partition crash."""
    out = []
    for part in spec.split(","):
        if not part:
            continue
        bits = part.split(":")
        if len(bits) != 2 or not all(b.isdigit() for b in bits):
            parser.error(f"--warm-schedule: expected R:L pairs, got {part!r}")
        rounds, levels = int(bits[0]), int(bits[1])
        if rounds < 1 or levels < 1:
            parser.error(f"--warm-schedule: R and L must be >= 1 in {part!r}")
        out.append((rounds, levels))
    return tuple(out)


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    # server verbs (ISSUE 10): `sheep serve ...` runs the resident
    # daemon, `sheep submit ...` talks to one — both also installed as
    # standalone console scripts (sheepd / sheep-submit). Dispatched
    # before argparse so the flat flag grammar stays untouched.
    if argv and argv[0] == "serve":
        from sheep_tpu.server.daemon import main as daemon_main

        return daemon_main(argv[1:])
    if argv and argv[0] == "submit":
        from sheep_tpu.server.client import main as submit_main

        return submit_main(argv[1:])
    if argv and argv[0] == "update":
        # ISSUE 15: `sheep update JOB --server S --deltas LOG` streams
        # a delta log's epochs at a resident served partition (sugar
        # over sheep-submit --update)
        from sheep_tpu.server.client import main as submit_main

        rest = list(argv[1:])
        if rest and not rest[0].startswith("-"):
            rest = ["--update", rest[0]] + rest[1:]
        return submit_main(rest)
    if argv and argv[0] == "top":
        # ISSUE 11: the live telemetry console (also installed as the
        # standalone `sheeptop` console script)
        from sheep_tpu.server.sheeptop import main as top_main

        return top_main(argv[1:])
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.heartbeat_secs is not None:
        if args.trace is None:
            parser.error("--heartbeat-secs requires --trace (heartbeats "
                         "are trace records)")
        if args.heartbeat_secs <= 0:
            parser.error("--heartbeat-secs must be > 0")
    # multi-host: one trace file, written by process 0 (every other rank
    # runs untraced — the obs facade is a no-op without an installed
    # tracer, so the instrumented loops cost nothing there). A
    # rank-autodetected launch (--coordinator without --process-id)
    # cannot know its rank this early, so it runs untraced rather than
    # risking every rank appending to one file.
    multi_host = args.coordinator or args.num_processes
    is_rank0 = args.process_id == 0 or (args.process_id is None
                                        and not multi_host)
    if args.trace is None or not is_rank0:
        return _run(parser, args)

    from sheep_tpu import obs

    tracer = obs.install(obs.Tracer(args.trace))
    root = None
    try:
        if not multi_host:
            _start_trace_run(tracer, args)
        # multi-host: the manifest's topology probe would initialize the
        # jax backend, and jax.distributed.initialize REQUIRES that no
        # computation ran yet — _run emits manifest + starts the
        # heartbeat right after the distributed bring-up instead
        root = obs.begin("run")
        return _run(parser, args)
    finally:
        if tracer.heartbeat is not None:
            tracer.heartbeat.stop()
        if root is not None:
            root.end()
        obs.uninstall()
        tracer.close()


def _start_trace_run(tracer, args) -> None:
    """Manifest + heartbeat for a traced run. Called only once probing
    the jax topology is safe: immediately for single-process runs,
    after ``jax.distributed.initialize`` for multi-host ones."""
    from sheep_tpu import obs

    obs.emit_manifest(tracer, config=vars(args), backend=args.backend)
    if args.heartbeat_secs:
        tracer.heartbeat = obs.Heartbeat(
            tracer, args.heartbeat_secs).start()


def _multihost_setup(args) -> tuple:
    """Distributed bring-up shared by the flat and --k-levels paths:
    initialize the runtime, resolve rank, default the backend to the
    sharded one, then start the deferred trace (the manifest's topology
    probe is only safe after jax.distributed.initialize, and it sits
    after the backend default so the manifest records the backend that
    will actually run). Returns (is_main, process_id, nprocs)."""
    from sheep_tpu.parallel.mesh import init_distributed

    init_distributed(args.coordinator, args.num_processes, args.process_id)
    import jax

    process_id = jax.process_index()
    nprocs = jax.process_count()
    if args.backend is None:
        args.backend = "tpu-sharded"
    from sheep_tpu import obs

    tracer = obs.get_tracer()
    if tracer is not None:
        _start_trace_run(tracer, args)
    return process_id == 0, process_id, nprocs


def _run(parser, args) -> int:

    def _score_only(args):
        """--score-only PARTS: evaluate an existing partition map against
        the input — the reference's standalone edge_cut_score() path."""
        import numpy as np

        from sheep_tpu.backends.base import score_stream
        from sheep_tpu.io.edgestream import open_input
        from sheep_tpu.io.formats import read_partition

        assignment = read_partition(args.score_only)
        with open_input(args.input, n_vertices=args.num_vertices) as es:
            n = es.num_vertices
            if len(assignment) != n:
                print(f"error: partition map has {len(assignment)} "
                      f"entries, graph has {n} vertices", file=sys.stderr)
                return 2
            k = int(args.k) if args.k is not None \
                else int(assignment.max()) + 1
            if assignment.min() < 0 or assignment.max() >= k:
                print(f"error: partition map assigns parts outside "
                      f"[0, {k})", file=sys.stderr)
                return 2
            t0 = time.perf_counter()
            w = None
            if args.weights == "degree":
                w = np.zeros(n, dtype=np.int64)
                for c in es.chunks(args.chunk_edges or (1 << 22)):
                    w += np.bincount(np.asarray(c, np.int64).ravel(),
                                     minlength=n)[:n]
            cut, total, balance, cv = score_stream(
                es, {k: assignment},
                chunk_edges=args.chunk_edges or (1 << 22),
                comm_volume=not args.no_comm_volume, weights=w)[k]
            wall = time.perf_counter() - t0
        line = {"k": k, "edge_cut": cut, "total_edges": total,
                "cut_ratio": cut / max(total, 1), "balance": balance,
                "comm_volume": cv, "backend": "score-only",
                "wall_seconds": round(wall, 4), "n_vertices": n}
        from sheep_tpu import obs

        obs.event("scores", **line)
        if not args.json:
            print(f"score-only: {args.score_only} vs {args.input}")
            print(f"k={k}: edge cut {cut:,} "
                  f"({100 * cut / max(total, 1):.2f}%)  "
                  f"balance {balance:.4f}"
                  + (f"  comm volume {cv:,}" if cv is not None else ""))
        print(json.dumps(line))
        return 0

    from sheep_tpu.utils.platform import enable_compilation_cache

    enable_compilation_cache()

    from sheep_tpu import list_backends
    from sheep_tpu.backends.base import get_backend
    from sheep_tpu.io.edgestream import open_input
    from sheep_tpu.io.formats import write_partition
    from sheep_tpu.types import UnsupportedGraphError

    if args.list_backends:
        print(" ".join(list_backends()))
        return 0
    if args.input is None or (args.k is None and not args.score_only
                              and not args.k_levels):
        build_parser().error("--input and --k are required")

    def _k_levels(args):
        """--k-levels K1,K2: hierarchical partitioning via the library's
        partition_hierarchical (see sheep_tpu/hierarchy.py)."""
        import sheep_tpu

        if args.k is not None:
            parser.error("--k-levels replaces --k")
        if args.resume and not args.checkpoint_dir:
            parser.error("--resume requires --checkpoint-dir")
        if args.balance is not None and args.alpha != 1.0:
            parser.error("--balance sets the per-level alpha "
                         "(BETA**(1/levels) per level); do not also "
                         "pass --alpha")
        # every other flag either forwards below or must not silently
        # diverge from what was requested
        ignored = [f for f, v in (
            ("--metrics-out", args.metrics_out),
            ("--profile-dir", args.profile_dir),
            ("--segment-rounds", args.segment_rounds),
            ("--warm-schedule", args.warm_schedule),
            ("--host-tail-threshold", args.host_tail_threshold),
            ("--no-cache-chunks", args.no_cache_chunks or None),
            ("--dispatch-batch", args.dispatch_batch),
            ("--inflight", args.inflight),
            ("--h2d-ring", args.h2d_ring),
            ("--lift-levels", args.lift_levels),
            ("--jumps", args.jumps),
            ("--hoist-bytes", args.hoist_bytes),
            ("--deltas", args.deltas),
        ) if v is not None]
        if ignored:
            parser.error(f"{', '.join(ignored)} not supported with "
                         f"--k-levels (would be silently ignored)")
        try:
            levels = [int(x) for x in args.k_levels.split(",") if x != ""]
        except ValueError:
            levels = []
        if not levels or any(k < 1 for k in levels):
            parser.error(f"--k-levels must be a comma list of "
                         f"positive ints (got {args.k_levels!r})")

        # multi-host: level 0 is an ordinary flat partition, so the
        # same distributed bring-up as the flat path applies; the
        # recursion then runs identically (and deterministically) on
        # every process, keeping collective schedules in lockstep
        is_main, process_id, nprocs = True, 0, 1
        if args.coordinator or args.num_processes:
            is_main, process_id, nprocs = _multihost_setup(args)

        ckpt_kw = {}
        if args.checkpoint_dir:
            from sheep_tpu.utils.checkpoint import Checkpointer

            ckpt_kw = {
                "checkpointer": Checkpointer(args.checkpoint_dir,
                                             every=args.checkpoint_every,
                                             process=process_id),
                "resume": args.resume,
                "nprocs": nprocs,
            }
        t0 = time.perf_counter()
        res = sheep_tpu.partition_hierarchical(
            args.input, levels, backend=args.backend,
            refine=8 if args.refine is None else args.refine,
            refine_alpha=args.refine_alpha,
            chunk_edges=args.chunk_edges or (1 << 22),
            comm_volume=not args.no_comm_volume, weights=args.weights,
            balance=args.balance, final_refine=args.final_refine or 0,
            spill_dir=args.spill_dir, n_vertices=args.num_vertices,
            refine_budget_bytes=int(args.refine_budget_gb * (1 << 30)),
            **ckpt_kw,
            **({} if args.balance is not None else
               {"alpha": args.alpha}))
        wall = time.perf_counter() - t0
        if not is_main:
            return 0
        if args.output:
            write_partition(args.output, res.assignment)
        summary = res.summary()
        summary["wall_seconds"] = round(wall, 4)
        summary["n_vertices"] = int(len(res.assignment))
        from sheep_tpu import obs

        obs.event("scores", **summary)
        if not args.json:
            print(f"graph: {args.input}  k-levels: {levels}")
            print(f"k={res.k}: edge cut {res.edge_cut:,} "
                  f"({100 * res.cut_ratio:.2f}%)  balance "
                  f"{res.balance:.4f}"
                  + (f"  comm volume {res.comm_volume:,}"
                     if res.comm_volume is not None else ""))
            if args.output:
                print(f"partition map written to {args.output}")
            print(f"wall: {wall:.2f}s")
        print(json.dumps(summary))
        return 0

    if args.k_levels:
        if args.score_only:
            build_parser().error("--k-levels does not combine with "
                                 "--score-only")
        if args.auto_recipe:
            build_parser().error("--auto-recipe asks the advisor to "
                                 "pick the levels; it replaces "
                                 "--k-levels")
        return _k_levels(args)
    if (args.final_refine and not args.auto_recipe) or args.spill_dir:
        build_parser().error("--final-refine/--spill-dir require "
                             "--k-levels (the flat pipeline has no "
                             "hierarchy to repair or spill; "
                             "--final-refine also composes with "
                             "--auto-recipe)")
    if args.auto_recipe and args.score_only:
        build_parser().error("--auto-recipe has no effect with "
                             "--score-only (nothing is partitioned)")
    if args.score_only:
        if args.deltas:
            build_parser().error("--deltas does not combine with "
                                 "--score-only (score the delta: "
                                 "input spec instead)")
        if args.balance is not None:
            build_parser().error("--balance has no effect with "
                                 "--score-only (the split already "
                                 "happened)")
        if args.k is not None:
            raw_k = args.k
            try:
                args.k = int(raw_k)
            except ValueError:
                args.k = 0
            if args.k < 1:
                build_parser().error(f"--score-only takes a single "
                                     f"positive --k (got {raw_k!r})")
        return _score_only(args)
    try:
        ks = [int(x) for x in str(args.k).split(",") if x != ""]
    except ValueError:
        ks = []
    if not ks or any(k < 1 for k in ks):
        build_parser().error(f"--k must be a positive int or comma list "
                             f"of them (got {args.k!r})")
    # duplicate ks would alias the per-k output paths and the marginal
    # wall accounting (both are keyed by k): dedupe preserving order
    ks = list(dict.fromkeys(ks))
    if len(ks) > 1 and (args.checkpoint_dir or args.refine):
        build_parser().error("--k lists do not combine with "
                             "--checkpoint-dir or --refine; run those "
                             "single-k")
    args.k = ks[0]
    if args.deltas:
        # the incremental replay is a flat, single-k, single-device
        # path; every combination it cannot honor is rejected up front
        bad = [f for f, v in (
            ("--k lists", len(ks) > 1 or None),
            ("--refine", args.refine),
            ("--auto-recipe", args.auto_recipe or None),
            ("--checkpoint-dir", args.checkpoint_dir),
            ("--resume", args.resume or None),
            ("--coordinator/--num-processes",
             args.coordinator or args.num_processes),
        ) if v]
        if bad:
            build_parser().error(f"{', '.join(bad)} not supported "
                                 f"with --deltas (the incremental "
                                 f"replay is flat, single-k, "
                                 f"single-process)")
        if not os.path.exists(args.deltas):
            build_parser().error(f"--deltas {args.deltas!r} does not "
                                 f"exist")
    if args.resume and not args.checkpoint_dir:
        build_parser().error("--resume requires --checkpoint-dir")
    if args.auto_recipe and len(ks) > 1:
        build_parser().error("--auto-recipe takes a single --k (the "
                             "recipe is per target k)")
    if args.auto_recipe:
        # flags a --k-levels run cannot honor are rejected UP FRONT:
        # letting them through would make the same command line a
        # usage error or not depending on the input's degree signal
        # (and the eventual error would name --k-levels, a flag the
        # user never passed)
        unsupported = [f for f, v in (
            ("--metrics-out", args.metrics_out),
            ("--profile-dir", args.profile_dir),
            ("--segment-rounds", args.segment_rounds),
            ("--warm-schedule", args.warm_schedule),
            ("--host-tail-threshold", args.host_tail_threshold),
            ("--no-cache-chunks", args.no_cache_chunks or None),
            ("--dispatch-batch", args.dispatch_batch),
            ("--inflight", args.inflight),
            ("--h2d-ring", args.h2d_ring),
            ("--lift-levels", args.lift_levels),
            ("--jumps", args.jumps),
            ("--hoist-bytes", args.hoist_bytes),
        ) if v is not None]
        if unsupported:
            build_parser().error(
                f"{', '.join(unsupported)} not supported with "
                f"--auto-recipe (the applied recipe is a --k-levels "
                f"run, which does not take them)")

    # ---- quality advisor (ISSUE 13) ----------------------------------
    # The degree pass's cheapest statistic (2E/V, O(1) for binary and
    # synthetic inputs) prices the LP signal BEFORE any device work: a
    # naive flat --k below the threshold silently lands an ~0.85-class
    # cut on community graphs where the three-flag hierarchy recipe
    # lands ~0.13 — so the tool now SAYS so, and --auto-recipe makes
    # the run the exact recipe invocation it prints (bit-identical to
    # the manual flags by construction: same code path, same knobs).
    if len(ks) == 1 and not args.score_only:
        advice = None
        try:
            with open_input(args.input,
                            n_vertices=args.num_vertices) as es0:
                from sheep_tpu.ops.degrees import advise_recipe

                m = es0.num_edges_cheap
                # the signal must stay O(1): never pay a stream scan
                # just to advise. num_edges_cheap is O(1) or None by
                # contract, but num_vertices SCANS the file for
                # binary/text inputs unless the caller supplied it —
                # synthetic/memory streams (no path) and CSR headers
                # are arithmetic, and an already-known _n_vertices
                # (--num-vertices) is free.
                cheap_v = (getattr(es0, "path", None) is None
                           or getattr(es0, "fmt", None) == "csr"
                           or getattr(es0, "_n_vertices", None)
                           is not None)
                if m is not None and cheap_v:
                    advice = advise_recipe(es0.num_vertices, m, args.k)
                else:
                    advice = {"mode": "unknown", "signal": None,
                              "k": args.k}
        except (OSError, ValueError):
            pass  # unopenable input: the main path raises the real error
        # mirror the trace gating: print on rank 0, and not at all on
        # rank-autodetected launches (every rank would print)
        adv_main = args.process_id == 0 or (
            args.process_id is None
            and not (args.coordinator or args.num_processes))
        if advice is not None and advice["mode"] == "hier":
            lv = ",".join(str(x) for x in advice["k_levels"])
            # `is None` tests: an EXPLICIT --final-refine 0 /
            # --balance must survive into the applied recipe
            fr = advice["final_refine"] if args.final_refine is None \
                else args.final_refine
            bal = args.balance if args.balance is not None \
                else advice["balance"]
            flags = f"--k-levels {lv} --final-refine {fr} --balance {bal}"
            if args.refine is not None:
                flags += f" --refine {args.refine}"
            if adv_main:
                print(f"note: quality advisor: intra-degree/k signal "
                      f"{advice['signal']:.2f} < "
                      f"{advice['threshold']:.2f} at k={args.k} — flat "
                      f"label propagation stalls below the signal "
                      f"threshold (BASELINE.md 'SBM quality'); "
                      f"recommended recipe: {flags}"
                      + ("" if args.auto_recipe else
                         "  (pass --auto-recipe to apply)"),
                      file=sys.stderr)
            if args.auto_recipe:
                args.k_levels = lv
                args.k = None
                args.final_refine = fr
                args.balance = bal
                return _k_levels(args)
        elif args.auto_recipe and adv_main:
            if advice is None or advice.get("signal") is None:
                why = ("the stream's size is not O(1)-knowable (text "
                       "inputs, or binary without --num-vertices), so "
                       "the signal is unknown")
            elif advice["signal"] >= advice["threshold"]:
                why = (f"signal {advice['signal']:.2f} >= "
                       f"{advice['threshold']:.2f} (flat LP is fine)")
            else:
                why = (f"signal {advice['signal']:.2f} is low but "
                       f"k={args.k} has no usable level split (prime "
                       f"past the per-level cap)")
            print(f"note: quality advisor: {why}; running the flat "
                  f"path as asked"
                  + (" (--final-refine only applies when the advisor "
                     "selects a hierarchy; ignored)"
                     if args.final_refine else ""), file=sys.stderr)

    is_main = True
    process_id = 0
    if args.coordinator or args.num_processes:
        is_main, process_id, _ = _multihost_setup(args)

    backend = args.backend
    if backend is None:
        avail = list_backends()
        backend = next(b for b in ("tpu", "cpu", "pure") if b in avail)
        auto = True
    else:
        auto = False

    t0 = time.perf_counter()
    with open_input(args.input, n_vertices=args.num_vertices) as es:
        if auto and backend.startswith("tpu") and "tpu-bigv" in list_backends():
            # replicated vertex tables past the single-chip ceiling need
            # the vertex-sharded mode (BASELINE.md HBM budget): the
            # ceiling comes from the device's reported (or generation-
            # known) HBM — never a guess. cpu-jax has no device ceiling.
            import jax

            from sheep_tpu.backends.tpu_backend import _device_hbm_bytes
            from sheep_tpu.utils.membudget import max_vertices_for

            hbm = None
            if jax.default_backend() != "cpu":
                hbm = _device_hbm_bytes(purpose="the replicated-table "
                                                "ceiling",
                                        override="--backend")
                if hbm <= 0:
                    parser.error(
                        "the device reports no bytes_limit and its "
                        "device_kind has no known HBM size; choose "
                        "--backend tpu or tpu-bigv explicitly")
            cs = args.chunk_edges or (1 << 22)
            if hbm and es.num_vertices > max_vertices_for(int(0.9 * hbm),
                                                          cs):
                backend = "tpu-bigv"
                print(f"note: V={es.num_vertices:,} exceeds the "
                      f"replicated-table ceiling for this device's HBM; "
                      f"auto-selected the vertex-sharded tpu-bigv backend",
                      file=sys.stderr)

        if args.balance is not None:
            if args.balance <= 1.0:
                parser.error("--balance must be > 1 (it bounds max part "
                             "load at BETA * total/k)")
            if args.alpha != 1.0:
                parser.error("--balance sets alpha = BETA - 1; do not "
                             "also pass --alpha")
            # LPT placement puts each flushed bag (<= alpha*total/k +
            # max_w) on a part whose load is <= total/k, so alpha =
            # BETA - 1 delivers max load <= BETA*total/k + max_w
            # (tests/test_balance.py pins this bound)
            args.alpha = min(args.balance - 1.0, 1.0)
            if args.refine and args.refine_alpha > args.balance:
                # refinement caps parts at refine_alpha*ceil(V/k): a
                # looser refine cap would silently void the --balance
                # contract end-to-end (ADVICE r4), so clamp it to BETA
                print(f"note: --balance {args.balance} clamps "
                      f"--refine-alpha {args.refine_alpha} to the "
                      f"contract bound", file=sys.stderr)
                args.refine_alpha = args.balance
        ctor = {"alpha": args.alpha}
        if args.chunk_edges:
            ctor["chunk_edges"] = args.chunk_edges
        if args.segment_rounds is not None:
            ctor["segment_rounds"] = args.segment_rounds
        if args.warm_schedule is not None:
            ctor["warm_schedule"] = _parse_warm_schedule(
                args.warm_schedule, parser)
        if args.host_tail_threshold is not None:
            ctor["host_tail_threshold"] = args.host_tail_threshold
        if args.no_cache_chunks:
            ctor["cache_chunks"] = False
        if args.dispatch_batch is not None:
            if args.dispatch_batch < 1:
                parser.error("--dispatch-batch must be >= 1")
            ctor["dispatch_batch"] = args.dispatch_batch
        if args.inflight is not None:
            if args.inflight < 1:
                parser.error("--inflight must be >= 1")
            ctor["inflight"] = args.inflight
        if args.h2d_ring is not None:
            if args.h2d_ring < 0:
                parser.error("--h2d-ring must be >= 0 (0 = auto)")
            ctor["h2d_ring"] = args.h2d_ring
        if args.lift_levels is not None:
            if args.lift_levels < 0:
                parser.error("--lift-levels must be >= 0")
            ctor["lift_levels"] = args.lift_levels
        if args.jumps is not None:
            if args.jumps < 1:
                parser.error("--jumps must be >= 1")
            ctor["jumps"] = args.jumps
        if args.hoist_bytes is not None:
            if args.hoist_bytes < 0:
                parser.error("--hoist-bytes must be >= 0")
            ctor["hoist_bytes"] = args.hoist_bytes
        # keep only the options this backend's constructor names; warn
        # about the rest instead of silently changing the run (the
        # tuning knobs vary per backend; every registered backend's ctor
        # names alpha and chunk_edges, so those survive the filter for
        # the built-ins — a third-party plugin without them gets the
        # stderr note). A plugin ctor taking **kwargs
        # accepts everything; an unknown backend name falls through to
        # get_backend's friendly available-backends error.
        import inspect

        from sheep_tpu.backends.base import _REGISTRY

        cls = _REGISTRY.get(backend)
        accepted = ctor
        if cls is not None:
            params = inspect.signature(cls.__init__).parameters
            if not any(p.kind is inspect.Parameter.VAR_KEYWORD
                       for p in params.values()):
                accepted = {k: v for k, v in ctor.items() if k in params}
                dropped = sorted(set(ctor) - set(accepted))
                if dropped and is_main:
                    print(f"note: backend {backend!r} does not take "
                          f"{', '.join(dropped)}; ignored", file=sys.stderr)
        be = get_backend(backend, **accepted)
        from sheep_tpu import obs

        # the manifest records the REQUESTED backend (null for auto);
        # this event records what auto-selection actually picked —
        # trace_report's manifest line falls back to it
        obs.event("backend_resolved", backend=backend, auto=auto)
        ckpt_kw = {}
        if args.checkpoint_dir:
            from sheep_tpu.utils.checkpoint import Checkpointer

            ckpt_kw = {
                "checkpointer": Checkpointer(args.checkpoint_dir,
                                             every=args.checkpoint_every,
                                             process=process_id),
                "resume": args.resume,
            }
        profile = None
        if args.profile_dir:
            import jax

            profile = jax.profiler.trace(args.profile_dir)
            profile.__enter__()
        try:
            try:
                if args.deltas:
                    # incremental replay (ISSUE 15): base build, then
                    # fold each logged epoch into the converged table
                    # — O(Δ) per epoch, bit-identical to the one-shot
                    # delta: build at the final epoch
                    from sheep_tpu import incremental
                    from sheep_tpu.io.deltalog import DeltaLogReader

                    if not getattr(be, "supports_incremental", False):
                        print(f"error: backend {be.name!r} does not "
                              f"support incremental updates; use "
                              f"--backend tpu/cpu/pure",
                              file=sys.stderr)
                        return 2

                    state, res = incremental.begin_incremental(
                        es, args.k, backend=be, weights=args.weights,
                        comm_volume=False)
                    applied = 0
                    for ep, d_adds, d_dels in DeltaLogReader(
                            args.deltas).epochs(
                                start_epoch=state.epoch):
                        be.partition_update(state, adds=d_adds,
                                            deletes=d_dels, epoch=ep,
                                            score=False)
                        applied += 1
                    res = incremental.refresh(
                        be, state,
                        comm_volume=not args.no_comm_volume)
                    if is_main and not args.json:
                        print(f"deltas: applied {applied} epoch(s) "
                              f"from {args.deltas} -> epoch "
                              f"{state.epoch} (stale deletes "
                              f"{state.stale_deletes}, compactions "
                              f"{state.compactions})")
                elif len(ks) > 1:
                    multi = be.partition_multi(
                        es, ks, weights=args.weights,
                        comm_volume=not args.no_comm_volume)
                    res = multi[0]
                else:
                    res = be.partition(es, args.k, weights=args.weights,
                                       comm_volume=not args.no_comm_volume,
                                       **ckpt_kw)
            except UnsupportedGraphError as exc:
                # documented envelope violations (e.g. >= 2^31 vertices on
                # an int32-table TPU backend) reject cleanly, not as a
                # mid-build stack trace
                print(f"error: {exc}", file=sys.stderr)
                return 2
            if args.refine and is_main:
                from sheep_tpu import refine_result

                res = refine_result(
                    res, es, rounds=args.refine,
                    alpha=args.refine_alpha, weights=args.weights,
                    budget_bytes=int(args.refine_budget_gb * (1 << 30)))
        finally:
            if profile is not None:
                profile.__exit__(None, None, None)
        wall = time.perf_counter() - t0
        n = es.num_vertices
        m = res.total_edges

    results = multi if len(ks) > 1 else [res]

    def _out_path(k: int) -> str:
        if len(ks) == 1:
            return args.output
        root, ext = os.path.splitext(args.output)
        return f"{root}.k{k}{ext}"

    if args.output and is_main:
        for r in results:
            write_partition(_out_path(r.k), r.assignment)

    if args.metrics_out and is_main:
        from sheep_tpu.utils.metrics import MetricsWriter, emit_run_metrics

        with MetricsWriter(args.metrics_out) as mw:
            for r in results:
                emit_run_metrics(mw, r, n, wall, graph=args.input)

    from sheep_tpu import obs

    tracer = obs.get_tracer()
    if tracer is not None and is_main:
        # the trace is self-contained: scores/phases/part-loads ride in
        # the same JSONL as the span tree (Tracer.emit is MetricsWriter-
        # compatible, so the one record-set implementation serves both)
        from sheep_tpu.utils.metrics import emit_run_metrics

        for r in results:
            emit_run_metrics(tracer, r, n, wall, graph=args.input)

    if not is_main:
        return 0
    if not args.json:
        print(f"graph: {args.input}  V={n:,}  E={m:,}")
        print(f"backend: {res.backend}  k={','.join(str(k) for k in ks)}")
        for phase, secs in res.phase_times.items():
            print(f"  {phase:>16}: {secs:.3f}s")
        for r in results:
            print(f"k={r.k}: edge cut {r.edge_cut:,} "
                  f"({100 * r.cut_ratio:.2f}%)  balance {r.balance:.4f}"
                  + (f"  comm volume {r.comm_volume:,}"
                     if r.comm_volume is not None else ""))
            if args.output:
                print(f"partition map written to {_out_path(r.k)}")
        print(f"wall: {wall:.2f}s  "
              f"({m / wall if wall > 0 else 0:,.0f} edges/s)")
    # JSON result lines LAST, one per k — consumers parse the tail.
    # Multi-k wall accounting: extra ks carry their MARGINAL cost (their
    # split + scoring share), the first k the remainder — rows sum to
    # the run wall instead of over-counting it len(ks) times.
    marginal = {r.k: sum(r.phase_times.values()) for r in results[1:]}
    for r in results:
        summary = r.summary()
        r_wall = marginal.get(r.k, wall - sum(marginal.values()))
        summary["wall_seconds"] = round(r_wall, 4)
        summary["edges_per_sec"] = round(m / r_wall, 1) if r_wall > 0 \
            else None
        summary["n_vertices"] = n
        print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
