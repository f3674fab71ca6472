#!/usr/bin/env python
"""Quality CI gate (ISSUE 13): run the fixed scenario sweep and/or
compare two committed ``QUALITY_*.json`` artifacts — cut regressions
get caught like perf ones (the ``bench_regress`` pattern).

    python tools/quality_regress.py --run NEW.json     # run the sweep
    python tools/quality_regress.py NEW.json OLD.json  # compare
    python tools/quality_regress.py                    # latest two QUALITY_*.json

The sweep covers graph CLASSES, not one generator: planted SBM (with
the per-level cut ledger + residual attribution against the planted
optimum), power-law SBM, R-MAT (the expander control), and the new
bipartite and near-clique streams (``io/generators.py``). Every
scenario is a fixed recipe over a fixed seed on the deterministic
partitioners, so two artifacts from the same code are bit-equal and
the gate can run tight: a ``cut_ratio`` or ``balance`` rise beyond
``--threshold`` on any shared scenario exits 2.

Scenarios present in exactly one artifact compare nothing — they are
listed on a ``skipped-incomparable: <names>`` line (the bench_regress
satellite's rule: a partial pass must read as partial) and the gate
stays vacuously green for them, because a sweep that grew a scenario
must not fail every older artifact retroactively.

Artifact shape::

    {"tool": "quality_regress", "suite": 1,
     "scenarios": {name: {"spec", "recipe", "cut_ratio", "balance",
                          "planted", "levels", "residual", ...}}}

Exit codes: 0 pass (or not comparable), 1 usage/IO error,
2 quality regression detected.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# bump when a scenario's spec/recipe changes: artifacts from different
# suites are not comparable (the bench_regress metric-string rule)
SUITE = 1

# Fixed sweep. Sized for CI: tiny streams, native-cpu partitioners,
# per-level refine 0 in the hierarchical scenarios (per-level refine
# re-jits one histogram per distinct subgraph shape — minutes of
# compile for zero gate value; final_refine at the one full-k shape
# carries the repair). The two hierarchical scenarios record the
# per-level cut ledger; the planted ones also record the residual
# attribution against the planted optimum.
SCENARIOS = (
    {"name": "sbm_planted", "spec": "sbm-hash:10:16:0.05:16:1",
     "k_levels": [4, 4], "refine": 0, "final_refine": 4,
     "balance": 1.05},
    {"name": "sbm_powerlaw", "spec": "plsbm-hash:11:16:0.05:16:1",
     "k": 16, "refine": 3},
    {"name": "rmat_expander", "spec": "rmat-hash:11:8:1",
     "k": 8, "refine": 2},
    {"name": "bipartite", "spec": "bipartite-hash:11:8:0.02:16:1",
     "k": 8, "refine": 2},
    {"name": "near_clique", "spec": "nearclique-hash:11:4:0.01:16:1",
     "k_levels": [4, 2], "refine": 0, "final_refine": 2,
     "balance": 1.1},
    # dynamic-graph scenario (ISSUE 15): half the shuffled stream
    # builds the base, the other half arrives as delta epochs through
    # the incremental path (sheep_tpu/incremental.py). cut_ratio is
    # the INCREMENTAL result's — gated against the committed artifact
    # like every row — and the run itself enforces the anchored-order
    # drift bound against the fresh-order one-shot build of the same
    # edges (cut_ratio <= oneshot + bound, else the sweep exits 2).
    {"name": "dynamic_sbm", "spec": "sbm-hash:10:16:0.05:16:2",
     "k": 16, "dynamic": {"epochs": 2, "bound": 0.05, "seed": 7}},
    # multi-device variant (ISSUE 19): the SAME dynamic recipe pinned
    # to the sharded backend — epochs fold through the lockstep
    # pipeline and every scored refresh rescores device-side (the
    # distributed score cache), still under the audit. The `backend`
    # key overrides the sweep-level choice for this row only.
    {"name": "dynamic_sbm_sharded", "spec": "sbm-hash:10:16:0.05:16:2",
     "k": 16, "backend": "tpu-sharded",
     "dynamic": {"epochs": 2, "bound": 0.05, "seed": 7}},
)


def run_dynamic_scenario(sc: dict, backend: str) -> dict:
    """Half-stream + deltas through the REAL incremental path; the
    one-shot build of the identical multiset rides along as the drift
    reference."""
    import numpy as np

    from sheep_tpu import incremental
    from sheep_tpu.backends.base import get_backend
    from sheep_tpu.io.edgestream import EdgeStream, open_input

    import os

    dyn = sc["dynamic"]
    with open_input(sc["spec"]) as es:
        edges = es.read_all()
        n = int(es.num_vertices)
    rng = np.random.default_rng(int(dyn.get("seed", 7)))
    e = edges[rng.permutation(len(edges))]
    half = len(e) // 2
    be = get_backend(backend)
    state, _ = incremental.begin_incremental(
        EdgeStream.from_array(e[:half], n_vertices=n), sc["k"],
        backend=be, comm_volume=False)
    res = None
    # the epochs run under SHEEP_SCORE_AUDIT (ISSUE 17): every
    # incremental rescore is cross-checked against a full score_stream
    # pass and RAISES on any divergence — so the gated cut_ratio below
    # is simultaneously a proof the O(delta) score path is exact here
    prev_audit = os.environ.get("SHEEP_SCORE_AUDIT")
    os.environ["SHEEP_SCORE_AUDIT"] = "1"
    try:
        for batch in np.array_split(e[half:],
                                    int(dyn.get("epochs", 2))):
            res = be.partition_update(state, adds=batch, score=True)
    finally:
        if prev_audit is None:
            os.environ.pop("SHEEP_SCORE_AUDIT", None)
        else:
            os.environ["SHEEP_SCORE_AUDIT"] = prev_audit
    if int(state.stats.get("score_incremental", 0)) < 1:
        # the first scored refresh seeds the cache (full pass); every
        # later epoch must take the incremental path — a silent
        # fallback to full rescoring would void the audit's coverage
        raise RuntimeError(
            f"dynamic scenario never exercised the incremental-score "
            f"path (stats={state.stats})")
    if hasattr(be, "_move_rescore") \
            and int(state.stats.get("score_distributed", 0)) < 1:
        # a multi-device backend must have taken the rescore
        # device-side at least once (ISSUE 19) — a silent fall-back
        # to the host scorer would leave the distributed path ungated
        raise RuntimeError(
            f"dynamic scenario never exercised the distributed-score "
            f"path on {be.name} (stats={state.stats})")
    oneshot = be.partition(EdgeStream.from_array(e, n_vertices=n),
                           sc["k"], comm_volume=False)
    row = {"spec": sc["spec"], "recipe": {"k": sc["k"],
                                          "dynamic": dict(dyn)},
           **({"backend": be.name} if "backend" in sc else {}),
           "k": int(res.k),
           "cut_ratio": round(float(res.cut_ratio), 6),
           "edge_cut": int(res.edge_cut),
           "total_edges": int(res.total_edges),
           "balance": round(float(res.balance), 4),
           "oneshot_cut_ratio": round(float(oneshot.cut_ratio), 6),
           "epoch": int(state.epoch)}
    bound = float(dyn.get("bound", 0.05))
    drift = float(res.cut_ratio) - float(oneshot.cut_ratio)
    row["anchored_drift"] = round(drift, 6)
    if drift > bound:
        row["bound_exceeded"] = True
    return row


def run_scenario(sc: dict, backend: str) -> dict:
    """One scenario -> its artifact row (deterministic: fixed spec,
    fixed recipe, deterministic partitioners)."""
    import sheep_tpu
    from sheep_tpu.io.edgestream import open_input
    from sheep_tpu.utils.metrics import ledger_residual

    if "dynamic" in sc:
        return run_dynamic_scenario(sc, backend)
    recipe = {k: sc[k] for k in ("k", "k_levels", "refine",
                                 "final_refine", "balance") if k in sc}
    if "k_levels" in sc:
        res = sheep_tpu.partition_hierarchical(
            sc["spec"], sc["k_levels"], backend=backend,
            refine=sc["refine"], final_refine=sc["final_refine"],
            balance=sc["balance"], comm_volume=False)
    else:
        res = sheep_tpu.partition(sc["spec"], sc["k"], backend=backend,
                                  comm_volume=False, refine=sc["refine"])
    row = {"spec": sc["spec"], "recipe": recipe, "k": int(res.k),
           "cut_ratio": round(float(res.cut_ratio), 6),
           "edge_cut": int(res.edge_cut),
           "total_edges": int(res.total_edges),
           "balance": round(float(res.balance), 4)}
    d = res.diagnostics or {}
    levels = {k: v for k, v in d.items()
              if str(k).startswith(("cut_level", "cut_ratio_level",
                                    "ledger_", "final_refine_"))}
    if levels:
        row["levels"] = levels
    with open_input(sc["spec"]) as es:
        planted_fn = getattr(es, "planted_cut_ratio", None)
        if planted_fn is not None:
            row["planted"] = round(planted_fn(), 6)
            if "k_levels" in sc:
                # the ledger vs the planted per-level optimum: which
                # level owns the residual (the ROADMAP item 4 question)
                residual = ledger_residual(d, sc["k_levels"],
                                           planted_fn, res.total_edges)
                if residual is not None:
                    row["residual"] = residual
    return row


def run_sweep(out_path: str, names=None, backend: str = None) -> dict:
    import sheep_tpu

    if backend is None:
        avail = sheep_tpu.list_backends()
        backend = next(b for b in ("cpu", "tpu", "pure") if b in avail)
    doc = {"tool": "quality_regress", "suite": SUITE,
           "backend": backend, "scenarios": {}}
    for sc in SCENARIOS:
        if names and sc["name"] not in names:
            continue
        # a scenario may pin its own backend (the multi-device rows);
        # everything else rides the sweep-level choice
        row = run_scenario(sc, sc.get("backend", backend))
        doc["scenarios"][sc["name"]] = row
        print(f"{sc['name']:<14} cut_ratio {row['cut_ratio']:.4f}  "
              f"balance {row['balance']:.3f}"
              + (f"  planted {row['planted']:.4f}"
                 if "planted" in row else ""), file=sys.stderr)
    exceeded = sorted(name for name, row in doc["scenarios"].items()
                      if row.get("bound_exceeded"))
    if exceeded:
        doc["bound_exceeded"] = exceeded
        print(f"BOUND EXCEEDED in: {', '.join(exceeded)} (anchored "
              f"drift past the scenario bound)", file=sys.stderr)
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"wrote {out_path}", file=sys.stderr)
    return doc


def load_artifact(path: str):
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return None, f"cannot load {path}: {e}"
    if not isinstance(doc, dict) or "scenarios" not in doc:
        return None, f"{path}: not a quality_regress artifact"
    return doc, None


def compare(new: dict, old: dict, threshold: float) -> dict:
    """Gate ``cut_ratio`` and ``balance`` per shared scenario (both
    higher-is-worse; an old value of 0 gates any rise absolutely, the
    bench_regress rule). Scenario sets may differ — the difference is
    reported as skipped, never gated."""
    out = {"comparable": True, "reason": None, "rows": [],
           "regressions": [], "skipped": []}
    if new.get("suite") != old.get("suite"):
        out["comparable"] = False
        out["reason"] = (f"suite mismatch: new={new.get('suite')!r} vs "
                         f"old={old.get('suite')!r} (scenario "
                         f"definitions differ — no fair compare)")
        return out
    sn, so = new["scenarios"], old["scenarios"]
    out["skipped"] = sorted(set(sn) ^ set(so))
    for name in sorted(set(sn) & set(so)):
        for field in ("cut_ratio", "balance"):
            a, b = sn[name].get(field), so[name].get(field)
            if not isinstance(a, (int, float)) \
                    or not isinstance(b, (int, float)):
                continue
            rel = (a - b) / abs(b) if b else None
            row = {"scenario": name, "field": field, "old": b, "new": a,
                   "rel_change": round(rel, 4) if rel is not None
                   else None}
            regressed = (a > b) if rel is None else rel > threshold
            row["verdict"] = "REGRESSION" if regressed else "ok"
            if regressed:
                out["regressions"].append(row)
            out["rows"].append(row)
    return out


def find_latest_pair(pattern: str):
    files = sorted(glob.glob(pattern))
    if len(files) < 2:
        return None
    return files[-1], files[-2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Quality CI gate: sweep fixed scenarios and flag "
                    "cut/balance regressions between QUALITY artifacts.")
    ap.add_argument("new", nargs="?", default=None,
                    help="newer artifact (default: latest QUALITY_*.json)")
    ap.add_argument("old", nargs="?", default=None,
                    help="older artifact (default: second-latest)")
    ap.add_argument("--run", default=None, metavar="OUT.json",
                    help="run the scenario sweep, write the artifact, "
                         "exit (no compare)")
    ap.add_argument("--scenarios", default=None, metavar="A,B",
                    help="with --run: comma list of scenario names "
                         "(default: all)")
    ap.add_argument("--backend", default=None,
                    help="with --run: partitioner backend (default: "
                         "best native available; results are "
                         "backend-invariant by the cross-backend "
                         "equality contract)")
    ap.add_argument("--threshold", type=float, default=0.02,
                    help="relative rise tolerated in cut_ratio/balance "
                         "before a scenario regresses (default 0.02 — "
                         "the sweep is deterministic, so the gate runs "
                         "tight)")
    ap.add_argument("--glob", default=None,
                    help="artifact pattern for auto-discovery "
                         "(default: QUALITY_*.json next to this repo)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if args.run:
        if args.new or args.old:
            ap.error("--run does not take NEW/OLD positionals")
        # quality runs are platform-invariant: they run on the CPU and
        # leave the chip to whatever else needs it
        from sheep_tpu.utils.platform import pin_platform

        pin_platform(os.environ.get("SHEEP_QUALITY_PLATFORM") or "cpu")
        names = set(args.scenarios.split(",")) if args.scenarios else None
        doc = run_sweep(args.run, names=names, backend=args.backend)
        return 2 if doc.get("bound_exceeded") else 0

    if (args.new is None) != (args.old is None):
        ap.error("pass both NEW and OLD, or neither (auto-discovery)")
    if args.new is None:
        pattern = args.glob or os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "QUALITY_*.json")
        pair = find_latest_pair(pattern)
        if pair is None:
            print(f"error: need >= 2 artifacts matching {pattern}",
                  file=sys.stderr)
            return 1
        args.new, args.old = pair

    new, err = load_artifact(args.new)
    if err:
        print(f"error: {err}", file=sys.stderr)
        return 1
    old, err = load_artifact(args.old)
    if err:
        print(f"error: {err}", file=sys.stderr)
        return 1
    res = compare(new, old, args.threshold)

    if args.json:
        json.dump({"new": args.new, "old": args.old,
                   "threshold": args.threshold, **res},
                  sys.stdout, indent=1)
        print()
    else:
        print(f"new: {args.new}")
        print(f"old: {args.old}")
        if not res["comparable"]:
            print(f"not comparable: {res['reason']}")
            print("verdict: PASS (vacuous — nothing gated)")
            return 0
        print(f"{'scenario':<16}{'field':<11}{'old':>10}{'new':>10}"
              f"{'change':>9}  verdict")
        for row in res["rows"]:
            change = (f"{100 * row['rel_change']:>8.2f}%"
                      if row["rel_change"] is not None else f"{'n/a':>9}")
            print(f"{row['scenario']:<16}{row['field']:<11}"
                  f"{row['old']:>10.4f}{row['new']:>10.4f}{change}"
                  f"  {row['verdict']}")
        if res["skipped"]:
            print(f"skipped-incomparable: {', '.join(res['skipped'])}")
        if res["regressions"]:
            names = ", ".join(f"{r['scenario']}.{r['field']}"
                              for r in res["regressions"])
            print(f"verdict: QUALITY REGRESSION beyond "
                  f"{args.threshold:.0%} in: {names}")
        else:
            print(f"verdict: PASS (no scenario moved beyond "
                  f"{args.threshold:.0%})")
    if not res["comparable"]:
        return 0
    return 2 if res["regressions"] else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # |head et al. closing stdout is not an error
        sys.exit(0)
