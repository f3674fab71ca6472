#!/usr/bin/env python
"""Compare two bench contract captures and flag perf regressions —
the start of a perf-CI gate.

    python tools/bench_regress.py                      # latest two BENCH_*.json
    python tools/bench_regress.py NEW.json OLD.json    # explicit pair
    python tools/bench_regress.py --threshold 0.10

Accepts either shape on both sides: a driver-written ``BENCH_*.json``
artifact (``{"n": ..., "parsed": {contract line}}``) or a raw bench.py
output line/file (``{"metric": ..., "value": ...}``). Gated fields,
each compared only when present in BOTH captures:

    value, vs_baseline                higher is better (relative drop
                                      beyond --threshold regresses)
    host_syncs, device_rounds,        lower is better (relative rise
    host_blocked_ms, h2d_blocked_ms,  beyond --threshold regresses —
    update_request_s,                 the resident-partition delta-fold
                                      wall (ISSUE 15), split since
    update_fold_s, update_score_s,    ISSUE 17 into the device fold vs
                                      the O(Δ) scored refresh (its
                                      epoch_scale_x2 probe rides
                                      info-only);
    sharded_update_request_s          the same scored epoch through the
                                      multi-device lockstep fold +
                                      distributed rescore (ISSUE 19);
    warm_up_s, warm_request_s,        warm_up_s is the cold-request jit
                                      tax and warm_request_s the warm
                                      served-request wall — the pair
                                      the sheepd server mode amortizes
                                      (ISSUE 10); a rise in either is a
                                      warm-path latency regression;
    dispatch_retries                  dispatch counts are deterministic,
                                      so a rise is a real scheduling
                                      change, not noise; host_blocked_ms
                                      is the dispatch pipeline's
                                      host-stall wall, the quantity the
                                      in-flight overlap exists to
                                      shrink; dispatch_retries is the
                                      fault-tolerance layer's
                                      graceful-degradation count — a
                                      healthy capture has 0, and any
                                      movement off 0 is gated
                                      absolutely)

Degradation info fields (never gated, always reported):
``degraded_dispatch_batch`` / ``degraded_inflight`` (the reduced knobs
after an OOM backoff), ``device_loss_recoveries``, and
``checkpoint_degraded`` (lossy checkpoint recoveries) — environmental
consequences that must be VISIBLE in the perf trajectory without
false-alarming the gate.

Contract fields present in exactly ONE capture compare nothing; they
are listed on a ``skipped-incomparable: <names>`` line (and in the
``skipped`` JSON field) so a cpu-jax fallback capture — which emits
fewer fields than a real-chip one — reads as the PARTIAL pass it is,
not a full-coverage green.

device_gap_ms (device idle between executions — collapses with
pipelining but swings with host load) is environmental and reported
but never gated. Two captures whose ``metric`` strings differ
(different RMAT scale or platform — e.g. a cpu-jax row vs a real-chip
row) are NOT comparable: the tool says so and exits 0 unless
``--force``, because a false regression alarm that fires on every
platform change would get the gate deleted within a week.

Exit codes: 0 pass (or not comparable), 1 usage/IO error,
2 regression detected.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

HIGHER_BETTER = ("value", "vs_baseline")
# host_blocked_ms is wall-derived (like value) and so can swing with
# link quality within one platform — gated anyway per the contract: a
# sustained rise is the dispatch pipeline regressing, and same-metric
# comparison plus the threshold absorb ordinary swings.
# dispatch_retries (ISSUE 9) gates graceful degradation: a healthy
# capture retries 0 times, so ANY rise (0 -> N is gated absolutely by
# the old==0 rule below) means the bench survived faults it used to
# not have — visible, not silent.
# h2d_blocked_ms (ISSUE 12) is the staged-ring underrun wall — the
# synchronous-upload tax the ring removed; a healthy depth>=2 capture
# holds it near 0, and the old==0 absolute rule below gates any
# reappearance. On the timed leg's device-stream input it is exactly 0
# (zero host bytes per chunk).
# update_request_s (ISSUE 15) is the resident-partition delta-fold
# wall — the O(Δ) promise of the incremental subsystem, gated with
# the warm_request_s convention (a rise is the update path slowing);
# its companion `compactions` count is info-only below (compactions
# are workload consequences, not regressions).
# update_fold_s / update_score_s (ISSUE 17) split that wall: the
# device delta-fold vs the scored refresh. update_score_s is THE
# number incremental scoring exists for — O(Δ) accounting holds it
# flat where full rescoring pays O(edges) per epoch — so both halves
# gate lower-better; their epoch_scale_x2 companion (scored-epoch
# wall on a 2x base, ~1.0 when the O(Δ) path holds) rides info-only
# as a property probe, not a perf series.
# cached_request_s (ISSUE 16) is the content-addressed result-store
# answer wall — a repeat submit served with zero build steps; its
# contract bar is >= 10x under warm_request_s, so a rise means the
# store read/decode path itself is slowing, gated like the warm path.
# sharded_update_request_s (ISSUE 19) is the same scored delta epoch
# through the multi-device lockstep fold + distributed rescore — the
# per-epoch cost of a resident SHARDED partition; gated lower-better
# with the update_request_s convention.
# oocore_request_s (ISSUE 20) is the build wall under a residency
# budget clamped to ~half the modeled working set — the price of
# running out-of-core (evict + reload through the disk tier); a rise
# means the spill/reload path is slowing, gated lower-better. Its
# spill_* companions describe the constraint (how much was evicted /
# re-uploaded / held resident), not a perf series — info-only below.
LOWER_BETTER = ("host_syncs", "device_rounds", "host_blocked_ms",
                "h2d_blocked_ms", "dispatch_retries", "warm_up_s",
                "warm_request_s", "cached_request_s",
                "update_request_s", "update_fold_s",
                "update_score_s", "sharded_update_request_s",
                "oocore_request_s")
# degraded_* and checkpoint_degraded are consequences of faults the
# environment injected, not regressions of the code under test — they
# ride as info so the degradation is VISIBLE in the perf trajectory
# while only the retry count itself gates
INFO_ONLY = ("dispatch_batch",
             "inflight_depth", "inflight_discards", "device_gap_ms",
             "h2d_staged_ms", "h2d_staged_bytes", "h2d_ring_depth",
             "device_stream_chunks",
             "degraded_dispatch_batch", "degraded_inflight",
             "degraded_h2d_ring",
             "device_loss_recoveries", "checkpoint_degraded",
             "cold_request_s", "compactions", "epoch_scale_x2",
             "spill_evictions", "spill_reload_bytes",
             "spill_resident_bytes")


def load_capture(path: str):
    """Contract-line dict from either artifact shape, or None with a
    reason string."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError as e:
        return None, f"cannot read {path}: {e}"
    line = None
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        # bench.py stdout style: JSONL, contract line last
        for raw in reversed(text.splitlines()):
            raw = raw.strip()
            if not raw:
                continue
            try:
                cand = json.loads(raw)
            except json.JSONDecodeError:
                continue
            if isinstance(cand, dict) and "value" in cand:
                line = cand
                break
        if line is None:
            return None, f"{path}: no parseable JSON contract line"
        return line, None
    if isinstance(doc, dict) and "parsed" in doc:
        line = doc["parsed"]
        if not isinstance(line, dict):
            return None, f"{path}: driver artifact has parsed=null " \
                         f"(the bench run produced no contract line)"
        return line, None
    if isinstance(doc, dict) and "value" in doc:
        return doc, None
    return None, f"{path}: unrecognized capture shape"


def compare(new: dict, old: dict, threshold: float) -> dict:
    """{"comparable": bool, "rows": [...], "regressions": [...],
    "skipped": [...]} — ``skipped`` lists contract fields present in
    exactly ONE capture (a cpu-jax fallback run emits fewer fields
    than a real-chip one): those comparisons are vacuous, and a
    vacuous pass that LOOKS like a full pass hides exactly the partial
    coverage it came from, so the caller prints them."""
    out = {"comparable": True, "reason": None, "rows": [],
           "regressions": [], "skipped": []}
    nm, om = new.get("metric"), old.get("metric")
    if nm != om:
        out["comparable"] = False
        out["reason"] = (f"metric mismatch: new={nm!r} vs old={om!r} "
                         f"(different scale/platform — no fair compare)")
        return out
    if not new.get("value") or not old.get("value"):
        out["comparable"] = False
        out["reason"] = "one capture has value 0/null (a failed run)"
        return out

    def _num(v):
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    for field in HIGHER_BETTER + LOWER_BETTER + INFO_ONLY:
        a, b = new.get(field), old.get(field)
        if not isinstance(a, (int, float)) or not isinstance(b, (int, float)):
            if _num(a) != _num(b):
                out["skipped"].append(field)
            continue
        # old == 0: no relative change exists, but ANY movement off zero
        # is gated absolutely — host_syncs 0 -> 500 must not pass just
        # because the ratio is undefined
        rel = (a - b) / abs(b) if b else None
        worse = (a < b) if field in HIGHER_BETTER else (a > b)
        row = {"field": field, "old": b, "new": a,
               "rel_change": round(rel, 4) if rel is not None else None,
               "gated": field not in INFO_ONLY}
        regressed = worse if rel is None else (
            rel < -threshold if field in HIGHER_BETTER
            else rel > threshold)
        if field in INFO_ONLY:
            row["verdict"] = "info"
        elif regressed:
            row["verdict"] = "REGRESSION"
            out["regressions"].append(row)
        else:
            row["verdict"] = "ok"
        out["rows"].append(row)
    return out


def find_latest_pair(pattern: str):
    files = sorted(glob.glob(pattern))
    if len(files) < 2:
        return None
    return files[-1], files[-2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Flag bench-contract regressions between two "
                    "captures (perf-CI gate).")
    ap.add_argument("new", nargs="?", default=None,
                    help="newer capture (default: latest BENCH_*.json)")
    ap.add_argument("old", nargs="?", default=None,
                    help="older capture (default: second-latest)")
    ap.add_argument("--threshold", type=float, default=0.15,
                    help="relative change tolerated before a gated "
                         "field regresses (default 0.15)")
    ap.add_argument("--glob", default=None,
                    help="artifact pattern for auto-discovery "
                         "(default: BENCH_*.json next to this repo)")
    ap.add_argument("--force", action="store_true",
                    help="gate even when the metric strings differ")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if (args.new is None) != (args.old is None):
        ap.error("pass both NEW and OLD, or neither (auto-discovery)")
    if args.new is None:
        pattern = args.glob or os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_*.json")
        pair = find_latest_pair(pattern)
        if pair is None:
            print(f"error: need >= 2 artifacts matching {pattern}",
                  file=sys.stderr)
            return 1
        args.new, args.old = pair

    new, err = load_capture(args.new)
    if err:
        print(f"error: {err}", file=sys.stderr)
        return 1
    old, err = load_capture(args.old)
    if err:
        print(f"error: {err}", file=sys.stderr)
        return 1

    res = compare(new, old, args.threshold)
    if args.force and not res["comparable"]:
        forced_reason = res["reason"]
        new2 = dict(new)
        old2 = dict(old)
        new2["metric"] = old2["metric"] = "(forced)"
        new2["value"] = new2.get("value") or 1e-12
        old2["value"] = old2.get("value") or 1e-12
        res = compare(new2, old2, args.threshold)
        res["reason"] = f"forced compare despite: {forced_reason}"

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
        if res.get("reason"):
            print(f"note: {res['reason']}")
        print(f"{'field':<16}{'old':>14}{'new':>14}{'change':>10}  verdict")
        for row in res["rows"]:
            change = (f"{100 * row['rel_change']:>9.1f}%"
                      if row["rel_change"] is not None else f"{'n/a':>10}")
            print(f"{row['field']:<16}{row['old']:>14,.3f}"
                  f"{row['new']:>14,.3f}{change}"
                  f"  {row['verdict']}")
        if res.get("skipped"):
            # fields one capture lacks compared nothing — say so, or a
            # cpu-jax fallback run reads as a full-coverage pass
            print(f"skipped-incomparable: {', '.join(res['skipped'])}")
        if res["regressions"]:
            names = ", ".join(r["field"] for r in res["regressions"])
            print(f"verdict: REGRESSION beyond {args.threshold:.0%} "
                  f"in: {names}")
        else:
            print(f"verdict: PASS (no gated field moved beyond "
                  f"{args.threshold:.0%})")
    if not res["comparable"]:
        return 0
    return 2 if res["regressions"] else 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # |head et al. closing stdout is not an error
        sys.exit(0)
