"""tools/trace_report.py + tools/bench_regress.py (ISSUE 3 toolchain):
golden render, well-formedness checks, dispatch attribution, regression
gate pass/fail."""

import importlib.util
import io
import json
import os
import sys
from contextlib import redirect_stdout

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOLDEN = os.path.join(REPO, "tests", "golden")


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


trace_report = _load_tool("trace_report")
bench_regress = _load_tool("bench_regress")


def _run_report(argv):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = trace_report.main(argv)
    return rc, buf.getvalue()


# -- trace_report ----------------------------------------------------------

def test_trace_report_golden():
    """Pinned render of a recorded trace: self/total decomposition,
    x-count aggregation, counter deltas net of children, heartbeat and
    final-counter summaries. The golden path is relative, so run with
    the repo-relative path the fixture was recorded with."""
    rel = os.path.join("tests", "golden", "trace_small.jsonl")
    cwd = os.getcwd()
    os.chdir(REPO)
    try:
        rc, out = _run_report([rel, "--check"])
    finally:
        os.chdir(cwd)
    assert rc == 0
    expect = open(os.path.join(GOLDEN, "trace_report.txt")).read()
    assert out == expect


def test_trace_report_json_tree_structure():
    rc, out = _run_report(
        [os.path.join(GOLDEN, "trace_small.jsonl"), "--json"])
    assert rc == 0
    doc = json.loads(out)
    t = doc["traces"][0]
    assert t["heartbeats"] == 2 and not t["unclosed"]
    run = t["spans"][0]
    assert run["name"] == "run" and run["total_s"] == 4.6
    part = run["children"][0]
    build = next(c for c in part["children"] if c["name"] == "build")
    seg = build["children"][0]
    assert seg["count"] == 2 and seg["total_s"] == 3.0
    assert seg["counters"] == {"device_rounds": 22, "host_syncs": 2}
    # build's self-delta nets out its children's counters entirely
    assert build["counters"] == {}
    assert abs(build["self_s"] - 0.2) < 1e-9


def test_trace_report_appended_runs_not_merged(tmp_path):
    """--trace appends and span ids restart per run: a two-run file must
    report the LAST run (with an n_runs note), never merge both trees
    under colliding ids (review finding)."""
    src = open(os.path.join(GOLDEN, "trace_small.jsonl")).read()
    p = str(tmp_path / "two.jsonl")
    open(p, "w").write(src + src)  # rerun appended to the same file
    rc, out = _run_report([p, "--check"])
    assert rc == 0, "each run alone is complete; no merge corruption"
    assert "holds 2 appended runs" in out
    rc, out = _run_report([p, "--json"])
    t = json.loads(out)["traces"][0]
    assert t["n_runs"] == 2 and not t["unclosed"]
    run = t["spans"][0]
    assert run["count"] == 1 and run["total_s"] == 4.6, \
        "one run's tree, not two runs summed"


def test_trace_report_deferred_manifest_not_split(tmp_path):
    """Multi-host CLI traces open the root span BEFORE the manifest
    (deferred until after jax.distributed.initialize): that ordering is
    ONE run, not two — splitting there orphaned the root's span_end and
    mis-reported a valid trace as malformed (review finding)."""
    p = str(tmp_path / "mh.jsonl")
    with open(p, "w") as f:
        for rec in [
            {"event": "span_start", "ts": 1.0, "span": "run", "id": 1,
             "parent": None},
            {"event": "manifest", "ts": 1.2, "backend": "tpu-sharded"},
            {"event": "span_start", "ts": 1.3, "span": "partition",
             "id": 2, "parent": 1},
            {"event": "span_end", "ts": 2.0, "span": "partition", "id": 2,
             "parent": 1, "secs": 0.7},
            {"event": "heartbeat", "ts": 2.0, "seq": 0, "final": True},
            {"event": "span_end", "ts": 2.1, "span": "run", "id": 1,
             "parent": None, "secs": 1.1},
        ]:
            f.write(json.dumps(rec) + "\n")
    rc, out = _run_report([p, "--check"])
    assert rc == 0, out
    assert "appended runs" not in out and "UNCLOSED" not in out


def test_trace_report_appended_runs_keep_their_manifest(tmp_path):
    """When a DEAD run (unclosed spans) is rerun into the same file, the
    second run's manifest precedes its first span; the split on span-id
    collision must carry that manifest into the new segment."""
    p = str(tmp_path / "dead_then_ok.jsonl")
    dead = [
        {"event": "manifest", "ts": 1.0, "backend": "tpu", "git_sha": "a"},
        {"event": "span_start", "ts": 1.0, "span": "run", "id": 1,
         "parent": None},
    ]
    ok = [
        {"event": "manifest", "ts": 9.0, "backend": "tpu", "git_sha": "b"},
        {"event": "span_start", "ts": 9.1, "span": "run", "id": 1,
         "parent": None},
        {"event": "span_end", "ts": 9.9, "span": "run", "id": 1,
         "parent": None, "secs": 0.8},
        {"event": "heartbeat", "ts": 9.9, "seq": 0, "final": True},
    ]
    with open(p, "w") as f:
        for rec in dead + ok:
            f.write(json.dumps(rec) + "\n")
    rc, out = _run_report([p, "--json"])
    assert rc == 0
    t = json.loads(out)["traces"][0]
    assert t["n_runs"] == 2 and t["manifest"]["git_sha"] == "b"
    assert not t["unclosed"] and not t["check_failures"]


def test_trace_report_flags_unclosed_spans(tmp_path):
    """A killed run leaves span_starts without ends; the report must
    say so (that is the dead-vs-slow distinction) and --check must
    fail."""
    p = str(tmp_path / "dead.jsonl")
    with open(p, "w") as f:
        f.write(json.dumps({"event": "manifest", "ts": 10.0}) + "\n")
        f.write(json.dumps({"event": "span_start", "ts": 10.0,
                            "span": "build", "id": 1,
                            "parent": None}) + "\n")
        f.write(json.dumps({"event": "heartbeat", "ts": 55.0,
                            "seq": 0}) + "\n")
    rc, out = _run_report([p])
    assert rc == 0 and "UNCLOSED" in out and "45.0" in out
    rc, _ = _run_report([p, "--check"])
    assert rc == 3


def test_trace_report_orphan_end_is_malformed(tmp_path):
    p = str(tmp_path / "bad.jsonl")
    with open(p, "w") as f:
        f.write(json.dumps({"event": "span_end", "ts": 1.0, "span": "x",
                            "id": 9, "secs": 1.0}) + "\n")
    rc, _ = _run_report([p])
    assert rc == 2


def test_trace_report_tolerates_truncated_last_line(tmp_path):
    src = open(os.path.join(GOLDEN, "trace_small.jsonl")).read()
    p = str(tmp_path / "cut.jsonl")
    open(p, "w").write(src + '{"event": "span_start", "ts": 99')
    rc, out = _run_report([p, "--check"])
    assert rc == 0 and "warning" not in out


def _mini_trace(path, wall_s, syncs, rounds):
    with open(path, "w") as f:
        for rec in [
            {"event": "manifest", "ts": 0.0},
            {"event": "span_start", "ts": 0.0, "span": "build", "id": 1,
             "parent": None},
            {"event": "span_end", "ts": wall_s, "span": "build", "id": 1,
             "parent": None, "secs": wall_s},
            {"event": "counters", "ts": wall_s, "host_syncs": syncs,
             "device_rounds": rounds},
        ]:
            f.write(json.dumps(rec) + "\n")


def test_trace_report_dispatch_attribution(tmp_path):
    """Two traces at different dispatch mixes solve the 2x2 count x
    round-cost system exactly: A(10s, 8 syncs, 20 rounds) and
    B(7s, 2 syncs, 20 rounds) -> 0.5 s/dispatch, 0.3 s/round."""
    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    _mini_trace(a, 10.0, 8, 20)
    _mini_trace(b, 7.0, 2, 20)
    rc, out = _run_report([a, b, "--json"])
    assert rc == 0
    att = json.loads(out)["attribution"]
    assert att["per_dispatch_s"] == pytest.approx(0.5)
    assert att["per_round_s"] == pytest.approx(0.3)


def test_trace_report_attribution_degenerate(tmp_path):
    a, b = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    _mini_trace(a, 10.0, 8, 20)
    _mini_trace(b, 5.0, 8, 20)  # same mix: nothing to attribute
    rc, out = _run_report([a, b, "--json"])
    assert rc == 0 and json.loads(out)["attribution"] is None


# -- bench_regress ---------------------------------------------------------

BASE = {"metric": "edges/sec partitioned (RMAT-20, k=64, tpu vs CPU)",
        "value": 1.0e6, "unit": "edges/sec", "vs_baseline": 2.0,
        "host_syncs": 10, "device_rounds": 40, "device_gap_ms": 5.0}


def _write(tmp_path, name, doc):
    p = str(tmp_path / name)
    json.dump(doc, open(p, "w"))
    return p


def test_bench_regress_pass(tmp_path):
    old = _write(tmp_path, "old.json", {"n": 1, "parsed": BASE})
    new = _write(tmp_path, "new.json",
                 {**BASE, "value": 1.05e6, "device_gap_ms": 50.0})
    rc = bench_regress.main([new, old, "--threshold", "0.15"])
    assert rc == 0, "faster run + environmental idle swing is a pass"


def test_bench_regress_detects_value_drop(tmp_path):
    old = _write(tmp_path, "old.json", BASE)
    new = _write(tmp_path, "new.json", {**BASE, "value": 0.7e6})
    assert bench_regress.main([new, old, "--threshold", "0.15"]) == 2
    # same drop passes a looser gate
    assert bench_regress.main([new, old, "--threshold", "0.40"]) == 0


def test_bench_regress_detects_dispatch_count_rise(tmp_path):
    old = _write(tmp_path, "old.json", BASE)
    new = _write(tmp_path, "new.json", {**BASE, "host_syncs": 30})
    assert bench_regress.main([new, old]) == 2


def test_bench_regress_gates_host_blocked_ms(tmp_path):
    """The dispatch-overlap contract field (ISSUE 4): host_blocked_ms
    is gated higher-is-worse like host_syncs; device_gap_ms is
    environmental (link-quality-coupled) and never gates."""
    old = _write(tmp_path, "old.json",
                 {**BASE, "host_blocked_ms": 100.0, "device_gap_ms": 10.0})
    new = _write(tmp_path, "new.json",
                 {**BASE, "host_blocked_ms": 200.0, "device_gap_ms": 10.0})
    assert bench_regress.main([new, old]) == 2
    drop = _write(tmp_path, "drop.json",
                  {**BASE, "host_blocked_ms": 40.0, "device_gap_ms": 10.0})
    assert bench_regress.main([drop, old]) == 0
    gap = _write(tmp_path, "gap.json",
                 {**BASE, "host_blocked_ms": 100.0,
                  "device_gap_ms": 900.0})
    assert bench_regress.main([gap, old]) == 0


def test_bench_regress_gates_warm_path(tmp_path):
    """The warm-vs-cold served-request contract (ISSUE 10): warm_up_s
    (the cold jit tax) and warm_request_s (the warm served wall) gate
    lower-is-better like host_blocked_ms; cold_request_s rides as info
    (it is warm_up_s under another name — double-gating one quantity
    would double-alarm one regression)."""
    old = _write(tmp_path, "old.json",
                 {**BASE, "warm_up_s": 10.0, "warm_request_s": 6.0,
                  "cold_request_s": 10.0})
    slow_warm = _write(tmp_path, "slow_warm.json",
                       {**BASE, "warm_up_s": 10.0,
                        "warm_request_s": 9.0, "cold_request_s": 10.0})
    assert bench_regress.main([slow_warm, old]) == 2
    slow_cold = _write(tmp_path, "slow_cold.json",
                       {**BASE, "warm_up_s": 20.0,
                        "warm_request_s": 6.0, "cold_request_s": 20.0})
    assert bench_regress.main([slow_cold, old]) == 2
    ok = _write(tmp_path, "ok.json",
                {**BASE, "warm_up_s": 9.0, "warm_request_s": 5.5,
                 "cold_request_s": 9.0})
    assert bench_regress.main([ok, old]) == 0


def test_bench_regress_rise_from_zero_is_gated(tmp_path):
    """old host_syncs == 0 has no relative change, but 0 -> 500 is a
    real scheduling regression and must not slip through the undefined
    ratio (review finding)."""
    old = _write(tmp_path, "old.json", {**BASE, "host_syncs": 0})
    new = _write(tmp_path, "new.json", {**BASE, "host_syncs": 500})
    assert bench_regress.main([new, old]) == 2
    same = _write(tmp_path, "same.json", {**BASE, "host_syncs": 0})
    assert bench_regress.main([same, old]) == 0


def test_bench_regress_gates_dispatch_retries(tmp_path):
    """ISSUE 9 contract: dispatch_retries is higher-is-worse. A healthy
    capture has 0, so any movement off zero gates absolutely (the
    old==0 rule); the degradation info fields report but never gate."""
    old = _write(tmp_path, "old.json", {**BASE, "dispatch_retries": 0})
    new = _write(tmp_path, "new.json", {**BASE, "dispatch_retries": 3})
    assert bench_regress.main([new, old]) == 2
    same = _write(tmp_path, "same.json", {**BASE, "dispatch_retries": 0})
    assert bench_regress.main([same, old]) == 0


def test_bench_regress_degradation_fields_are_info_only(tmp_path):
    """degraded_dispatch_batch / device_loss_recoveries /
    checkpoint_degraded are consequences of environmental faults, not
    code regressions: visible in the rows, never gating."""
    old = _write(tmp_path, "old.json",
                 {**BASE, "degraded_dispatch_batch": 8,
                  "device_loss_recoveries": 0,
                  "checkpoint_degraded": 0})
    new = _write(tmp_path, "new.json",
                 {**BASE, "degraded_dispatch_batch": 1,
                  "device_loss_recoveries": 2,
                  "checkpoint_degraded": 1})
    assert bench_regress.main([new, old]) == 0
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        bench_regress.main([new, old])
    out = buf.getvalue()
    assert "degraded_dispatch_batch" in out and "info" in out


def test_bench_regress_skipped_incomparable_fields_reported(tmp_path):
    """ISSUE 13 satellite: a field present in exactly one capture (the
    cpu-jax fallback emits fewer contract fields than a real-chip run)
    compares NOTHING — the pass must say so instead of reading as full
    coverage."""
    old = _write(tmp_path, "old.json",
                 {**BASE, "host_blocked_ms": 120.0, "warm_up_s": 9.0})
    new = _write(tmp_path, "new.json", BASE)  # fallback: fields absent
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_regress.main([new, old])
    out = buf.getvalue()
    assert rc == 0
    assert "skipped-incomparable: host_blocked_ms, warm_up_s" in out
    # json shape carries them too
    buf = io.StringIO()
    with redirect_stdout(buf):
        bench_regress.main([new, old, "--json"])
    doc = json.loads(buf.getvalue())
    assert doc["skipped"] == ["host_blocked_ms", "warm_up_s"]
    # fields absent from BOTH captures are not "skipped" — there was
    # nothing to compare and nothing partial about it
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = bench_regress.main([_write(tmp_path, "n2.json", BASE),
                                 _write(tmp_path, "o2.json", BASE)])
    assert rc == 0 and "skipped-incomparable" not in buf.getvalue()


def test_bench_regress_incomparable_metrics_pass(tmp_path):
    """A cpu-jax fallback row must never false-alarm against a real
    accelerator row — different metric strings are vacuously PASS."""
    old = _write(tmp_path, "old.json", BASE)
    new = _write(tmp_path, "new.json",
                 {**BASE, "metric": "edges/sec (RMAT-18, k=64, cpu)",
                  "value": 100.0})
    assert bench_regress.main([new, old]) == 0


def test_bench_regress_null_parsed_is_error(tmp_path):
    old = _write(tmp_path, "old.json", {"n": 1, "parsed": None})
    new = _write(tmp_path, "new.json", BASE)
    assert bench_regress.main([new, old]) == 1


def test_bench_regress_raw_jsonl_capture(tmp_path):
    """bench.py stdout shape (stderr noise + one contract line) loads
    too."""
    p = str(tmp_path / "raw.json")
    with open(p, "w") as f:
        f.write("some stderr-ish noise\n")
        f.write(json.dumps(BASE) + "\n")
    old = _write(tmp_path, "old.json", BASE)
    assert bench_regress.main([p, old]) == 0
