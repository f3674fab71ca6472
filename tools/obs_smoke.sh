#!/usr/bin/env bash
# obs smoke: the observability acceptance gate, small enough for tier-1.
#
# Runs a tiny RMAT build through the real CLI with tracing + heartbeat
# on, then asserts (via trace_report --check) that the trace parses and
# contains a manifest, a COMPLETE span tree (every start has its end,
# parents intact), and >= 1 heartbeat. Wired as a fast tier-1 test by
# tests/test_obs_smoke.py.
#
# Usage: tools/obs_smoke.sh [OUT_DIR]   (default: a fresh mktemp dir)
set -euo pipefail

cd "$(dirname "$0")/.."
OUT="${1:-$(mktemp -d /tmp/sheep_obs_smoke.XXXXXX)}"
mkdir -p "$OUT"
TRACE="$OUT/trace.jsonl"
rm -f "$TRACE"

# pure backend: no device warm-up, runs in seconds on any host; the
# heartbeat's final flush guarantees >= 1 record even this fast
JAX_PLATFORMS=cpu python -m sheep_tpu.cli \
    --input rmat:10:8:1 --k 4 --backend pure \
    --trace "$TRACE" --heartbeat-secs 0.2 --json \
    > "$OUT/result.json"

# the gate: parseable + manifest + complete span tree + >= 1 heartbeat
python tools/trace_report.py "$TRACE" --check > "$OUT/report.txt"

# and the render is non-trivial: the tree shows the partition phases
grep -q "partition" "$OUT/report.txt"
grep -q "heartbeats:" "$OUT/report.txt"

# second leg: the in-flight dispatch pipeline (ISSUE 4) through the tpu
# backend on cpu-jax — the traced smoke must show a complete span tree
# with the pipelined dispatch spans AND the overlap counters
# (host_blocked_ms / device_gap_ms) flowing into the trace
TRACE2="$OUT/trace_inflight.jsonl"
rm -f "$TRACE2"
JAX_PLATFORMS=cpu python -m sheep_tpu.cli \
    --input rmat:10:8:1 --k 4 --backend tpu \
    --dispatch-batch 2 --inflight 2 --chunk-edges 1024 \
    --trace "$TRACE2" --heartbeat-secs 0.2 --json \
    > "$OUT/result_inflight.json"
python tools/trace_report.py "$TRACE2" --check > "$OUT/report_inflight.txt"
grep -q "dispatch" "$OUT/report_inflight.txt"
grep -q "host_blocked_ms" "$TRACE2"
grep -q "inflight_depth" "$TRACE2"

# third leg: the same pipelined build under SHEEP_SANITIZE=1 (ISSUE 6)
# — stray-sync traps armed around the dispatch chain, donation
# poisoning checks live, span balance asserted at tracer close. A
# stray int()/bool() on a device value anywhere in the fold/dispatch
# path, a silently dropped donation, or a leaked span fails this leg.
TRACE3="$OUT/trace_sanitized.jsonl"
rm -f "$TRACE3"
JAX_PLATFORMS=cpu SHEEP_SANITIZE=1 python -m sheep_tpu.cli \
    --input rmat:10:8:1 --k 4 --backend tpu \
    --dispatch-batch 2 --inflight 2 --chunk-edges 1024 \
    --trace "$TRACE3" --heartbeat-secs 0.2 --json \
    > "$OUT/result_sanitized.json"
python tools/trace_report.py "$TRACE3" --check > "$OUT/report_sanitized.txt"
grep -q "dispatch" "$OUT/report_sanitized.txt"

# fourth leg: production survival (ISSUE 8) — a tiny --k-levels build
# killed at a level boundary by SHEEP_FAULT_INJECT, then resumed from
# its checkpoint into the SAME trace file. The resumed run must pass
# the --check gate and the report must show the resume seam.
TRACE4="$OUT/trace_resume.jsonl"
CKPT4="$OUT/ckpt_resume"
rm -rf "$TRACE4" "$CKPT4"
# native cpu backend when built (no jit warm-up); tpu-on-cpu-jax otherwise
BK=$(JAX_PLATFORMS=cpu python -c \
    "from sheep_tpu import list_backends; bs = list_backends(); \
     print('cpu' if 'cpu' in bs else 'tpu')")
if JAX_PLATFORMS=cpu SHEEP_FAULT_INJECT=level:1 python -m sheep_tpu.cli \
    --input rmat:9:8:1 --k-levels 2,2 --backend "$BK" --refine 1 \
    --chunk-edges 512 --no-comm-volume \
    --checkpoint-dir "$CKPT4" --checkpoint-every 1 \
    --trace "$TRACE4" --heartbeat-secs 0.2 --json \
    > /dev/null 2> "$OUT/fault.err"; then
    echo "fault-injected run unexpectedly succeeded" >&2
    exit 1
fi
JAX_PLATFORMS=cpu python -m sheep_tpu.cli \
    --input rmat:9:8:1 --k-levels 2,2 --backend "$BK" --refine 1 \
    --chunk-edges 512 --no-comm-volume \
    --checkpoint-dir "$CKPT4" --resume \
    --trace "$TRACE4" --heartbeat-secs 0.2 --json \
    > "$OUT/result_resume.json"
python tools/trace_report.py "$TRACE4" --check > "$OUT/report_resume.txt"
grep -q "resume:" "$OUT/report_resume.txt"
grep -q '"event": "resume"' "$TRACE4"

# fifth leg: chaos (ISSUE 9) — one seeded chaos schedule through the
# CLI over a real file stream (so read-error points are live), absorbed
# IN-PROCESS by the retry/degrade layer: the run must exit 0, the trace
# must pass the --check gate, and the injected faults + their handling
# (retry / dispatch_degraded / device_reinit) must be on the record.
# Seed 46 is pinned: on this code's point sequence it injects two read
# faults, both absorbed by the edgestream's bounded retry (read points
# dominate the chaos draw — three passes touch every chunk). The
# OOM-degrade and device-reinit paths are pinned deterministically by
# tests/test_chaos.py instead; the grep below accepts either shape so
# a shifted point sequence only needs a seed with >= 1 absorbed fault.
TRACE5="$OUT/trace_chaos.jsonl"
GRAPH5="$OUT/chaos.bin64"
rm -f "$TRACE5"
JAX_PLATFORMS=cpu python - "$GRAPH5" <<'PYEOF'
import sys
from sheep_tpu.io import formats, generators
formats.write_edges(sys.argv[1], generators.random_graph(512, 4096, seed=7))
PYEOF
JAX_PLATFORMS=cpu SHEEP_FAULT_INJECT=chaos:46:2:0.15 SHEEP_RETRY_BASE_S=0.01 \
    python -m sheep_tpu.cli \
    --input "$GRAPH5" --num-vertices 512 --k 4 --backend tpu \
    --dispatch-batch 2 --inflight 2 --chunk-edges 512 --no-comm-volume \
    --trace "$TRACE5" --heartbeat-secs 0.2 --json \
    > "$OUT/result_chaos.json" 2> "$OUT/chaos.err"
python tools/trace_report.py "$TRACE5" --check > "$OUT/report_chaos.txt"
grep -q '"event": "chaos_inject"' "$TRACE5"
grep -q '"event": "retry"' "$TRACE5"
grep -qE '"event": "(dispatch_degraded|device_reinit)"' "$TRACE5" || \
    grep -q '"kind": "read"' "$TRACE5"

# sixth leg: partition-as-a-service (ISSUE 10) — sheepd on a unix
# socket, two concurrent tiny jobs from different tenants plus a
# multi-k query, one job cancelled mid-flight, clean shutdown. The
# gate: trace_report --check green (zero UNCLOSED spans survive the
# cancel + shutdown paths), per-job span trees + tenant cost rows in
# the report, and the repeat-shape job proving warm program reuse
# (jit_compiles == 0).
TRACE6="$OUT/trace_served.jsonl"
SOCK6="$OUT/sheepd.sock"
rm -f "$TRACE6" "$SOCK6"
JAX_PLATFORMS=cpu python -m sheep_tpu.server.daemon \
    --socket "$SOCK6" --trace "$TRACE6" --heartbeat-secs 0.2 \
    2> "$OUT/sheepd.err" &
SHEEPD_PID=$!
for _ in $(seq 1 100); do [ -S "$SOCK6" ] && break; sleep 0.2; done
[ -S "$SOCK6" ] || { echo "sheepd never bound $SOCK6" >&2; exit 1; }
if ! JAX_PLATFORMS=cpu python - "$SOCK6" > "$OUT/served.json" \
        2> "$OUT/served.err" <<'PYEOF'
import json
import sys

from sheep_tpu.server.client import SheepClient

with SheepClient(sys.argv[1]) as c:
    # two concurrent tenants + a multi-k query on a shared tree
    a = c.submit("rmat:10:8:1", k=4, tenant="alice", chunk_edges=1024)
    b = c.submit("rmat:10:8:2", k=[4, 8], tenant="bob",
                 chunk_edges=1024)
    # third job with many small chunks: cancelled mid-flight — poll
    # until the scheduler actually started stepping it so the cancel
    # exercises the running-job path (generator close -> prefetcher
    # cancel -> span end), not the cheap queued-job dequeue
    import time

    v = c.submit("rmat:12:8:3", k=4, tenant="victim", chunk_edges=512)
    for _ in range(500):
        st = c.status(v["job_id"])
        if st["state"] != "queued" or st["steps"]:
            break
        time.sleep(0.01)
    assert st["state"] == "running", st
    c.cancel(v["job_id"])  # async for running jobs; wait observes it
    cancelled = c.wait(v["job_id"], timeout_s=60)["state"]
    ja = c.wait(a["job_id"], timeout_s=120)
    jb = c.wait(b["job_id"], timeout_s=120)
    # repeat of job a's shape: must reuse every compiled program
    w = c.submit("rmat:10:8:1", k=4, tenant="alice", chunk_edges=1024)
    jw = c.wait(w["job_id"], timeout_s=120)
    assert ja["state"] == "done", ja
    assert jb["state"] == "done" and len(jb["results"]) == 2, jb
    assert cancelled == "cancelled", cancelled
    assert jw["state"] == "done", jw
    assert jw.get("jit_compiles") == 0, \
        f"repeat shape recompiled: {jw.get('jit_compiles')}"
    print(json.dumps({"a": ja["state"], "b": jb["state"],
                      "victim": cancelled,
                      "warm_jit_compiles": jw["jit_compiles"]}))
    c.shutdown()
PYEOF
then
    echo "served smoke client failed:" >&2
    cat "$OUT/served.err" >&2
    kill "$SHEEPD_PID" 2>/dev/null || true
    exit 1
fi
wait "$SHEEPD_PID"
python tools/trace_report.py "$TRACE6" --check > "$OUT/report_served.txt"
grep -q "job:j1" "$OUT/report_served.txt"      # per-job span trees
grep -q "tenant alice:" "$OUT/report_served.txt"   # cost attribution
grep -q "tenant bob:" "$OUT/report_served.txt"
grep -q "state=cancelled" "$OUT/report_served.txt" # the mid-flight cancel
grep -q "jit_compiles=0" "$OUT/report_served.txt"  # warm program reuse

# seventh leg: the live telemetry plane (ISSUE 11) — sheepd with
# --metrics-port under an admission budget sized so the second job
# QUEUES: mid-build the HTTP scrape must show a non-zero queue-depth
# gauge and live per-job progress gauges; after both jobs finish, the
# per-tenant request-latency histogram series; one on-demand `profile`
# capture must land files in the requested directory; and a
# `sheep-submit --watch` submission must render live progress lines.
# Part B: a fault-storm daemon whose job FAILS must leave a
# flight-recorder dump in the trace, rendered by --last-errors.
# Finally sheeplint stays at zero over sheep_tpu + tools (the new
# telemetry modules included).
TRACE7="$OUT/trace_telemetry.jsonl"
SOCK7="$OUT/sheepd_tele.sock"
PROF7="$OUT/profile_capture"
rm -f "$TRACE7" "$SOCK7"
rm -rf "$PROF7"
# budget: 1.1x the BIG job's modeled footprint at dispatch_batch=1 —
# the big job reserves almost all of it, so the small job (~25% of the
# big one's model) queues behind the reservation until release
BUDGET7=$(JAX_PLATFORMS=cpu python -c \
    "from sheep_tpu.utils import membudget; \
     print(int(1.1 * membudget.build_phase_bytes( \
         1 << 12, 512, dispatch_batch=1)['total_bytes']))")
JAX_PLATFORMS=cpu python -m sheep_tpu.server.daemon \
    --socket "$SOCK7" --trace "$TRACE7" --heartbeat-secs 0.2 \
    --metrics-port 0 --budget-bytes "$BUDGET7" \
    2> "$OUT/sheepd_tele.err" &
SHEEPD7_PID=$!
SHEEPD7B_PID=""
# any failure below must not leak a resident daemon holding the
# harness's pipes open (a leaked sheepd turns one failed assert into
# a hung CI job)
trap 'kill $SHEEPD7_PID $SHEEPD7B_PID 2>/dev/null || true' EXIT
for _ in $(seq 1 100); do [ -S "$SOCK7" ] && break; sleep 0.2; done
[ -S "$SOCK7" ] || { echo "telemetry sheepd never bound $SOCK7" >&2; exit 1; }
MPORT7=$(grep -oE 'metrics on http://[^/]+' "$OUT/sheepd_tele.err" \
    | grep -oE '[0-9]+$')
[ -n "$MPORT7" ] || { echo "no metrics port in sheepd stderr" >&2; exit 1; }
if ! JAX_PLATFORMS=cpu python - "$SOCK7" "$MPORT7" "$PROF7" \
        > "$OUT/telemetry.json" 2> "$OUT/telemetry.err" <<'PYEOF'
import json
import sys
import time
import urllib.request

from sheep_tpu.obs.metrics import parse_prometheus
from sheep_tpu.server.client import SheepClient

sock, port, prof_dir = sys.argv[1], sys.argv[2], sys.argv[3]


def scrape():
    url = f"http://127.0.0.1:{port}/metrics"
    return parse_prometheus(
        urllib.request.urlopen(url, timeout=10).read().decode())


with SheepClient(sock) as c:
    # big job fills the budget; small job must queue behind it
    a = c.submit("rmat:12:8:3", k=4, tenant="alice", chunk_edges=512,
                 dispatch_batch=1)
    b = c.submit("rmat:10:8:2", k=4, tenant="bob", chunk_edges=512,
                 dispatch_batch=1)
    for _ in range(500):
        st = c.status(a["job_id"])
        if st["state"] == "running" and st["steps"]:
            break
        time.sleep(0.01)
    mid = scrape()
    assert mid["sheepd_queue_depth"][0][1] >= 1, \
        f"queued job not visible: {mid.get('sheepd_queue_depth')}"
    assert any(lb.get("job") == a["job_id"] and v >= 1
               for lb, v in mid.get("sheepd_job_steps", [])), \
        "no live per-job progress gauge mid-build"
    prof = c.profile(prof_dir, steps=2)
    assert prof["state"] == "armed", prof
    ja = c.wait(a["job_id"], timeout_s=240)
    jb = c.wait(b["job_id"], timeout_s=240)
    assert ja["state"] == "done" and jb["state"] == "done", (ja, jb)
    done = scrape()
    lat = {lb["tenant"]: v for lb, v in
           done.get("sheepd_request_latency_seconds_count", [])}
    assert lat.get("alice") == 1 and lat.get("bob") == 1, lat
    # the metrics VERB answers the same families as the HTTP scrape
    verb = parse_prometheus(c.metrics())
    assert "sheepd_request_latency_seconds_bucket" in verb
    assert c.stats()["profile"]["state"] == "done"
    print(json.dumps({"mid_queue_depth": mid["sheepd_queue_depth"][0][1],
                      "latency_counts": lat}))
PYEOF
then
    echo "telemetry smoke client failed:" >&2
    cat "$OUT/telemetry.err" >&2
    kill "$SHEEPD7_PID" 2>/dev/null || true
    exit 1
fi
# --watch renders live progress lines on stderr, descriptor on stdout
# (small chunk/batch: the job must FIT the deliberately tiny budget)
JAX_PLATFORMS=cpu python -m sheep_tpu.server.client \
    --server "$SOCK7" --input rmat:10:8:1 --k 4 --tenant carol \
    --chunk-edges 512 --dispatch-batch 1 \
    --watch --poll 0.1 > "$OUT/watch.json" 2> "$OUT/watch.err"
grep -qE "running|done" "$OUT/watch.err"
python -c "import json,sys; d=json.load(open(sys.argv[1])); \
    assert d['state']=='done', d" "$OUT/watch.json"
JAX_PLATFORMS=cpu python -m sheep_tpu.server.client \
    --server "$SOCK7" --shutdown > /dev/null
wait "$SHEEPD7_PID"
[ -n "$(find "$PROF7" -type f 2>/dev/null)" ] || {
    echo "profile capture left no files in $PROF7" >&2; exit 1; }
python tools/trace_report.py "$TRACE7" --check > "$OUT/report_tele.txt"
grep -q '"queue_depth"' "$TRACE7"   # heartbeat carries service pressure

# part B: a failed job's flight-recorder dump, rendered by --last-errors
TRACE7B="$OUT/trace_flight.jsonl"
SOCK7B="$OUT/sheepd_flight.sock"
rm -f "$TRACE7B" "$SOCK7B"
JAX_PLATFORMS=cpu SHEEP_FAULT_INJECT=oom@dispatch:1:99 \
    SHEEP_RETRY_MAX=2 SHEEP_RETRY_BASE_S=0.01 \
    python -m sheep_tpu.server.daemon \
    --socket "$SOCK7B" --trace "$TRACE7B" --heartbeat-secs 0.2 \
    2> "$OUT/sheepd_flight.err" &
SHEEPD7B_PID=$!
for _ in $(seq 1 100); do [ -S "$SOCK7B" ] && break; sleep 0.2; done
[ -S "$SOCK7B" ] || { echo "flight sheepd never bound $SOCK7B" >&2; exit 1; }
if JAX_PLATFORMS=cpu python -m sheep_tpu.server.client \
    --server "$SOCK7B" --input rmat:10:8:1 --k 4 --tenant doomed \
    --wait > "$OUT/flight_job.json" 2>&1; then
    echo "fault-storm served job unexpectedly succeeded" >&2
    kill "$SHEEPD7B_PID" 2>/dev/null || true
    exit 1
fi
JAX_PLATFORMS=cpu python -m sheep_tpu.server.client \
    --server "$SOCK7B" --shutdown > /dev/null
wait "$SHEEPD7B_PID"
grep -q '"event": "flight_dump"' "$TRACE7B"
python tools/trace_report.py "$TRACE7B" --last-errors 8 \
    > "$OUT/report_flight.txt"
grep -q "job_failed" "$OUT/report_flight.txt"
grep -q "fault_inject" "$OUT/report_flight.txt"

# eighth leg: zero-copy ingest (ISSUE 12) — (a) a host-format stream
# through the pipelined tpu backend with an explicit staged H2D ring:
# the trace's diagnostics must carry the new ingest counters and the
# ringed result must bit-equal the unringed pipelined run (leg 2's
# result, same rmat: input); (b) a device-generated rmat-hash stream
# (a DIFFERENT generator — no cross-leg score compare; device==host
# bit-equality is pinned by tests/test_h2d_ring.py): zero per-chunk
# host staging bytes on the record.
TRACE8="$OUT/trace_ring.jsonl"
rm -f "$TRACE8"
JAX_PLATFORMS=cpu python -m sheep_tpu.cli \
    --input rmat:10:8:1 --k 4 --backend tpu \
    --dispatch-batch 2 --inflight 2 --h2d-ring 2 --chunk-edges 1024 \
    --trace "$TRACE8" --heartbeat-secs 0.2 --json \
    > "$OUT/result_ring.json"
python tools/trace_report.py "$TRACE8" --check > "$OUT/report_ring.txt"
grep -q '"h2d_staged_ms"' "$TRACE8"
grep -q '"h2d_blocked_ms"' "$TRACE8"
grep -q '"h2d_ring_depth"' "$TRACE8"
TRACE8B="$OUT/trace_devstream.jsonl"
rm -f "$TRACE8B"
JAX_PLATFORMS=cpu python -m sheep_tpu.cli \
    --input rmat-hash:10:8:1 --k 4 --backend tpu \
    --dispatch-batch 2 --inflight 2 --chunk-edges 1024 \
    --trace "$TRACE8B" --heartbeat-secs 0.2 --json \
    > "$OUT/result_devstream.json"
python tools/trace_report.py "$TRACE8B" --check > "$OUT/report_devstream.txt"
grep -q '"device_stream_chunks"' "$TRACE8B"
python - "$OUT/result_inflight.json" "$OUT/result_ring.json" "$TRACE8B" <<'PYEOF'
import json
import sys

ringed = json.load(open(sys.argv[2]))
base = json.load(open(sys.argv[1]))
assert ringed["edge_cut"] == base["edge_cut"], (base, ringed)
for line in open(sys.argv[3]):
    rec = json.loads(line)
    if rec.get("event") == "diagnostics":
        assert rec.get("h2d_staged_bytes") == 0, rec
        assert rec.get("device_stream_chunks", 0) > 0, rec
        break
else:
    raise SystemExit("no diagnostics record in the device-stream trace")
PYEOF

# ninth leg: the quality observability plane (ISSUE 13) — (a) a tiny
# --k-levels build must emit the cut ledger (per-level attribution +
# refine-round + split-balance events) into the trace, with
# trace_report rendering the quality tree and --check staying green;
# (b) the naive low-signal flat invocation must PRINT the advisor's
# recipe; (c) quality_regress's fresh full sweep must pass the gate
# against the committed QUALITY_r01.json seed artifact — cut
# regressions caught like perf ones.
TRACE9="$OUT/trace_quality.jsonl"
rm -f "$TRACE9"
JAX_PLATFORMS=cpu python -m sheep_tpu.cli \
    --input sbm-hash:10:16:0.05:8:1 --k-levels 4,4 --backend pure \
    --refine 0 --final-refine 2 --no-comm-volume \
    --trace "$TRACE9" --heartbeat-secs 0.2 --json \
    > "$OUT/result_quality.json"
python tools/trace_report.py "$TRACE9" --check > "$OUT/report_quality.txt"
grep -q '"event": "quality_ledger"' "$TRACE9"
grep -q '"event": "refine_round"' "$TRACE9"
grep -q '"event": "split_balance"' "$TRACE9"
grep -q "quality ledger:" "$OUT/report_quality.txt"
grep -q "level0 (fragmentation)" "$OUT/report_quality.txt"
JAX_PLATFORMS=cpu python -m sheep_tpu.cli \
    --input sbm-hash:10:16:0.05:4:1 --k 16 --backend pure --refine 0 \
    --no-comm-volume --json > /dev/null 2> "$OUT/advisor.err"
grep -q "quality advisor" "$OUT/advisor.err"
grep -q -- "--k-levels 4,4" "$OUT/advisor.err"
QUAL9="$OUT/QUALITY_fresh.json"
JAX_PLATFORMS=cpu python tools/quality_regress.py --run "$QUAL9" \
    2> "$OUT/quality_sweep.err"
python tools/quality_regress.py "$QUAL9" QUALITY_r02.json \
    > "$OUT/quality_gate.txt"
grep -q "verdict: PASS" "$OUT/quality_gate.txt"

# tenth leg: durable sheepd (ISSUE 14) — kill -9 the daemon mid-build
# through the real CLI, restart it on the same socket/journal/state
# dir: the journaled job must RESUME from its per-job checkpoint (the
# resume event on the record, rendered by trace_report), the restart
# counters must be exported at /metrics, and --check must stay green
# across the appended daemon runs. The first daemon holds every fold
# dispatch for SHEEP_FAULT_INJECT's stall (0.5 s each), so the job is
# still building when the poller sees it at step 3, however fast the
# host: the kill window does not race. The restarted daemon runs
# without the stall.
TRACE10="$OUT/trace_durable.jsonl"
SOCK10="$OUT/sheepd_durable.sock"
STATE10="$OUT/sheepd_state"
rm -f "$TRACE10" "$SOCK10"
rm -rf "$STATE10"
JAX_PLATFORMS=cpu SHEEP_FAULT_INJECT=stall@dispatch:1:100000 \
    python -m sheep_tpu.server.daemon \
    --socket "$SOCK10" --trace "$TRACE10" --heartbeat-secs 0.2 \
    --state-dir "$STATE10" --checkpoint-every 1 --metrics-port 0 \
    2> "$OUT/sheepd_durable.err" &
SHEEPD10_PID=$!
trap 'kill $SHEEPD7_PID $SHEEPD7B_PID $SHEEPD10_PID 2>/dev/null || true' EXIT
for _ in $(seq 1 100); do [ -S "$SOCK10" ] && break; sleep 0.2; done
[ -S "$SOCK10" ] || { echo "durable sheepd never bound $SOCK10" >&2; exit 1; }
# submit through the real CLI (small chunks + batch 1: many observable
# build steps), then poll until the kill window is INSIDE the build
JID10=$(JAX_PLATFORMS=cpu python -m sheep_tpu.server.client \
    --server "$SOCK10" --input rmat:12:8:3 --k 4 --tenant durable \
    --chunk-edges 512 --dispatch-batch 1 \
    | python -c "import json,sys; print(json.load(sys.stdin)['job_id'])")
JAX_PLATFORMS=cpu python - "$SOCK10" "$JID10" <<'PYEOF'
import sys
import time

from sheep_tpu.server.client import SheepClient

with SheepClient(sys.argv[1]) as c:
    for _ in range(4000):
        st = c.status(sys.argv[2])
        if st.get("phase") == "build" and st.get("steps", 0) >= 3:
            sys.exit(0)
        if st.get("state") not in ("queued", "running"):
            raise SystemExit(f"job left the kill window: {st}")
        time.sleep(0.005)
raise SystemExit("job never reached the build phase")
PYEOF
kill -9 "$SHEEPD10_PID"
wait "$SHEEPD10_PID" 2>/dev/null || true
JAX_PLATFORMS=cpu python -m sheep_tpu.server.daemon \
    --socket "$SOCK10" --trace "$TRACE10" --heartbeat-secs 0.2 \
    --state-dir "$STATE10" --checkpoint-every 1 --metrics-port 0 \
    2>> "$OUT/sheepd_durable.err" &
SHEEPD10_PID=$!
trap 'kill $SHEEPD7_PID $SHEEPD7B_PID $SHEEPD10_PID 2>/dev/null || true' EXIT
# the client failover path rides out the restart window (stale socket
# file, then the rebinding daemon) and the resumed job must finish;
# the restart counters come from the HTTP /metrics scrape
JAX_PLATFORMS=cpu python - "$SOCK10" "$JID10" "$OUT/sheepd_durable.err" \
    > "$OUT/durable.json" 2> "$OUT/durable.err" <<'PYEOF'
import json
import re
import sys
import urllib.request

from sheep_tpu.obs.metrics import parse_prometheus
from sheep_tpu.server.client import SheepClient

sock, jid, err_path = sys.argv[1], sys.argv[2], sys.argv[3]
with SheepClient(sock, reconnect=40, reconnect_base_s=0.3) as c:
    job = c.wait(jid, timeout_s=300)
    assert job["state"] == "done", job
    ports = re.findall(r"metrics on http://[^:]+:(\d+)",
                       open(err_path).read())
    url = f"http://127.0.0.1:{ports[-1]}/metrics"
    m = parse_prometheus(
        urllib.request.urlopen(url, timeout=10).read().decode())
    restarts = sum(v for _, v in m.get("sheepd_restarts_total", []))
    resumed = sum(v for _, v in m.get("sheepd_jobs_resumed_total", []))
    assert restarts >= 1, m.get("sheepd_restarts_total")
    assert resumed >= 1, m.get("sheepd_jobs_resumed_total")
    print(json.dumps({"state": job["state"], "restarts": restarts,
                      "jobs_resumed": resumed}))
    c.shutdown()
PYEOF
wait "$SHEEPD10_PID"
python tools/trace_report.py "$TRACE10" --check > "$OUT/report_durable.txt"
grep -q '"event": "resume"' "$TRACE10"        # the checkpoint resume seam
grep -q '"event": "job_recovered"' "$TRACE10" # the journal replay seam
grep -q "resume:" "$OUT/report_durable.txt"

# eleventh leg: incremental repartitioning served end-to-end (ISSUE
# 15) — a resident partition built through the real CLI/clients, two
# delta epochs streamed at it with the `sheep update` verb, a
# compaction, a kill -9 + restart on the same state dir: the resident
# partition must resume at its journaled epoch, sheep_updates_total /
# sheep_update_latency_seconds must join the /metrics catalog, the
# scored update must bit-match the one-shot delta: build, and --check
# must stay green across the appended daemon runs.
TRACE11="$OUT/trace_incremental.jsonl"
SOCK11="$OUT/sheepd_inc.sock"
STATE11="$OUT/sheepd_inc_state"
rm -f "$TRACE11" "$SOCK11"
rm -rf "$STATE11"
JAX_PLATFORMS=cpu python - "$OUT" <<'PYEOF'
import os
import sys

import numpy as np

from sheep_tpu.io import deltalog as dl

out = sys.argv[1]
rng = np.random.default_rng(11)
E = rng.integers(0, 512, (6000, 2))
base = os.path.join(out, "inc_base.bin64")
with open(base, "wb") as f:
    f.write(E[:3000].astype("<u8").tobytes())
with dl.DeltaLogWriter(os.path.join(out, "inc.dlog"),
                       base_spec=base) as w:
    w.append(E[3000:4500])
    w.append(E[4500:])
PYEOF
JAX_PLATFORMS=cpu python -m sheep_tpu.server.daemon \
    --socket "$SOCK11" --trace "$TRACE11" --heartbeat-secs 0.2 \
    --state-dir "$STATE11" --checkpoint-every 4 --metrics-port 0 \
    2> "$OUT/sheepd_inc.err" &
SHEEPD11_PID=$!
trap 'kill $SHEEPD7_PID $SHEEPD7B_PID $SHEEPD10_PID $SHEEPD11_PID 2>/dev/null || true' EXIT
for _ in $(seq 1 100); do [ -S "$SOCK11" ] && break; sleep 0.2; done
[ -S "$SOCK11" ] || { echo "inc sheepd never bound $SOCK11" >&2; exit 1; }
JID11=$(JAX_PLATFORMS=cpu python -m sheep_tpu.server.client \
    --server "$SOCK11" --input "$OUT/inc_base.bin64" --k 4 \
    --num-vertices 512 --chunk-edges 512 --tenant inc --resident --wait \
    | python -c "import json,sys; print(json.load(sys.stdin)['job_id'])")
# stream the log's two epochs at the resident partition (the real
# `sheep update` CLI verb), scoring the final state
JAX_PLATFORMS=cpu python -m sheep_tpu.cli update "$JID11" \
    --server "$SOCK11" --deltas "$OUT/inc.dlog" --score \
    > "$OUT/inc_update.json"
JAX_PLATFORMS=cpu python -m sheep_tpu.server.client --server "$SOCK11" \
    --compact "$JID11" > "$OUT/inc_compact.json"
# metrics BEFORE the kill (counters are per-process):
# sheep_updates_total and the latency series must be in the catalog
JAX_PLATFORMS=cpu python - "$OUT/sheepd_inc.err" <<'PYEOF'
import re
import sys
import urllib.request

from sheep_tpu.obs.metrics import parse_prometheus

ports = re.findall(r"metrics on http://[^:]+:(\d+)",
                   open(sys.argv[1]).read())
url = f"http://127.0.0.1:{ports[-1]}/metrics"
text = urllib.request.urlopen(url, timeout=10).read().decode()
m = parse_prometheus(text)
updates = sum(v for _, v in m.get("sheep_updates_total", []))
assert updates >= 2, m.get("sheep_updates_total")
assert "sheep_update_latency_seconds_bucket" in text, \
    "update latency histogram missing from /metrics"
assert sum(v for _, v in m.get("sheepd_resident_partitions", [])) >= 1
PYEOF
kill -9 "$SHEEPD11_PID"
wait "$SHEEPD11_PID" 2>/dev/null || true
JAX_PLATFORMS=cpu python -m sheep_tpu.server.daemon \
    --socket "$SOCK11" --trace "$TRACE11" --heartbeat-secs 0.2 \
    --state-dir "$STATE11" --checkpoint-every 4 --metrics-port 0 \
    2>> "$OUT/sheepd_inc.err" &
SHEEPD11_PID=$!
trap 'kill $SHEEPD7_PID $SHEEPD7B_PID $SHEEPD10_PID $SHEEPD11_PID 2>/dev/null || true' EXIT
# the resident partition resumes at its journaled epoch (2) across
# the SIGKILL, and the scored update bit-matches the one-shot build
# of the same delta: input through the plain CLI
JAX_PLATFORMS=cpu python - "$SOCK11" "$JID11" "$OUT" \
    > "$OUT/inc_resume.json" <<'PYEOF'
import json
import os
import subprocess
import sys

from sheep_tpu.server.client import SheepClient

sock, jid, out = sys.argv[1], sys.argv[2], sys.argv[3]
with SheepClient(sock, reconnect=40, reconnect_base_s=0.3) as c:
    ep = c.epoch(jid)
    assert ep["epoch"] == 2, ep
    upd = json.load(open(os.path.join(out, "inc_update.json")))
    assert upd["epoch"] == 2 and upd["applied"], upd
    served_cut = upd["results"][0]["edge_cut"]
    one = subprocess.run(
        [sys.executable, "-m", "sheep_tpu.cli", "--input",
         f"delta:{os.path.join(out, 'inc.dlog')}", "--k", "4",
         "--num-vertices", "512", "--chunk-edges", "512", "--json"],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert one.returncode == 0, one.stderr[-800:]
    oneshot = json.loads(one.stdout.strip().splitlines()[-1])
    assert served_cut == oneshot["edge_cut"], (served_cut, oneshot)
    print(json.dumps({"epoch": ep["epoch"],
                      "served_cut": served_cut,
                      "oneshot_cut": oneshot["edge_cut"]}))
    c.shutdown()
PYEOF
wait "$SHEEPD11_PID"
python tools/trace_report.py "$TRACE11" --check \
    > "$OUT/report_incremental.txt"
grep -q '"event": "delta_epoch_applied"' "$TRACE11"
grep -q '"event": "resident_resumed"' "$TRACE11"

# twelfth leg: fleet serving (ISSUE 16) — two replicas, each with a
# content-addressed result store under its state dir. A cold submit
# through the new `--endpoints` CLI plumbing builds and publishes on
# replica A; a repeat submit pointed at B FIRST is digest-routed back
# to the store holder and answered with ZERO build steps and ZERO
# compiles (sheepd_result_cache_{hits,misses}_total and
# sheepd_result_cache_bytes on A's /metrics record it); then replica
# B is SIGKILLed mid-build of a third job and the fleet client must
# fail over (reattach-idempotent resubmit) to A, completing the job —
# with every routing decision (cache_hit / headroom / failover) in
# the CLIENT-side trace as fleet_route events.
TRACE12A="$OUT/trace_fleet_a.jsonl"
TRACE12B="$OUT/trace_fleet_b.jsonl"
TRACE12C="$OUT/trace_fleet_client.jsonl"
SOCK12A="$OUT/sheepd_fleet_a.sock"
SOCK12B="$OUT/sheepd_fleet_b.sock"
STATE12A="$OUT/fleet_state_a"
STATE12B="$OUT/fleet_state_b"
rm -f "$TRACE12A" "$TRACE12B" "$TRACE12C" "$SOCK12A" "$SOCK12B"
rm -rf "$STATE12A" "$STATE12B"
JAX_PLATFORMS=cpu python -m sheep_tpu.server.daemon \
    --socket "$SOCK12A" --trace "$TRACE12A" --heartbeat-secs 0.2 \
    --state-dir "$STATE12A" --checkpoint-every 1 --metrics-port 0 \
    2> "$OUT/sheepd_fleet_a.err" &
SHEEPD12A_PID=$!
JAX_PLATFORMS=cpu python -m sheep_tpu.server.daemon \
    --socket "$SOCK12B" --trace "$TRACE12B" --heartbeat-secs 0.2 \
    --state-dir "$STATE12B" --checkpoint-every 1 --metrics-port 0 \
    2> "$OUT/sheepd_fleet_b.err" &
SHEEPD12B_PID=$!
trap 'kill $SHEEPD7_PID $SHEEPD7B_PID $SHEEPD10_PID $SHEEPD11_PID $SHEEPD12A_PID $SHEEPD12B_PID 2>/dev/null || true' EXIT
for _ in $(seq 1 100); do
    [ -S "$SOCK12A" ] && [ -S "$SOCK12B" ] && break; sleep 0.2
done
[ -S "$SOCK12A" ] || { echo "fleet sheepd A never bound" >&2; exit 1; }
[ -S "$SOCK12B" ] || { echo "fleet sheepd B never bound" >&2; exit 1; }
# cold fill through the --endpoints CLI (fleet of one): builds on A
JAX_PLATFORMS=cpu python -m sheep_tpu.server.client \
    --endpoints "$SOCK12A" --input rmat:10:8:1 --k 4 --tenant fleet \
    --chunk-edges 1024 --wait > "$OUT/fleet_cold.json"
if ! JAX_PLATFORMS=cpu python - "$SOCK12A" "$SOCK12B" \
        "$SHEEPD12B_PID" "$OUT/fleet_cold.json" "$TRACE12C" \
        > "$OUT/fleet.json" 2> "$OUT/fleet.err" <<'PYEOF'
import json
import os
import signal
import sys
import time

from sheep_tpu import obs
from sheep_tpu.obs.metrics import parse_prometheus
from sheep_tpu.server.client import FleetClient, SheepClient, fleet_digest

sock_a, sock_b, pid_b, cold_path, trace = sys.argv[1:6]
cold = json.load(open(cold_path))
assert cold["state"] == "done", cold
dg = fleet_digest("rmat:10:8:1", [4], tenant="fleet", chunk_edges=1024)
# the store publish is post-terminal on A's dispatch thread
with SheepClient(sock_a) as ca:
    deadline = time.monotonic() + 30
    while not ca.lookup(dg) and time.monotonic() < deadline:
        time.sleep(0.05)
    assert ca.lookup(dg), "cold result never published to A's store"
with obs.tracing(trace):
    with FleetClient([sock_b, sock_a]) as fleet:
        # digest hit on A short-circuits routing even though B is
        # listed first — answered from the store, zero work
        rep = fleet.submit("rmat:10:8:1", k=[4], tenant="fleet",
                           chunk_edges=1024)
        assert rep["endpoint"] == sock_a, rep
        desc = fleet.wait(rep, timeout_s=120)
        assert desc["state"] == "done", desc
        assert desc.get("steps", 0) == 0, \
            f"cache hit dispatched {desc.get('steps')} steps"
        assert desc.get("jit_compiles") == 0, desc
        assert desc["results"][0]["edge_cut"] \
            == cold["results"][0]["edge_cut"], (cold, desc)
        # third job (new digest): headroom routing ties break to B
        # (listed first); SIGKILL it mid-build and fail over to A
        third = fleet.submit("rmat:12:8:5", k=[4], tenant="fleet",
                             chunk_edges=512, dispatch_batch=1)
        assert third["endpoint"] == sock_b, third
        with SheepClient(sock_b) as cb:
            for _ in range(4000):
                st = cb.status(third["job_id"])
                if st.get("phase") == "build" and st.get("steps", 0) >= 3:
                    break
                time.sleep(0.005)
            else:
                raise SystemExit("third job never reached build")
        os.kill(int(pid_b), signal.SIGKILL)
        fin = fleet.wait(third, timeout_s=300)
        assert fin["state"] == "done", fin
        counts = dict(fleet.route_counts)
        assert counts[sock_a] >= 1 and counts[sock_b] >= 1, counts
with SheepClient(sock_a) as ca:
    m = parse_prometheus(ca.metrics())
    hits = sum(v for _, v in m.get("sheepd_result_cache_hits_total", []))
    misses = sum(v for _, v in
                 m.get("sheepd_result_cache_misses_total", []))
    rc_bytes = sum(v for _, v in m.get("sheepd_result_cache_bytes", []))
    assert hits >= 1, m.get("sheepd_result_cache_hits_total")
    assert misses >= 1, m.get("sheepd_result_cache_misses_total")
    assert rc_bytes > 0, m.get("sheepd_result_cache_bytes")
    ca.shutdown()
print(json.dumps({"cache_hits": hits, "cache_misses": misses,
                  "route_counts": counts}))
PYEOF
then
    echo "fleet smoke client failed:" >&2
    cat "$OUT/fleet.err" >&2
    exit 1
fi
wait "$SHEEPD12A_PID"
wait "$SHEEPD12B_PID" 2>/dev/null || true
python tools/trace_report.py "$TRACE12A" --check > "$OUT/report_fleet.txt"
grep -q '"event": "result_cache_store"' "$TRACE12A"  # the publish
grep -q '"event": "result_cache_hit"' "$TRACE12A"    # the served hit
grep -q '"why": "cache_hit"' "$TRACE12C"   # client-side route record
grep -q '"why": "headroom"' "$TRACE12C"
grep -q '"why": "failover"' "$TRACE12C"

# thirteenth leg: O(delta) end-to-end epochs (ISSUE 17) — a resident
# partition absorbs concurrent un-epoched updates under a tiny
# per-cycle byte budget (sheepd_update_throttled_total must tick and
# sheepd_update_score_seconds must join the HTTP /metrics catalog),
# then streams a >1 MiB epoch through the chunked update wire form
# (one txn, folded + scored as ONE epoch), then the daemon is
# SIGKILLed while a rebase compaction is in flight: the restart must
# come back at the same epoch — with SHEEP_SCORE_AUDIT cross-checking
# every incremental score — and a final scored epoch must bit-match
# the one-shot build of the reconstructed delta log.
TRACE13="$OUT/trace_odelta.jsonl"
SOCK13="$OUT/sheepd_odelta.sock"
STATE13="$OUT/sheepd_odelta_state"
rm -f "$TRACE13" "$SOCK13"
rm -rf "$STATE13"
JAX_PLATFORMS=cpu SHEEP_SCORE_AUDIT=1 SHEEP_UPDATE_BYTES_PER_CYCLE=16384 \
python -m sheep_tpu.server.daemon \
    --socket "$SOCK13" --trace "$TRACE13" --heartbeat-secs 0.2 \
    --state-dir "$STATE13" --checkpoint-every 1 --metrics-port 0 \
    2> "$OUT/sheepd_odelta.err" &
SHEEPD13_PID=$!
trap 'kill $SHEEPD7_PID $SHEEPD7B_PID $SHEEPD10_PID $SHEEPD11_PID $SHEEPD12A_PID $SHEEPD12B_PID $SHEEPD13_PID 2>/dev/null || true' EXIT
for _ in $(seq 1 100); do [ -S "$SOCK13" ] && break; sleep 0.2; done
[ -S "$SOCK13" ] || { echo "odelta sheepd never bound $SOCK13" >&2; exit 1; }
JAX_PLATFORMS=cpu python - "$SOCK13" "$OUT" "$OUT/sheepd_odelta.err" \
    > "$OUT/odelta_stream.json" <<'PYEOF'
import json
import os
import re
import sys
import threading
import urllib.request

import numpy as np

from sheep_tpu.server.client import SheepClient

sock, out, errf = sys.argv[1:4]
rng = np.random.default_rng(13)
n = 2048
E = rng.integers(0, n, (200000, 2)).astype(np.int64)
base = os.path.join(out, "odelta_base.bin64")
with open(base, "wb") as f:
    f.write(E[:40000].astype("<u8").tobytes())
np.save(os.path.join(out, "odelta_edges.npy"), E)


def metrics_text():
    ports = re.findall(r"metrics on http://[^:]+:(\d+)",
                       open(errf).read())
    url = f"http://127.0.0.1:{ports[-1]}/metrics"
    return urllib.request.urlopen(url, timeout=10).read().decode()


def throttled():
    m = re.search(
        r'sheepd_update_throttled_total\{tenant="odelta"\} (\d+)',
        metrics_text())
    return int(m.group(1)) if m else 0


applied = []
lock = threading.Lock()
with SheepClient(sock, timeout_s=600) as c:
    jid = c.submit(base, k=[4], tenant="odelta", resident=True,
                   chunk_edges=4096, num_vertices=n)["job_id"]
    assert c.wait(jid, timeout_s=600)["state"] == "done"

    def push(lo, hi):
        with SheepClient(sock, timeout_s=600) as cc:
            r = cc.update(jid, adds=E[lo:hi])
            assert r["applied"], r
            with lock:
                applied.append((int(r["epoch"]), lo, hi))

    # concurrent un-epoched updates against a 16 KiB/cycle budget:
    # each 2000-edge item stages 32 KB, so any drain cycle that sees
    # a backlog defers all but one item and ticks the throttle
    # counter; bounded retry rounds make the race with the drain
    # loop benign (a fast drain just means another round)
    nxt = 40000
    for _ in range(10):
        ths = [threading.Thread(
            target=push, args=(nxt + 2000 * i, nxt + 2000 * (i + 1)))
            for i in range(3)]
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        nxt += 6000
        if throttled() >= 1:
            break
    assert throttled() >= 1, "update drain never throttled a backlog"

    # >1 MiB epoch through the chunked wire form: auto-chunking kicks
    # in past UPDATE_CHUNK_EDGES and the commit answers with the txn
    big_lo, big_hi = 100000, 184000
    big = E[big_lo:big_hi]
    assert big.nbytes > (1 << 20), big.nbytes
    r = c.update(jid, adds=big, score=True)
    assert r["applied"] and r.get("txn"), r
    applied.append((int(r["epoch"]), big_lo, big_hi))
    assert 'sheepd_update_score_seconds_count{tenant="odelta"}' \
        in metrics_text(), "scored-refresh histogram missing"
    eps = sorted(e for e, _, _ in applied)
    assert eps == list(range(1, len(applied) + 1)), applied
    json.dump({"job_id": jid, "epochs": sorted(applied)},
              open(os.path.join(out, "odelta_plan.json"), "w"))
    print(json.dumps({"job_id": jid, "last_epoch": eps[-1],
                      "scored_cut": r["results"][0]["edge_cut"],
                      "throttled": throttled()}))
PYEOF
JID13=$(python -c "import json,sys; \
print(json.load(open(sys.argv[1]))['job_id'])" "$OUT/odelta_plan.json")
# SIGKILL the daemon while a rebase compaction is in flight: whether
# the base rewrite committed or not, the restart must be consistent
(JAX_PLATFORMS=cpu python -m sheep_tpu.server.client \
    --server "$SOCK13" --compact "$JID13" --compact-mode rebase \
    > "$OUT/odelta_compact.json" 2>&1 || true) &
COMPACT13_PID=$!
sleep 0.6
kill -9 "$SHEEPD13_PID"
wait "$SHEEPD13_PID" 2>/dev/null || true
wait "$COMPACT13_PID" 2>/dev/null || true
JAX_PLATFORMS=cpu SHEEP_SCORE_AUDIT=1 SHEEP_UPDATE_BYTES_PER_CYCLE=16384 \
python -m sheep_tpu.server.daemon \
    --socket "$SOCK13" --trace "$TRACE13" --heartbeat-secs 0.2 \
    --state-dir "$STATE13" --checkpoint-every 1 --metrics-port 0 \
    2>> "$OUT/sheepd_odelta.err" &
SHEEPD13_PID=$!
trap 'kill $SHEEPD7_PID $SHEEPD7B_PID $SHEEPD10_PID $SHEEPD11_PID $SHEEPD12A_PID $SHEEPD12B_PID $SHEEPD13_PID 2>/dev/null || true' EXIT
JAX_PLATFORMS=cpu python - "$SOCK13" "$OUT" \
    > "$OUT/odelta_resume.json" <<'PYEOF'
import json
import os
import subprocess
import sys

import numpy as np

from sheep_tpu.io import deltalog as dl
from sheep_tpu.server.client import SheepClient

sock, out = sys.argv[1], sys.argv[2]
plan = json.load(open(os.path.join(out, "odelta_plan.json")))
jid = plan["job_id"]
E = np.load(os.path.join(out, "odelta_edges.npy"))
last = max(e for e, _, _ in plan["epochs"])
fin_lo, fin_hi = 184000, 186000
with SheepClient(sock, reconnect=40, reconnect_base_s=0.3,
                 timeout_s=600) as c:
    ep = c.epoch(jid)
    assert ep["epoch"] == last, (ep, last)  # the SIGKILL lost nothing
    r = c.update(jid, adds=E[fin_lo:fin_hi], epoch=last + 1,
                 score=True)
    assert r["applied"] and r["epoch"] == last + 1, r
    served_cut = r["results"][0]["edge_cut"]
    c.shutdown()
# the one-shot reference: replay the exact applied epoch order into a
# fresh delta log and build it cold — served must bit-match, straight
# through the backlog, the chunked epoch, the (maybe-torn) rebase
# compaction, and the restart
log = os.path.join(out, "odelta_ref.dlog")
with dl.DeltaLogWriter(
        log, base_spec=os.path.join(out, "odelta_base.bin64")) as w:
    for _, lo, hi in sorted(plan["epochs"]):
        w.append(E[lo:hi])
    w.append(E[fin_lo:fin_hi])
one = subprocess.run(
    [sys.executable, "-m", "sheep_tpu.cli", "--input",
     f"delta:{log}", "--k", "4", "--num-vertices", "2048",
     "--chunk-edges", "4096", "--json"],
    capture_output=True, text=True,
    env={**os.environ, "JAX_PLATFORMS": "cpu"})
assert one.returncode == 0, one.stderr[-800:]
oneshot = json.loads(one.stdout.strip().splitlines()[-1])
assert served_cut == oneshot["edge_cut"], (served_cut, oneshot)
print(json.dumps({"epoch": last + 1, "served_cut": served_cut,
                  "oneshot_cut": oneshot["edge_cut"]}))
PYEOF
wait "$SHEEPD13_PID"
python tools/trace_report.py "$TRACE13" --check \
    > "$OUT/report_odelta.txt"
grep -q '"event": "delta_epoch_applied"' "$TRACE13"

# fourteenth leg: fleet observability (ISSUE 18) — a fleet submit
# mints ONE trace id, the wire `trace` field carries it to whichever
# replica takes the job, and a mid-build SIGKILL + failover leaves the
# SAME id in the client trace and BOTH replicas' traces; `--stitch`
# renders the three files as one tree (the killed replica's job span
# UNCLOSED under the client request span, the survivor's closed beside
# it) with --check green; `sheep-fleet-metrics` federates the two
# saved scrapes with the merged p99 matching a hand-summed bucket
# merge exactly; and the SLO gate passes sane rules / exits 2 on a
# deliberately-tight one.
TRACE14A="$OUT/trace_fobs_a.jsonl"
TRACE14B="$OUT/trace_fobs_b.jsonl"
TRACE14C="$OUT/trace_fobs_client.jsonl"
SOCK14A="$OUT/sheepd_fobs_a.sock"
SOCK14B="$OUT/sheepd_fobs_b.sock"
STATE14A="$OUT/fobs_state_a"
STATE14B="$OUT/fobs_state_b"
rm -f "$TRACE14A" "$TRACE14B" "$TRACE14C" "$SOCK14A" "$SOCK14B"
rm -rf "$STATE14A" "$STATE14B"
JAX_PLATFORMS=cpu python -m sheep_tpu.server.daemon \
    --socket "$SOCK14A" --trace "$TRACE14A" --heartbeat-secs 0.2 \
    --state-dir "$STATE14A" --checkpoint-every 1 --metrics-port 0 \
    2> "$OUT/sheepd_fobs_a.err" &
SHEEPD14A_PID=$!
JAX_PLATFORMS=cpu python -m sheep_tpu.server.daemon \
    --socket "$SOCK14B" --trace "$TRACE14B" --heartbeat-secs 0.2 \
    --state-dir "$STATE14B" --checkpoint-every 1 --metrics-port 0 \
    2> "$OUT/sheepd_fobs_b.err" &
SHEEPD14B_PID=$!
trap 'kill $SHEEPD7_PID $SHEEPD7B_PID $SHEEPD10_PID $SHEEPD11_PID $SHEEPD12A_PID $SHEEPD12B_PID $SHEEPD13_PID $SHEEPD14A_PID $SHEEPD14B_PID 2>/dev/null || true' EXIT
for _ in $(seq 1 100); do
    [ -S "$SOCK14A" ] && [ -S "$SOCK14B" ] && break; sleep 0.2
done
[ -S "$SOCK14A" ] || { echo "fobs sheepd A never bound" >&2; exit 1; }
[ -S "$SOCK14B" ] || { echo "fobs sheepd B never bound" >&2; exit 1; }
# the fleet console sees both replicas (sheeptop --endpoints mode)
JAX_PLATFORMS=cpu python -m sheep_tpu.server.sheeptop \
    --endpoints "$SOCK14A,$SOCK14B" --once > "$OUT/fobs_sheeptop.txt"
grep -q "2/2 replicas up" "$OUT/fobs_sheeptop.txt"
if ! JAX_PLATFORMS=cpu python - "$SOCK14A" "$SOCK14B" \
        "$SHEEPD14B_PID" "$OUT" "$TRACE14C" \
        > "$OUT/fobs.json" 2> "$OUT/fobs.err" <<'PYEOF'
import json
import os
import signal
import sys
import time

from sheep_tpu import obs
from sheep_tpu.server.client import FleetClient, SheepClient

sock_a, sock_b, pid_b, out, trace = sys.argv[1:6]
# one small job per replica first, so BOTH scrapes carry request
# latency observations for the federation checks below
for ep in (sock_a, sock_b):
    with SheepClient(ep, timeout_s=600) as c:
        jid = c.submit("rmat:8:8:1", k=[4], tenant="fleetobs",
                       chunk_edges=1024)["job_id"]
        assert c.wait(jid, timeout_s=300)["state"] == "done"
with obs.tracing(trace):
    with FleetClient([sock_b, sock_a]) as fleet:
        rep = fleet.submit("rmat:12:8:5", k=[4], tenant="fleetobs",
                           chunk_edges=512, dispatch_batch=1)
        assert rep["endpoint"] == sock_b, rep
        with SheepClient(sock_b) as cb:
            # snapshot B's exposition BEFORE the kill: the saved
            # file stands in for the dead replica downstream
            with open(os.path.join(out, "fobs_scrape_b.txt"),
                      "w") as f:
                f.write(cb.metrics())
            for _ in range(4000):
                st = cb.status(rep["job_id"])
                if st.get("phase") == "build" \
                        and st.get("steps", 0) >= 3:
                    break
                time.sleep(0.005)
            else:
                raise SystemExit("fleet job never reached build on B")
        os.kill(int(pid_b), signal.SIGKILL)
        fin = fleet.wait(rep, timeout_s=300)
        assert fin["state"] == "done", fin
with SheepClient(sock_a) as ca:
    with open(os.path.join(out, "fobs_scrape_a.txt"), "w") as f:
        f.write(ca.metrics())
    ca.shutdown()
print(json.dumps({"cut": fin["results"][0]["edge_cut"]}))
PYEOF
then
    echo "fleet obs client failed:" >&2
    cat "$OUT/fobs.err" >&2
    exit 1
fi
wait "$SHEEPD14A_PID"
wait "$SHEEPD14B_PID" 2>/dev/null || true
# the SAME 32-hex trace id in the client trace and BOTH replicas'
# traces: B stamped it at submit, A on the failover resubmit
TID14=$(JAX_PLATFORMS=cpu python - "$TRACE14C" <<'PYEOF'
import json
import sys

for line in open(sys.argv[1]):
    rec = json.loads(line)
    if rec.get("span") == "fleet_request" and rec.get("trace"):
        print(rec["trace"])
        break
else:
    raise SystemExit("no traced fleet_request span in client trace")
PYEOF
)
echo "$TID14" | grep -Eq '^[0-9a-f]{32}$'
grep -q "\"trace\": \"$TID14\"" "$TRACE14A"
grep -q "\"trace\": \"$TID14\"" "$TRACE14B"
grep -q "\"trace\": \"$TID14\"" "$TRACE14C"
# the routing-scrape wall counter landed in the client trace
# (satellite: FleetClient scrape cache, ISSUE 18)
grep -q "fleet_scrape_ms" "$TRACE14C"
# one stitched tree across the three files, --check green, the
# killed job span flagged and both job spans remote-grafted
python tools/trace_report.py --stitch "$TRACE14C" "$TRACE14A" \
    "$TRACE14B" --check > "$OUT/report_fobs_stitch.txt"
grep -q "UNCLOSED (died mid-span" "$OUT/report_fobs_stitch.txt"
N14=$(grep -c -- "<-remote" "$OUT/report_fobs_stitch.txt" || true)
[ "$N14" -ge 2 ] || {
    echo "expected both job spans grafted remotely, got $N14" >&2
    exit 1
}
python tools/trace_report.py "$TRACE14A" --check > "$OUT/report_fobs_a.txt"
# federation: the CLI's merged p99 equals a hand-summed bucket merge
grep -q "sheepd_requests_total{" "$OUT/fobs_scrape_a.txt"
JAX_PLATFORMS=cpu python -m sheep_tpu.obs.federate \
    "$OUT/fobs_scrape_a.txt" "$OUT/fobs_scrape_b.txt" \
    --quantile sheepd_request_latency_seconds:0.99 \
    --json > "$OUT/fobs_fed.json"
JAX_PLATFORMS=cpu python - "$OUT" <<'PYEOF'
import json
import os
import sys

from sheep_tpu.obs.metrics import parse_prometheus, \
    quantile_from_cumulative

out = sys.argv[1]
fed = json.load(open(os.path.join(out, "fobs_fed.json")))
agg = {}
for rep in ("a", "b"):
    with open(os.path.join(out, f"fobs_scrape_{rep}.txt")) as f:
        m = parse_prometheus(f.read())
    for labels, v in m.get("sheepd_request_latency_seconds_bucket", []):
        agg[labels["le"]] = agg.get(labels["le"], 0) + v
rows = sorted(agg.items(),
              key=lambda kv: float(kv[0].replace("+Inf", "inf")))
uppers = [float(le) for le, _ in rows if le != "+Inf"]
cum = [int(c) for _, c in rows]
hand = quantile_from_cumulative(uppers, cum, 0.99)
got = fed["quantiles"]["sheepd_request_latency_seconds:0.99"]
assert got is not None and abs(got - hand) < 1e-12, (got, hand)
smp = fed["samples"]
assert any(lb.get("outcome") == "ok"
           for lb, _ in smp["sheepd_requests_total"]), \
    sorted(smp)
ups = {lb["replica"]: v for lb, v in smp["sheep_federated_up"]}
assert len(ups) == 2 and all(v == 1 for v in ups.values()), ups
print(json.dumps({"fleet_p99": got, "hand_p99": hand}))
PYEOF
# the SLO gate: sane rules hold (exit 0), a deliberately-tight p99
# bound burns (exit 2) — over the same two saved scrapes
cat > "$OUT/fobs_slo.json" <<'JSON'
{"tenants": {
    "fleetobs": {"p99_latency_s": 600.0, "max_update_throttled": 0},
    "*": {"p99_latency_s": 600.0, "max_error_rate": 0.25}}}
JSON
JAX_PLATFORMS=cpu python tools/slo_check.py --rules "$OUT/fobs_slo.json" \
    "$OUT/fobs_scrape_a.txt" "$OUT/fobs_scrape_b.txt" \
    > "$OUT/fobs_slo_ok.txt"
grep -q "4/4 bounds hold" "$OUT/fobs_slo_ok.txt"
cat > "$OUT/fobs_slo_tight.json" <<'JSON'
{"tenants": {"*": {"p99_latency_s": 0.000001}}}
JSON
rc=0
JAX_PLATFORMS=cpu python tools/slo_check.py \
    --rules "$OUT/fobs_slo_tight.json" \
    "$OUT/fobs_scrape_a.txt" "$OUT/fobs_scrape_b.txt" \
    > "$OUT/fobs_slo_burn.txt" || rc=$?
[ "$rc" -eq 2 ] || { echo "tight SLO rule did not burn (rc=$rc)" >&2; exit 1; }
grep -q "BURN" "$OUT/fobs_slo_burn.txt"

# fifteenth leg: multi-device O(delta) epochs served end-to-end
# (ISSUE 19) — a tiny RESIDENT partition submitted with
# update_backend=tpu-sharded (the daemon runs an 8-way virtual device
# mesh), absorbs one >UPDATE_CHUNK_EDGES epoch through the chunked
# begin/chunk/commit wire form — folded through the sharded lockstep
# pipeline and rescored with the distributed score cache (the scored
# reply's diagnostics must carry update_folds and score_distributed),
# with SHEEP_SCORE_AUDIT shadow-checking every incremental score —
# then the daemon is SIGKILLed and the restart must reattach at the
# applied epoch and absorb one more scored epoch.
TRACE15="$OUT/trace_shupd.jsonl"
SOCK15="$OUT/sheepd_shupd.sock"
STATE15="$OUT/sheepd_shupd_state"
rm -f "$TRACE15" "$SOCK15"
rm -rf "$STATE15"
JAX_PLATFORMS=cpu SHEEP_SCORE_AUDIT=1 \
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
python -m sheep_tpu.server.daemon \
    --socket "$SOCK15" --trace "$TRACE15" --heartbeat-secs 0.2 \
    --state-dir "$STATE15" --checkpoint-every 1 --metrics-port 0 \
    2> "$OUT/sheepd_shupd.err" &
SHEEPD15_PID=$!
trap 'kill $SHEEPD7_PID $SHEEPD7B_PID $SHEEPD10_PID $SHEEPD11_PID $SHEEPD12A_PID $SHEEPD12B_PID $SHEEPD13_PID $SHEEPD14A_PID $SHEEPD14B_PID $SHEEPD15_PID 2>/dev/null || true' EXIT
for _ in $(seq 1 100); do [ -S "$SOCK15" ] && break; sleep 0.2; done
[ -S "$SOCK15" ] || { echo "shupd sheepd never bound $SOCK15" >&2; exit 1; }
JAX_PLATFORMS=cpu python - "$SOCK15" "$OUT" \
    > "$OUT/shupd_plan.json" <<'PYEOF'
import json
import os
import sys

import numpy as np

from sheep_tpu.server.client import SheepClient

sock, out = sys.argv[1], sys.argv[2]
rng = np.random.default_rng(15)
n = 2048
# SPARSE on purpose: a dense random graph's elimination forest is so
# stable that delta epochs move no labels and the rescore (correctly)
# has nothing to do — sparse epochs actually exercise it
E = rng.integers(0, n, (30000, 2)).astype(np.int64)
base = os.path.join(out, "shupd_base.bin64")
with open(base, "wb") as f:
    f.write(E[:6000].astype("<u8").tobytes())
np.save(os.path.join(out, "shupd_edges.npy"), E)
with SheepClient(sock, timeout_s=600) as c:
    jid = c.submit(base, k=[4], tenant="shupd", resident=True,
                   chunk_edges=2048, num_vertices=n,
                   update_backend="tpu-sharded")["job_id"]
    assert c.wait(jid, timeout_s=600)["state"] == "done"
    # a 4k-edge epoch at chunk_edges=1024 rides the chunked
    # begin/chunk/commit framing (one txn, applied as ONE epoch);
    # its scored refresh SEEDS the score cache (one full pass)
    r = c.update(jid, adds=E[6000:10000], score=True,
                 chunk_edges=1024)
    assert r["applied"] and r.get("txn"), r
    diag = r["results"][0]["diagnostics"]
    assert diag.get("update_folds", 0) >= 1, diag
    # the next epoch takes the O(delta) path: folded through the
    # sharded lockstep pipeline, rescored with ONE all-reduce
    r = c.update(jid, adds=E[10000:13000], score=True)
    assert r["applied"], r
    diag = r["results"][0]["diagnostics"]
    assert diag.get("score_distributed", 0) >= 1, diag
    print(json.dumps({"job_id": jid, "epoch": int(r["epoch"]),
                      "cut": r["results"][0]["edge_cut"]}))
PYEOF
EPOCH15=$(python -c "import json,sys; \
print(json.load(open(sys.argv[1]))['epoch'])" "$OUT/shupd_plan.json")
JID15=$(python -c "import json,sys; \
print(json.load(open(sys.argv[1]))['job_id'])" "$OUT/shupd_plan.json")
kill -9 "$SHEEPD15_PID"
wait "$SHEEPD15_PID" 2>/dev/null || true
JAX_PLATFORMS=cpu SHEEP_SCORE_AUDIT=1 \
XLA_FLAGS=--xla_force_host_platform_device_count=8 \
python -m sheep_tpu.server.daemon \
    --socket "$SOCK15" --trace "$TRACE15" --heartbeat-secs 0.2 \
    --state-dir "$STATE15" --checkpoint-every 1 --metrics-port 0 \
    2>> "$OUT/sheepd_shupd.err" &
SHEEPD15_PID=$!
trap 'kill $SHEEPD7_PID $SHEEPD7B_PID $SHEEPD10_PID $SHEEPD11_PID $SHEEPD12A_PID $SHEEPD12B_PID $SHEEPD13_PID $SHEEPD14A_PID $SHEEPD14B_PID $SHEEPD15_PID 2>/dev/null || true' EXIT
JAX_PLATFORMS=cpu python - "$SOCK15" "$OUT" "$JID15" "$EPOCH15" \
    > "$OUT/shupd_resume.json" <<'PYEOF'
import json
import os
import sys

import numpy as np

from sheep_tpu.server.client import SheepClient

sock, out, jid, last = sys.argv[1], sys.argv[2], sys.argv[3], \
    int(sys.argv[4])
E = np.load(os.path.join(out, "shupd_edges.npy"))
with SheepClient(sock, reconnect=40, reconnect_base_s=0.3,
                 timeout_s=600) as c:
    ep = c.epoch(jid)
    assert ep["epoch"] == last, (ep, last)  # the SIGKILL lost nothing
    # first scored epoch after the restart seeds the score cache with
    # one full pass (the snapshot carries tables, not the cache); the
    # second takes the O(delta) path — distributed, and audited
    r = c.update(jid, adds=E[13000:15000], epoch=last + 1, score=True)
    assert r["applied"] and r["epoch"] == last + 1, r
    r = c.update(jid, adds=E[15000:17000], epoch=last + 2, score=True)
    assert r["applied"] and r["epoch"] == last + 2, r
    diag = r["results"][0]["diagnostics"]
    assert diag.get("update_folds", 0) >= 2, diag
    assert diag.get("score_distributed", 0) >= 1, diag
    c.shutdown()
print(json.dumps({"epoch": last + 2,
                  "cut": r["results"][0]["edge_cut"]}))
PYEOF
wait "$SHEEPD15_PID"
python tools/trace_report.py "$TRACE15" --check \
    > "$OUT/report_shupd.txt"
grep -q '"event": "delta_epoch_applied"' "$TRACE15"

# sixteenth leg: out-of-core (ISSUE 20) — a CLI build under a
# SHEEP_CACHE_BYTES budget clamped well under the modeled working set
# (RMAT-10 x 8 at chunk 256 = 32 chunks x 2 KiB = 64 KiB resident;
# budget 20000 bytes), so the residency manager MUST evict and reload
# through the disk tier. The run is killed 4 chunks into the build
# (mid-spill: evictions have already happened), resumed from its
# checkpoint under the SAME budget into the SAME trace, and the
# resumed partition must bit-equal an UNCONSTRAINED oracle — eviction
# moves bytes, never bits. Gates: trace --check green, the resume
# seam + spill counters on the record, cmp on the partition maps.
TRACE16="$OUT/trace_oocore.jsonl"
CKPT16="$OUT/ckpt_oocore"
rm -rf "$TRACE16" "$CKPT16" "$OUT/oocore_oracle.part" "$OUT/oocore.part"
JAX_PLATFORMS=cpu python -m sheep_tpu.cli \
    --input rmat:10:8:3 --k 4 --backend tpu \
    --chunk-edges 256 --no-comm-volume \
    --output "$OUT/oocore_oracle.part" --json \
    > "$OUT/result_oocore_oracle.json"
if JAX_PLATFORMS=cpu SHEEP_CACHE_BYTES=20000 SHEEP_FAULT_INJECT=build:4 \
    python -m sheep_tpu.cli \
    --input rmat:10:8:3 --k 4 --backend tpu \
    --chunk-edges 256 --no-comm-volume \
    --checkpoint-dir "$CKPT16" --checkpoint-every 1 \
    --trace "$TRACE16" --heartbeat-secs 0.2 --json \
    > /dev/null 2> "$OUT/oocore.err"; then
    echo "budget-clamped fault run unexpectedly succeeded" >&2
    exit 1
fi
JAX_PLATFORMS=cpu SHEEP_CACHE_BYTES=20000 python -m sheep_tpu.cli \
    --input rmat:10:8:3 --k 4 --backend tpu \
    --chunk-edges 256 --no-comm-volume \
    --checkpoint-dir "$CKPT16" --resume \
    --output "$OUT/oocore.part" \
    --trace "$TRACE16" --heartbeat-secs 0.2 --json \
    > "$OUT/result_oocore.json"
python tools/trace_report.py "$TRACE16" --check > "$OUT/report_oocore.txt"
grep -q '"event": "resume"' "$TRACE16"
cmp "$OUT/oocore_oracle.part" "$OUT/oocore.part"
JAX_PLATFORMS=cpu python - "$TRACE16" <<'PYEOF'
import json
import sys

ctr = {}
with open(sys.argv[1]) as f:
    for line in f:
        e = json.loads(line)
        if e.get("event") == "counters":
            ctr = e  # counter totals ride inline on the event
# the build ran out-of-core: it evicted, re-uploaded, and never held
# more than the budget resident
assert ctr.get("spill_evictions", 0) > 0, ctr
assert ctr.get("spill_reload_bytes", 0) > 0, ctr
assert 0 < ctr.get("spill_resident_bytes", 0) <= 20000, ctr
PYEOF

# and the static gate stays at zero with the new telemetry modules in
python tools/sheeplint.py --check sheep_tpu tools > "$OUT/sheeplint.txt"

echo "obs smoke OK: $TRACE $TRACE2 $TRACE3 $TRACE4 $TRACE5 $TRACE6 $TRACE7 $TRACE8 $TRACE9 $TRACE10 $TRACE11 $TRACE12A $TRACE13 $TRACE14A $TRACE15 $TRACE16"
