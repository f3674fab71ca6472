"""sheepd / sheep_tpu.server tests (ISSUE 10).

The acceptance pins, against the in-process Scheduler (the daemon's
socket layer is exercised end-to-end by tools/obs_smoke.sh leg 6 via
test_obs_smoke, and the fault legs by tools/served_soak.py):

- a served job's forest bit-equals the cold CLI build of the same
  input, and a repeat request reuses every compiled program
  (jit_compiles == 0 — the warm-server guarantee);
- two concurrently submitted jobs INTERLEAVE on one dispatch chain
  and each bit-equals its solo run (per-job fixpoint independence);
- admission: a job over a tiny SHEEP_CACHE_BYTES budget is rejected
  with a modeled-bytes diagnosis; jobs that fit the budget but not
  the headroom queue and run serially;
- cancellation frees the queue (a queued job admits the moment the
  blocking job is cancelled);
- a deadline-expired job reports deadline_exceeded without poisoning
  the dispatch chain (the jobs around it stay bit-identical);
- the per-job fault layer: an injected OOM and an injected read fault
  each degrade the job on record, bit-identically, with the daemon
  (scheduler) still serving afterwards.
"""

import json
import os
import subprocess
import sys
import threading
import time
from contextlib import contextmanager

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from sheep_tpu.server import protocol  # noqa: E402
from sheep_tpu.server.protocol import JobSpec, ProtocolError  # noqa: E402
from sheep_tpu.server.scheduler import Scheduler  # noqa: E402

INPUT_A = "rmat:10:8:1"
INPUT_B = "rmat:10:8:2"
CHUNK = 1024


@contextmanager
def running_scheduler(**kw):
    sched = Scheduler(**kw)
    t = threading.Thread(target=sched.run, daemon=True,
                         name="test-sheepd-dispatch")
    t.start()
    try:
        yield sched
    finally:
        sched.shutdown()
        t.join(timeout=30)
        assert not t.is_alive(), "dispatch loop failed to shut down"


def spec(input=INPUT_A, ks=(4,), tenant="t", **fields):
    body = {"input": input, "k": list(ks), "chunk_edges": CHUNK}
    body.update(fields)
    return JobSpec.from_request(body, tenant=tenant)


def serve_one(sched, sp, timeout=240):
    job = sched.submit(sp)
    job = sched.wait(job.id, timeout_s=timeout)
    return job


def solo_assignment(input, k, chunk_edges=CHUNK):
    import sheep_tpu

    return sheep_tpu.partition(input, k, backend="tpu",
                               chunk_edges=chunk_edges,
                               comm_volume=False).assignment


def test_served_bit_equals_cli_build(tmp_path):
    """Acceptance: the served forest is bit-identical to the cold CLI
    build of the same input, and the scores agree."""
    out = tmp_path / "cli.parts"
    from sheep_tpu import cli

    rc = cli.main(["--input", INPUT_A, "--k", "4", "--backend", "tpu",
                   "--chunk-edges", str(CHUNK), "--no-comm-volume",
                   "--output", str(out), "--json"])
    assert rc == 0
    from sheep_tpu.io.formats import read_partition

    cli_assign = read_partition(str(out))
    with running_scheduler() as sched:
        job = serve_one(sched, spec())
        assert job.state == "done", job.error
        res = job.results[0]
        assert np.array_equal(res.assignment, cli_assign)
        assert res.backend == "sheepd"
        assert res.edge_cut > 0 and res.total_edges > 0


def test_warm_repeat_request_zero_recompiles():
    """Acceptance: a warm sheepd serves a repeat request with ZERO jit
    recompilation — the compile-cache counter on the job descriptor
    proves the fixpoint/degree/order/score programs were reused."""
    with running_scheduler() as sched:
        first = serve_one(sched, spec())
        repeat = serve_one(sched, spec(tenant="again"))
        assert first.state == "done" and repeat.state == "done"
        assert repeat.jit_compiles == 0, \
            f"repeat shape recompiled {repeat.jit_compiles} programs"
        assert np.array_equal(first.results[0].assignment,
                              repeat.results[0].assignment)


def test_interleaved_jobs_bit_equal_solo_runs():
    """Acceptance: two concurrently submitted jobs interleave on one
    dispatch chain and EACH produces the forest of its solo run."""
    ref_a = solo_assignment(INPUT_A, 4)
    ref_b = solo_assignment(INPUT_B, 4)
    with running_scheduler() as sched:
        ja = sched.submit(spec(INPUT_A, tenant="alice"))
        jb = sched.submit(spec(INPUT_B, tenant="bob"))
        ja = sched.wait(ja.id, timeout_s=240)
        jb = sched.wait(jb.id, timeout_s=240)
        assert ja.state == "done" and jb.state == "done"
        # genuinely concurrent: each started before the other finished
        assert ja.start_t < jb.end_t and jb.start_t < ja.end_t
        assert np.array_equal(ja.results[0].assignment, ref_a)
        assert np.array_equal(jb.results[0].assignment, ref_b)


def test_multi_k_query_one_shared_tree():
    """Multi-k from one shared tree is one served query: one build,
    one scoring pass, per-k results matching the solo builds."""
    with running_scheduler() as sched:
        job = serve_one(sched, spec(ks=(4, 8)))
        assert job.state == "done"
        assert [r.k for r in job.results] == [4, 8]
        for r in job.results:
            assert np.array_equal(r.assignment,
                                  solo_assignment(INPUT_A, r.k))
        # one build amortized: the per-k phase walls are shared
        assert job.results[0].total_edges == job.results[1].total_edges


def test_admission_rejects_over_tiny_budget(monkeypatch):
    """Acceptance: under a tiny SHEEP_CACHE_BYTES budget the job's
    modeled footprint cannot fit even at dispatch_batch=1 — REJECTED
    with the modeled-bytes diagnosis, not queued forever."""
    monkeypatch.setenv("SHEEP_CACHE_BYTES", "10000")
    with running_scheduler() as sched:
        assert sched.budget == 10000
        job = serve_one(sched, spec(), timeout=30)
        assert job.state == "rejected"
        assert "admission budget" in (job.error or "")
        assert "10,000" in job.error


def test_admission_queues_on_headroom_then_serializes():
    """Two jobs that each fit the budget but not together: the second
    queues and starts only after the first releases its reservation."""
    from sheep_tpu.utils import membudget

    n = 1 << 10
    m = membudget.build_phase_bytes(n, CHUNK,
                                    dispatch_batch=1)["total_bytes"]
    with running_scheduler(budget_bytes=int(1.5 * m)) as sched:
        ja = sched.submit(spec(INPUT_A, dispatch_batch=1))
        jb = sched.submit(spec(INPUT_B, dispatch_batch=1))
        ja = sched.wait(ja.id, timeout_s=240)
        jb = sched.wait(jb.id, timeout_s=240)
        assert ja.state == "done" and jb.state == "done"
        assert jb.start_t >= ja.end_t, \
            "second job admitted before the first released its bytes"


def test_cancellation_frees_the_queue():
    """Acceptance: cancelling the running job admits the queued one
    immediately; cancelling a queued job removes it outright."""
    from sheep_tpu.utils import membudget

    # budget fits the big victim alone; the small jobs queue behind it
    mv = membudget.build_phase_bytes(1 << 12, 256,
                                     dispatch_batch=1)["total_bytes"]
    with running_scheduler(budget_bytes=int(1.1 * mv)) as sched:
        victim = sched.submit(JobSpec.from_request(
            {"input": "rmat:12:8:3", "k": [4], "chunk_edges": 256,
             "dispatch_batch": 1}, tenant="victim"))
        jb = sched.submit(spec(INPUT_B, dispatch_batch=1))
        jc = sched.submit(spec(INPUT_A, dispatch_batch=1))
        # cancel the queued c first: it must leave the queue now
        assert sched.cancel(jc.id) == "cancelled"
        deadline = time.monotonic() + 30
        while sched.get(victim.id).state == "queued" \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        sched.cancel(victim.id)
        victim = sched.wait(victim.id, timeout_s=60)
        jb = sched.wait(jb.id, timeout_s=240)
        assert victim.state == "cancelled"
        assert jb.state == "done", jb.error
        assert np.array_equal(jb.results[0].assignment,
                              solo_assignment(INPUT_B, 4))


def test_deadline_exceeded_does_not_poison_the_chain():
    """Acceptance: a deadline-expired job reports deadline_exceeded;
    the jobs interleaved around it finish bit-identical — the dispatch
    chain is not poisoned."""
    ref_b = solo_assignment(INPUT_B, 4)
    with running_scheduler() as sched:
        doomed = sched.submit(JobSpec.from_request(
            {"input": "rmat:12:8:3", "k": [4], "chunk_edges": 256,
             "deadline_s": 0.005}, tenant="doomed"))
        jb = sched.submit(spec(INPUT_B, tenant="bob"))
        doomed = sched.wait(doomed.id, timeout_s=120)
        jb = sched.wait(jb.id, timeout_s=240)
        assert doomed.state == "deadline_exceeded"
        assert jb.state == "done", jb.error
        assert np.array_equal(jb.results[0].assignment, ref_b)
        # and the daemon keeps serving: one more job end-to-end
        again = serve_one(sched, spec(INPUT_B))
        assert again.state == "done"
        assert np.array_equal(again.results[0].assignment, ref_b)


def test_served_job_absorbs_oom_and_read_faults(tmp_path, monkeypatch):
    """The served mini-soak's tier-1 twin (the full daemon-subprocess
    version is tools/served_soak.py, pinned @slow below): one injected
    OOM at the first dispatch and one injected read fault each degrade
    the JOB on record — bit-identical result, retry trail in the
    diagnostics — with the scheduler still serving afterwards."""
    from sheep_tpu.io import formats, generators
    from sheep_tpu.utils import fault

    graph = str(tmp_path / "soak.bin64")
    formats.write_edges(graph,
                        generators.random_graph(512, 4096, seed=7))
    ref = None
    with running_scheduler() as sched:
        clean = serve_one(sched, JobSpec.from_request(
            {"input": graph, "k": [4], "chunk_edges": 512,
             "num_vertices": 512}, tenant="clean"))
        assert clean.state == "done"
        ref = clean.results[0].assignment
        for inject, want_retry in (("oom@dispatch:1", True),
                                   ("read@read:2", False)):
            monkeypatch.setenv("SHEEP_FAULT_INJECT", inject)
            monkeypatch.setenv("SHEEP_RETRY_BASE_S", "0.01")
            fault.reset()
            try:
                job = serve_one(sched, JobSpec.from_request(
                    {"input": graph, "k": [4], "chunk_edges": 512,
                     "num_vertices": 512}, tenant=inject))
            finally:
                monkeypatch.delenv("SHEEP_FAULT_INJECT")
                fault.reset()
            assert job.state == "done", (inject, job.error)
            assert np.array_equal(job.results[0].assignment, ref), inject
            if want_retry:
                assert job.stats.get("dispatch_retries", 0) >= 1, \
                    "OOM injection left no retry trail"


def test_job_fault_budget_exhaustion_fails_job_not_daemon(monkeypatch):
    """A fault storm beyond the retry budget fails THAT job; the
    scheduler answers the next request normally."""
    from sheep_tpu.utils import fault

    monkeypatch.setenv("SHEEP_FAULT_INJECT", "oom@dispatch:1:99")
    monkeypatch.setenv("SHEEP_RETRY_BASE_S", "0.001")
    monkeypatch.setenv("SHEEP_RETRY_MAX", "2")
    fault.reset()
    with running_scheduler() as sched:
        doomed = serve_one(sched, spec(tenant="doomed"))
        assert doomed.state == "failed"
        assert "RESOURCE_EXHAUSTED" in doomed.error
        monkeypatch.delenv("SHEEP_FAULT_INJECT")
        fault.reset()
        ok = serve_one(sched, spec(tenant="after"))
        assert ok.state == "done", ok.error


def test_refused_dispatch_batch_fails_job_not_daemon(monkeypatch):
    """A job asking for the dispatch batch the platform's compiler
    aborts on (N=2 on a TPU, here reported so on the CPU) fails THAT
    job before any fold compiles; the next request is served."""
    from sheep_tpu.backends import tpu_backend as tb

    monkeypatch.setattr(tb, "refused_dispatch_batch", lambda: 2)
    with running_scheduler() as sched:
        doomed = serve_one(sched, spec(tenant="doomed", dispatch_batch=2))
        assert doomed.state == "failed"
        assert "dispatch_batch=2 is refused" in doomed.error
        ok = serve_one(sched, spec(tenant="after", dispatch_batch=4))
        assert ok.state == "done", ok.error


@pytest.mark.parametrize("field", ["dispatch_batch", "inflight"])
def test_protocol_dispatch_knobs_default_to_one(field):
    """The in-job dispatch knobs default to 1 (per-segment, synchronous)
    and have no 0 = auto spelling."""
    assert getattr(JobSpec.from_request({"input": "g", "k": 4}), field) == 1
    with pytest.raises(ProtocolError, match=f"{field} must be >= 1"):
        JobSpec.from_request({"input": "g", "k": 4, field: 0})


def test_protocol_validation_and_codec():
    with pytest.raises(ProtocolError):
        JobSpec.from_request({"k": [4]})          # no input
    with pytest.raises(ProtocolError):
        JobSpec.from_request({"input": "g", "k": []})
    with pytest.raises(ProtocolError):
        JobSpec.from_request({"input": "g", "k": [0]})
    with pytest.raises(ProtocolError):
        JobSpec.from_request({"input": "g", "k": 4, "bogus": 1})
    with pytest.raises(ProtocolError):
        JobSpec.from_request({"input": "g", "k": 4, "deadline_s": -1})
    sp = JobSpec.from_request({"input": "g", "k": [8, 8, 4]})
    assert sp.ks == [8, 4]  # dupes dropped, order kept
    # update_backend (ISSUE 19): resident epochs may fold multi-device
    assert sp.update_backend == "tpu"  # the single-device default
    sh = JobSpec.from_request({"input": "g", "k": 4, "resident": True,
                               "update_backend": "tpu-sharded"})
    assert sh.update_backend == "tpu-sharded"
    with pytest.raises(ProtocolError, match="update_backend"):
        JobSpec.from_request({"input": "g", "k": 4,
                              "update_backend": "gpu"})
    a = np.arange(1000, dtype=np.int32) % 7
    assert np.array_equal(
        protocol.decode_assignment(protocol.encode_assignment(a)), a)
    with pytest.raises(ProtocolError):
        protocol.parse_request(b'{"op": "frobnicate"}')
    req = protocol.parse_request(b'{"op": "ping"}')
    assert req["op"] == "ping"


def test_terminal_jobs_evicted_beyond_retention_cap(monkeypatch):
    """A resident daemon must not grow host memory monotonically with
    traffic: terminal jobs (and their result arrays) beyond the
    retention cap are evicted oldest-first (review finding)."""
    monkeypatch.setattr(Scheduler, "MAX_TERMINAL_RETAINED", 3)
    with running_scheduler() as sched:
        ids = [serve_one(sched, spec(tenant=f"t{i}")).id
               for i in range(5)]
        assert sched.get(ids[0]) is None and sched.get(ids[1]) is None
        for jid in ids[2:]:
            assert sched.get(jid) is not None
            assert sched.get(jid).state == "done"


def test_submit_unopenable_input_is_answered_not_enqueued():
    with running_scheduler() as sched:
        with pytest.raises(ProtocolError, match="cannot open"):
            sched.submit(spec("/nonexistent/graph.bin64"))
        assert sched.stats()["jobs"]["submitted"] == 0


# ---------------------------------------------------------------------------
# live telemetry plane (ISSUE 11)
# ---------------------------------------------------------------------------

def test_metrics_expose_per_tenant_latency_under_two_jobs():
    """Acceptance: with two concurrent tenant jobs served, the
    scheduler's Prometheus rendering carries per-tenant request-latency
    histograms, live queue/reservation gauges, and the submitted/
    terminal counters — the series a replica router would route on."""
    from sheep_tpu.obs.metrics import parse_prometheus

    with running_scheduler() as sched:
        ja = sched.submit(spec(INPUT_A, tenant="alice"))
        jb = sched.submit(spec(INPUT_B, tenant="bob"))
        ja = sched.wait(ja.id, timeout_s=240)
        jb = sched.wait(jb.id, timeout_s=240)
        assert ja.state == "done" and jb.state == "done"
        assert ja.start_t < jb.end_t and jb.start_t < ja.end_t
        parsed = parse_prometheus(sched.render_metrics())
    counts = dict()
    for labels, v in parsed["sheepd_request_latency_seconds_count"]:
        counts[labels["tenant"]] = v
    assert counts == {"alice": 1.0, "bob": 1.0}
    assert ({"le": "+Inf", "tenant": "alice"}, 1.0) in \
        parsed["sheepd_request_latency_seconds_bucket"]
    assert parsed["sheepd_queue_depth"][0][1] == 0.0
    assert parsed["sheepd_active_jobs"][0][1] == 0.0
    submitted = {lb["tenant"]: v
                 for lb, v in parsed["sheepd_jobs_submitted_total"]}
    assert submitted == {"alice": 1.0, "bob": 1.0}
    done = {(lb["tenant"], lb["state"]): v
            for lb, v in parsed["sheepd_jobs_terminal_total"]}
    assert done[("alice", "done")] == 1.0
    # queue-wait observed for both admissions
    qw = {lb["tenant"]: v
          for lb, v in parsed["sheepd_queue_wait_seconds_count"]}
    assert qw == {"alice": 1.0, "bob": 1.0}
    # live progress surfaced while running: phase/steps on descriptors
    assert ja.phase == "score" and ja.steps > 0
    assert ja.descriptor()["phase"] == "score"
    # the quality plane (ISSUE 13): per-tenant cut/balance
    # distributions observed at DONE, per-job gauges for recent
    # results, and the engine's job_quality value matching the
    # scraped gauge exactly
    qcut = {lb["tenant"]: v
            for lb, v in parsed["sheep_quality_cut_ratio_count"]}
    assert qcut == {"alice": 1.0, "bob": 1.0}
    qbal = {lb["tenant"]: v
            for lb, v in parsed["sheep_quality_balance_count"]}
    assert qbal == {"alice": 1.0, "bob": 1.0}
    jobs_cut = {lb["job"]: v
                for lb, v in parsed["sheep_quality_job_cut_ratio"]}
    assert jobs_cut[ja.id] == pytest.approx(
        float(ja.results[0].cut_ratio), abs=1e-6)
    jobs_bal = {(lb["job"], lb["k"]): v
                for lb, v in parsed["sheep_quality_job_balance"]}
    assert jobs_bal[(jb.id, "4")] == pytest.approx(
        float(jb.results[0].balance), abs=1e-4)


def test_active_job_progress_gauges_live_mid_build():
    """Mid-build scrape shows the per-active-job progress gauges and a
    nonzero active count; the gauges leave the scrape once the job is
    terminal (no frozen series)."""
    from sheep_tpu.obs.metrics import parse_prometheus

    with running_scheduler() as sched:
        job = sched.submit(JobSpec.from_request(
            {"input": "rmat:12:8:3", "k": [4], "chunk_edges": 256},
            tenant="alice"))
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if sched.get(job.id).steps > 0:
                break
            time.sleep(0.01)
        parsed = parse_prometheus(sched.render_metrics())
        assert parsed["sheepd_active_jobs"][0][1] >= 1.0
        rows = parsed.get("sheepd_job_steps", [])
        assert any(lb == {"job": job.id, "tenant": "alice"} and v >= 1
                   for lb, v in rows), rows
        job = sched.wait(job.id, timeout_s=240)
        assert job.state == "done"
        parsed = parse_prometheus(sched.render_metrics())
        assert not parsed.get("sheepd_job_steps")


def test_failed_job_leaves_flight_dump_with_fault_event(tmp_path,
                                                        monkeypatch):
    """Acceptance: a job failed by an injected fault leaves a
    flight-recorder dump in the trace containing the fault event —
    and trace_report --last-errors renders it."""
    from sheep_tpu import obs
    from sheep_tpu.utils import fault

    trace = tmp_path / "served.jsonl"
    monkeypatch.setenv("SHEEP_FAULT_INJECT", "oom@dispatch:1:99")
    monkeypatch.setenv("SHEEP_RETRY_BASE_S", "0.001")
    monkeypatch.setenv("SHEEP_RETRY_MAX", "2")
    fault.reset()
    try:
        with obs.tracing(str(trace)):
            with running_scheduler() as sched:
                doomed = serve_one(sched, spec(tenant="doomed"))
                assert doomed.state == "failed"
    finally:
        monkeypatch.delenv("SHEEP_FAULT_INJECT")
        fault.reset()
    dumps = [json.loads(line) for line in
             trace.read_text().splitlines()
             if '"flight_dump"' in line]
    failed = [d for d in dumps if d["job"] == doomed.id
              and d["reason"].startswith("job_failed")]
    assert failed, [d.get("reason") for d in dumps]
    kinds = [e["ev"] for e in failed[-1]["events"]]
    assert "fault_inject" in kinds and "retry" in kinds
    assert "job_done" in kinds  # the terminal event made the ring
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_report.py"),
         str(trace), "--last-errors", "6"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0
    assert "job_failed" in r.stdout and "fault_inject" in r.stdout


def test_daemon_metrics_verb_http_scrape_and_profile(tmp_path):
    """The daemon end of the tentpole, in-process: the `metrics` verb
    and HTTP GET /metrics answer the same exposition, and the
    `profile` verb captures the next K dispatch steps into the
    requested directory."""
    import urllib.request

    from sheep_tpu.server.client import SheepClient, ServerError
    from sheep_tpu.server.daemon import Daemon, build_parser

    sock = str(tmp_path / "d.sock")
    prof_dir = str(tmp_path / "prof")
    args = build_parser().parse_args(
        ["--socket", sock, "--metrics-port", "0"])
    d = Daemon(args)
    t = threading.Thread(target=d.serve, daemon=True,
                         name="test-sheepd")
    t.start()
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if os.path.exists(sock) and d.metrics_port:
            break
        time.sleep(0.05)
    assert os.path.exists(sock), "daemon never bound its socket"
    try:
        with SheepClient(sock) as c:
            prof = c.profile(prof_dir, steps=2)
            assert prof["state"] == "armed"
            with pytest.raises(ServerError, match="already"):
                c.profile(prof_dir, steps=2)
            jid = c.submit(INPUT_A, k=4, tenant="alice",
                           chunk_edges=CHUNK)["job_id"]
            job = c.wait(jid, timeout_s=240)
            assert job["state"] == "done"
            verb_text = c.metrics()
            http_text = urllib.request.urlopen(
                f"http://127.0.0.1:{d.metrics_port}/metrics",
                timeout=10).read().decode()
            for text in (verb_text, http_text):
                assert 'sheepd_request_latency_seconds_count' \
                       '{tenant="alice"} 1' in text
                assert "sheepd_queue_depth" in text
            assert c.stats()["profile"]["state"] == "done"
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{d.metrics_port}/nope",
                    timeout=10)
            c.shutdown()
    finally:
        t.join(timeout=60)
    assert not t.is_alive(), "daemon failed to shut down"
    captured = [f for _, _, fs in os.walk(prof_dir) for f in fs]
    assert captured, "profile verb captured nothing into the dir"


def test_profile_arm_validation():
    with running_scheduler() as sched:
        with pytest.raises(ProtocolError):
            sched.arm_profile("/tmp/x", steps=0)
        with pytest.raises(ProtocolError):
            sched.arm_profile("/tmp/x", steps="nope")


def test_profile_capture_stops_when_jobs_drain(tmp_path):
    """Regression: a capture armed for more steps than the job set
    will ever take must STOP when the daemon goes idle (an open
    jax.profiler capture grows host memory forever and blocks every
    re-arm) — and the next arm succeeds."""
    with running_scheduler() as sched:
        sched.arm_profile(str(tmp_path / "p1"), steps=10_000)
        job = serve_one(sched, spec())
        assert job.state == "done"
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            prof = sched.stats()["profile"]
            if prof and prof.get("state") in ("aborted", "done",
                                              "error"):
                break
            time.sleep(0.05)
        assert prof["state"] == "aborted", prof
        assert prof["steps_captured"] >= 1
        assert "remaining" not in prof  # internals stay internal
        # the slot is free again
        assert sched.arm_profile(str(tmp_path / "p2"),
                                 steps=5)["state"] == "armed"


@pytest.mark.slow
def test_served_soak_tool():
    """The full daemon-subprocess mini-soak: one oom + one read leg,
    plus the durable restart (SIGKILL) and drain (SIGTERM) legs,
    through real sheepds on unix sockets (see tools/served_soak.py);
    the tier-1 twins (here and tests/test_journal.py) cover the same
    faults in-process."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "served_soak.py")],
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu",
                       "PYTHONPATH": REPO},
        capture_output=True, text=True, timeout=1200)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    verdicts = [json.loads(line) for line in r.stdout.splitlines()]
    assert verdicts[-1]["ok"] is True


def _await_published(sched, digest, timeout=30.0):
    """The store publish runs post-terminal on the dispatch thread —
    poll the advisory lookup until the digest lands."""
    deadline = time.monotonic() + timeout
    while not sched.lookup_digest(digest) \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert sched.lookup_digest(digest), "store publish never landed"


def test_result_cache_hit_zero_steps_bit_identical(tmp_path):
    """Acceptance (ISSUE 16): a repeat submit of the same digest is
    answered FROM THE STORE — zero dispatch steps, zero compiles —
    and the decoded assignment + scores bit-equal the original."""
    with running_scheduler(result_store=str(tmp_path / "rs")) as sched:
        first = serve_one(sched, spec())
        assert first.state == "done", first.error
        assert first.stats.get("result_cache_hit") is None
        _await_published(sched, first.digest)
        repeat = serve_one(sched, spec())
        assert repeat.state == "done", repeat.error
        assert repeat.stats.get("result_cache_hit") == 1
        assert repeat.steps == 0, "a cache hit must never dispatch"
        assert repeat.jit_compiles == 0
        fr, rr = first.results[0], repeat.results[0]
        assert np.array_equal(fr.assignment, rr.assignment)
        assert (fr.edge_cut, fr.total_edges, fr.balance) \
            == (rr.edge_cut, rr.total_edges, rr.balance)
        # metrics plane: the hit and the miss both counted
        text = sched.metrics.render()
        assert "sheepd_result_cache_hits_total" in text
        assert "sheepd_result_cache_misses_total" in text


def test_result_cache_digest_sensitivity(tmp_path):
    """A different spec (other k) must MISS: content addressing keys
    the full spec digest, not the input alone."""
    with running_scheduler(result_store=str(tmp_path / "rs")) as sched:
        first = serve_one(sched, spec(ks=(4,)))
        _await_published(sched, first.digest)
        other = serve_one(sched, spec(ks=(8,)))
        assert other.state == "done", other.error
        assert other.stats.get("result_cache_hit") is None
        assert other.steps > 0


def test_resident_jobs_bypass_result_cache(tmp_path):
    """Resident submits carry incremental state a cached answer lacks
    — they must build even when the digest is stored."""
    with running_scheduler(result_store=str(tmp_path / "rs")) as sched:
        first = serve_one(sched, spec())
        _await_published(sched, first.digest)
        res = serve_one(sched, spec(resident=True))
        assert res.state == "done", res.error
        assert res.stats.get("result_cache_hit") is None
        assert res.steps > 0
        sched.cancel(res.id)  # release the residency reservation


@pytest.mark.parametrize("depth", (2, 3))
def test_pipelined_dispatch_bit_identical_to_depth_1(depth):
    """Acceptance (ISSUE 16): depth-D in-job pipelining reorders only
    WHEN host syncs happen, never what is computed — the forest
    bit-equals the depth-1 build."""
    with running_scheduler() as sched:
        base = serve_one(sched, spec(INPUT_B, inflight=1))
        piped = serve_one(sched, spec(INPUT_B, inflight=depth,
                                      tenant=f"d{depth}"))
        assert base.state == "done" and piped.state == "done", \
            (base.error, piped.error)
        assert piped.stats.get("inflight_depth") == depth
        assert np.array_equal(base.results[0].assignment,
                              piped.results[0].assignment)
        assert base.results[0].edge_cut == piped.results[0].edge_cut


def test_pipelined_checkpoint_resume_bit_identical(tmp_path):
    """A checkpoint taken mid-pipeline only covers CONFIRMED groups;
    resume re-folds the unconfirmed tail and still bit-equals the
    uninterrupted build."""
    ref = solo_assignment(INPUT_A, 4)
    with running_scheduler(checkpoint_every=2,
                           checkpoint_dir=str(tmp_path)) as sched:
        job = serve_one(sched, spec(inflight=2))
        assert job.state == "done", job.error
        assert np.array_equal(job.results[0].assignment, ref)


def test_concurrent_same_input_jobs_share_chunk_cache():
    """Two live jobs on ONE input: the second rides the first's device
    chunk cache as a reader (no duplicate device residency), both
    bit-equal the solo run."""
    ref = solo_assignment(INPUT_A, 4)
    with running_scheduler() as sched:
        ja = sched.submit(spec(INPUT_A, tenant="alice"))
        jb = sched.submit(spec(INPUT_A, tenant="bob", ks=(4,)))
        ja = sched.wait(ja.id, timeout_s=240)
        jb = sched.wait(jb.id, timeout_s=240)
        assert ja.state == "done" and jb.state == "done", \
            (ja.error, jb.error)
        assert np.array_equal(ja.results[0].assignment, ref)
        assert np.array_equal(jb.results[0].assignment, ref)


def test_pipelined_interleaved_overlap(tmp_path):
    """Acceptance (ISSUE 16): depth-2 pipelining turns an engine step
    into one CONFIRMED execution instead of one drained group, so two
    interleaved jobs overlap one job's host staging with the other's
    device folds — the interleaved wall lands under the sum of the
    solo walls. Host-format (text) inputs make staging real host
    work, and every serve gets a fresh path so the shared chunk
    cache cannot hide it. Wall-clock is noisy under CI load: any of
    three attempts under the 0.9 bar passes; a true serialization
    regression (ratio pinned at ~1.0) fails all three."""
    from sheep_tpu.io import formats, generators

    def fresh(seed, tag):
        st = generators.RmatHashStream(14, 8, seed=seed)
        es = np.concatenate([np.asarray(c)
                             for c in st.chunks(1 << 20)])
        p = str(tmp_path / f"{tag}.edges")
        formats.write_edges(p, es)
        return p

    def sp(path, tenant):
        return JobSpec.from_request(
            {"input": path, "k": [4], "chunk_edges": 4096,
             "inflight": 2}, tenant=tenant)

    with running_scheduler() as sched:
        def serve(s):
            job = sched.submit(s)
            job = sched.wait(job.id, timeout_s=240)
            assert job.state == "done", job.error

        serve(sp(fresh(9, "warm"), "warm"))  # compile warm-up
        ratios = []
        for attempt in range(3):
            t0 = time.perf_counter()
            serve(sp(fresh(1, f"solo_a{attempt}"), f"sa{attempt}"))
            solo_a = time.perf_counter() - t0
            t0 = time.perf_counter()
            serve(sp(fresh(2, f"solo_b{attempt}"), f"sb{attempt}"))
            solo_b = time.perf_counter() - t0
            pa = fresh(1, f"int_a{attempt}")
            pb = fresh(2, f"int_b{attempt}")
            t0 = time.perf_counter()
            ja = sched.submit(sp(pa, f"ia{attempt}"))
            jb = sched.submit(sp(pb, f"ib{attempt}"))
            ja = sched.wait(ja.id, timeout_s=240)
            jb = sched.wait(jb.id, timeout_s=240)
            wall = time.perf_counter() - t0
            assert ja.state == "done" and jb.state == "done", \
                (ja.error, jb.error)
            ratios.append(round(wall / (solo_a + solo_b), 3))
            if ratios[-1] < 0.9:
                return
        pytest.fail(
            f"no dispatch overlap measured: interleaved/sum ratios "
            f"{ratios} (expected < 0.9 in at least one attempt)")

def test_fleet_job_handles_survive_replica_id_collision():
    """Daemon job ids are per-process counters, so two replicas
    routinely both mint "j1". The fleet client must never guess
    between them: descriptors (endpoint + job_id) resolve exactly,
    and a bare id is honored only while unambiguous."""
    from sheep_tpu.server.client import FleetClient, ServerError

    fleet = FleetClient(["/run/a.sock", "/run/b.sock"])
    fleet._jobs[("/run/a.sock", "j1")] = (INPUT_A, [4], "alice", {})
    assert fleet._resolve("j1") == ("/run/a.sock", "j1")
    fleet._jobs[("/run/b.sock", "j1")] = (INPUT_B, [4], "bob", {})
    with pytest.raises(ServerError, match="ambiguous"):
        fleet._resolve("j1")
    assert fleet._resolve(
        {"endpoint": "/run/b.sock", "job_id": "j1"}) \
        == ("/run/b.sock", "j1")
    assert fleet._resolve(
        {"endpoint": "/run/a.sock", "job_id": "j1"}) \
        == ("/run/a.sock", "j1")
    with pytest.raises(ServerError, match="unknown fleet job"):
        fleet._resolve("j9")
