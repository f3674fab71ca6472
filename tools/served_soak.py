#!/usr/bin/env python
"""Served-job mini-soak (ISSUE 10 satellite; chaos_soak's pattern
applied to sheepd): inject one OOM-class fault, one read fault, one
SIGKILL, one SIGTERM drain and one replica kill under fleet routing
into served jobs and assert the DAEMON (or its restarted incarnation,
or the surviving replica) delivers the job with the verdict
``identical`` or ``degraded_documented``.

    python tools/served_soak.py [--out DIR]

Five legs, each against REAL ``sheepd`` subprocesses on unix sockets
over a real on-disk graph (so the edgestream read points are live):

    oom      SHEEP_FAULT_INJECT=oom@dispatch:1 — RESOURCE_EXHAUSTED at
             the first issued dispatch of the served build; the per-job
             retry layer must degrade/re-fold bit-identically and leave
             the ``dispatch_retries`` trail in the job diagnostics.
    read     SHEEP_FAULT_INJECT=read@read:2 — a torn physical read; the
             edgestream's bounded transient retry absorbs it below the
             scheduler entirely.
    restart  (ISSUE 14) SIGKILL the durable daemon mid-build, restart
             it on the same socket/journal/state dir: the journaled job
             must RESUME from its per-job checkpoint (the
             ``sheepd_jobs_resumed_total`` counter is required — a leg
             where the kill landed after completion proved nothing) and
             finish bit-identical to the clean oracle.
    drain    (ISSUE 14) SIGTERM the durable daemon mid-build: it must
             exit rc=0 after checkpointing the job at its next flush
             barrier (the graceful drain), and the restarted daemon
             must resume it to a bit-identical finish.
    fleet    (ISSUE 16) two replicas behind the FleetClient: headroom
             routing must SPLIT concurrent jobs across both (route
             counters nonzero on each), then one replica is SIGKILLed
             mid-build and EVERY job must still complete via the
             reattach-idempotent failover resubmit, each forest
             bit-equal to the clean oracle.

Per leg the verdict is exactly chaos_soak's classification:

    identical            served assignment bit-equals the clean oracle
    degraded_documented  differs, but the job carries a documented
                         degradation marker (quarantined chunks)
    wrong_forest         differs with NO documentation — a real bug
    unhandled_crash      the job failed, the daemon died (or, durable
                         legs: never resumed / drain exited nonzero),
                         or it stopped answering pings after the fault

After each job the daemon must still answer ``ping`` (the fault
degraded the JOB, not the service) and must shut down rc=0. Exit 0
iff every leg is identical/degraded_documented; wired tier-1 by
tests/test_server.py.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

LEGS = (
    ("oom", "oom@dispatch:1"),
    ("read", "read@read:2"),
)

# the durable legs (ISSUE 14) kill/drain the daemon MID-BUILD; the
# graph is bigger and the chunks smaller so the build phase has
# dozens of observable steps to land the signal in
DURABLE_V = 4096
DURABLE_E = 32768
DURABLE_CHUNK = 256


def build_graph(path: str, n: int = 512, m: int = 4096) -> None:
    from sheep_tpu.io import formats, generators

    formats.write_edges(path, generators.random_graph(n, m, seed=7))


def clean_oracle(path: str, n: int = 512, chunk_edges: int = 512):
    """The fault-free reference assignment, computed in THIS process
    (the daemons never see a fault-free run — the oracle must not)."""
    from sheep_tpu import _partition_stream
    from sheep_tpu.io.edgestream import open_input

    with open_input(path, n_vertices=n) as es:
        res = _partition_stream(es, 4, backend="tpu",
                                chunk_edges=chunk_edges,
                                comm_volume=False)
    return res.assignment


def run_leg(name: str, inject: str, graph: str, out_dir: str,
            oracle) -> dict:
    import numpy as np

    from sheep_tpu.server.client import ServerError, SheepClient

    sock = os.path.join(out_dir, f"soak_{name}.sock")
    trace = os.path.join(out_dir, f"soak_{name}.jsonl")
    err_path = os.path.join(out_dir, f"soak_{name}.err")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           "SHEEP_FAULT_INJECT": inject, "SHEEP_RETRY_BASE_S": "0.01"}
    rec = {"leg": name, "inject": inject}
    with open(err_path, "w") as err_f:
        proc = subprocess.Popen(
            [sys.executable, "-m", "sheep_tpu.server.daemon",
             "--socket", sock, "--trace", trace,
             "--heartbeat-secs", "0.2"],
            cwd=REPO, env=env, stderr=err_f)
    try:
        for _ in range(150):
            if os.path.exists(sock) or proc.poll() is not None:
                break
            time.sleep(0.2)
        if not os.path.exists(sock):
            rec["verdict"] = "unhandled_crash"
            rec["error"] = f"daemon never bound (rc={proc.poll()})"
            return rec
        with SheepClient(sock) as c:
            try:
                r = c.submit(graph, k=4, tenant="soak",
                             chunk_edges=512, num_vertices=512,
                             return_assignment=True)
                job = c.wait(r["job_id"], timeout_s=120)
            except ServerError as e:
                rec["verdict"] = "unhandled_crash"
                rec["error"] = f"daemon refused the job: {e}"
                return rec
            rec["state"] = job.get("state")
            diags = (job.get("results") or [{}])[0].get(
                "diagnostics", {})
            rec["dispatch_retries"] = diags.get("dispatch_retries")
            # the daemon must still be serving AFTER the fault
            try:
                c.ping()
            except (ServerError, OSError) as e:
                rec["verdict"] = "unhandled_crash"
                rec["error"] = f"daemon dead after fault: {e}"
                return rec
            if job.get("state") != "done":
                rec["verdict"] = "unhandled_crash"
                rec["error"] = job.get("error", "job not done")
                return rec
            served = c.result_assignment(job)
            if np.array_equal(served, np.asarray(oracle)):
                rec["verdict"] = "identical"
            else:
                # documented degradation = quarantined input (the only
                # lossy absorb on these paths); anything else is wrong
                quarantined = False
                try:
                    with open(trace) as f:
                        quarantined = '"chunk_quarantined"' in f.read()
                except OSError:
                    pass
                rec["verdict"] = "degraded_documented" if quarantined \
                    else "wrong_forest"
            try:
                c.shutdown()
            except (ServerError, OSError):
                pass
        proc.wait(timeout=30)
        rec["daemon_rc"] = proc.returncode
        if proc.returncode != 0:
            rec["verdict"] = "unhandled_crash"
            rec["error"] = f"daemon exit rc={proc.returncode}"
        return rec
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


def _spawn_durable_daemon(sock, trace, state_dir, err_f):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    return subprocess.Popen(
        [sys.executable, "-m", "sheep_tpu.server.daemon",
         "--socket", sock, "--trace", trace,
         "--state-dir", state_dir, "--checkpoint-every", "1",
         "--drain-grace-s", "30", "--heartbeat-secs", "0.2"],
        cwd=REPO, env=env, stderr=err_f)


def run_durable_leg(name: str, sig: int, graph: str, out_dir: str,
                    oracle) -> dict:
    """ISSUE 14: signal the durable daemon mid-build (SIGKILL for the
    restart leg, SIGTERM for the graceful drain), restart it on the
    same socket/journal, and require the job to RESUME — counter on
    the record — to a forest bit-equal to the clean oracle."""
    import numpy as np

    from sheep_tpu.obs.metrics import parse_prometheus
    from sheep_tpu.server.client import ServerError, SheepClient

    sock = os.path.join(out_dir, f"soak_{name}.sock")
    trace = os.path.join(out_dir, f"soak_{name}.jsonl")
    state_dir = os.path.join(out_dir, f"soak_{name}.state")
    err_path = os.path.join(out_dir, f"soak_{name}.err")
    rec = {"leg": name,
           "inject": "SIGKILL mid-build" if sig == signal.SIGKILL
           else "SIGTERM graceful drain mid-build"}
    err_f = open(err_path, "w")
    proc = _spawn_durable_daemon(sock, trace, state_dir, err_f)
    proc2 = None
    try:
        for _ in range(300):
            if os.path.exists(sock) or proc.poll() is not None:
                break
            time.sleep(0.2)
        if not os.path.exists(sock):
            rec["verdict"] = "unhandled_crash"
            rec["error"] = f"daemon never bound (rc={proc.poll()})"
            return rec
        with SheepClient(sock) as c:
            r = c.submit(graph, k=4, tenant="soak",
                         chunk_edges=DURABLE_CHUNK,
                         num_vertices=DURABLE_V, dispatch_batch=1,
                         return_assignment=True)
            job_id = r["job_id"]
            # land the signal INSIDE the build phase: a kill that
            # arrives after completion proves nothing
            landed = False
            for _ in range(4000):
                st = c.status(job_id)
                if st["state"] in ("done", "failed"):
                    break
                if st.get("phase") == "build" \
                        and st.get("steps", 0) >= 3:
                    landed = True
                    break
                time.sleep(0.005)
            if not landed:
                rec["verdict"] = "unhandled_crash"
                rec["error"] = (f"signal window missed: job reached "
                                f"{st.get('state')}/{st.get('phase')} "
                                f"before mid-build")
                return rec
            rec["killed_at_steps"] = st.get("steps")
        proc.send_signal(sig)
        proc.wait(timeout=120)
        rec["first_daemon_rc"] = proc.returncode
        if sig == signal.SIGTERM and proc.returncode != 0:
            rec["verdict"] = "unhandled_crash"
            rec["error"] = (f"graceful drain exited "
                            f"rc={proc.returncode}, want 0")
            return rec
        # restart on the SAME socket/journal/state dir; the stale
        # socket file (SIGKILL case) must be probed away and the
        # journaled job must come back resumable
        proc2 = _spawn_durable_daemon(sock, trace, state_dir, err_f)
        with SheepClient(sock, reconnect=40,
                         reconnect_base_s=0.3) as c:
            try:
                job = c.wait(job_id, timeout_s=300)
            except ServerError as e:
                rec["verdict"] = "unhandled_crash"
                rec["error"] = f"restarted daemon lost the job: {e}"
                return rec
            rec["state"] = job.get("state")
            metrics = parse_prometheus(c.metrics())
            rec["jobs_resumed"] = sum(
                v for _, v in
                metrics.get("sheepd_jobs_resumed_total", []))
            rec["restarts"] = sum(
                v for _, v in metrics.get("sheepd_restarts_total", []))
            if job.get("state") != "done":
                rec["verdict"] = "unhandled_crash"
                rec["error"] = job.get("error", "job not done")
                return rec
            served = c.result_assignment(job)
            rec["verdict"] = "identical" if np.array_equal(
                served, np.asarray(oracle)) else "wrong_forest"
            try:
                c.shutdown()
            except (ServerError, OSError):
                pass
        proc2.wait(timeout=60)
        rec["daemon_rc"] = proc2.returncode
        if proc2.returncode != 0:
            rec["verdict"] = "unhandled_crash"
            rec["error"] = f"restarted daemon exit rc={proc2.returncode}"
        return rec
    finally:
        for p in (proc, proc2):
            if p is not None and p.poll() is None:
                p.kill()
                p.wait(timeout=10)
        err_f.close()


def run_fleet_leg(graph: str, out_dir: str, oracle) -> dict:
    """ISSUE 16: two replicas behind the fleet client. Headroom
    routing must SPLIT concurrent jobs across both replicas, then
    replica a is SIGKILLed mid-build and every job must still finish
    via the reattach-idempotent failover resubmit — each served
    forest bit-equal to the clean oracle (including any answered from
    the survivor's result store)."""
    import numpy as np

    from sheep_tpu.server.client import (FleetClient, ServerError,
                                         SheepClient)

    rec = {"leg": "fleet", "inject": "SIGKILL replica a mid-build"}
    socks, procs, errs = [], [], []
    try:
        for tag in ("a", "b"):
            sock = os.path.join(out_dir, f"soak_fleet_{tag}.sock")
            trace = os.path.join(out_dir, f"soak_fleet_{tag}.jsonl")
            state = os.path.join(out_dir, f"soak_fleet_{tag}.state")
            err_f = open(os.path.join(out_dir,
                                      f"soak_fleet_{tag}.err"), "w")
            errs.append(err_f)
            socks.append(sock)
            procs.append(_spawn_durable_daemon(sock, trace, state,
                                               err_f))
        for _ in range(300):
            if all(os.path.exists(s) for s in socks):
                break
            if any(p.poll() is not None for p in procs):
                break
            time.sleep(0.2)
        if not all(os.path.exists(s) for s in socks):
            rec["verdict"] = "unhandled_crash"
            rec["error"] = "a fleet replica never bound"
            return rec
        with FleetClient(socks) as fleet:
            # three concurrent jobs; the short sleep lets each
            # replica's load gauges see the previous admit so the
            # headroom sort actually alternates
            jobs = []
            for _ in range(3):
                jobs.append(fleet.submit(
                    graph, k=4, tenant="fleet",
                    chunk_edges=DURABLE_CHUNK, num_vertices=DURABLE_V,
                    dispatch_batch=1, return_assignment=True))
                time.sleep(0.5)
            rec["route_counts"] = dict(fleet.route_counts)
            if len({r["endpoint"] for r in jobs}) < 2:
                rec["verdict"] = "unhandled_crash"
                rec["error"] = (f"headroom routing never split: "
                                f"{rec['route_counts']}")
                return rec
            # land the kill INSIDE a replica-a build (a kill after
            # completion would prove reattach, not failover)
            victim = next(r for r in jobs
                          if r["endpoint"] == socks[0])
            with SheepClient(socks[0]) as c:
                landed = False
                for _ in range(4000):
                    st = c.status(victim["job_id"])
                    if st["state"] in ("done", "failed"):
                        break
                    if st.get("phase") == "build" \
                            and st.get("steps", 0) >= 3:
                        landed = True
                        break
                    time.sleep(0.005)
            if not landed:
                rec["verdict"] = "unhandled_crash"
                rec["error"] = (f"kill window missed: victim reached "
                                f"{st.get('state')}/{st.get('phase')}")
                return rec
            rec["killed_at_steps"] = st.get("steps")
            pre_kill_counts = dict(fleet.route_counts)
            procs[0].kill()
            procs[0].wait(timeout=30)
            # every job must complete: replica-b's directly, replica
            # a's via failover resubmission to the survivor
            # wait on DESCRIPTORS: both replicas mint per-process job
            # ids, so the bare ids collide across the fleet
            for r in jobs:
                try:
                    job = fleet.wait(r, timeout_s=300)
                except ServerError as e:
                    rec["verdict"] = "unhandled_crash"
                    rec["error"] = f"fleet lost a job: {e}"
                    return rec
                if job.get("state") != "done":
                    rec["verdict"] = "unhandled_crash"
                    rec["error"] = job.get("error", "job not done")
                    return rec
                served = fleet.result_assignment(job)
                if not np.array_equal(served, np.asarray(oracle)):
                    rec["verdict"] = "wrong_forest"
                    return rec
            rec["route_counts"] = dict(fleet.route_counts)
            rec["failovers"] = sum(
                fleet.route_counts[ep] - pre_kill_counts.get(ep, 0)
                for ep in fleet.route_counts)
            # the survivor must still be serving, and shut down clean
            with SheepClient(socks[1]) as c:
                try:
                    c.ping()
                except (ServerError, OSError) as e:
                    rec["verdict"] = "unhandled_crash"
                    rec["error"] = f"survivor dead after failover: {e}"
                    return rec
                try:
                    c.shutdown()
                except (ServerError, OSError):
                    pass
        procs[1].wait(timeout=60)
        rec["daemon_rc"] = procs[1].returncode
        if procs[1].returncode != 0:
            rec["verdict"] = "unhandled_crash"
            rec["error"] = f"survivor exit rc={procs[1].returncode}"
            return rec
        rec["verdict"] = "identical"
        return rec
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
        for f in errs:
            f.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="sheepd fault mini-soak (oom + read + restart + "
                    "drain + fleet legs)")
    ap.add_argument("--out", default=None,
                    help="artifact dir (default: fresh temp dir)")
    args = ap.parse_args(argv)
    out_dir = args.out or tempfile.mkdtemp(prefix="sheep_served_soak.")
    os.makedirs(out_dir, exist_ok=True)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    graph = os.path.join(out_dir, "soak.bin64")
    build_graph(graph)
    oracle = clean_oracle(graph)

    ok = True
    for name, inject in LEGS:
        rec = run_leg(name, inject, graph, out_dir, oracle)
        print(json.dumps(rec), flush=True)
        if rec["verdict"] not in ("identical", "degraded_documented"):
            ok = False
        if name == "oom" and not rec.get("dispatch_retries"):
            # the injected fault must have been absorbed ON RECORD —
            # a silently-clean run means the injection missed and the
            # soak proved nothing
            print(json.dumps({"leg": name,
                              "error": "no dispatch_retries trail — "
                                       "injection never fired"}),
                  flush=True)
            ok = False

    # the durable legs (ISSUE 14): kill -9 + restart, then graceful
    # drain + restart, both resuming to the clean oracle's bits
    big_graph = os.path.join(out_dir, "soak_big.bin64")
    build_graph(big_graph, n=DURABLE_V, m=DURABLE_E)
    big_oracle = clean_oracle(big_graph, n=DURABLE_V,
                              chunk_edges=DURABLE_CHUNK)
    for name, sig in (("restart", signal.SIGKILL),
                      ("drain", signal.SIGTERM)):
        rec = run_durable_leg(name, sig, big_graph, out_dir,
                              big_oracle)
        print(json.dumps(rec), flush=True)
        if rec["verdict"] not in ("identical", "degraded_documented"):
            ok = False
        if rec.get("verdict") == "identical" \
                and not rec.get("jobs_resumed"):
            print(json.dumps({"leg": name,
                              "error": "no sheepd_jobs_resumed_total "
                                       "trail — the restart never "
                                       "resumed anything"}),
                  flush=True)
            ok = False

    # the fleet leg (ISSUE 16): two replicas, headroom-split jobs,
    # SIGKILL one replica mid-build, failover finishes everything
    rec = run_fleet_leg(big_graph, out_dir, big_oracle)
    print(json.dumps(rec), flush=True)
    if rec["verdict"] not in ("identical", "degraded_documented"):
        ok = False
    if rec.get("verdict") == "identical" and not rec.get("failovers"):
        print(json.dumps({"leg": "fleet",
                          "error": "no failover resubmit happened — "
                                   "the kill proved nothing"}),
              flush=True)
        ok = False
    print(json.dumps({"soak": "served", "ok": ok, "out": out_dir}),
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
