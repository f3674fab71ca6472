#!/usr/bin/env python
"""Chip smoke: the partitioner's main path, end to end, on a real TPU.

    python chip_smoke.py              # one chip: phases (a) partition, (b) serve
    python chip_smoke.py --chips 4    # four chips: tpu-sharded + tpu-bigv only

(a) partition — RMAT-22 ef16 (4,194,304 vertices, 67,108,864 edges from
    the counter-hash R-MAT, seed 42) into k=64 parts through
    ``sheep_tpu.partition(..., backend="tpu")``, checked against the
    native ``cpu`` backend on the bit-identical host edges: same
    elimination-tree ``parent``, same assignment, same edge cut.
(b) serve — ``sheepd`` is the only process on the chip; the client
    sends a cold submit, the same submit again (answered from the
    result store), a resident submit and one incremental ``update``,
    each checked against the native backend on the same edges
    (RMAT-18, so the phase stays short).
--chips 4 — ``tpu-sharded`` and ``tpu-bigv`` on a 4-device mesh at
    RMAT-22, each against the same native oracle.

Every phase runs in its own child process, one after the other, with
``JAX_PLATFORMS`` pinned (``tpu`` unless ``--platform`` says otherwise)
so a failed TPU init raises instead of dropping to the CPU; this parent
never initializes a JAX backend. The run fails (exit 1, no result line)
when a phase ran on anything but a TPU, the native core is missing, a
retry/degradation counter is non-zero or an oracle check fails. On
success the last stdout line is exactly::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

``--platform cpu --scale 12 --serve-scale 10`` rehearses every phase on
the CPU at a tiny size; it still exits 1, because the platform is not a
TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
PHASE_TAG = "CHIP_SMOKE_PHASE "
# seconds for all phases together: the driver's 1,200 s limit, less a
# minute for interpreter start-up and the final line
DEADLINE_S = 1140.0
EDGE_FACTOR = 16
SEED = 42
# retry ladder / degradation counters (utils/retry.py, membudget): any
# non-zero value means the run recovered from a fault and changed shape
FAULT_COUNTERS = ("dispatch_retries", "degraded_dispatch_batch",
                  "degraded_inflight", "degraded_h2d_ring",
                  "device_loss_recoveries", "spill_degrades",
                  "checkpoint_degraded")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# child side: everything below runs inside a process that owns the chip
# ---------------------------------------------------------------------------
def _compile_meter() -> dict:
    """Count backend compiles (persistent-cache fetches included) and
    their wall, per program name, from JAX's own monitoring events."""
    from jax import monitoring

    rec = {"compiles": 0, "compile_s": 0.0, "programs": {}}

    def on_duration(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            rec["compiles"] += 1
            rec["compile_s"] += float(secs)
            name = str(kw.get("fun_name", "?"))
            n, s = rec["programs"].get(name, (0, 0.0))
            rec["programs"][name] = (n + 1, s + float(secs))

    monitoring.register_event_duration_secs_listener(on_duration)
    return rec


def _device() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def _fault_counters(diag: dict) -> dict:
    return {c: int(diag.get(c, 0)) for c in FAULT_COUNTERS}


def _oracle(edges, n, k):
    """Native-core partition of the host edges (no JAX involved)."""
    from sheep_tpu.backends.base import get_backend
    from sheep_tpu.io.edgestream import EdgeStream

    es = EdgeStream.from_array(edges, n_vertices=n)
    return get_backend("cpu", chunk_edges=1 << 24).partition(
        es, k, comm_volume=False, keep_tree=True)


def _partition(spec, k, backend, **ctor):
    """One build through the library entry point (backend registry +
    input spec), keeping the elimination tree for the oracle check."""
    from sheep_tpu import get_backend
    from sheep_tpu.io.edgestream import open_input

    with open_input(spec) as stream:
        return get_backend(backend, **ctor).partition(
            stream, k, comm_volume=False, keep_tree=True)


def _compare(res, ref) -> dict:
    import numpy as np

    return {"parent_equal": bool(np.array_equal(res.tree["parent"],
                                                ref.tree["parent"])),
            "assignment_equal": bool(np.array_equal(res.assignment,
                                                    ref.assignment)),
            "edge_cut": int(res.edge_cut), "oracle_edge_cut":
                int(ref.edge_cut),
            "cut_equal": int(res.edge_cut) == int(ref.edge_cut)
            and int(res.total_edges) == int(ref.total_edges)}


def phase_partition(args) -> dict:
    """(a) RMAT-SCALE through the library entry point on ``tpu``."""
    meter = _compile_meter()
    from sheep_tpu.core import native
    from sheep_tpu.io import generators

    dev = _device()
    log(f"[partition] on {dev}; building")
    spec = f"rmat-hash:{args.scale}:{EDGE_FACTOR}:{SEED}"
    t0 = time.perf_counter()
    res = _partition(spec, args.k, "tpu", chunk_edges=1 << 23)
    wall = time.perf_counter() - t0
    log(f"[partition] tpu build {wall:.1f} s; native oracle")
    edges = generators.RmatHashStream(args.scale, EDGE_FACTOR,
                                      seed=SEED).read_all()
    t1 = time.perf_counter()
    ref = _oracle(edges, 1 << args.scale, args.k)
    return {"phase": "partition", "input": spec, "k": args.k,
            "edges": int(len(edges)), "device": dev,
            "result_platform": res.diagnostics.get("platform"),
            "result_device_kind": res.diagnostics.get("device_kind"),
            "wall_s": wall, "oracle_s": time.perf_counter() - t1,
            "phase_times": res.phase_times,
            "compile_s": meter["compile_s"], "compiles": meter["compiles"],
            "programs": meter["programs"],
            "native_core": native.available(),
            "faults": _fault_counters(res.diagnostics),
            "oracle": _compare(res, ref)}


def phase_multichip(args) -> dict:
    """--chips 4: the two vertex/edge-sharded backends on a 4-device
    mesh, each against the native oracle."""
    meter = _compile_meter()
    import jax
    import numpy as np

    from sheep_tpu.core import native
    from sheep_tpu.io import generators

    dev = _device()
    if dev["count"] != args.chips:
        raise SystemExit(f"expected {args.chips} devices, JAX sees "
                         f"{dev['count']}")
    spec = f"rmat-hash:{args.scale}:{EDGE_FACTOR}:{SEED}"
    edges = generators.RmatHashStream(args.scale, EDGE_FACTOR,
                                      seed=SEED).read_all()
    ref = _oracle(edges, 1 << args.scale, args.k)
    out = {"phase": "multichip", "input": spec, "k": args.k,
           "edges": int(len(edges)), "device": dev,
           "native_core": native.available(), "backends": {}}
    for name in ("tpu-sharded", "tpu-bigv"):
        log(f"[multichip] {name} on {dev}")
        c0, s0 = meter["compiles"], meter["compile_s"]
        t0 = time.perf_counter()
        res = _partition(spec, args.k, name, n_devices=args.chips)
        wall = time.perf_counter() - t0
        # peak bytes per device: a program or a put that lands
        # everything on device 0 shows up as a lopsided peak
        peaks = []
        for d in jax.devices():
            stats = d.memory_stats() or {}
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        out["backends"][name] = {
            "wall_s": wall, "compile_s": meter["compile_s"] - s0,
            "compiles": meter["compiles"] - c0,
            "result_platform": res.diagnostics.get("platform"),
            "result_device_kind": res.diagnostics.get("device_kind"),
            "peak_bytes_per_device": peaks,
            "faults": _fault_counters(res.diagnostics),
            "oracle": _compare(res, ref)}
    out["wall_s"] = sum(b["wall_s"] for b in out["backends"].values())
    out["compile_s"] = meter["compile_s"]
    out["compiles"] = meter["compiles"]
    out["programs"] = meter["programs"]
    # the sharded state must be spread: no device may peak at more
    # than twice the mean of the others
    peaks = np.asarray(out["backends"]["tpu-bigv"]
                       ["peak_bytes_per_device"], dtype=np.float64)
    out["device0_lopsided"] = bool(peaks.size > 1 and peaks[1:].mean() > 0
                                   and peaks[0] > 2 * peaks[1:].mean())
    return out


def run_sheepd(argv) -> int:
    """sheepd with a compile meter: its counts land in
    ``$CHIP_SMOKE_METER`` when the daemon exits."""
    meter = _compile_meter()
    from sheep_tpu.server import daemon

    try:
        return daemon.main(argv)
    finally:
        path = os.environ.get("CHIP_SMOKE_METER")
        if path:
            rec = dict(meter)
            try:
                rec["device"] = _device()
            except Exception as e:  # report what failed, not nothing
                rec["device_error"] = f"{type(e).__name__}: {e}"
            with open(path, "w") as f:
                json.dump(rec, f)


# ---------------------------------------------------------------------------
# parent side: orchestrates children, never initializes a JAX backend
# ---------------------------------------------------------------------------
def _child_env(args) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = args.platform
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if args.platform == "cpu" and args.chips > 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{args.chips}").strip()
    return env


def run_phase(args, name: str, timeout: float) -> dict:
    """One phase in its own process; its stderr streams through live."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
           "--scale", str(args.scale), "--k", str(args.k),
           "--chips", str(args.chips), "--platform", args.platform]
    r = subprocess.run(cmd, stdout=subprocess.PIPE, text=True,
                       env=_child_env(args), cwd=REPO, timeout=timeout)
    for line in r.stdout.splitlines():
        if line.startswith(PHASE_TAG):
            return json.loads(line[len(PHASE_TAG):])
    raise RuntimeError(f"phase {name} died rc={r.returncode} without a "
                       f"result")


def phase_serve(args, timeout: float) -> dict:
    """(b) sheepd on the chip; client + oracles here (native core only,
    no JAX backend in this process)."""
    import numpy as np

    from sheep_tpu.io import deltalog, generators
    from sheep_tpu.io.edgestream import open_input
    from sheep_tpu.server.client import SheepClient, fleet_digest

    sc, k = args.serve_scale, args.k
    n = 1 << sc
    spec = f"rmat-hash:{sc}:{EDGE_FACTOR}:{SEED}"
    edges = generators.RmatHashStream(sc, EDGE_FACTOR, seed=SEED).read_all()
    ref = _oracle(edges, n, k)
    delta = np.random.default_rng(SEED).integers(
        0, n, (max(1024, len(edges) // 64), 2), dtype=np.int64)
    out = {"phase": "serve", "input": spec, "k": k,
           "edges": int(len(edges)), "delta_edges": int(len(delta))}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as td:
        log_path = os.path.join(td, "g.dlog")
        with deltalog.DeltaLogWriter(log_path, base_spec=spec) as w:
            w.append(delta)
        from sheep_tpu.backends.base import get_backend

        with open_input(f"delta:{log_path}") as ds:
            ref_upd = get_backend("cpu", chunk_edges=1 << 24).partition(
                ds, k, comm_volume=False)
        sock = os.path.join(td, "sheepd.sock")
        meter_path = os.path.join(td, "meter.json")
        env = _child_env(args)
        env["CHIP_SMOKE_METER"] = meter_path
        cmd = [sys.executable, os.path.abspath(__file__), "--phase",
               "sheepd", "--", "--socket", sock,
               "--state-dir", os.path.join(td, "state")]
        t0 = time.perf_counter()
        log("[serve] starting sheepd; oracles ready")
        proc = subprocess.Popen(cmd, stdout=subprocess.DEVNULL, env=env,
                                cwd=REPO)
        try:
            while not os.path.exists(sock):
                if proc.poll() is not None:
                    raise RuntimeError(f"sheepd exited rc={proc.returncode}")
                if time.perf_counter() - t0 > 120:
                    raise RuntimeError("sheepd never bound its socket")
                time.sleep(0.1)
            body = {"chunk_edges": 1 << 20, "return_assignment": True}
            reqs = {}
            with SheepClient(sock, timeout_s=timeout) as c:
                for name, extra in (("cold", {}), ("repeat", {}),
                                    ("resident", {"resident": True})):
                    t1 = time.perf_counter()
                    jid = c.submit(spec, k=[k], tenant="smoke", **body,
                                   **extra)["job_id"]
                    job = c.wait(jid, timeout_s=timeout)
                    row = (job.get("results") or [{}])[0]
                    a = c.result_assignment(job, k)
                    diag = row.get("diagnostics") or {}
                    reqs[name] = {
                        "wall_s": time.perf_counter() - t1,
                        "state": job["state"], "steps": job.get("steps"),
                        "jit_compiles": job.get("jit_compiles"),
                        # a store answer takes no dispatch step
                        "result_cache_hit": job.get("steps") == 0,
                        "result_platform": diag.get("platform"),
                        "result_device_kind": diag.get("device_kind"),
                        "faults": _fault_counters(diag),
                        "oracle": {
                            "assignment_equal": a is not None and bool(
                                np.array_equal(a, ref.assignment)),
                            "edge_cut": row.get("edge_cut"),
                            "oracle_edge_cut": int(ref.edge_cut),
                            "cut_equal": row.get("edge_cut") == int(
                                ref.edge_cut)}}
                    log(f"[serve] {name}: {job['state']} in "
                        f"{reqs[name]['wall_s']:.1f} s")
                    if name == "cold":
                        # the store publishes after the terminal, on
                        # the dispatch thread: the repeat must find it
                        digest = fleet_digest(spec, [k], tenant="smoke",
                                              **body)
                        t2 = time.perf_counter()
                        while not c.lookup(digest) \
                                and time.perf_counter() - t2 < 60:
                            time.sleep(0.05)
                    if name == "resident":
                        resident_id = jid
                t1 = time.perf_counter()
                upd = c.update(resident_id, adds=delta, epoch=1,
                               score=True)
                urow = (upd.get("results") or [{}])[0]
                reqs["update"] = {
                    "wall_s": time.perf_counter() - t1,
                    "applied": bool(upd.get("applied")),
                    "epoch": upd.get("epoch"),
                    "oracle": {
                        "edge_cut": urow.get("edge_cut"),
                        "oracle_edge_cut": int(ref_upd.edge_cut),
                        "cut_equal": urow.get("edge_cut") == int(
                            ref_upd.edge_cut)
                        and urow.get("total_edges") == int(
                            ref_upd.total_edges)}}
                c.shutdown()
            proc.wait(timeout=120)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        out["wall_s"] = time.perf_counter() - t0
        out["requests"] = reqs
        meter = {}
        if os.path.exists(meter_path):
            with open(meter_path) as f:
                meter = json.load(f)
        out["device"] = meter.get("device", {})
        out["compile_s"] = meter.get("compile_s")
        out["compiles"] = meter.get("compiles")
        out["programs"] = meter.get("programs")
        from sheep_tpu.core import native

        out["native_core"] = native.available()
        out["sheepd_rc"] = proc.returncode
    return out


def check_phase(rec: dict) -> list:
    """Every reason this phase fails the smoke, [] when it passes."""
    bad = []
    dev = rec.get("device") or {}
    if dev.get("platform") != "tpu":
        bad.append(f"ran on platform {dev.get('platform')!r}, not tpu")
    if not rec.get("native_core"):
        bad.append("native core unavailable")
    legs = rec.get("backends") or rec.get("requests") or {"": rec}
    for leg, r in legs.items():
        tag = f"{leg}: " if leg else ""
        plat = r.get("result_platform")
        if "result_platform" in r and plat != "tpu":
            bad.append(f"{tag}result diagnostics name platform {plat!r}")
        for c, v in (r.get("faults") or {}).items():
            if v:
                bad.append(f"{tag}{c}={v}")
        for c, v in (r.get("oracle") or {}).items():
            if c.endswith("_equal") and not v:
                bad.append(f"{tag}oracle check {c} failed")
        if "state" in r and r["state"] != "done":
            bad.append(f"{tag}job state {r['state']}")
        if "applied" in r and not r["applied"]:
            bad.append(f"{tag}update not applied")
    reqs = rec.get("requests") or {}
    if reqs:
        rep = reqs.get("repeat") or {}
        if not rep.get("result_cache_hit") or rep.get("jit_compiles"):
            bad.append("repeat submit was not answered from the store")
        if (reqs.get("resident") or {}).get("result_cache_hit"):
            bad.append("resident submit was answered from the store")
        if rec.get("sheepd_rc") not in (0, None):
            bad.append(f"sheepd exited rc={rec['sheepd_rc']}")
    if rec.get("device0_lopsided"):
        bad.append("device 0 peaked at > 2x the other devices' mean")
    return bad


def summarize(rec: dict) -> None:
    dev = rec.get("device") or {}
    top = sorted((rec.get("programs") or {}).items(),
                 key=lambda kv: -kv[1][1])[:8]
    log(f"[{rec['phase']}] wall {rec.get('wall_s')} s, compile "
        f"{rec.get('compile_s')} s over {rec.get('compiles')} compiles, "
        f"platform {dev.get('platform')} kind {dev.get('kind')!r} count "
        f"{dev.get('count')}, native_core {rec.get('native_core')}")
    log(f"[{rec['phase']}] slowest compiles (count, s): "
        + json.dumps(dict(top)))
    legs = rec.get("backends") or rec.get("requests") or {"": rec}
    for leg, r in legs.items():
        log(f"[{rec['phase']}{'/' + leg if leg else ''}] "
            + json.dumps({key: r.get(key) for key in
                          ("wall_s", "oracle_s", "compile_s", "compiles",
                           "steps", "jit_compiles", "result_cache_hit",
                           "result_platform", "result_device_kind",
                           "faults", "oracle", "applied", "epoch",
                           "peak_bytes_per_device", "phase_times")
                          if key in r}))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    p.add_argument("--platform", default="tpu",
                   help="JAX_PLATFORMS for every child (cpu = rehearsal; "
                        "never passes)")
    p.add_argument("--scale", type=int, default=22)
    p.add_argument("--serve-scale", type=int, default=18)
    p.add_argument("--k", type=int, default=64)
    p.add_argument("--phase", default=None, help=argparse.SUPPRESS)
    p.add_argument("rest", nargs="*", help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.phase == "sheepd":
        return run_sheepd(args.rest)
    if args.phase is not None:
        rec = {"partition": phase_partition,
               "multichip": phase_multichip}[args.phase](args)
        print(PHASE_TAG + json.dumps(rec), flush=True)
        return 0

    try:
        from sheep_tpu.core import native
    except ImportError as e:
        log(f"chip_smoke: FAIL: the sheep_tpu package is not next to this "
            f"script ({e})")
        return 1
    # build the native core from the committed sources on this host
    # before any child loads it
    if not native.available():
        log("chip_smoke: FAIL: native core unavailable (see "
            "sheep_tpu/core/native.py)")
        return 1
    deadline = time.perf_counter() + DEADLINE_S
    records = []
    phases = ["multichip"] if args.chips > 1 else ["partition", "serve"]
    try:
        for name in phases:
            left = deadline - time.perf_counter()
            records.append(phase_serve(args, left) if name == "serve"
                           else run_phase(args, name, left))
            summarize(records[-1])
    except Exception as e:
        log(f"chip_smoke: FAIL: {type(e).__name__}: {e}")
        return 1
    finally:
        if records:
            out = os.path.join(REPO, "chiprun_out")
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, f"chip_smoke_{args.chips}chip_"
                                        f"{args.platform}.json"), "w") as f:
                json.dump(records, f, indent=1)
    bad = [f"{rec['phase']}: {b}" for rec in records
           for b in check_phase(rec)]
    log(f"chip_smoke: total wall "
        f"{DEADLINE_S - (deadline - time.perf_counter()):.1f} s")
    if bad:
        for b in bad:
            log(f"chip_smoke: FAIL: {b}")
        return 1
    dev = records[0]["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
