#!/usr/bin/env python
"""Headline benchmark: edges/sec partitioned, TPU backend vs CPU baseline.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

``vs_baseline`` is the TPU/CPU edges-per-second ratio — the north-star
target is >=10x (BASELINE.json). Graph: RMAT (Graph500 params), k=64,
matching the driver's streaming eval shape. Scale via SHEEP_BENCH_SCALE
(default 22 -> 4.2M vertices, 67M edges on the TPU; 18 on cpu-jax).

The platform is the TPU unless ``SHEEP_BENCH_PLATFORM=cpu`` asks for
cpu-jax (the tests' mode; such a line carries ``vs_baseline: null`` and
names its platform). There is no fallback: a run that finds no TPU
exits non-zero and prints no number. The measurement runs in a
subprocess worker (``bench.py --measure SCALE PLATFORM``, with
``JAX_PLATFORMS`` pinned so a failed TPU init raises instead of
dropping to the CPU); a worker crash or timeout retries down a scale
ladder (22 -> 20 -> 18), recording the failures in the JSON line. The
CPU baseline falls back native->pure if the C++ toolchain is absent.

Secondary metrics (cut ratio parity vs CPU, per-phase times) go to stderr
so the stdout contract stays one line.
"""

import json
import os
import subprocess
import sys
import time

METRIC = "edges/sec partitioned"


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def emit(value, vs_baseline, metric=METRIC, **extra):
    line = {"metric": metric, "value": value, "unit": "edges/sec",
            "vs_baseline": vs_baseline}
    line.update(extra)
    print(json.dumps(line), flush=True)


def measure(scale: int, platform: str) -> dict:
    """Worker body: measure CPU baseline + accelerated backend at one RMAT
    scale. Runs in a subprocess so a TPU worker crash only loses this
    attempt. Returns the result dict (also printed as the last stdout
    line when invoked via --measure)."""
    # persistent compilation cache: a retried/repeated bench skips the
    # first-compile warm-up (the programs are identical)
    from sheep_tpu.utils.platform import enable_compilation_cache, \
        pin_platform

    if platform == "cpu":
        pin_platform("cpu")
    enable_compilation_cache()
    import jax

    found = jax.devices()[0].platform
    if found != platform:
        raise NoDevice(f"asked for {platform!r}, JAX found {found!r}")

    from sheep_tpu.backends.base import get_backend, list_backends

    if "cpu" in list_backends():
        base_name = "cpu"
    else:
        log("native cpu backend unavailable (C++ toolchain?); baseline=pure")
        base_name = "pure"

    edge_factor = int(os.environ.get("SHEEP_BENCH_EDGE_FACTOR", "16"))
    k = int(os.environ.get("SHEEP_BENCH_K", "64"))

    from sheep_tpu.io import generators
    from sheep_tpu.io.edgestream import EdgeStream

    # Counter-based R-MAT: the accelerated side materializes chunks ON
    # DEVICE (generators.rmat_hash_chunk_device) so the bench measures the
    # pipeline, not the host-to-device upload (a PCIe pass per chunk
    # per streaming pass). The CPU baseline gets
    # the IDENTICAL edges (bit-equal host twin), materialized once so its
    # passes read memory rather than re-hashing.
    t0 = time.perf_counter()
    n = 1 << scale
    dev_stream = generators.RmatHashStream(scale, edge_factor, seed=42)
    edges = dev_stream.read_all()
    es = EdgeStream.from_array(edges, n_vertices=n)
    m = len(edges)
    log(f"graph: RMAT-{scale} ef={edge_factor} (counter-hash)  "
        f"V={n:,} E={m:,}  (gen {time.perf_counter() - t0:.1f}s)  k={k}")

    # --- CPU single-socket baseline (the denominator) ---------------------
    cpu = get_backend(base_name, chunk_edges=1 << 24)
    t0 = time.perf_counter()
    res_cpu = cpu.partition(es, k, comm_volume=False)
    cpu_s = time.perf_counter() - t0
    cpu_eps = m / cpu_s
    log(f"{base_name}: {cpu_s:.2f}s = {cpu_eps / 1e6:.2f} Me/s  "
        f"cut_ratio={res_cpu.cut_ratio:.4f} balance={res_cpu.balance:.3f} "
        f"phases={ {p: round(s, 2) for p, s in res_cpu.phase_times.items()} }")

    dev = jax.devices()
    out = {"scale": scale, "k": k, "edges": m, "platform": platform,
           "device_kind": dev[0].device_kind, "device_count": len(dev),
           "baseline": base_name, "cpu_eps": round(cpu_eps, 1),
           "cpu_cut_ratio": round(res_cpu.cut_ratio, 6)}

    if "tpu" not in list_backends():
        raise NoDevice("the tpu backend is unregistered (jax absent?)")

    # --- accelerated backend ---------------------------------------------
    # chunk sizes from the tools/tune_fixpoint.py sweeps: 2^23 on the
    # real chip (RMAT-20/22, fewest fixpoint sequences that still hand
    # the tail off early), 2^22 on cpu-jax (width-
    # proportional round cost thrashes host caches)
    accel_chunk = 1 << (23 if platform != "cpu" else 22)

    def timed_leg(backend_name):
        """Warm-up (compile) partition + one timed partition; shared by
        the single-chip and multi-chip legs so the timing methodology
        cannot drift between them. SHEEP_BENCH_TRACE=DIR captures a
        structured obs trace (manifest + span tree + counters; see
        tools/trace_report.py) of the TIMED leg only — the warm-up's
        compile wall would drown the steady-state tree. Tracing off is
        the default and adds nothing to the measured path."""
        be = get_backend(backend_name, chunk_edges=min(accel_chunk, m))
        t0 = time.perf_counter()
        be.partition(dev_stream, k, comm_volume=False)  # compile warm-up
        warm = time.perf_counter() - t0
        # the timed leg runs with the ALWAYS-ON flight recorder
        # installed, exactly as every request under sheepd does
        # (ISSUE 11): warm_request_s therefore carries the telemetry
        # tax inside the gated contract number — if the "negligible
        # overhead" claim ever rots, bench_regress catches it as a
        # warm-path regression, not as an untested assertion
        from sheep_tpu import obs as _obs
        from sheep_tpu.obs.flightrec import FlightRecorder as _FR

        _obs.install_flight(_FR())
        try:
            trace_dir = os.environ.get("SHEEP_BENCH_TRACE")
            if trace_dir:
                from sheep_tpu import obs

                os.makedirs(trace_dir, exist_ok=True)
                path = os.path.join(
                    trace_dir, f"trace_{backend_name}_s{scale}.jsonl")
                with obs.tracing(path) as tr:
                    obs.emit_manifest(tr, backend=backend_name,
                                      config={"scale": scale, "k": k,
                                              "edge_factor": edge_factor,
                                              "platform": platform})
                    t0 = time.perf_counter()
                    res = be.partition(dev_stream, k, comm_volume=False)
                    leg_s = time.perf_counter() - t0
                log(f"obs trace captured: {path}")
                return res, leg_s, warm
            t0 = time.perf_counter()
            res = be.partition(dev_stream, k, comm_volume=False)
            return res, time.perf_counter() - t0, warm
        finally:
            _obs.uninstall_flight()

    res_tpu, tpu_s, warm_s = timed_leg("tpu")
    tpu_eps = m / tpu_s
    log(f"{platform}: {tpu_s:.2f}s = {tpu_eps / 1e6:.2f} Me/s (warm-up {warm_s:.1f}s)  "
        f"cut_ratio={res_tpu.cut_ratio:.4f} balance={res_tpu.balance:.3f} "
        f"rounds={res_tpu.diagnostics.get('fixpoint_rounds')} "
        f"phases={ {p: round(s, 2) for p, s in res_tpu.phase_times.items()} }")
    # warm-vs-cold served-request contract (ISSUE 10 satellite): the
    # warm-up leg IS a cold request (first call, jit compiles included)
    # and the timed leg IS a warm one (what a resident sheepd serves
    # from its warm program caches) — emit both so bench_regress can
    # gate the warm path and the jit tax like the other perf fields.
    # bench.py printed warm-up for three rounds (BENCH_r03-r05) but
    # never emitted it; the 8-13 s gap is the number the server mode
    # exists to amortize.
    out["warm_up_s"] = round(warm_s, 2)
    out["cold_request_s"] = round(warm_s, 2)
    out["warm_request_s"] = round(tpu_s, 2)
    log(f"served-request comparison: cold {warm_s:.2f}s vs warm "
        f"{tpu_s:.2f}s ({warm_s / max(tpu_s, 1e-9):.1f}x)")
    # incremental contract field (ISSUE 15): one resident-partition
    # update — a delta batch folded into a converged carried table —
    # timed at a reduced scale so the leg stays seconds everywhere
    # (the metric tracks the UPDATE machinery, not the headline build;
    # scale rides in the metric string via the derived size). Gated
    # lower-better by bench_regress like warm_request_s; compactions
    # rides info-only.
    try:
        import numpy as np

        from sheep_tpu import incremental as inc_mod

        us = max(10, scale - 4)
        un = 1 << us
        delta = np.random.default_rng(1234).integers(
            0, un, (min(1 << 15, max(1024, (un * edge_factor) // 256)),
                    2), dtype=np.int64)

        def scored_epoch(sc, name="tpu"):
            """One SCORED update epoch at RMAT-``sc``: returns the
            (fold_s, score_s, state) split — the score side comes
            from the state's own update_score_s accounting, so it
            measures exactly the refresh's scoring pass. A seed
            refresh runs first so the timed epoch takes the
            O(delta) incremental-score path, not the one-time full
            pass that builds the survivor index."""
            stream = generators.RmatHashStream(sc, edge_factor,
                                               seed=42)
            be = get_backend(name, chunk_edges=min(
                accel_chunk, (1 << sc) * edge_factor))
            st, _ = inc_mod.begin_incremental(stream, k, backend=be,
                                              comm_volume=False)
            inc_mod.refresh(be, st)  # seed the score cache
            s0 = float(st.stats.get("update_score_s", 0.0))
            t0 = time.perf_counter()
            be.partition_update(st, adds=delta, score=True)
            wall = time.perf_counter() - t0
            score_s = float(st.stats.get("update_score_s", 0.0)) - s0
            return max(0.0, wall - score_s), score_s, st

        fold_s, score_s, ustate = scored_epoch(us)
        out["update_fold_s"] = round(fold_s, 4)
        out["update_score_s"] = round(score_s, 4)
        out["update_request_s"] = round(fold_s + score_s, 4)
        out["compactions"] = int(ustate.compactions)
        inc_hits = int(ustate.stats.get("score_incremental", 0))
        log(f"incremental: update_fold_s {out['update_fold_s']}s + "
            f"update_score_s {out['update_score_s']}s (RMAT-{us}, "
            f"{len(delta)} delta edges, epoch {ustate.epoch}, "
            f"score_incremental={inc_hits})")
        # epoch-cost scaling probe (ISSUE 17): the SAME delta folded
        # + scored over a 2x larger base; O(delta) epochs keep the
        # scored-epoch wall roughly flat (the contract bar is
        # ~<=1.2x), O(edges) rescoring would double it. Rides
        # info-only in bench_regress — it is a property, not a perf
        # series.
        fold2, score2, _ = scored_epoch(us + 1)
        w1, w2 = fold_s + score_s, fold2 + score2
        out["epoch_scale_x2"] = round(w2 / max(w1, 1e-9), 3)
        log(f"incremental scaling: 2x base -> "
            f"{out['epoch_scale_x2']}x scored-epoch wall "
            f"({w1:.4f}s -> {w2:.4f}s; score "
            f"{score_s:.4f}s -> {score2:.4f}s)")
        if out["epoch_scale_x2"] > 1.5:
            log(f"WARNING: scored-epoch wall scaled "
                f"{out['epoch_scale_x2']}x on a 2x base — the "
                f"O(delta) incremental-score path may have fallen "
                f"back to full rescoring")
        # multi-device update leg (ISSUE 19): the SAME scored epoch
        # through the sharded lockstep fold + distributed rescore —
        # what a resident sharded partition pays per delta epoch.
        # Gated lower-better by bench_regress like update_request_s.
        fold_sh, score_sh, sh_state = scored_epoch(us,
                                                   name="tpu-sharded")
        out["sharded_update_request_s"] = round(fold_sh + score_sh, 4)
        log(f"sharded incremental: {out['sharded_update_request_s']}s "
            f"(fold {fold_sh:.4f}s + score {score_sh:.4f}s, "
            f"update_folds="
            f"{int(sh_state.stats.get('update_folds', 0))}, "
            f"score_distributed="
            f"{int(sh_state.stats.get('score_distributed', 0))}, "
            f"device_rounds="
            f"{int(sh_state.stats.get('device_rounds', 0))})")
    except Exception as e:  # noqa: BLE001 — the leg must not kill bench
        log(f"incremental leg skipped: {type(e).__name__}: "
            f"{str(e)[:200]}")
    # fleet warm-path contract field (ISSUE 16): cached_request_s —
    # one repeat submit answered from the content-addressed result
    # store (zero dispatch steps, zero recompiles, bit-identical) at
    # the reduced update-leg scale against an in-process scheduler.
    # Gated lower-better by bench_regress; the contract bar is at
    # least 10x under warm_request_s (the store read is file IO +
    # decode, not a build).
    try:
        import tempfile
        import threading

        from sheep_tpu.server import journal as journal_mod
        from sheep_tpu.server import protocol as proto_mod
        from sheep_tpu.server.scheduler import Scheduler

        cs2 = max(10, scale - 4)
        body = {"input": f"rmat:{cs2}:{edge_factor}:7", "k": [k],
                "chunk_edges": min(accel_chunk,
                                   (1 << cs2) * edge_factor)}
        with tempfile.TemporaryDirectory() as td:
            sched = Scheduler(
                result_store=os.path.join(td, "results"))
            th = threading.Thread(target=sched.run, daemon=True,
                                  name="bench-sheepd-dispatch")
            th.start()
            try:
                sp = proto_mod.JobSpec.from_request(body,
                                                    tenant="bench")
                dg = journal_mod.job_digest(sp)
                cold = sched.submit(sp, digest=dg)
                cold = sched.wait(cold.id, timeout_s=600)
                if cold.state != "done":
                    raise RuntimeError(
                        f"cold fill {cold.state}: {cold.error}")
                # the store publish runs after the terminal on the
                # dispatch thread; wait for the digest to land
                deadline = time.time() + 30
                while not sched.lookup_digest(dg) \
                        and time.time() < deadline:
                    time.sleep(0.01)
                sp2 = proto_mod.JobSpec.from_request(body,
                                                     tenant="bench")
                t0 = time.perf_counter()
                rep = sched.submit(sp2, digest=dg)
                rep = sched.wait(rep.id, timeout_s=600)
                cached_s = time.perf_counter() - t0
                hit = int(rep.stats.get("result_cache_hit", 0))
                if rep.state == "done" and hit:
                    out["cached_request_s"] = round(cached_s, 4)
                    log(f"result cache: cached_request_s "
                        f"{out['cached_request_s']}s (RMAT-{cs2}, "
                        f"digest {dg[:12]}, jit_compiles="
                        f"{rep.jit_compiles})")
                    warm = out.get("warm_request_s")
                    if warm and cached_s > warm / 10.0:
                        # the contract bar: a store answer is file IO
                        # + decode, >= 10x under the warm build wall
                        log(f"WARNING: cached_request_s {cached_s:.4f}"
                            f"s is not >=10x under warm_request_s "
                            f"{warm}s — store path slowing?")
                else:
                    log(f"result-cache leg unusable: "
                        f"state={rep.state} hit={hit}")
            finally:
                sched.shutdown()
                th.join(timeout=30)
    except Exception as e:  # noqa: BLE001 — the leg must not kill bench
        log(f"result-cache leg skipped: {type(e).__name__}: "
            f"{str(e)[:200]}")
    # out-of-core contract field (ISSUE 20): oocore_request_s — one
    # full build with SHEEP_CACHE_BYTES clamped to ~half the modeled
    # working set, so the residency manager MUST evict and re-upload
    # mid-build (the disk tier is live, not idle). Gated lower-better
    # by bench_regress; the spill counters ride info-only — they
    # describe the constraint, not a perf series. Runs at the reduced
    # update-leg scale with a small chunk so the stream has enough
    # chunks to rotate, and stays seconds everywhere.
    try:
        os2 = max(10, scale - 4)
        m2 = (1 << os2) * edge_factor
        oc_chunk = max(1024, m2 // 8)       # ~8 chunks to rotate over
        nchunks = -(-m2 // oc_chunk)
        # modeled working set: every padded (cs, 2) int32 chunk resident
        working = nchunks * oc_chunk * 2 * 4
        budget = max(1, working // 2)
        oc_stream = generators.RmatHashStream(os2, edge_factor, seed=42)
        oc_be = get_backend("tpu", chunk_edges=oc_chunk)
        oc_be.partition(oc_stream, k, comm_volume=False)  # compile warm-up
        prev = os.environ.get("SHEEP_CACHE_BYTES")
        os.environ["SHEEP_CACHE_BYTES"] = str(budget)
        try:
            t0 = time.perf_counter()
            res_oc = oc_be.partition(oc_stream, k, comm_volume=False)
            oc_s = time.perf_counter() - t0
        finally:
            if prev is None:
                os.environ.pop("SHEEP_CACHE_BYTES", None)
            else:
                os.environ["SHEEP_CACHE_BYTES"] = prev
        out["oocore_request_s"] = round(oc_s, 4)
        for f in ("spill_evictions", "spill_reload_bytes",
                  "spill_resident_bytes"):
            out[f] = int(res_oc.diagnostics.get(f, 0))
        log(f"out-of-core: oocore_request_s {out['oocore_request_s']}s "
            f"(RMAT-{os2}, {nchunks} chunks, budget {budget:,} of "
            f"modeled {working:,} bytes; spill_evictions="
            f"{out['spill_evictions']}, spill_reload_bytes="
            f"{out['spill_reload_bytes']}, spill_resident_bytes="
            f"{out['spill_resident_bytes']})")
        if not out["spill_evictions"]:
            log("WARNING: out-of-core leg evicted nothing — the "
                "budget clamp is not constraining the build and "
                "oocore_request_s is measuring a fully-resident run")
    except Exception as e:  # noqa: BLE001 — the leg must not kill bench
        log(f"out-of-core leg skipped: {type(e).__name__}: "
            f"{str(e)[:200]}")
    # per-segment build-wall attribution (t_warm_s/t_full_s/t_small_s/
    # t_host_tail_s — elim.py accumulates them per sync), the numbers
    # that decompose build wall into device floor vs host tax
    seg_t = {k: round(v, 3) for k, v in res_tpu.diagnostics.items()
             if k.startswith("t_")}
    if seg_t:
        log(f"build wall attribution: {seg_t}")
    # count x round-cost attribution inputs: with the dispatch counts in
    # the contract, two bench rows at different --dispatch-batch solve
    # per-dispatch overhead vs per-round device cost exactly
    # (sheep_tpu.utils.metrics.solve_dispatch_attribution) — the batched
    # dispatch win is provable from counts alone, even on the CPU mesh
    disp = {k: int(res_tpu.diagnostics[k])
            for k in ("host_syncs", "device_rounds", "batch_execs",
                      "dispatch_batch", "inflight_depth",
                      "inflight_discards", "dispatch_retries",
                      "degraded_dispatch_batch", "degraded_inflight",
                      "degraded_h2d_ring", "device_loss_recoveries",
                      "checkpoint_degraded", "h2d_staged_bytes",
                      "device_stream_chunks", "h2d_ring_depth")
            if k in res_tpu.diagnostics}
    # fault-tolerance contract fields (ISSUE 9): ALWAYS emit
    # dispatch_retries so the regression gate can see 0 -> N movement
    # (a field missing on one side is incomparable, not zero)
    disp.setdefault("dispatch_retries",
                    int(res_tpu.diagnostics.get("dispatch_retries", 0)))
    if disp:
        log(f"dispatch counts (count x round-cost attribution): {disp}")
        out.update(disp)
    # dispatch-overlap contract fields (ISSUE 4) + the ingest pair
    # (ISSUE 12): host wall blocked in stats pulls, device idle between
    # executions, and the H2D staging/underrun walls — the timed leg
    # runs the device-stream path for its rmat-hash input, so
    # h2d_blocked_ms/h2d_staged_bytes SHOULD be 0 there (zero host
    # bytes per chunk); a file-backed capture reports the ring's
    # numbers instead. h2d_blocked_ms is gated lower-is-better by
    # bench_regress like host_blocked_ms.
    overlap = {k: round(float(res_tpu.diagnostics[k]), 1)
               for k in ("host_blocked_ms", "device_gap_ms",
                         "h2d_staged_ms", "h2d_blocked_ms")
               if k in res_tpu.diagnostics}
    if overlap:
        log(f"dispatch overlap: {overlap}")
        out.update(overlap)
    reg = (res_tpu.cut_ratio - res_cpu.cut_ratio) / max(res_cpu.cut_ratio, 1e-9)
    log(f"edge-cut regression vs cpu: {100 * reg:+.2f}% (target <= +2%)")
    out.update(tpu_eps=round(tpu_eps, 1), ratio=round(tpu_eps / cpu_eps, 3),
               tpu_cut_ratio=round(res_tpu.cut_ratio, 6),
               cut_regression_pct=round(100 * reg, 2))

    # --- multi-chip leg (VERDICT r3 item 6a) ------------------------------
    # The north star is R x S(D): on a host with more than one chip,
    # measure the D-device tpu-sharded product instead of projecting it
    # from collective counts. Opt-in on cpu-jax
    # (SHEEP_BENCH_MULTICHIP=1) so the virtual 8-device mesh can dryrun
    # this exact code path in tests.
    n_dev = jax.device_count()
    force_multi = os.environ.get("SHEEP_BENCH_MULTICHIP") == "1"
    if n_dev > 1 and (platform != "cpu" or force_multi):
        res_sh, sh_s, sh_warm = timed_leg("tpu-sharded")
        sh_eps = m / sh_s
        log(f"tpu-sharded D={n_dev}: {sh_s:.2f}s = {sh_eps / 1e6:.2f} Me/s "
            f"(warm-up {sh_warm:.1f}s) cut_ratio={res_sh.cut_ratio:.4f} "
            f"balance={res_sh.balance:.3f}")
        out.update(n_devices=n_dev, sharded_eps=round(sh_eps, 1),
                   ratio_multichip=round(sh_eps / cpu_eps, 3),
                   sharded_cut_ratio=round(res_sh.cut_ratio, 6))
    return out


_RESULT_TAG = "SHEEP_BENCH_RESULT "


_NO_DEVICE_RC = 3


class NoDevice(RuntimeError):
    """The worker found no device of the asked platform."""


def run_attempt(scale: int, platform: str, timeout: float):
    """One subprocess measurement attempt; returns (result dict | None,
    failure string | None, no_device). ``JAX_PLATFORMS`` is pinned for
    the worker, so a failed TPU init raises there."""
    env = dict(os.environ, JAX_PLATFORMS=platform)
    try:
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--measure", str(scale), platform],
            capture_output=True, text=True, timeout=timeout, env=env)
    except subprocess.TimeoutExpired:
        return None, f"scale {scale}: timed out after {int(timeout)}s", \
            False
    sys.stderr.write(r.stderr or "")
    for line in (r.stdout or "").splitlines():
        if line.startswith(_RESULT_TAG):
            try:
                return json.loads(line[len(_RESULT_TAG):]), None, False
            except json.JSONDecodeError as e:
                return None, f"scale {scale}: bad worker result ({e})", \
                    False
    tail = (r.stderr or "").strip().splitlines()
    return None, (f"scale {scale}: worker died rc={r.returncode}: "
                  + (tail[-1][:300] if tail else "no stderr")), \
        r.returncode == _NO_DEVICE_RC


def main() -> int:
    platform = os.environ.get("SHEEP_BENCH_PLATFORM") or "tpu"
    default_scale = {"cpu": "18"}.get(platform, "22")
    top = int(os.environ.get("SHEEP_BENCH_SCALE", default_scale))
    from sheep_tpu.core import native

    if not native.available() and "SHEEP_BENCH_SCALE" not in os.environ:
        # pure-numpy baseline is O(V) python per vertex: scale-18 attempts
        # would just burn the attempt timeout before 14 could succeed
        top = min(top, 14)
    ladder = list(range(top, max(top - 5, 13), -2)) or [top]
    # budget per attempt: graph gen + native baseline + first-compile
    # warm-up (amortized by the persistent compilation cache on reruns)
    # + the timed legs
    attempt_timeout = float(os.environ.get("SHEEP_BENCH_ATTEMPT_TIMEOUT",
                                           "1800"))

    failures = []
    result = None
    for scale in ladder:
        result, fail, no_device = run_attempt(scale, platform,
                                              attempt_timeout)
        if result is not None:
            break
        failures.append(fail)
        if no_device:
            log(f"bench: no {platform} device: {fail}")
            return 1
        log(f"attempt failed: {fail}; "
            + ("retrying down the ladder" if scale != ladder[-1] else
               "ladder exhausted"))
    if result is None:
        log("bench: every attempt failed: " + "; ".join(failures)[:600])
        return 1

    metric = (f"{METRIC} (RMAT-{result['scale']}, k={result['k']}, "
              f"{result['platform']} vs 1-socket CPU)")
    extra = {"platform": result["platform"],
             "device_kind": result.get("device_kind"),
             "device_count": result.get("device_count")}
    # dispatch-attribution + served-path contract fields
    for f in ("host_syncs", "device_rounds", "dispatch_batch",
              "inflight_depth", "inflight_discards", "host_blocked_ms",
              "device_gap_ms", "h2d_staged_ms", "h2d_blocked_ms",
              "h2d_staged_bytes", "h2d_ring_depth", "device_stream_chunks",
              "dispatch_retries", "degraded_dispatch_batch",
              "degraded_inflight", "degraded_h2d_ring",
              "device_loss_recoveries",
              "checkpoint_degraded", "warm_up_s", "cold_request_s",
              "warm_request_s", "cached_request_s", "update_request_s",
              "update_fold_s", "update_score_s", "epoch_scale_x2",
              "sharded_update_request_s", "compactions",
              "oocore_request_s", "spill_evictions",
              "spill_reload_bytes", "spill_resident_bytes"):
        if f in result:
            extra[f] = result[f]
    if failures:
        extra["retries"] = failures
    vs = result["ratio"]
    if result["platform"] == "cpu":
        # VERDICT r3 item 6b: a cpu-jax run measures framework overhead
        # (cpu-jax vs native CPU), not the north-star TPU ratio. Report
        # vs_baseline as null so the number can't be mistaken for
        # progress against the 10x target; the ratio survives under a
        # diagnostic name.
        extra["cpu_jax_vs_native_cpu"] = vs
        vs = None
    elif result.get("n_devices", 1) > 1 and "ratio_multichip" in result:
        # the R x S(D) product, measured on a multi-chip host
        extra[f"vs_baseline_{result['n_devices']}chip"] = \
            result["ratio_multichip"]
    emit(result["tpu_eps"], vs, metric=metric, **extra)
    return 0


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "--measure":
        try:
            out = measure(int(sys.argv[2]), sys.argv[3])
        except NoDevice as e:
            log(f"bench worker: {e}")
            sys.exit(_NO_DEVICE_RC)
        except RuntimeError as e:
            if "Unable to initialize backend" not in str(e):
                raise
            log(f"bench worker: {e}")
            sys.exit(_NO_DEVICE_RC)
        print(_RESULT_TAG + json.dumps(out), flush=True)
        sys.exit(0)
    sys.exit(main())
