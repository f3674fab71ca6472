"""bench.py JSON contract tests (VERDICT r3 item 6).

Properties the driver relies on:
  (a) the multi-chip leg — the exact code path that will emit
      ``vs_baseline_4chip`` on real multi-chip hardware — compiles and
      runs on the 8-device virtual mesh (``SHEEP_BENCH_MULTICHIP=1``
      forces it on cpu-jax);
  (b) a requested cpu-jax run (``SHEEP_BENCH_PLATFORM=cpu``) emits
      ``vs_baseline: null`` (the cpu-jax vs native-CPU ratio is
      framework overhead, not the north-star metric, and lives under
      ``cpu_jax_vs_native_cpu``) and names its platform;
  (c) without that request and without a TPU, bench.py exits non-zero
      and prints no number — there is no CPU fallback.
"""

import json
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def test_measure_multichip_leg_on_virtual_mesh(monkeypatch):
    assert jax.device_count() == 8, "conftest should force 8 virtual devices"
    monkeypatch.setenv("SHEEP_BENCH_MULTICHIP", "1")
    monkeypatch.setenv("SHEEP_BENCH_K", "8")
    sys.path.insert(0, REPO)
    try:
        import bench
        out = bench.measure(12, "cpu")
    finally:
        sys.path.remove(REPO)
    assert out["n_devices"] == 8
    assert out["sharded_eps"] > 0
    assert out["ratio_multichip"] > 0
    assert out["platform"] == "cpu" and out["device_count"] == 8
    assert out["host_syncs"] >= 0 and out["device_rounds"] > 0
    # dispatch-overlap contract pair (ISSUE 4): on every measured row,
    # so --inflight A/Bs and the bench_regress host_blocked_ms gate
    # have their inputs even on cpu-jax windows
    assert out["host_blocked_ms"] >= 0
    assert out["device_gap_ms"] >= 0
    # the sharded path partitions the same counter-hash graph: its cut
    # must be in the same regime as the baselines (not degenerate)
    assert 0.0 < out["sharded_cut_ratio"] <= 1.0
    assert abs(out["sharded_cut_ratio"] - out["cpu_cut_ratio"]) < 0.2


def test_cpu_run_emits_null_vs_baseline():
    env = dict(os.environ)
    env.update(SHEEP_BENCH_PLATFORM="cpu", SHEEP_BENCH_SCALE="12",
               SHEEP_BENCH_K="8", SHEEP_BENCH_ATTEMPT_TIMEOUT="600")
    r = subprocess.run([sys.executable, BENCH], capture_output=True,
                       text=True, env=env, timeout=900, cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["vs_baseline"] is None
    assert line["value"] > 0
    assert line["cpu_jax_vs_native_cpu"] > 0
    assert line["platform"] == "cpu" and line["device_kind"] == "cpu"
    assert "cpu" in line["metric"]
    # the overlap counters ride the emitted line too (ISSUE 4)
    for f in ("host_blocked_ms", "device_gap_ms"):
        assert line[f] >= 0, f
    # the fault-tolerance contract (ISSUE 9): dispatch_retries is
    # ALWAYS emitted (0 on a healthy run) so the regression gate can
    # see 0 -> N movement instead of an incomparable missing field
    assert line["dispatch_retries"] == 0
    # the warm-vs-cold served-request contract (ISSUE 10): warm_up_s
    # (the cold first-request jit tax bench.py printed for three
    # rounds but never emitted) and the cold/warm request walls ride
    # every measured line so bench_regress gates warm-path latency
    assert line["warm_up_s"] > 0
    assert line["cold_request_s"] > 0 and line["warm_request_s"] > 0
    # the incremental contract (ISSUE 15): update_request_s — one
    # resident-partition delta fold — rides every measured line with
    # its compactions companion, so bench_regress can gate the O(Δ)
    # update wall like the warm path
    assert line["update_request_s"] > 0
    assert line["compactions"] == 0
    # the multi-device incremental contract (ISSUE 19): the same scored
    # delta epoch through the tpu-sharded fold + distributed rescore
    # rides every measured line, gated lower-better by bench_regress
    assert line["sharded_update_request_s"] > 0


def test_no_tpu_exits_nonzero_without_a_number():
    """No SHEEP_BENCH_PLATFORM and no TPU: the worker's pinned TPU init
    fails, bench.py stops at the first attempt (no scale ladder, no
    CPU fallback), exits 1 and prints nothing on stdout."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("SHEEP_BENCH_PLATFORM", "JAX_PLATFORMS")}
    env.update(SHEEP_BENCH_SCALE="12", TPU_LOG_DIR="disabled")
    r = subprocess.run([sys.executable, BENCH], capture_output=True,
                       text=True, env=env, timeout=300, cwd=REPO)
    assert r.returncode == 1, r.stderr[-2000:]
    assert r.stdout.strip() == ""
    assert "no tpu device" in r.stderr
    assert r.stderr.count("--measure") == 0
