"""The obs profiler sink and stats scope: program spans on the JAX
profiler's trace, and the counters the partition's layers add to its
diagnostics (pulls, native core, chunk wait, compiles)."""

import glob
import math
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sheep_tpu import obs
from sheep_tpu.backends.tpu_backend import TpuBackend
from sheep_tpu.io import generators
from sheep_tpu.io.edgestream import EdgeStream
from sheep_tpu.obs import profiler as obs_profiler
from sheep_tpu.ops import degrees as degrees_ops
from sheep_tpu.utils.prefetch import H2DRing, prefetch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import harness, tracereduce  # noqa: E402


def _graph(scale, ef=8, seed=5):
    e = generators.rmat(scale, ef, seed=seed)
    return e, int(e.max()) + 1


def _partition(e, n, k=4, **kw):
    return TpuBackend(**kw).partition(EdgeStream.from_array(e, n_vertices=n),
                                      k, comm_volume=False)


def _trace(tmp_path, fn):
    """Run ``fn`` under the profiler; the host lines' ``sheep/`` events
    as lists of (name, start_ns, end_ns), and the result."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in line.events if ev.name.startswith("sheep/")]
            if evs:
                lines.append(evs)
    return lines, out, path


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_partition_spans_nest_on_one_host_line(tmp_path):
    e, n = _graph(scale=12, seed=3)  # ends in a host tail
    _partition(e, n)  # compile outside the trace
    lines, res, _ = _trace(tmp_path, lambda: _partition(e, n))
    assert res.diagnostics["host_tails"] >= 1
    line, = [evs for evs in lines
             if any(name == "sheep/partition" for name, _, _ in evs)]

    def first(name, within=None):
        return next(ev for ev in line if ev[0] == name
                    and (within is None or _inside(ev, within)))

    part = first("sheep/partition")
    build = first("sheep/build", part)
    seg = first("sheep/segment", build)
    first("sheep/pull:adaptive-sv-pull", seg)
    tail = first("sheep/host_tail", seg)
    first("sheep/native:build_elim_tree", tail)
    first("sheep/compact", tail)
    first("sheep/native:tree_split", first("sheep/split", part))
    for name in ("sheep/degrees", "sheep/sort", "sheep/score",
                 "sheep/chunk_wait", "sheep/fold"):
        first(name, part)
    # every pull span lies inside the partition, on its line
    pulls = [ev for ev in line if ev[0].startswith("sheep/pull:")]
    assert len(pulls) == res.diagnostics["pulls"]
    assert all(_inside(ev, part) for ev in pulls)


def test_spans_end_on_their_thread_and_begin_end_pair(tmp_path):
    def body():
        sp = obs.begin("outer", i=1)
        inner = obs.begin_detached("detached")
        inner.end()

        def work():
            with obs.span("worker"):
                time.sleep(0.002)
        t = threading.Thread(target=work)
        t.start()
        t.join()
        sp.end()

    lines, _, _ = _trace(tmp_path, body)
    names = [sorted(name for name, _, _ in evs) for evs in lines]
    assert ["sheep/detached", "sheep/outer"] in names
    assert ["sheep/worker"] in names


def test_benchmark_annotations_stay_apart(tmp_path):
    """The program's spans never reach the benchmark's own host list."""
    e, n = _graph(scale=9)

    def body():
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("partition"):
                _partition(e, n)

    lines, _, path = _trace(tmp_path, body)
    assert any(name == "sheep/partition" for evs in lines
               for name, _, _ in evs)
    ev = tracereduce.extract(path)
    assert sorted(name for name, _, _ in ev["host"]) == ["partition",
                                                         "window"]


class _CountingAnnotation:
    built = 0

    def __init__(self, name):
        type(self).built += 1
        self.name = name

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_no_tracer_no_profiler_builds_nothing(monkeypatch):
    class Off(_CountingAnnotation):
        built = 0

        @staticmethod
        def is_enabled():
            return False

    monkeypatch.setattr(obs_profiler, "_ANNOTATION", Off)
    assert obs.get_tracer() is None
    assert obs.span("x", i=1) is obs.NULL_SPAN
    assert obs.begin("x") is obs.NULL_SPAN
    assert obs.begin_detached("x") is obs.NULL_SPAN
    assert obs.profiler_span("x") is obs.NULL_SPAN
    with obs.timed("native:x", "t_native_s"):
        pass
    obs.pull("x", jnp.arange(3))
    assert Off.built == 0


def test_profiler_session_builds_one_annotation_per_span(monkeypatch):
    class On(_CountingAnnotation):
        built = 0

        @staticmethod
        def is_enabled():
            return True

    monkeypatch.setattr(obs_profiler, "_ANNOTATION", On)
    sp = obs.begin("x")
    assert isinstance(sp, obs.ProfiledSpan) and sp._ann.name == "sheep/x"
    sp.end()
    sp.end()  # idempotent
    with obs.span("y"):
        pass
    obs.pull("lbl", jnp.arange(3))
    assert On.built == 3


def test_profiler_check_never_imports_jax(monkeypatch):
    monkeypatch.setattr(obs_profiler, "_ANNOTATION", None)
    monkeypatch.delitem(sys.modules, "jax")
    assert obs_profiler.annotation_cls() is None
    assert "jax" not in sys.modules


def test_pulls_equal_the_designed_sites_hit():
    e, n = _graph(scale=10)
    cs = 1024
    res = _partition(e, n, chunk_edges=cs)
    d = res.diagnostics
    chunks = math.ceil(len(e) / cs)
    flushes = chunks // degrees_ops.flush_every_for(cs) + 1
    expect = (flushes            # degrees-flush
              + 1                # sort-barrier
              + 1                # pos-host
              + d["host_syncs"]  # adaptive-sv-pull, one per segment
              + 3 * d.get("host_tails", 0)  # host-tail lo, hi, table
              + 1                # build-barrier
              + 2                # split-table, split-order
              + 2 * chunks)      # score-cut, score-total
    assert d["pulls"] == expect
    assert d["pull_bytes"] >= 4 * (2 * n)  # pos-host and split-table


def test_pull_and_native_walls_fit_in_the_phases():
    e, n = _graph(scale=10)
    res = _partition(e, n, chunk_edges=2048)
    d = res.diagnostics
    assert d["pulls"] > 0 and d["t_native_s"] >= 0
    assert 0 <= d["t_transfer_max_s"] <= d["t_transfer_s"]
    assert d["t_ready_wait_s"] + d["t_transfer_s"] + d["t_native_s"] \
        <= sum(res.phase_times.values())


def test_pull_keeps_host_blocked_ms_and_skips_host_values():
    stats: dict = {}
    with obs.stats_scope(stats):
        out = obs.pull("x", jnp.arange(5, dtype=jnp.int32) * 2,
                       host_blocked=stats)
        host = obs.pull("x", np.arange(3))
    assert out.tolist() == [0, 2, 4, 6, 8] and host.tolist() == [0, 1, 2]
    assert stats["pulls"] == 1 and stats["pull_bytes"] == 20
    assert stats["host_blocked_ms"] >= 1e3 * (stats["t_ready_wait_s"]
                                              + stats["t_transfer_s"]) - 1e-6


def _ring_wait(delay_s: float, k: int = 4) -> dict:
    def produce():
        for i in range(k):
            time.sleep(delay_s)
            yield np.full((16, 2), i, np.int32)

    stats: dict = {}
    with prefetch(produce()) as pf:
        assert len(list(H2DRing(pf, depth=1, stats=stats))) == k
    return stats


def test_chunk_wait_grows_when_the_producer_is_slowed():
    fast, slow = _ring_wait(0.0), _ring_wait(0.05)
    assert slow["t_chunk_wait_s"] >= 0.15
    assert slow["t_chunk_wait_s"] > fast["t_chunk_wait_s"]
    # the same clock reads as the h2d pair, the first block included
    for s in (fast, slow):
        assert s["t_chunk_wait_s"] * 1e3 <= s["h2d_blocked_ms"] + \
            s["h2d_staged_ms"] + 1e-6


def test_prefetch_worker_counts_into_the_callers_scope():
    def produce():
        obs.add("worker_items", 1)
        yield 1
        obs.add("worker_items", 1)
        yield 2

    stats: dict = {}
    with obs.stats_scope(stats):
        with prefetch(produce()) as pf:
            assert list(pf) == [1, 2]
    assert stats["worker_items"] == 2


def test_compiles_counted_like_the_harness_meter():
    meter = harness.CompileMeter()
    stats: dict = {}
    f = jax.jit(lambda x: x * 3 + 1)
    c0 = meter.count
    with obs.stats_scope(stats):
        f(jnp.arange(4099)).block_until_ready()   # a new shape: compiles
        f(jnp.arange(4099)).block_until_ready()   # cached: does not
    assert stats["compiles"] == meter.count - c0 >= 1
    assert stats["t_compile_s"] > 0
    # a partition counts its own compiles, and none once warm
    e, n = _graph(scale=9, seed=11)
    c0 = meter.count
    first = _partition(e, n, chunk_edges=768)
    assert first.diagnostics["compiles"] == meter.count - c0 >= 1
    c0 = meter.count
    again = _partition(e, n, chunk_edges=768)
    assert again.diagnostics["compiles"] == meter.count - c0 == 0


def test_scope_counters_lose_no_update_across_threads():
    """Worker threads that carry one scope add into one dict: every
    add lands, under a switch interval short enough to interleave the
    read-modify-write of each counter."""
    stats: dict = {}
    workers, adds = 32, 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with obs.stats_scope(stats):
            scope = obs.current_stats()

            def work():
                with obs.stats_scope(scope):
                    for _ in range(adds):
                        obs.add("t_native_s", 1)
            threads = [threading.Thread(target=work) for _ in range(workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert stats["t_native_s"] == workers * adds
