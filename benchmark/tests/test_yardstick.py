"""The yardstick's own parts on the CPU: the generator copy, the plain
reference, and the reduction from a profiler trace."""

import json
import os

import numpy as np
import pytest

from benchmark import graph500, reference, tracereduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.mark.parametrize("scale,start,count,seed", [
    (10, 0, 16 << 10, 3), (14, 12345, 5000, 2**31 + 5),
    (18, (1 << 32) - 100, 300, 7)])
def test_generator_copy_equals_program(scale, start, count, seed):
    from sheep_tpu.io import generators

    ours = graph500.rmat_range(scale, start, count, 0.57, 0.19, 0.19, seed)
    theirs = generators.rmat_hash_range(scale, start, count, seed=seed)
    assert np.array_equal(ours, theirs)


def _cfg(scale):
    return {"scale": scale, "edge_factor": 16, "A": 0.57, "B": 0.19,
            "C": 0.19, "gen_seed": 5}


def test_seeds_relabel_one_graph():
    a, b = graph500.Graph500(_cfg(10), 1), graph500.Graph500(_cfg(10), 2)
    ea, eb = a.base(), b.base()
    assert not np.array_equal(ea, eb)
    assert np.array_equal(ea, graph500.Graph500(_cfg(10), 1).base())
    # same degree sequence: the permutation only renames vertices
    da = np.sort(np.bincount(ea.ravel(), minlength=a.n))
    db = np.sort(np.bincount(eb.ravel(), minlength=b.n))
    assert np.array_equal(da, db)


def test_fixed_labels_give_every_seed_one_graph():
    a = graph500.Graph500(_cfg(10), 1, relabel=False)
    b = graph500.Graph500(_cfg(10), 2, relabel=False)
    x, y = a.base(), b.base()
    assert not np.array_equal(x, y)
    assert np.array_equal(np.unique(x, axis=0, return_counts=True)[1],
                          np.unique(y, axis=0, return_counts=True)[1])
    assert np.array_equal(np.unique(x, axis=0), np.unique(y, axis=0))


@pytest.mark.parametrize("scale,k", [(8, 4), (12, 64), (14, 16)])
def test_reference_equals_native_backend(scale, k):
    from sheep_tpu.backends.base import get_backend
    from sheep_tpu.io.edgestream import EdgeStream

    g = graph500.Graph500(_cfg(scale), 11)
    e = g.base()
    ref = reference.partition(e, g.n, k)
    res = get_backend("cpu").partition(EdgeStream.from_array(e, g.n), k,
                                       comm_volume=False, keep_tree=True)
    assert np.array_equal(ref.parent, res.tree["parent"])
    assert np.array_equal(ref.part, res.assignment)
    assert (ref.cut, ref.total) == (res.edge_cut, res.total_edges)


def _events():
    """Two chips, a window of 100 ns, one partition span, Python frames."""
    return {
        "host": [["window", 0, 100], ["partition", 10, 80]],
        "frames": [["$a.py:1 outer", 5, 90], ["$b.py:2 tail", 40, 20]],
        "device": {
            "/device:TPU:0": {
                "modules": [["jit_fold(1)", 10, 30], ["jit_score(2)", 70, 20]],
                "ops": [["", 10, 10], ["", 15, 25], ["", 70, 20]]},
            "/device:TPU:1": {
                "modules": [["jit_fold(9)", 0, 100]],
                "ops": [["", 0, 100]]}}}


def test_reduce_synthetic_trace():
    red = tracereduce.reduce(_events())
    # chip 0 busy 10..40 and 70..90 (50 ns), chip 1 all 100 ns
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(75e-9)
    assert red["idle_share"] == pytest.approx(0.25)
    assert red["programs"] == pytest.approx(
        {"jit_fold": 65e-9, "jit_score": 10e-9})
    gaps = dict(red["idle_gaps"])
    # chip 0's gaps: 0..10 (harness, in frame outer), 40..70 (partition,
    # in the tail frame at 55), 90..100 (outer ended at 95: frame outer)
    assert gaps == pytest.approx({
        "window: $a.py:1 outer": 10e-9 / 2,
        "partition: $b.py:2 tail": 30e-9 / 2,
        "window: after jit_score": 10e-9 / 2})


def test_reduce_recorded_tpu_trace():
    """A trace recorded on one v5e (a scale-14 partition of the batch
    cell's graph, two partitions through the tpu backend under
    ``jax.profiler``, kept as ``tracereduce.extract`` gives it), against
    an independent sweep."""
    with open(os.path.join(DATA, "tpu_trace_s14.json")) as f:
        ev = json.load(f)
    red = tracereduce.reduce(ev)
    (w0, w1), = [(s, s + d) for n, s, d in ev["host"] if n == "window"]
    ops = next(iter(ev["device"].values()))["ops"]
    pts = sorted([(max(s, w0), 1) for _, s, d in ops if s + d > w0
                  and s < w1] + [(min(s + d, w1), -1) for _, s, d in ops
                                 if s + d > w0 and s < w1])
    busy, depth, last = 0, 0, None
    for t, step in pts:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    assert red["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9)
    assert red["window_s"] == pytest.approx((w1 - w0) / 1e9)
    assert 0 < red["idle_share"] < 1
    assert sum(s for _, s in red["idle_gaps"]) <= red["window_s"] - busy / 1e9 + 1e-9
    assert any("fold" in n for n, _ in red["device_ops"])


def test_cpu_trace_has_no_device_numbers(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x * 2))
    x = jnp.arange(4096.0)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("window"):
        with jax.profiler.TraceAnnotation("partition"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = tracereduce.extract(tracereduce.newest_xplane(str(tmp_path)))
    assert [n for n, _, _ in ev["host"]] == ["window", "partition"] or \
        sorted(n for n, _, _ in ev["host"]) == ["partition", "window"]
    assert ev["device"] == {}
    assert tracereduce.reduce(ev) is None
