"""Counter-based R-MAT (generators.rmat_hash_*, RmatHashStream).

The contract that makes the device fast path sound: the numpy twin and
the jnp device generator produce IDENTICAL bits, any chunking of the
edge-index range concatenates to the same sequence, and the stream
plugs into every backend with exact cross-backend equality (SURVEY.md
§4.3 — here exact, not tolerance-based, because both sides consume the
same edge multiset).
"""

import numpy as np
import pytest

from sheep_tpu.io import generators
from sheep_tpu.io.generators import (RmatHashStream, rmat_hash_chunk_device,
                                     rmat_hash_range)


def test_range_chunking_invariance():
    full = rmat_hash_range(8, 0, 4096, seed=3)
    pieces = [rmat_hash_range(8, s, c, seed=3)
              for s, c in ((0, 1000), (1000, 96), (1096, 3000))]
    np.testing.assert_array_equal(full, np.concatenate(pieces))


def test_determinism_and_seed_sensitivity():
    a = rmat_hash_range(10, 500, 2048, seed=7)
    b = rmat_hash_range(10, 500, 2048, seed=7)
    c = rmat_hash_range(10, 500, 2048, seed=8)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.dtype == np.int64 and a.shape == (2048, 2)
    assert a.min() >= 0 and a.max() < 1 << 10


def test_device_chunk_bit_identical_to_host_twin():
    cs, n = 1 << 12, 1 << 9
    stream = RmatHashStream(9, edge_factor=16, seed=11)
    host = list(stream.chunks(cs))
    for i, h in enumerate(host):
        d = np.asarray(stream.device_chunk(i, cs, n))
        assert d.shape == (cs, 2) and d.dtype == np.int32
        np.testing.assert_array_equal(d[: len(h)], h)
        assert np.all(d[len(h):] == n)  # sentinel padding


def test_device_chunk_on_synthesizes_on_the_target_device():
    """The multi-device placement hook computes each shard's chunk on
    its own device: nothing is synthesized on (or left behind on)
    device 0, and the bits equal the default-device chunk."""
    import jax

    devs = jax.devices()
    assert len(devs) >= 4, "conftest should force virtual devices"
    s = generators.RmatHashStream(10, 8, seed=3)
    want = np.asarray(s.device_chunk(1, 1024, 1 << 10))
    for dev in devs[1:4]:
        got = s.device_chunk_on(dev, 1, 1024, 1 << 10)
        assert got.devices() == {dev}
        np.testing.assert_array_equal(np.asarray(got), want)


def test_device_chunk_64bit_counter_carry():
    # a start index straddling the 2^32 boundary must hash the same as
    # the numpy twin (the device carries the counter as two uint32 words)
    start = (1 << 32) - 100
    host = rmat_hash_range(20, start, 256, seed=5)
    dev = np.asarray(rmat_hash_chunk_device(20, start, 256, 256, 1 << 20,
                                            seed=5))
    np.testing.assert_array_equal(dev, host)


def test_power_law_degree_skew():
    # Graph500 parameters concentrate edges on hub vertices: the max
    # degree must dwarf the mean by orders of magnitude
    e = rmat_hash_range(14, 0, 16 << 14, seed=1)
    deg = np.bincount(e.ravel(), minlength=1 << 14)
    assert deg.max() > 40 * deg.mean()
    # both uniform halves are exercised: u and v marginals differ but
    # both cover the low id range densely (a-quadrant recursion)
    assert (deg[: 1 << 7] > 0).mean() > 0.9


def test_stream_edgestream_surface():
    s = RmatHashStream(8, edge_factor=4, seed=2)
    assert s.num_edges == 4 << 8
    assert s.num_vertices == 1 << 8
    assert s.num_edges_cheap == s.num_edges_upper_bound == s.num_edges
    assert s.clamp_chunk_edges(1 << 22) == 4 << 8
    # round-robin sharding covers every edge exactly once
    cs = 128
    all_edges = np.concatenate(list(s.chunks(cs)))
    shard_union = np.concatenate(
        [c for p in range(3) for c in s.chunks(cs, shard=p, num_shards=3)])
    assert len(shard_union) == len(all_edges)
    np.testing.assert_array_equal(
        np.sort(all_edges.view("i8,i8"), axis=0),
        np.sort(shard_union.view("i8,i8"), axis=0))
    # start_chunk resume skips exactly the first chunks
    resumed = np.concatenate(list(s.chunks(cs, start_chunk=2)))
    np.testing.assert_array_equal(resumed, all_edges[2 * cs:])
    np.testing.assert_array_equal(s.read_all(), all_edges)


def test_count_edges_in_span_matches_replay():
    # the O(1) arithmetic must equal what summing owned chunks yields
    from sheep_tpu.io.edgestream import DEFAULT_CHUNK_EDGES

    s = RmatHashStream(8, edge_factor=5, seed=13)  # 1280 edges
    for num_shards in (1, 2, 3, 8):
        for shard in range(num_shards):
            replay = sum(len(c) for c in s.chunks(
                DEFAULT_CHUNK_EDGES, shard=shard, num_shards=num_shards))
            assert s.count_edges_in_span(shard, num_shards) == replay


def test_device_chunk_fn_is_cached():
    # one jitted wrapper for all chunks (a per-call closure would
    # retrace + recompile the scale-deep hash for every chunk)
    from sheep_tpu.io.generators import _device_chunk_fn

    rmat_hash_chunk_device(8, 0, 64, 64, 256, seed=1)
    assert _device_chunk_fn() is _device_chunk_fn()


@pytest.mark.parametrize("backend_name", ["pure", "tpu"])
def test_backends_partition_hash_stream(backend_name):
    from sheep_tpu.backends.base import get_backend, list_backends

    if backend_name not in list_backends():
        pytest.skip(f"{backend_name} unavailable")
    s = RmatHashStream(9, edge_factor=8, seed=4)
    res = get_backend(backend_name, chunk_edges=1 << 10).partition(s, k=4)
    e = s.read_all()
    assert res.total_edges == int((e[:, 0] != e[:, 1]).sum())  # non-loops
    assert len(res.assignment) == s.num_vertices
    assert res.assignment.min() >= 0 and res.assignment.max() < 4


def test_cross_backend_exact_equality_on_hash_stream():
    """pure vs tpu on the same RmatHashStream: same edges -> same scores
    (the tpu side reads device_chunk, the pure side host chunks)."""
    from sheep_tpu.backends.base import get_backend, list_backends

    if "tpu" not in list_backends():
        pytest.skip("tpu backend unavailable")
    s1 = RmatHashStream(9, edge_factor=8, seed=6)
    s2 = RmatHashStream(9, edge_factor=8, seed=6)
    a = get_backend("pure", chunk_edges=1 << 10).partition(s1, k=8)
    b = get_backend("tpu", chunk_edges=1 << 10).partition(s2, k=8)
    assert a.edge_cut == b.edge_cut
    assert a.total_edges == b.total_edges
    assert a.comm_volume == b.comm_volume
    np.testing.assert_array_equal(a.assignment, b.assignment)


def test_checkpoint_resume_on_hash_stream(tmp_path, monkeypatch):
    """Fault mid-build, then resume from the checkpoint and match the
    uninterrupted result (the stream's random-access chunks make resume
    replay-free)."""
    from sheep_tpu.backends.base import get_backend, list_backends
    from sheep_tpu.utils.checkpoint import Checkpointer
    from sheep_tpu.utils.fault import ENV_VAR, InjectedFault

    if "cpu" not in list_backends():
        pytest.skip("native cpu backend unavailable")
    s = RmatHashStream(9, edge_factor=8, seed=9)
    ref = get_backend("cpu", chunk_edges=1 << 10).partition(s, k=4)

    ck = Checkpointer(str(tmp_path), every=1)
    monkeypatch.setenv(ENV_VAR, "build:2")
    with pytest.raises(InjectedFault):
        get_backend("cpu", chunk_edges=1 << 10).partition(
            s, k=4, checkpointer=ck)
    monkeypatch.delenv(ENV_VAR)
    res = get_backend("cpu", chunk_edges=1 << 10).partition(
        s, k=4, checkpointer=Checkpointer(str(tmp_path), every=1),
        resume=True)
    assert res.edge_cut == ref.edge_cut
    np.testing.assert_array_equal(res.assignment, ref.assignment)


def test_open_input_specs():
    from sheep_tpu.io.edgestream import EdgeStream, open_input

    s = open_input("rmat-hash:9:4:3")
    assert isinstance(s, RmatHashStream)
    assert (s.num_vertices, s.num_edges, s.seed) == (512, 2048, 3)
    g = open_input("rmat:8:2")
    assert isinstance(g, EdgeStream) and g.num_edges == 512
    np.testing.assert_array_equal(  # defaults: ef=16, seed=0
        open_input("rmat-hash:8").read_all(),
        RmatHashStream(8, 16, seed=0).read_all())
    with pytest.raises(ValueError, match="synthetic input spec"):
        open_input("rmat-hash:notanint")
    with pytest.raises(ValueError, match="SCALE"):
        open_input("rmat:99:1")
    with pytest.raises(ValueError, match="contradicts"):
        open_input("rmat-hash:9:4", n_vertices=100)
    with pytest.raises(FileNotFoundError):
        open_input("/does/not/exist.bin32").num_edges


def test_cli_accepts_synthetic_spec(tmp_path):
    import json as _json
    import subprocess
    import sys

    out = tmp_path / "p.parts"
    r = subprocess.run(
        [sys.executable, "-m", "sheep_tpu.cli", "--input", "rmat-hash:8:4:1",
         "--k", "4", "--backend", "pure", "--json", "--output", str(out)],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr
    line = _json.loads(r.stdout.strip().splitlines()[-1])
    assert line["k"] == 4 and line["n_vertices"] == 256
    assert len(out.read_text().splitlines()) == 256


def test_api_accepts_synthetic_spec():
    import sheep_tpu

    res = sheep_tpu.partition("rmat-hash:8:4:1", 4, backend="pure")
    assert res.k == 4 and len(res.assignment) == 256


def test_scale_bounds_and_path_inputs(tmp_path):
    from pathlib import Path

    from sheep_tpu.io import formats
    from sheep_tpu.io.edgestream import open_input

    # uint32 bit accumulation caps rmat-hash at scale 32 (33 would
    # silently confine ids below 2^32); the int64 PCG spec goes further
    with pytest.raises(ValueError, match="SCALE"):
        open_input("rmat-hash:33")
    with pytest.raises(ValueError, match="1..32"):
        RmatHashStream(33)
    assert open_input("rmat:33:1").num_vertices == 1 << 33
    # pathlib.Path inputs must keep working through open_input
    p = tmp_path / "tiny.edges"
    formats.write_edges(str(p), generators.karate_club())
    assert open_input(Path(p)).num_edges == 78


def test_native_generator_bit_identical_and_fast():
    from sheep_tpu.core import native
    from sheep_tpu.io.generators import (_rmat_hash_keys, _rmat_hash_keys2,
                                         _rmat_hash_thresholds,
                                         _rmat_hash_uv)

    if not native.available():
        pytest.skip("native core unavailable")
    scale, seed, start, count = 20, 17, (1 << 32) - 500, 20000
    keys = _rmat_hash_keys(scale, seed)
    th = _rmat_hash_thresholds(0.57, 0.19, 0.19)
    nat = native.rmat_hash_range(scale, start, count, keys,
                                 _rmat_hash_keys2(keys), th)
    idx = start + np.arange(count, dtype=np.int64)
    u, v = _rmat_hash_uv(np, (idx & 0xFFFFFFFF).astype(np.uint32),
                         (idx >> 32).astype(np.uint32), keys, th, np.int64)
    np.testing.assert_array_equal(nat, np.stack([u, v], axis=1))
    # and the public entry point (which routes large counts natively)
    np.testing.assert_array_equal(
        nat, rmat_hash_range(scale, start, count, seed=17))
