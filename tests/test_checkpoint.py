"""Checkpoint/resume + fault-injection recovery (SURVEY.md §5, §4).

The core property: a run killed mid-stream and resumed from its last
checkpoint produces the *identical* partition to an uninterrupted run —
sound because the carried state (degree counts, partial forests, score
counters) is mergeable across chunk boundaries.
"""

import os

import numpy as np
import pytest

from sheep_tpu.backends.base import get_backend, list_backends
from sheep_tpu.io.edgestream import EdgeStream
from sheep_tpu.io import generators
from sheep_tpu.utils.checkpoint import Checkpointer, resume_state, stream_meta
from sheep_tpu.utils.fault import ENV_VAR, InjectedFault

K = 4
CHUNK = 256  # small so even tiny graphs span many chunks


def graph():
    e = generators.rmat(10, 8, seed=3)
    return EdgeStream.from_array(e, n_vertices=1 << 10)


STREAMING_BACKENDS = [b for b in ("cpu", "tpu", "tpu-sharded", "tpu-bigv")
                      if b in list_backends()]


# ---------------------------------------------------------------- unit level

def test_save_load_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path), every=2)
    arrays = {"deg": np.arange(10, dtype=np.int64), "cut": np.int64(7)}
    ck.save("build", 6, arrays, {"k": 4})
    state = ck.load()
    assert state.phase == "build" and state.chunk_idx == 6
    assert np.array_equal(state.arrays["deg"], arrays["deg"])
    assert int(state.arrays["cut"]) == 7
    assert state.meta == {"k": 4}


def test_sweep_keeps_latest_and_previous(tmp_path):
    """Two steps are retained (multi-host skew fallback needs the previous
    one); older steps are swept."""
    ck = Checkpointer(str(tmp_path), every=1)
    for idx in (1, 2, 3):
        ck.save("degrees", idx, {"deg": np.zeros(4, np.int64)})
    npz = sorted(f for f in os.listdir(tmp_path) if f.endswith(".npz"))
    assert len(npz) == 2
    assert any("_2" in f for f in npz) and any("_3" in f for f in npz)
    assert ck.load().chunk_idx == 3
    assert ck.load_at("degrees", 2).chunk_idx == 2
    assert ck.load_at("degrees", 1) is None


def test_clear(tmp_path):
    ck = Checkpointer(str(tmp_path), every=1)
    ck.save("degrees", 1, {"deg": np.zeros(4, np.int64)})
    ck.clear()
    assert ck.load() is None


def test_per_process_isolation(tmp_path):
    a = Checkpointer(str(tmp_path), every=1, process=0)
    b = Checkpointer(str(tmp_path), every=1, process=1)
    a.save("degrees", 1, {"deg": np.zeros(4, np.int64)})
    b.save("build", 9, {"deg": np.ones(4, np.int64)})
    assert a.load().phase == "degrees"
    assert b.load().phase == "build" and b.load().chunk_idx == 9


def _meta(es, **over):
    kw = dict(k=8, chunk_edges=CHUNK, weights="unit", alpha=1.0,
              comm_volume=True)
    kw.update(over)
    return stream_meta(es, **kw)


@pytest.mark.parametrize("change", [
    {"k": 4}, {"alpha": 0.9}, {"comm_volume": False}, {"weights": "degree"},
    {"chunk_edges": CHUNK * 2},
])
def test_resume_refuses_mismatched_options(tmp_path, change):
    ck = Checkpointer(str(tmp_path), every=1)
    es = graph()
    ck.save("build", 2, {"deg": np.zeros(4, np.int64)}, _meta(es))
    with pytest.raises(ValueError, match="does not match"):
        resume_state(ck, _meta(es, **change), resume=True)


def test_resume_refuses_cross_backend_state(tmp_path):
    """A sharded checkpoint resumed by the single-device backend must be a
    clean refusal, not a KeyError deep in partition()."""
    es = graph()
    ck = Checkpointer(str(tmp_path), every=1)
    ck.save("build", 2, {"deg": np.zeros(4, np.int64)},
            _meta(es, state_format="sharded", devices=8))
    with pytest.raises(ValueError, match="does not match"):
        resume_state(ck, _meta(es, state_format="minp"), resume=True)


def test_pure_backend_rejects_checkpointer(tmp_path):
    ck = Checkpointer(str(tmp_path), every=1)
    with pytest.raises(ValueError, match="does not checkpoint"):
        get_backend("pure").partition(graph(), K, checkpointer=ck)


def test_cadence(tmp_path):
    ck = Checkpointer(str(tmp_path), every=3)
    assert [i for i in range(1, 10) if ck.due(i)] == [3, 6, 9]


# ------------------------------------------------------- recovery end-to-end

@pytest.mark.parametrize("backend", STREAMING_BACKENDS)
@pytest.mark.parametrize("phase", ["degrees", "build", "score"])
def test_fault_then_resume_matches_uninterrupted(tmp_path, backend, phase,
                                                 monkeypatch):
    es = graph()
    kw = {"chunk_edges": CHUNK}
    expect = get_backend(backend, **kw).partition(es, K, comm_volume=True)

    ck = Checkpointer(str(tmp_path), every=1)
    monkeypatch.setenv(ENV_VAR, f"{phase}:2")
    with pytest.raises(InjectedFault):
        get_backend(backend, **kw).partition(
            es, K, comm_volume=True, checkpointer=ck)
    monkeypatch.delenv(ENV_VAR)
    saved = ck.load()
    assert saved is not None, "no checkpoint written before the fault"

    res = get_backend(backend, **kw).partition(
        es, K, comm_volume=True, checkpointer=ck, resume=True)
    assert np.array_equal(res.assignment, expect.assignment)
    assert res.edge_cut == expect.edge_cut
    assert res.total_edges == expect.total_edges
    assert res.comm_volume == expect.comm_volume


def test_successful_run_clears_checkpoint(tmp_path):
    es = graph()
    ck = Checkpointer(str(tmp_path), every=1)
    get_backend(STREAMING_BACKENDS[0], chunk_edges=CHUNK).partition(
        es, K, checkpointer=ck)
    assert ck.load() is None, "completed run left a stale checkpoint"
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".npz")]


def test_resume_refuses_different_inmemory_graph(tmp_path):
    """Two in-memory graphs with identical (V, E) but different edges must
    not cross-resume: the fingerprint hashes sampled edge content."""
    a = EdgeStream.from_array(generators.rmat(10, 8, seed=3), n_vertices=1 << 10)
    b = EdgeStream.from_array(generators.rmat(10, 8, seed=4), n_vertices=1 << 10)
    ck = Checkpointer(str(tmp_path), every=1)
    ck.save("build", 2, {"deg": np.zeros(4, np.int64)}, _meta(a))
    with pytest.raises(ValueError, match="does not match"):
        resume_state(ck, _meta(b), resume=True)


def test_resume_refuses_regenerated_input_file(tmp_path):
    """Same path + same shape but different bytes must not resume: the
    fingerprint includes file size/mtime (content identity)."""
    from sheep_tpu.io import formats

    gpath = str(tmp_path / "g.bin64")
    formats.write_edges(gpath, generators.rmat(9, 8, seed=1))
    with EdgeStream.open(gpath) as es:
        meta_a = _meta(es)
    ck = Checkpointer(str(tmp_path / "ck"), every=1)
    ck.save("build", 2, {"deg": np.zeros(4, np.int64)}, meta_a)

    os.utime(gpath, ns=(1, 1))  # same bytes, different mtime
    with EdgeStream.open(gpath) as es:
        with pytest.raises(ValueError, match="does not match"):
            resume_state(ck, _meta(es), resume=True)


@pytest.mark.parametrize("backend", STREAMING_BACKENDS[:1])
def test_resume_without_checkpoint_is_fresh_run(tmp_path, backend):
    es = graph()
    kw = {"chunk_edges": CHUNK}
    ck = Checkpointer(str(tmp_path), every=4)
    expect = get_backend(backend, **kw).partition(es, K)
    res = get_backend(backend, **kw).partition(es, K, checkpointer=ck,
                                               resume=True)
    assert np.array_equal(res.assignment, expect.assignment)


def test_cli_checkpoint_resume(tmp_path, monkeypatch):
    from sheep_tpu import cli
    from sheep_tpu.io import formats

    e = generators.rmat(9, 8, seed=5)
    gpath = str(tmp_path / "g.bin64")
    formats.write_edges(gpath, e)
    ckdir = str(tmp_path / "ck")

    out1 = str(tmp_path / "full.parts")
    assert cli.main(["--input", gpath, "--k", "4", "--backend",
                     STREAMING_BACKENDS[0], "--chunk-edges", str(CHUNK),
                     "--output", out1, "--json"]) == 0

    monkeypatch.setenv(ENV_VAR, "build:2")
    with pytest.raises(InjectedFault):
        cli.main(["--input", gpath, "--k", "4", "--backend",
                  STREAMING_BACKENDS[0], "--chunk-edges", str(CHUNK),
                  "--checkpoint-dir", ckdir, "--checkpoint-every", "1",
                  "--json"])
    monkeypatch.delenv(ENV_VAR)

    out2 = str(tmp_path / "resumed.parts")
    assert cli.main(["--input", gpath, "--k", "4", "--backend",
                     STREAMING_BACKENDS[0], "--chunk-edges", str(CHUNK),
                     "--checkpoint-dir", ckdir, "--resume",
                     "--output", out2, "--json"]) == 0
    assert np.array_equal(formats.read_partition(out1),
                          formats.read_partition(out2))


@pytest.mark.parametrize("phase", ["build", "score"])
def test_fault_then_resume_under_warm_collapse(tmp_path, phase,
                                               monkeypatch):
    """Kill+resume where the second chunk's warm segment collapses
    duplicates and hands the distinct constraints straight to the host
    tail (threshold between the distinct and the live count): the
    resumed run matches the uninterrupted one, and a resumed build
    refolds that chunk the same way."""
    if "tpu" not in list_backends():
        pytest.skip("tpu backend unavailable")
    import jax.numpy as jnp

    from sheep_tpu.backends.tpu_backend import pad_chunk
    from sheep_tpu.ops import elim as elim_ops

    n, c = 1 << 12, 1 << 15
    e = generators.rmat(12, 16, seed=1)
    assert len(e) == 2 * c
    es = EdgeStream.from_array(e, n_vertices=n)
    pos_host = get_backend("tpu", chunk_edges=c).partition(
        es, K, keep_tree=True).tree["pos"]
    pos = jnp.asarray(np.append(pos_host, n).astype(np.int32))
    P, _ = elim_ops.build_chunk_step_adaptive_pos(
        jnp.full(n + 1, n, dtype=jnp.int32), pad_chunk(e[:c], c, n), pos,
        pos_host, n)
    loP, hiP = elim_ops.orient_edges_pos(
        jnp.asarray(pad_chunk(e[c:], c, n)), pos, n)
    wlo, whi, _, _ = elim_ops.fold_segment_pos(
        P, loP, hiP, n, lift_levels=8, segment_rounds=1, descent="stream")
    live, distinct = (int(x) for x in
                      elim_ops.count_live_distinct(wlo, whi, n))
    threshold = (live + distinct) // 2
    assert distinct <= threshold < live

    kw = {"chunk_edges": c, "host_tail_threshold": threshold}
    expect = get_backend("tpu", **kw).partition(es, K, comm_volume=True)
    assert expect.diagnostics["warm_duplicates"] >= live - distinct

    ck = Checkpointer(str(tmp_path), every=1)
    monkeypatch.setenv(ENV_VAR, f"{phase}:2")
    with pytest.raises(InjectedFault):
        get_backend("tpu", **kw).partition(
            es, K, comm_volume=True, checkpointer=ck)
    monkeypatch.delenv(ENV_VAR)
    assert ck.load().phase == phase and ck.load().chunk_idx == 1

    res = get_backend("tpu", **kw).partition(
        es, K, comm_volume=True, checkpointer=ck, resume=True)
    assert np.array_equal(res.assignment, expect.assignment)
    assert res.edge_cut == expect.edge_cut
    assert res.comm_volume == expect.comm_volume
    if phase == "build":
        assert res.diagnostics["warm_duplicates"] == live - distinct
        assert res.diagnostics["host_tails"] == 1
        assert res.diagnostics.get("full_segments", 0) == 0


# ------------------------------------- graceful degradation (ISSUE 8)

def _truncate_mid_byte(path):
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(max(1, size // 2))


def _manifest_data(ck):
    import json

    with open(ck._manifest_path) as f:
        return json.load(f)["data"]


def test_corrupt_latest_falls_back_to_previous(tmp_path, capsys):
    """A truncated newest .npz degrades to the retained previous step
    with a warning — never a traceback mid-recovery."""
    ck = Checkpointer(str(tmp_path), every=1)
    ck.save("build", 1, {"deg": np.arange(4, dtype=np.int64)}, {"k": 4})
    ck.save("build", 2, {"deg": np.arange(4, dtype=np.int64) * 2}, {"k": 4})
    _truncate_mid_byte(str(tmp_path / _manifest_data(ck)))
    state = ck.load()
    assert state is not None and state.chunk_idx == 1
    assert np.array_equal(state.arrays["deg"], np.arange(4))
    assert "unreadable" in capsys.readouterr().err


def test_corrupt_all_degrades_to_clean_start(tmp_path, capsys):
    ck = Checkpointer(str(tmp_path), every=1)
    ck.save("build", 1, {"deg": np.zeros(4, np.int64)}, {"k": 4})
    ck.save("build", 2, {"deg": np.zeros(4, np.int64)}, {"k": 4})
    for f in os.listdir(tmp_path):
        if f.endswith(".npz"):
            _truncate_mid_byte(str(tmp_path / f))
    assert ck.load() is None
    err = capsys.readouterr().err
    assert "unreadable" in err and "clean start" in err


def test_torn_manifest_degrades_to_clean_start(tmp_path, capsys):
    ck = Checkpointer(str(tmp_path), every=1)
    ck.save("build", 1, {"deg": np.zeros(4, np.int64)}, {"k": 4})
    with open(ck._manifest_path, "r+") as f:
        raw = f.read()
        f.seek(0)
        f.truncate(len(raw) // 2)  # torn mid-write
    assert ck.load() is None
    assert "torn" in capsys.readouterr().err


def test_resume_with_corrupt_checkpoint_completes(tmp_path, monkeypatch):
    """End-to-end: fault a run, corrupt EVERY data file mid-byte, resume.
    Recovery degrades to a clean start (warning, no raise) and the final
    partition still matches the uninterrupted run bit for bit."""
    es = graph()
    kw = {"chunk_edges": CHUNK}
    backend = STREAMING_BACKENDS[0]
    expect = get_backend(backend, **kw).partition(es, K, comm_volume=True)

    ck = Checkpointer(str(tmp_path), every=1)
    monkeypatch.setenv(ENV_VAR, "build:4")
    with pytest.raises(InjectedFault):
        get_backend(backend, **kw).partition(
            es, K, comm_volume=True, checkpointer=ck)
    monkeypatch.delenv(ENV_VAR)
    for f in os.listdir(tmp_path):
        if f.endswith(".npz"):
            _truncate_mid_byte(str(tmp_path / f))

    res = get_backend(backend, **kw).partition(
        es, K, comm_volume=True, checkpointer=ck, resume=True)
    assert np.array_equal(res.assignment, expect.assignment)
    assert res.edge_cut == expect.edge_cut
    assert res.comm_volume == expect.comm_volume


# --------------------------- hierarchy survival drills (ISSUE 8 tentpole)

def _hier_graph(tmp_path):
    from sheep_tpu.io import formats

    p = str(tmp_path / "hg.bin64")
    formats.write_edges(p, generators.rmat(9, 8, seed=3))
    return p


HIER_KW = dict(refine=1, comm_volume=False, chunk_edges=CHUNK)


def test_hier_fault_resume_mid_level0_bit_identical(tmp_path, monkeypatch):
    """Kill the hierarchical run INSIDE level 0 (chunk granularity: the
    level-0 flat partition checkpoints into the nested level0/ domain),
    resume, and require a bit-identical final assignment."""
    import sheep_tpu

    p = _hier_graph(tmp_path)
    backend = STREAMING_BACKENDS[0]
    expect = sheep_tpu.partition_hierarchical(p, [2, 2], backend=backend,
                                              **HIER_KW)
    ck = Checkpointer(str(tmp_path / "ck"), every=1)
    monkeypatch.setenv(ENV_VAR, "level0:2")
    with pytest.raises(InjectedFault):
        sheep_tpu.partition_hierarchical(p, [2, 2], backend=backend,
                                         checkpointer=ck, **HIER_KW)
    monkeypatch.delenv(ENV_VAR)
    sub = Checkpointer(str(tmp_path / "ck" / "level0"), every=1)
    assert sub.load() is not None, \
        "no chunk-level checkpoint inside level 0 before the fault"

    res = sheep_tpu.partition_hierarchical(p, [2, 2], backend=backend,
                                           checkpointer=ck, resume=True,
                                           **HIER_KW)
    assert np.array_equal(res.assignment, expect.assignment)
    assert res.edge_cut == expect.edge_cut
    # success clears the whole recovery domain, spill shards included
    assert ck.load() is None
    assert os.listdir(tmp_path / "ck") == []


def test_hier_fault_resume_level_boundary_bit_identical(tmp_path,
                                                        monkeypatch):
    """Kill the run AT a level boundary (one part's subtree finished and
    checkpointed), resume, and require bit-identity; the saved state
    must record the queue position and the spill manifest."""
    import sheep_tpu

    p = _hier_graph(tmp_path)
    backend = STREAMING_BACKENDS[0]
    expect = sheep_tpu.partition_hierarchical(p, [2, 2], backend=backend,
                                              **HIER_KW)
    ck = Checkpointer(str(tmp_path / "ck"), every=1)
    monkeypatch.setenv(ENV_VAR, "level:1")
    with pytest.raises(InjectedFault):
        sheep_tpu.partition_hierarchical(p, [2, 2], backend=backend,
                                         checkpointer=ck, **HIER_KW)
    monkeypatch.delenv(ENV_VAR)
    st = ck.load()
    assert st is not None and st.phase == "hier" and st.chunk_idx == 1
    assert {"assign", "final", "spill_names", "spill_sizes"} <= set(st.arrays)
    # part 0's shard was consumed at its boundary; part 1's is pending
    assert int(st.arrays["spill_sizes"][0]) == -1
    assert int(st.arrays["spill_sizes"][1]) >= 0

    res = sheep_tpu.partition_hierarchical(p, [2, 2], backend=backend,
                                           checkpointer=ck, resume=True,
                                           **HIER_KW)
    assert np.array_equal(res.assignment, expect.assignment)
    assert res.edge_cut == expect.edge_cut
    assert ck.load() is None


def test_hier_resume_reuses_spill_manifest(tmp_path, monkeypatch):
    """A level-boundary resume must REUSE the spill shards named in the
    manifest, not re-stream the graph: _spill_intra is replaced with a
    bomb for the resumed run."""
    import sheep_tpu
    from sheep_tpu import hierarchy

    p = _hier_graph(tmp_path)
    backend = STREAMING_BACKENDS[0]
    expect = sheep_tpu.partition_hierarchical(p, [2, 2], backend=backend,
                                              **HIER_KW)
    ck = Checkpointer(str(tmp_path / "ck"), every=1)
    monkeypatch.setenv(ENV_VAR, "level:1")
    with pytest.raises(InjectedFault):
        sheep_tpu.partition_hierarchical(p, [2, 2], backend=backend,
                                         checkpointer=ck, **HIER_KW)
    monkeypatch.delenv(ENV_VAR)

    def bomb(*a, **kw):
        raise AssertionError("resume re-spilled instead of reusing the "
                             "manifest's shards")

    monkeypatch.setattr(hierarchy, "_spill_intra", bomb)
    res = sheep_tpu.partition_hierarchical(p, [2, 2], backend=backend,
                                           checkpointer=ck, resume=True,
                                           **HIER_KW)
    assert np.array_equal(res.assignment, expect.assignment)


def test_hier_resume_corrupt_spill_degrades(tmp_path, monkeypatch, capsys):
    """A pending spill shard that went missing/torn degrades the resume
    to a from-scratch level rebuild (warning, no raise) that still
    matches the uninterrupted run."""
    import sheep_tpu

    p = _hier_graph(tmp_path)
    backend = STREAMING_BACKENDS[0]
    expect = sheep_tpu.partition_hierarchical(p, [2, 2], backend=backend,
                                              **HIER_KW)
    ck = Checkpointer(str(tmp_path / "ck"), every=1)
    monkeypatch.setenv(ENV_VAR, "level:1")
    with pytest.raises(InjectedFault):
        sheep_tpu.partition_hierarchical(p, [2, 2], backend=backend,
                                         checkpointer=ck, **HIER_KW)
    monkeypatch.delenv(ENV_VAR)
    st = ck.load()
    pending = str(st.arrays["spill_names"][1])
    _truncate_mid_byte(str(tmp_path / "ck" / "hier_spill_p0"
                           / "level0_shards" / pending))

    res = sheep_tpu.partition_hierarchical(p, [2, 2], backend=backend,
                                           checkpointer=ck, resume=True,
                                           **HIER_KW)
    assert np.array_equal(res.assignment, expect.assignment)
    assert "spill shard" in capsys.readouterr().err


def test_hier_corrupt_latest_falls_back_to_previous_boundary(tmp_path,
                                                             monkeypatch,
                                                             capsys):
    """A corrupt LATEST level-boundary .npz falls back to the retained
    previous step — whose manifest still names shards the latest step
    marked consumed. Shard files outlive their manifest entry by one
    save for exactly this fallback, so the resume replays from the
    shards (no re-spill: _spill_intra is bombed) bit-identically."""
    import sheep_tpu
    from sheep_tpu import hierarchy

    p = _hier_graph(tmp_path)
    backend = STREAMING_BACKENDS[0]
    expect = sheep_tpu.partition_hierarchical(p, [2, 2], backend=backend,
                                              **HIER_KW)
    ck = Checkpointer(str(tmp_path / "ck"), every=1)
    monkeypatch.setenv(ENV_VAR, "level:1")
    with pytest.raises(InjectedFault):
        sheep_tpu.partition_hierarchical(p, [2, 2], backend=backend,
                                         checkpointer=ck, **HIER_KW)
    monkeypatch.delenv(ENV_VAR)
    _truncate_mid_byte(str(tmp_path / "ck" / _manifest_data(ck)))
    st = ck.load()  # previous step: nothing recursed yet
    assert st is not None and st.chunk_idx == 0
    capsys.readouterr()

    def bomb(*a, **kw):
        raise AssertionError("previous-step fallback re-spilled instead "
                             "of reusing the retained shards")

    monkeypatch.setattr(hierarchy, "_spill_intra", bomb)
    res = sheep_tpu.partition_hierarchical(p, [2, 2], backend=backend,
                                           checkpointer=ck, resume=True,
                                           **HIER_KW)
    assert np.array_equal(res.assignment, expect.assignment)
    assert "unreadable" in capsys.readouterr().err


def test_hier_boundary_checkpoint_without_chunk_support(tmp_path,
                                                        monkeypatch):
    """The pure backend cannot chunk-checkpoint (supports_checkpoint is
    False), but hierarchy still gives it level-BOUNDARY recovery instead
    of refusing the checkpointer outright."""
    import sheep_tpu

    p = _hier_graph(tmp_path)
    expect = sheep_tpu.partition_hierarchical(p, [2, 2], backend="pure",
                                              **HIER_KW)
    ck = Checkpointer(str(tmp_path / "ck"), every=1)
    monkeypatch.setenv(ENV_VAR, "level:1")
    with pytest.raises(InjectedFault):
        sheep_tpu.partition_hierarchical(p, [2, 2], backend="pure",
                                         checkpointer=ck, **HIER_KW)
    monkeypatch.delenv(ENV_VAR)
    assert ck.load() is not None

    res = sheep_tpu.partition_hierarchical(p, [2, 2], backend="pure",
                                           checkpointer=ck, resume=True,
                                           **HIER_KW)
    assert np.array_equal(res.assignment, expect.assignment)


def test_cli_k_levels_checkpoint_resume(tmp_path, monkeypatch):
    """The CLI drill: --k-levels + --checkpoint-dir killed at a level
    boundary, resumed with --resume, written map identical to an
    uninterrupted run's."""
    from sheep_tpu import cli
    from sheep_tpu.io import formats

    p = _hier_graph(tmp_path)
    base = ["--input", p, "--k-levels", "2,2", "--backend",
            STREAMING_BACKENDS[0], "--refine", "1", "--chunk-edges",
            str(CHUNK), "--no-comm-volume", "--json"]
    out1 = str(tmp_path / "full.parts")
    assert cli.main(base + ["--output", out1]) == 0

    ckdir = str(tmp_path / "ck")
    monkeypatch.setenv(ENV_VAR, "level:1")
    with pytest.raises(InjectedFault):
        cli.main(base + ["--checkpoint-dir", ckdir,
                         "--checkpoint-every", "1"])
    monkeypatch.delenv(ENV_VAR)

    out2 = str(tmp_path / "resumed.parts")
    assert cli.main(base + ["--checkpoint-dir", ckdir, "--resume",
                            "--output", out2]) == 0
    assert np.array_equal(formats.read_partition(out1),
                          formats.read_partition(out2))


def test_resume_refuses_legacy_carry_format(tmp_path, monkeypatch):
    """A build checkpoint of the retired format that carried chunk tails
    (state_format "minp_carry", with its in-flight actives) is refused on
    resume rather than resumed without those constraints; the same
    payload under the current format resumes to the uninterrupted
    partition."""
    if "tpu" not in list_backends():
        pytest.skip("tpu backend unavailable")
    es = graph()
    expect = get_backend("tpu", chunk_edges=CHUNK).partition(es, K)
    ck = Checkpointer(str(tmp_path / "minp"), every=1)
    monkeypatch.setenv(ENV_VAR, "build:2")
    with pytest.raises(InjectedFault):
        get_backend("tpu", chunk_edges=CHUNK).partition(
            es, K, checkpointer=ck)
    monkeypatch.delenv(ENV_VAR)
    state = ck.load()
    assert state.phase == "build" and state.meta["state_format"] == "minp"

    legacy = Checkpointer(str(tmp_path / "carry"), every=1)
    empty = np.full(1 << 14, 1 << 10, dtype=np.int32)
    legacy.save("build", state.chunk_idx,
                {**state.arrays, "carry_lo": empty, "carry_hi": empty},
                {**state.meta, "state_format": "minp_carry"})
    with pytest.raises(ValueError, match="does not match"):
        get_backend("tpu", chunk_edges=CHUNK).partition(
            es, K, checkpointer=legacy, resume=True)

    res = get_backend("tpu", chunk_edges=CHUNK).partition(
        es, K, checkpointer=ck, resume=True)
    assert np.array_equal(res.assignment, expect.assignment)
