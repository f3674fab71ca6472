"""Asynchronous in-flight dispatch pipeline + donated buffers (the
ISSUE 4 tentpole).

The acceptance properties, all assertable on the CPU mesh:

  (a) forest bit-identity — ``--inflight D`` for D in {1, 2, 3} produces
      the identical elimination forest to the synchronous path, across
      the driver, backend, sharded and CLI entry points, including runs
      that hit early-convergence discard and budget-exhaustion resume
      (the fixpoint is the unique forest of the constraint multiset,
      independent of fold order and of which speculations ran);
  (b) donation equivalence — the donated programs are pure buffer
      aliasing: enabled/disabled runs are bit-identical, and donated
      inputs really are consumed;
  (c) counter flow — ``host_blocked_ms``/``device_gap_ms`` exist on
      every driver run, flow into obs span deltas, and (with
      tests/test_bench_contract.py and tests/test_trace_tools.py) ride
      the bench contract into the bench_regress gate;
  (d) HBM model — D in-flight staging blocks multiply the staging term
      and donation credits state back (tests/test_membudget.py holds
      the sizing assertions).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from sheep_tpu.backends.tpu_backend import TpuBackend, pad_chunk
from sheep_tpu.io import generators
from sheep_tpu.io.edgestream import EdgeStream
from sheep_tpu.ops import degrees as degrees_ops
from sheep_tpu.ops import elim as elim_ops
from sheep_tpu.ops import order as order_ops


def _order(e, n):
    deg = degrees_ops.init_degrees(n)
    deg = degrees_ops.degree_chunk(deg, pad_chunk(e, len(e), n), n)
    return order_ops.elimination_order(deg, n)


def _oracle(e, n, pos, order):
    whole, _ = elim_ops.build_chunk_step(
        jnp.full(n + 1, n, dtype=jnp.int32), pad_chunk(e, len(e), n),
        pos, order, n)
    return np.asarray(whole)


def _staged(e, cs, n, pos, batch):
    """Generator of (loB, hiB, tag) staged oriented blocks, fresh per
    call (the pipelined driver consumes/donates its inputs)."""
    chunks = [pad_chunk(e[off:off + cs], cs, n)
              for off in range(0, len(e), cs)]
    while len(chunks) % batch:
        chunks.append(np.full((cs, 2), n, np.int32))
    for i in range(0, len(chunks), batch):
        loB, hiB = elim_ops.orient_chunks_batch_pos(
            jnp.asarray(np.stack(chunks[i:i + batch])), pos, n)
        yield loB, hiB, batch


@pytest.mark.parametrize("inflight", [1, 2, 3])
@pytest.mark.parametrize("donate", [False, True])
def test_pipelined_matches_oracle_rmat14(inflight, donate):
    """Oracle equality at RMAT-14 for D in {1, 2, 3}, donation on and
    off (acceptance criterion of the in-flight pipeline)."""
    e = generators.rmat(14, 4, seed=7)
    n = 1 << 14
    pos, order = _order(e, n)
    whole = _oracle(e, n, pos, order)
    stats: dict = {}
    P, _ = elim_ops.fold_segments_pipelined(
        jnp.full(n + 1, n, dtype=jnp.int32),
        _staged(e, 1 << 13, n, pos, 2), n,
        inflight=inflight, segment_rounds=2, donate=donate, stats=stats)
    np.testing.assert_array_equal(np.asarray(P[pos]), whole)
    assert stats["host_syncs"] > 0
    assert stats["host_blocked_ms"] >= 0.0
    assert stats["device_gap_ms"] >= 0.0
    assert "batch_incomplete_segments" not in stats


def test_pipelined_sync_depth_matches_batched_driver():
    """``inflight=1`` degenerates to the synchronous driver exactly:
    same executions in the same order, so the sync/round counters (not
    just the forest) agree with fold_segments_batch over the groups."""
    e = generators.rmat(12, 8, seed=5)
    n = 1 << 12
    pos, _ = _order(e, n)
    sa: dict = {}
    Pa = jnp.full(n + 1, n, dtype=jnp.int32)
    for loB, hiB, _tag in _staged(e, 1 << 10, n, pos, 2):
        Pa, _ = elim_ops.fold_segments_batch(Pa, loB, hiB, n,
                                             segment_rounds=2, stats=sa)
    sb: dict = {}
    Pb, _ = elim_ops.fold_segments_pipelined(
        jnp.full(n + 1, n, dtype=jnp.int32),
        _staged(e, 1 << 10, n, pos, 2), n,
        inflight=1, segment_rounds=2, donate=False, stats=sb)
    np.testing.assert_array_equal(np.asarray(Pa), np.asarray(Pb))
    assert sb["host_syncs"] == sa["host_syncs"]
    assert sb["device_rounds"] == sa["device_rounds"]
    assert sb["inflight_discards"] == 0


def test_early_convergence_discards_speculation():
    """A stream whose final blocks converge in one execution forces the
    stream-end speculation to be wrong: the speculative re-dispatches
    are discarded UNREAD (no extra host syncs) and the adopted chain
    tip is the bit-identical re-confirmation of the converged table."""
    e = generators.rmat(12, 8, seed=3)
    n = 1 << 12
    pos, order = _order(e, n)
    whole = _oracle(e, n, pos, order)
    stats: dict = {}
    P, _ = elim_ops.fold_segments_pipelined(
        jnp.full(n + 1, n, dtype=jnp.int32),
        _staged(e, len(e), n, pos, 1), n,   # one group, one execution
        inflight=3, batch_rounds=1 << 14, donate=True, stats=stats)
    np.testing.assert_array_equal(np.asarray(P[pos]), whole)
    assert stats["inflight_discards"] == 2   # both speculations wasted
    assert stats["host_syncs"] == 1          # their svs were never read


@pytest.mark.parametrize("inflight", [1, 2, 3])
def test_budget_exhaustion_resumes_to_oracle(inflight):
    """A per-execution round budget far below the need forces repeated
    mid-block exhaustion: the leftover blocks are re-queued onto the
    live chain and the stream still converges to the oracle forest
    (the budget-exhaustion resume path)."""
    e = generators.rmat(12, 8, seed=11)
    n = 1 << 12
    pos, order = _order(e, n)
    whole = _oracle(e, n, pos, order)
    stats: dict = {}
    P, _ = elim_ops.fold_segments_pipelined(
        jnp.full(n + 1, n, dtype=jnp.int32),
        _staged(e, 1 << 10, n, pos, 2), n,
        inflight=inflight, batch_rounds=3, donate=True, stats=stats)
    np.testing.assert_array_equal(np.asarray(P[pos]), whole)
    assert "batch_incomplete_segments" not in stats


def test_max_rounds_backstop_flags_incomplete():
    """The round backstop must not exit silently: in-flight executions
    are drained (and counted) and the undrained remainder is flagged."""
    e = generators.rmat(11, 8, seed=2)
    n = 1 << 11
    pos, _ = _order(e, n)
    stats: dict = {}
    _, total = elim_ops.fold_segments_pipelined(
        jnp.full(n + 1, n, dtype=jnp.int32),
        _staged(e, 256, n, pos, 2), n,
        inflight=2, max_rounds=4, donate=True, stats=stats)
    assert total >= 4
    assert stats["batch_incomplete_segments"] > 0


def test_donated_program_consumes_inputs():
    """The donated fold really donates: its inputs are invalidated, so
    the membudget credit corresponds to actual buffer reuse."""
    e = generators.rmat(10, 8, seed=1)
    n = 1 << 10
    pos, _ = _order(e, n)
    (loB, hiB, _tag), = list(_staged(e, len(e), n, pos, 1))
    P = jnp.full(n + 1, n, dtype=jnp.int32)
    elim_ops.fold_segments_batch_pos_donated(P, loB, hiB, n)
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(P)


def test_fold_segments_batch_donate_resumes_after_exhaustion():
    """The donated program composes with budget-exhaustion resume at
    the fold_segments_batch level (the synchronous driver's donate
    knob): repeated donated executions on the returned state converge
    to the oracle."""
    e = generators.rmat(10, 8, seed=3)
    n = 1 << 10
    pos, order = _order(e, n)
    whole = _oracle(e, n, pos, order)
    (loB, hiB, _tag), = list(_staged(e, len(e), n, pos, 1))
    stats: dict = {}
    P, _ = elim_ops.fold_segments_batch(
        jnp.full(n + 1, n, dtype=jnp.int32), loB, hiB, n,
        batch_rounds=3, stats=stats, donate=True)
    np.testing.assert_array_equal(np.asarray(P[pos]), whole)
    assert stats["batch_execs"] > 1  # the tiny budget really exhausted


def test_pipelined_rejects_bad_depth():
    with pytest.raises(ValueError, match="inflight"):
        elim_ops.fold_segments_pipelined(
            jnp.full(8, 7, dtype=jnp.int32), iter(()), 7, inflight=0)


# -- backend / sharded / CLI entry points ----------------------------------


@pytest.mark.parametrize("inflight", [2, 3])
def test_backend_inflight_bit_identical(inflight):
    """End-to-end TpuBackend equality: pipelined dispatch vs the
    synchronous default, multi-chunk stream with a sentinel-padded tail
    group, donation on (the default) and off."""
    e = generators.rmat(11, 8, seed=9)
    n = 1 << 11
    es = EdgeStream.from_array(e, n_vertices=n)
    base = TpuBackend(chunk_edges=512).partition(es, 8)
    got = TpuBackend(chunk_edges=512, dispatch_batch=2,
                     inflight=inflight).partition(es, 8)
    np.testing.assert_array_equal(got.assignment, base.assignment)
    assert got.edge_cut == base.edge_cut
    assert got.comm_volume == base.comm_volume
    assert got.diagnostics["inflight_depth"] == inflight
    assert got.diagnostics["host_blocked_ms"] >= 0
    assert got.diagnostics["device_gap_ms"] >= 0
    nod = TpuBackend(chunk_edges=512, dispatch_batch=2, inflight=inflight,
                     donate_buffers=False).partition(es, 8)
    np.testing.assert_array_equal(nod.assignment, base.assignment)
    assert nod.edge_cut == base.edge_cut


def test_backend_inflight_without_batching():
    """--inflight alone engages the pipeline even where dispatch_batch
    auto-resolves to 1 (cpu-jax): N=1 staged blocks, same forest."""
    e = generators.rmat(11, 8, seed=9)
    n = 1 << 11
    es = EdgeStream.from_array(e, n_vertices=n)
    base = TpuBackend(chunk_edges=512).partition(es, 8)
    got = TpuBackend(chunk_edges=512, inflight=2).partition(es, 8)
    np.testing.assert_array_equal(got.assignment, base.assignment)
    assert got.diagnostics["dispatch_batch"] == 1
    assert got.diagnostics["inflight_depth"] == 2


def test_backend_inflight_excludes_tail_strategies():
    """A depth below one is refused."""
    for bad in (0, -1):
        with pytest.raises(ValueError, match="inflight"):
            TpuBackend(inflight=bad)


def test_adaptive_driver_emits_overlap_counters():
    """The synchronous per-segment driver emits the same counter pair,
    so an --inflight A/B is readable from any two runs' diagnostics."""
    e = generators.rmat(11, 8, seed=9)
    n = 1 << 11
    es = EdgeStream.from_array(e, n_vertices=n)
    res = TpuBackend(chunk_edges=512).partition(es, 8)
    assert res.diagnostics["host_blocked_ms"] >= 0
    assert res.diagnostics["device_gap_ms"] >= 0


@pytest.mark.parametrize("inflight", [2, 3])
def test_sharded_pipeline_inflight_matches(inflight):
    """The sharded batched path's speculative one-behind pipelining
    (pmin-done lockstep, discard-unread on convergence) must match the
    per-segment sharded run on the 8-device virtual mesh."""
    from sheep_tpu.backends.base import get_backend, list_backends

    if "tpu-sharded" not in list_backends():
        pytest.skip("sharded backend unavailable")
    e = generators.rmat(11, 8, seed=9)
    n = 1 << 11
    es = EdgeStream.from_array(e, n_vertices=n)
    base = get_backend("tpu-sharded", chunk_edges=256).partition(
        es, 8, comm_volume=False)
    got = get_backend("tpu-sharded", chunk_edges=256, dispatch_batch=2,
                      inflight=inflight).partition(es, 8,
                                                   comm_volume=False)
    np.testing.assert_array_equal(got.assignment, base.assignment)
    assert got.edge_cut == base.edge_cut
    assert got.diagnostics["inflight_depth"] == inflight
    assert got.diagnostics["host_blocked_ms"] >= 0


@pytest.mark.parametrize("inflight", [2, 3])
def test_checkpoint_resume_through_pipeline(tmp_path, monkeypatch,
                                            inflight):
    """Checkpoints are FLUSH BARRIERS (regression test): mid-pipeline
    the tip table can under-represent a confirmed group whose
    budget-exhausted leftovers are still queued host-side, so a naive
    cut loses constraints on resume. segment_rounds=1 keeps the
    per-execution budget tight enough that leftovers genuinely occur;
    fault -> resume must still land on the oracle forest."""
    from sheep_tpu.utils.checkpoint import Checkpointer
    from sheep_tpu.utils.fault import InjectedFault

    e = generators.rmat(11, 8, seed=9)
    n = 1 << 11
    es = EdgeStream.from_array(e, n_vertices=n)
    base = TpuBackend(chunk_edges=256).partition(es, 8)
    ck_dir = str(tmp_path / f"ck{inflight}")
    monkeypatch.setenv("SHEEP_FAULT_INJECT", "build:9")
    with pytest.raises(InjectedFault):
        TpuBackend(chunk_edges=256, dispatch_batch=2, segment_rounds=1,
                   inflight=inflight).partition(
            es, 8, checkpointer=Checkpointer(ck_dir, every=4))
    monkeypatch.delenv("SHEEP_FAULT_INJECT")
    res = TpuBackend(chunk_edges=256, dispatch_batch=2, segment_rounds=1,
                     inflight=inflight).partition(
        es, 8, checkpointer=Checkpointer(ck_dir, every=4), resume=True)
    np.testing.assert_array_equal(res.assignment, base.assignment)
    assert res.edge_cut == base.edge_cut


def test_obs_span_deltas_absorb_overlap_counters(tmp_path):
    """Counter flow hop 2: the stats-dict counters surface as obs span
    counter deltas on a traced run. Pinned at depth 1, where BOTH
    counters are guaranteed nonzero (the tracer omits zero deltas, and
    at D >= 2 a collapsed-to-zero device_gap_ms is the success mode)."""
    import json

    from sheep_tpu import obs

    e = generators.rmat(10, 8, seed=4)
    n = 1 << 10
    es = EdgeStream.from_array(e, n_vertices=n)
    trace = tmp_path / "t.jsonl"
    with obs.tracing(str(trace)):
        TpuBackend(chunk_edges=256, dispatch_batch=2,
                   inflight=1).partition(es, 4)
    merged: dict = {}
    for line in trace.read_text().splitlines():
        rec = json.loads(line)
        if rec.get("event") == "span_end":
            merged.update(rec.get("counters", {}))
        if rec.get("event") == "counters":
            merged.update(rec)
    assert merged["host_blocked_ms"] > 0
    assert merged["device_gap_ms"] > 0
    assert merged["inflight_depth"] == 1


def test_cli_inflight_flag(tmp_path, capsys):
    """--inflight plumbs through the CLI to the backend and the
    pipelined run scores identically to the synchronous default."""
    import json

    from sheep_tpu.cli import main as cli_main
    from sheep_tpu.io import formats

    p = tmp_path / "g.edges"
    formats.write_edges(str(p), generators.rmat(9, 8, seed=2))
    assert cli_main(["--input", str(p), "--k", "4", "--backend", "tpu",
                     "--json", "--chunk-edges", "128",
                     "--inflight", "1"]) == 0
    base = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    for d in ("2", "3"):
        assert cli_main(["--input", str(p), "--k", "4", "--backend",
                         "tpu", "--json", "--chunk-edges", "128",
                         "--dispatch-batch", "2", "--inflight", d]) == 0
        got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert got["edge_cut"] == base["edge_cut"]
        assert got["comm_volume"] == base["comm_volume"]


def test_cli_inflight_validation(tmp_path):
    from sheep_tpu.cli import main as cli_main
    from sheep_tpu.io import formats

    p = tmp_path / "g.edges"
    formats.write_edges(str(p), generators.rmat(8, 4, seed=2))
    with pytest.raises(SystemExit):
        cli_main(["--input", str(p), "--k", "4", "--inflight", "-1"])
    with pytest.raises(SystemExit):
        cli_main(["--input", str(p), "--k", "4", "--inflight", "0"])
