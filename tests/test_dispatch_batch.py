"""Batched segment dispatch (the ISSUE 1 tentpole).

Two properties, both assertable on the CPU mesh:

  (a) forest bit-identity — N staged streaming segments folded inside
      one bounded device program (ops/elim.py batch_segment_fixpoint)
      must reproduce the per-segment path's elimination forest exactly,
      at every batch size including the N=1 degenerate batch (the
      fixpoint is unique given the constraint multiset);
  (b) dispatch-count drop — host->device syncs per chunk fall from
      O(segments) to O(segments / N), asserted from the deterministic
      ``host_syncs``/``device_rounds`` counters that feed the
      count x round-cost A/B attribution
      (sheep_tpu.utils.metrics.solve_dispatch_attribution).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from sheep_tpu.backends.tpu_backend import TpuBackend, pad_chunk
from sheep_tpu.core import pure
from sheep_tpu.io import generators
from sheep_tpu.io.edgestream import EdgeStream
from sheep_tpu.ops import degrees as degrees_ops
from sheep_tpu.ops import elim as elim_ops
from sheep_tpu.ops import order as order_ops
from sheep_tpu.utils.membudget import build_phase_bytes, degraded_dispatch
from sheep_tpu.utils.metrics import solve_dispatch_attribution


def _order(e, n):
    deg = degrees_ops.init_degrees(n)
    deg = degrees_ops.degree_chunk(deg, pad_chunk(e, len(e), n), n)
    return order_ops.elimination_order(deg, n)


def _staged_blocks(e, cs, n, pos, batch):
    """Pad the edge stream into [batch, cs] oriented position blocks
    (sentinel rows fill the tail group, as the backend does)."""
    chunks = [pad_chunk(e[off:off + cs], cs, n)
              for off in range(0, len(e), cs)]
    while len(chunks) % batch:
        chunks.append(np.full((cs, 2), n, np.int32))
    return [elim_ops.orient_chunks_batch_pos(
                jnp.asarray(np.stack(chunks[i:i + batch])), pos, n)
            for i in range(0, len(chunks), batch)]


@pytest.mark.parametrize("batch", [1, 2, 4])
def test_batched_dispatch_matches_oracle_rmat14(batch):
    """Oracle equality at RMAT-14 across batch sizes, including the N=1
    degenerate batch (acceptance criterion of the batched dispatch)."""
    e = generators.rmat(14, 4, seed=7)
    n = 1 << 14
    pos, order = _order(e, n)
    whole, _ = elim_ops.build_chunk_step(
        jnp.full(n + 1, n, dtype=jnp.int32), pad_chunk(e, len(e), n),
        pos, order, n)
    P = jnp.full(n + 1, n, dtype=jnp.int32)
    for loB, hiB in _staged_blocks(e, 1 << 13, n, pos, batch):
        P, _ = elim_ops.fold_segments_batch(P, loB, hiB, n,
                                            segment_rounds=2)
    np.testing.assert_array_equal(np.asarray(P[pos]), np.asarray(whole))


def test_batch_program_resumes_after_budget_exhaustion():
    """A round budget too small to finish one execution must leave
    resumable blocks: re-dispatching the returned state converges to the
    identical forest (the on-device stop condition contract)."""
    e = generators.rmat(10, 8, seed=3)
    n = 1 << 10
    pos, order = _order(e, n)
    whole, _ = elim_ops.build_chunk_step(
        jnp.full(n + 1, n, dtype=jnp.int32), pad_chunk(e, len(e), n),
        pos, order, n)
    (loB, hiB), = _staged_blocks(e, len(e), n, pos, 1)
    P = jnp.full(n + 1, n, dtype=jnp.int32)
    execs = 0
    while True:
        loB, hiB, P, sv = elim_ops.fold_segments_batch_pos(
            P, loB, hiB, n, batch_rounds=3)  # far below the round need
        execs += 1
        if int(np.asarray(sv)[0]) >= 1:
            break
        assert execs < 1000
    assert execs > 1  # the tiny budget really did exhaust mid-segment
    np.testing.assert_array_equal(np.asarray(P[pos]), np.asarray(whole))


def test_batched_stats_word_shape():
    """The packed stats word is int32[4] = (segments_done, rounds, live,
    retired): done == N and live == 0 after convergence, retires equal
    the slots that went dead."""
    e = generators.rmat(9, 8, seed=1)
    n = 512
    pos, order = _order(e, n)
    (loB, hiB), = _staged_blocks(e, len(e), n, pos, 2)
    live0 = int(jnp.sum(loB != n))
    P = jnp.full(n + 1, n, dtype=jnp.int32)
    loB, hiB, P, sv = elim_ops.fold_segments_batch_pos(
        P, loB, hiB, n, batch_rounds=1 << 14)
    done, rounds, live, retired = (int(x) for x in np.asarray(sv))
    assert done == 2 and live == 0
    assert 0 < rounds < 1 << 14
    # every initially-live slot dies exactly once; displacement reuse can
    # add deaths but never remove one
    assert retired >= live0 > 0


def test_small_explicit_batch_rounds_still_converges():
    """An explicit per-execution round budget below N used to stall the
    segment cursor forever on already-converged prefixes (each costs one
    confirmation round, and every execution restarts at segment 0), then
    silently return an unconverged forest at the max_rounds backstop —
    the budget is now clamped to N (review finding)."""
    e = generators.rmat(10, 8, seed=9)
    n = 1 << 10
    pos, order = _order(e, n)
    cs = 256
    N = 4
    oracle = None
    P = jnp.full(n + 1, n, dtype=jnp.int32)
    for loB, hiB in _staged_blocks(e, cs, n, pos, N):
        stats: dict = {}
        P, _ = elim_ops.fold_segments_batch(P, loB, hiB, n,
                                            batch_rounds=1, stats=stats)
        assert "batch_incomplete_segments" not in stats, stats
    ref = jnp.full(n + 1, n, dtype=jnp.int32)
    for loB, hiB in _staged_blocks(e, cs, n, pos, N):
        ref, _ = elim_ops.fold_segments_batch(ref, loB, hiB, n,
                                              segment_rounds=2)
    np.testing.assert_array_equal(np.asarray(P), np.asarray(ref))


def test_dispatch_count_drops_o_segments_over_n():
    """The acceptance criterion: host syncs per chunk drop from
    O(segments) to O(segments / N). A = the per-segment driver (one sv
    pull per bounded fold_segment_pos execution), B = the batched
    dispatch at N=4 with the same per-segment round allowance. Counters
    are deterministic on the CPU mesh, so the assertion needs no timing."""
    e = generators.rmat(12, 8, seed=5)
    n = 1 << 12
    pos, order = _order(e, n)
    cs = 1024
    chunks = [pad_chunk(e[off:off + cs], cs, n)
              for off in range(0, len(e), cs)]

    sa = {"host_syncs": 0, "device_rounds": 0}
    P = jnp.full(n + 1, n, dtype=jnp.int32)
    for c in chunks:
        loP, hiP = elim_ops.orient_edges_pos(jnp.asarray(c), pos, n)
        while True:
            loP, hiP, P, sv = elim_ops.fold_segment_pos(
                P, loP, hiP, n, segment_rounds=2)
            changed, r, live = (int(x) for x in np.asarray(sv))
            sa["host_syncs"] += 1
            sa["device_rounds"] += r
            if not changed or live == 0:
                break

    N = 4
    sb: dict = {}
    Pb = jnp.full(n + 1, n, dtype=jnp.int32)
    for loB, hiB in _staged_blocks(e, cs, n, pos, N):
        Pb, _ = elim_ops.fold_segments_batch(Pb, loB, hiB, n,
                                             segment_rounds=2, stats=sb)

    np.testing.assert_array_equal(np.asarray(P), np.asarray(Pb))
    assert sa["host_syncs"] >= len(chunks)  # O(segments): >= 1 per chunk
    # O(segments / N): comfortably under half at N=4 (segment-transition
    # rounds cost the batched path a little, so not exactly 1/4)
    assert sb["host_syncs"] * 2 <= sa["host_syncs"], (sa, sb)


def test_solve_dispatch_attribution_exact():
    """The count x round-cost solver recovers planted coefficients
    exactly and reports degenerate systems as None."""
    pd, pr = 0.073, 0.0021  # per-dispatch RTT, per-round device cost
    a = {"syncs": 200, "rounds": 420}
    b = {"syncs": 55, "rounds": 460}
    a["wall_s"] = a["syncs"] * pd + a["rounds"] * pr
    b["wall_s"] = b["syncs"] * pd + b["rounds"] * pr
    out = solve_dispatch_attribution(a, b)
    assert abs(out["per_dispatch_s"] - pd) < 1e-12
    assert abs(out["per_round_s"] - pr) < 1e-12
    assert solve_dispatch_attribution(a, a) is None


@pytest.mark.parametrize("db", [2, 4])
def test_backend_dispatch_batch_bit_identical(db):
    """End-to-end TpuBackend equality: batched dispatch vs the default
    per-segment driver (auto resolves to 1), multi-chunk
    stream with a sentinel-padded tail group."""
    e = generators.rmat(11, 8, seed=9)
    n = 1 << 11
    es = EdgeStream.from_array(e, n_vertices=n)
    base = TpuBackend(chunk_edges=512).partition(es, 8)
    ref = pure.partition_arrays(e, 8, n=n)
    np.testing.assert_array_equal(base.assignment, ref.assignment)
    got = TpuBackend(chunk_edges=512, dispatch_batch=db).partition(es, 8)
    np.testing.assert_array_equal(got.assignment, base.assignment)
    assert got.edge_cut == base.edge_cut
    assert got.comm_volume == base.comm_volume
    assert got.diagnostics["dispatch_batch"] == db
    assert got.diagnostics["host_syncs"] > 0


def test_default_dispatch_is_adaptive_on_an_accelerator(monkeypatch):
    """The chip-only branches, taken on the CPU: with the platform
    reported as ``tpu`` and a 16 GiB HBM, the default dispatch is the
    adaptive per-segment driver (no batched executions, accelerator
    host-tail handoff at C/2), bit-identical to the oracle (PR 21: on
    a v5e the batched pipeline at N=16 took 313 rounds / 70.5 s at
    RMAT-18 where the adaptive driver took 14 / 2.6 s); N=2, where the
    TPU compiler aborts, is refused."""
    from sheep_tpu.backends import tpu_backend as tb

    monkeypatch.setattr(tb.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(tb, "_device_hbm_bytes",
                        lambda purpose="": 16 << 30)
    e = generators.rmat(11, 8, seed=9)
    n = 1 << 11
    es = EdgeStream.from_array(e, n_vertices=n)
    got = TpuBackend(chunk_edges=512).partition(es, 8)
    ref = pure.partition_arrays(e, 8, n=n)
    np.testing.assert_array_equal(got.assignment, ref.assignment)
    assert "batch_execs" not in got.diagnostics
    assert "dispatch_batch" not in got.diagnostics
    with pytest.raises(ValueError, match="refused on tpu"):
        TpuBackend(chunk_edges=512, dispatch_batch=2).partition(es, 8)
    assert tb.check_dispatch_batch(4) == 4


@pytest.mark.parametrize("caller", ["chunk_cache", "admission"])
def test_hbm_budgets_on_an_accelerator(monkeypatch, capsys, caller):
    """The chip-only HBM branches, taken on the CPU: a v5e that reports
    no bytes_limit gets its 16 GiB from device_kind, for the chunk cache
    and for sheepd's admission budget alike, each naming its own
    override (a bad call here killed sheepd on the chip, PR 21)."""
    from sheep_tpu.backends import tpu_backend as tb
    from sheep_tpu.server import scheduler

    class V5e:
        device_kind = "TPU v5 lite"

        def memory_stats(self):
            return {}

    monkeypatch.delenv("SHEEP_CACHE_BYTES", raising=False)
    monkeypatch.setattr(tb.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(tb.jax, "local_devices", lambda: [V5e()])
    hbm = 16 << 30
    if caller == "chunk_cache":
        n, cs = 1 << 20, 1 << 16
        want = int(0.9 * hbm) - build_phase_bytes(n, cs)["total_bytes"] \
            - (1 << 30)
        assert tb._chunk_cache_budget(n, cs) == want
        assert "for the chunk cache" in capsys.readouterr().err
    else:
        assert scheduler.resolve_budget_bytes() == int(0.9 * hbm)
        assert "for the admission budget" in capsys.readouterr().err


@pytest.mark.parametrize("platform,start,want", [
    ("tpu", 4, 1),     # steps over the refused N=2
    ("tpu", 8, 4),
    ("cpu", 4, 2),     # other platforms keep plain halving
])
def test_degrade_ladder_skips_refused_batch(monkeypatch, platform, start,
                                            want):
    """A RESOURCE fault halves the dispatch batch through
    utils/retry.degrade_dispatch; on a TPU it never lands on N=2, the
    width whose donated fold the compiler aborts the process on."""
    from sheep_tpu.backends import tpu_backend as tb
    from sheep_tpu.utils import retry

    monkeypatch.setattr(tb.jax, "default_backend", lambda: platform)
    stats = {}
    nxt = retry.degrade_dispatch(1 << 22, 1 << 23, start, 1, True, stats, 0)
    assert nxt == (want, 1)
    assert stats["degraded_dispatch_batch"] == want
    assert degraded_dispatch(1 << 22, 1 << 23, start, 1,
                             refused_batch=tb.refused_dispatch_batch()) \
        == (want, 1)


def test_backend_dispatch_batch_excludes_tail_strategies():
    """A batch below one is refused."""
    for bad in (0, -1):
        with pytest.raises(ValueError, match="dispatch_batch"):
            TpuBackend(dispatch_batch=bad)


def test_sharded_pipeline_dispatch_batch_matches():
    """The sharded pipeline's batch staging (one replicated stats pull
    per bounded execution, pmin-done lockstep) must match the
    per-segment sharded run on the 8-device virtual mesh."""
    from sheep_tpu.backends.base import get_backend, list_backends

    if "tpu-sharded" not in list_backends():
        pytest.skip("sharded backend unavailable")
    e = generators.rmat(11, 8, seed=9)
    n = 1 << 11
    es = EdgeStream.from_array(e, n_vertices=n)
    base = get_backend("tpu-sharded", chunk_edges=256).partition(
        es, 8, comm_volume=False)
    got = get_backend("tpu-sharded", chunk_edges=256,
                      dispatch_batch=2).partition(es, 8, comm_volume=False)
    np.testing.assert_array_equal(got.assignment, base.assignment)
    assert got.edge_cut == base.edge_cut
    assert got.diagnostics["dispatch_batch"] == 2
    assert got.diagnostics["host_syncs"] > 0


def test_membudget_staging_model():
    """The [N, C] staging blocks are counted (the O(C) transient
    invariant becomes O(N*C))."""
    n, cs = 1 << 20, 1 << 16
    base = build_phase_bytes(n, cs)
    b4 = build_phase_bytes(n, cs, dispatch_batch=4)
    assert b4["staging_bytes"] == 4 * 4 * cs * 4
    assert b4["total_bytes"] == base["total_bytes"] + b4["staging_bytes"]


def test_cli_dispatch_batch_flag(tmp_path, capsys):
    """--dispatch-batch plumbs through the CLI to the backend and the
    batched run scores identically to the default."""
    import json

    from sheep_tpu.cli import main as cli_main
    from sheep_tpu.io import formats

    p = tmp_path / "g.edges"
    formats.write_edges(str(p), generators.rmat(9, 8, seed=2))
    assert cli_main(["--input", str(p), "--k", "4", "--backend", "tpu",
                     "--json", "--chunk-edges", "128"]) == 0
    base = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert cli_main(["--input", str(p), "--k", "4", "--backend", "tpu",
                     "--json", "--chunk-edges", "128",
                     "--dispatch-batch", "4"]) == 0
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert got["edge_cut"] == base["edge_cut"]
    assert got["comm_volume"] == base["comm_volume"]
