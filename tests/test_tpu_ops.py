"""TPU-path ops vs the numpy oracle (run on CPU-jax; SURVEY.md §4.3).

The elimination tree is unique given the order, so the device fixpoint
must reproduce the oracle's parent array exactly on every graph shape —
including adversarial ones (paths, stars) that stress fixpoint depth.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from sheep_tpu.core import pure
from sheep_tpu.io import generators
from sheep_tpu.io.edgestream import EdgeStream
from sheep_tpu.ops import degrees as degrees_ops
from sheep_tpu.ops import elim as elim_ops
from sheep_tpu.ops import order as order_ops
from sheep_tpu.ops import score as score_ops
from sheep_tpu.backends.tpu_backend import TpuBackend, pad_chunk


def _cases():
    return {
        "karate": (generators.karate_club(), 34),
        "path": (generators.path_graph(64), 64),
        "star": (generators.star_graph(50), 50),
        "grid": (generators.grid_graph(8, 8), 64),
        "random": (generators.random_graph(200, 1600, seed=11), 200),
        "rmat": (generators.rmat(9, 8, seed=12), 512),
        "two_components": (
            np.concatenate([generators.path_graph(30),
                            30 + generators.grid_graph(5, 6)]), 60),
    }


@pytest.fixture(params=list(_cases()))
def graph(request):
    return _cases()[request.param]


def _device_order(e, n):
    deg = degrees_ops.init_degrees(n)
    deg = degrees_ops.degree_chunk(deg, pad_chunk(e, len(e), n), n)
    return order_ops.elimination_order(deg, n)


def test_degrees_and_order_match_oracle(graph):
    e, n = graph
    pos, order = _device_order(e, n)
    np.testing.assert_array_equal(np.asarray(pos[:n]),
                                  pure.elimination_order(pure.degrees(e, n)))
    assert int(pos[n]) == n and int(order[n]) == n


def test_degree_chunk_matches_bincount():
    """One scatter per endpoint column counts every endpoint: self-loops
    twice, padding into slot n."""
    n, chunk = 1 << 12, 1 << 10
    e = np.random.default_rng(5).integers(0, n, (chunk - 7, 2))
    e[:3, 1] = e[:3, 0]
    deg = degrees_ops.degree_chunk(degrees_ops.init_degrees(n),
                                   pad_chunk(e, chunk, n), n)
    want = np.bincount(e.ravel(), minlength=n + 1)
    want[n] = 2 * 7
    np.testing.assert_array_equal(np.asarray(deg), want)


@pytest.mark.parametrize("lift_levels", [1, 0])
def test_fixpoint_tree_matches_oracle(graph, lift_levels):
    e, n = graph
    pos, order = _device_order(e, n)
    minp, rounds = elim_ops.build_chunk_step(
        jnp.full(n + 1, n, dtype=jnp.int32), pad_chunk(e, len(e), n),
        pos, order, n, lift_levels=lift_levels)
    parent = elim_ops.minp_to_parent(minp, order, n)
    expect = pure.build_elim_tree(e, pure.elimination_order(pure.degrees(e, n))).parent
    np.testing.assert_array_equal(parent, expect)
    assert int(rounds) < n  # converged well before the trivial bound


@pytest.mark.parametrize("descent", ["exact", "stream"])
def test_fixpoint_descent_modes_match_oracle(graph, descent):
    e, n = graph
    pos, order = _device_order(e, n)
    lo, hi = elim_ops.orient_edges(
        jnp.asarray(pad_chunk(e, len(e), n)), pos, n)
    minp, rounds = elim_ops.elim_fixpoint(lo, hi, pos, order, n,
                                          descent=descent)
    parent = elim_ops.minp_to_parent(minp, order, n)
    expect = pure.build_elim_tree(
        e, pure.elimination_order(pure.degrees(e, n))).parent
    np.testing.assert_array_equal(parent, expect)


@pytest.mark.parametrize("segment_rounds", [1, 3, 32])
def test_segmented_fixpoint_bit_identical(graph, segment_rounds):
    """Host-driven bounded segments (the watchdog-safe device path) must
    reproduce the monolithic while_loop fixpoint bit-for-bit, including
    the total round count."""
    e, n = graph
    pos, order = _device_order(e, n)
    padded = pad_chunk(e, len(e), n)
    whole, rounds_mono = elim_ops.build_chunk_step(
        jnp.full(n + 1, n, dtype=jnp.int32), padded, pos, order, n)
    seg, rounds_seg = elim_ops.build_chunk_step_segmented(
        jnp.full(n + 1, n, dtype=jnp.int32), padded, pos, order, n,
        segment_rounds=segment_rounds)
    np.testing.assert_array_equal(np.asarray(seg), np.asarray(whole))
    assert rounds_seg == int(rounds_mono)


def test_segmented_honors_max_rounds_exactly(graph):
    """A binding max_rounds must stop the segmented fixpoint at the same
    round as the monolithic one (review r2: the tail segment used to
    overshoot by up to segment_rounds-1)."""
    e, n = graph
    pos, order = _device_order(e, n)
    padded = pad_chunk(e, len(e), n)
    clo, chi = elim_ops.orient_edges(jnp.asarray(padded), pos, n)
    for cap in (1, 3, 7):
        mono, r_mono = elim_ops.fold_edges(
            jnp.full(n + 1, n, dtype=jnp.int32), clo, chi, pos, order, n,
            max_rounds=cap)
        seg, r_seg = elim_ops.fold_edges_segmented(
            jnp.full(n + 1, n, dtype=jnp.int32), clo, chi, pos, order, n,
            segment_rounds=2, max_rounds=cap)
        assert r_seg == int(r_mono)
        np.testing.assert_array_equal(np.asarray(seg), np.asarray(mono))


def test_adaptive_fixpoint_matches_monolithic(graph):
    """Compaction + jump-mode tail must produce the identical forest (the
    elimination forest is unique given the order; compaction preserves the
    active multiset and jump-mode rounds are closure-preserving rewrites).
    small_size=8 forces the compaction path and jump-mode tail even on
    tiny graphs; streaming in two chunks also exercises a non-empty
    carried table."""
    e, n = graph
    pos, order = _device_order(e, n)
    padded = pad_chunk(e, len(e), n)
    whole, _ = elim_ops.build_chunk_step(
        jnp.full(n + 1, n, dtype=jnp.int32), padded, pos, order, n)
    clo, chi = elim_ops.orient_edges(jnp.asarray(padded), pos, n)
    got, _ = elim_ops.fold_edges_adaptive(
        jnp.full(n + 1, n, dtype=jnp.int32), clo, chi, pos, order, n,
        segment_rounds=4, small_size=8, small_jumps=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(whole))

    half = len(e) // 2
    minp = jnp.full(n + 1, n, dtype=jnp.int32)
    for part in (e[:half], e[half:]):
        c = pad_chunk(part, max(half, len(e) - half), n)
        clo, chi = elim_ops.orient_edges(jnp.asarray(c), pos, n)
        minp, _ = elim_ops.fold_edges_adaptive(
            minp, clo, chi, pos, order, n,
            segment_rounds=4, small_size=8, small_jumps=2)
    np.testing.assert_array_equal(np.asarray(minp), np.asarray(whole))


def test_compact_actives_preserves_multiset():
    lo = jnp.asarray(np.array([5, 3, 5, 3, 1, 5], np.int32))
    hi = jnp.asarray(np.array([2, 4, 2, 4, 0, 2], np.int32))
    n = 5  # treat vertex id 5 as the sentinel
    clo, chi = elim_ops.compact_actives(lo, hi, n, 4)
    pairs = sorted(zip(np.asarray(clo).tolist(), np.asarray(chi).tolist()))
    assert pairs == [(1, 0), (3, 4), (3, 4), (5, 5)]


def test_compact_actives_dedup_drops_duplicates():
    lo = jnp.asarray(np.array([5, 3, 5, 3, 1, 5], np.int32))
    hi = jnp.asarray(np.array([2, 4, 2, 4, 0, 2], np.int32))
    n = 5
    clo, chi = elim_ops.compact_actives(lo, hi, n, 4, dedup=True)
    pairs = sorted(zip(np.asarray(clo).tolist(), np.asarray(chi).tolist()))
    assert pairs == [(1, 0), (3, 4), (5, 5), (5, 5)]
    live, distinct = elim_ops.count_live_distinct(lo, hi, n)
    assert int(live) == 3 and int(distinct) == 2


def test_adaptive_warm_schedule_and_thresholds(graph):
    """Warm low-lift rounds, dedup compaction, and every host-tail
    handoff point must all produce the identical unique forest."""
    e, n = graph
    pos, order = _device_order(e, n)
    padded = pad_chunk(e, len(e), n)
    whole, _ = elim_ops.build_chunk_step(
        jnp.full(n + 1, n, dtype=jnp.int32), padded, pos, order, n)
    for warm, tail_at in [(((1, 2),), 0), (((1, 4), (1, 8)), len(e) // 2),
                          (((2, 3),), len(e)), ((), len(e) // 2)]:
        got, _ = elim_ops.build_chunk_step_adaptive(
            jnp.full(n + 1, n, dtype=jnp.int32), padded, pos, order, n,
            segment_rounds=2, warm_schedule=warm,
            host_tail_threshold=tail_at)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(whole))


def test_cut_pair_compact_matches_dense(graph):
    """Device-deduped cv rows must yield the same distinct key set as the
    dense pull, and the tiny-cap overflow path must fall back cleanly."""
    e, n = graph
    k = 4
    rng = np.random.default_rng(5)
    assign = jnp.asarray(
        np.concatenate([rng.integers(0, k, n), [0]]).astype(np.int32))
    padded = jnp.asarray(pad_chunk(e, len(e), n))
    dense = np.asarray(score_ops.cut_pairs(padded, assign, n))
    dense = dense[dense[:, 0] < n]
    expect = np.unique(dense[:, 0].astype(np.int64) * k + dense[:, 1])

    compact, count = score_ops.cut_pair_rows_compact(padded, assign, n,
                                                     cap=2 * len(e))
    rows = np.asarray(compact)
    rows = rows[rows[:, 0] < n]
    got = rows[:, 0].astype(np.int64) * k + rows[:, 1]
    assert int(count) == len(expect)
    np.testing.assert_array_equal(np.sort(got), expect)

    # overflow: cap smaller than the distinct count -> count says so
    if len(expect) > 2:
        _, count2 = score_ops.cut_pair_rows_compact(padded, assign, n,
                                                    cap=2)
        assert int(count2) == len(expect) > 2

    keys = score_ops.cut_pair_keys_host(np.asarray(padded), assign, n, k)
    np.testing.assert_array_equal(np.unique(keys), expect)


def test_streaming_chunks_match_batch(graph):
    e, n = graph
    pos, order = _device_order(e, n)
    whole, _ = elim_ops.build_chunk_step(
        jnp.full(n + 1, n, dtype=jnp.int32), pad_chunk(e, len(e), n), pos, order, n)
    minp = jnp.full(n + 1, n, dtype=jnp.int32)
    size = 37
    for off in range(0, len(e), size):
        minp, _ = elim_ops.build_chunk_step(
            minp, pad_chunk(e[off:off + size], size, n), pos, order, n)
    np.testing.assert_array_equal(np.asarray(minp), np.asarray(whole))


def test_streaming_worst_case_displacement_order(graph):
    """Stream edges in DESCENDING pos[hi] order: every later chunk offers
    earlier parents, maximizing in-place displacement chains in
    fold_edges (the slot-reuse path of the displacement fixpoint)."""
    e, n = graph
    pos_np = pure.elimination_order(pure.degrees(e, n))
    key = np.maximum(pos_np[e[:, 0]], pos_np[e[:, 1]])
    e_desc = e[np.argsort(-key, kind="stable")]
    pos, order = _device_order(e, n)
    minp = jnp.full(n + 1, n, dtype=jnp.int32)
    size = 23
    for off in range(0, len(e_desc), size):
        minp, _ = elim_ops.build_chunk_step(
            minp, pad_chunk(e_desc[off:off + size], size, n), pos, order, n)
    parent = elim_ops.minp_to_parent(minp, order, n)
    expect = pure.build_elim_tree(e, pos_np).parent
    np.testing.assert_array_equal(parent, expect)


def test_duplicate_heavy_multigraph_streaming():
    """Many duplicate edges retire simultaneously; their duplicate
    displacements must stay harmless."""
    base = generators.random_graph(50, 120, seed=17)
    e = np.concatenate([base] * 5)  # 5 copies of every edge
    rng = np.random.default_rng(3)
    e = e[rng.permutation(len(e))]
    n = 50
    pos, order = _device_order(e, n)
    minp = jnp.full(n + 1, n, dtype=jnp.int32)
    for off in range(0, len(e), 41):
        minp, _ = elim_ops.build_chunk_step(
            minp, pad_chunk(e[off:off + 41], 41, n), pos, order, n)
    parent = elim_ops.minp_to_parent(minp, order, n)
    expect = pure.build_elim_tree(
        e, pure.elimination_order(pure.degrees(e, n))).parent
    np.testing.assert_array_equal(parent, expect)


def test_merge_forests_matches_whole(graph):
    e, n = graph
    pos, order = _device_order(e, n)
    half = len(e) // 2
    a, _ = elim_ops.build_chunk_step(
        jnp.full(n + 1, n, dtype=jnp.int32), pad_chunk(e[:half], max(half, 1), n),
        pos, order, n)
    b, _ = elim_ops.build_chunk_step(
        jnp.full(n + 1, n, dtype=jnp.int32),
        pad_chunk(e[half:], max(len(e) - half, 1), n), pos, order, n)
    merged = elim_ops.merge_forests(a, b, pos, order, n)
    whole, _ = elim_ops.build_chunk_step(
        jnp.full(n + 1, n, dtype=jnp.int32), pad_chunk(e, len(e), n), pos, order, n)
    np.testing.assert_array_equal(np.asarray(merged), np.asarray(whole))


def test_merge_forests_commutative_and_associative(graph):
    """merge(A,B) == merge(B,A) and any merge order of three shards
    yields the identical table — the property that makes the
    distributed algorithm correct (SURVEY.md §4.1: the single most
    important property test)."""
    e, n = graph
    pos, order = _device_order(e, n)
    third = max(1, len(e) // 3)
    shards = [e[:third], e[third:2 * third], e[2 * third:]]
    forests = []
    for s in shards:
        f, _ = elim_ops.build_chunk_step(
            jnp.full(n + 1, n, dtype=jnp.int32),
            pad_chunk(s, max(len(s), 1), n), pos, order, n)
        forests.append(f)
    a, b, c = forests
    ab = elim_ops.merge_forests(a, b, pos, order, n)
    ba = elim_ops.merge_forests(b, a, pos, order, n)
    np.testing.assert_array_equal(np.asarray(ab), np.asarray(ba))
    left = elim_ops.merge_forests(ab, c, pos, order, n)
    right = elim_ops.merge_forests(a, elim_ops.merge_forests(
        b, c, pos, order, n), pos, order, n)
    np.testing.assert_array_equal(np.asarray(left), np.asarray(right))
    rotated = elim_ops.merge_forests(c, elim_ops.merge_forests(
        a, b, pos, order, n), pos, order, n)
    np.testing.assert_array_equal(np.asarray(left), np.asarray(rotated))


def test_minp_parent_roundtrip(graph):
    e, n = graph
    pos, order = _device_order(e, n)
    minp, _ = elim_ops.build_chunk_step(
        jnp.full(n + 1, n, dtype=jnp.int32), pad_chunk(e, len(e), n), pos, order, n)
    parent = elim_ops.minp_to_parent(minp, order, n)
    back = elim_ops.parent_to_minp(parent, np.asarray(pos[:n]), n)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(minp))


def test_score_ops_match_oracle(graph):
    e, n = graph
    k = 4
    rng = np.random.default_rng(2)
    assign_np = rng.integers(0, k, n).astype(np.int32)
    assign = jnp.concatenate([jnp.asarray(assign_np), jnp.zeros(1, jnp.int32)])
    cut, total = (int(x) for x in
                  score_ops.score_chunk(pad_chunk(e, len(e) + 5, n), assign, n))
    ecut, etotal, _, ecv = pure.edge_cut_score(e, assign_np, k)
    assert (cut, total) == (ecut, etotal)
    rows = np.asarray(score_ops.cut_pairs(pad_chunk(e, len(e) + 5, n), assign, n))
    rows = rows[rows[:, 0] < n]
    got_cv = len(np.unique(rows[:, 0].astype(np.int64) * k + rows[:, 1]))
    assert got_cv == ecv


@pytest.mark.parametrize("k", [2, 8])
def test_tpu_backend_end_to_end(k):
    e = generators.rmat(9, 8, seed=13)
    n = int(e.max()) + 1
    be = TpuBackend(chunk_edges=1024)
    res = be.partition(EdgeStream.from_array(e), k)
    res.validate(n)
    ref = pure.partition_arrays(e, k)
    # identical tree + identical split semantics => identical scores
    assert res.edge_cut == ref.edge_cut
    assert res.total_edges == ref.total_edges
    assert res.comm_volume == ref.comm_volume
    np.testing.assert_array_equal(res.assignment, ref.assignment)


@pytest.mark.parametrize("k", [1, 1024, 4096, 5000])
def test_extreme_k_cross_backend(k):
    """k spanning 1 .. > V (BASELINE config 5 uses k=1024): no backend
    may crash, scores must agree exactly, and k=1 means zero cut."""
    from sheep_tpu.backends.base import get_backend, list_backends

    e = generators.rmat(12, 8, seed=3)
    es = EdgeStream.from_array(e, n_vertices=4096)
    ref = get_backend("pure").partition(es, k, comm_volume=False)
    if k == 1:
        assert ref.edge_cut == 0
    assert ref.assignment.min() >= 0 and ref.assignment.max() < max(k, 1)
    for b in ("tpu", "tpu-bigv"):
        if b not in list_backends():
            continue
        got = get_backend(b, chunk_edges=2048).partition(
            es, k, comm_volume=False)
        assert got.edge_cut == ref.edge_cut
        np.testing.assert_array_equal(got.assignment, ref.assignment)


def test_sorted_lookup_matches_gather(graph):
    """sorted_lookup (sort-join table read) == plain gather, elementwise,
    for multiple tables in one call."""
    import jax

    e, n = graph
    key = jax.random.PRNGKey(3)
    k1, k2, k3 = jax.random.split(key, 3)
    t1 = jax.random.randint(k1, (n + 1,), 0, n + 1, dtype=jnp.int32)
    t2 = jax.random.randint(k2, (n + 1,), 0, n + 1, dtype=jnp.int32)
    idx = jax.random.randint(k3, (257,), 0, n + 1, dtype=jnp.int32)
    a, b = elim_ops.sorted_lookup((t1, t2), idx, n)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(t1[idx]))
    np.testing.assert_array_equal(np.asarray(b), np.asarray(t2[idx]))


@pytest.mark.parametrize("jumps", [1, 4])
def test_sortmerge_round_bit_identical(graph, jumps):
    """The sort-merge prototype (VERDICT r2 item 2) must reproduce the
    jump-mode round's full state trajectory bit-for-bit — same
    retire/displace/climb semantics, different primitive mix — so the
    keep/reject decision is purely the measured-throughput question
    recorded in BASELINE.md."""
    e, n = graph
    pos, order = _device_order(e, n)
    padded = pad_chunk(e, len(e), n)
    loP, hiP = elim_ops.orient_edges_pos(jnp.asarray(padded), pos, n)
    P0 = jnp.full(n + 1, n, dtype=jnp.int32)
    for rounds in (1, 5, 300):
        a = elim_ops.fold_segment_small_pos(
            P0, loP, hiP, n, jumps=jumps, segment_rounds=rounds)
        b = elim_ops.fold_segment_sortmerge_pos(
            P0, loP, hiP, n, jumps=jumps, segment_rounds=rounds)
        for name, x, y in zip(("loP", "hiP", "P", "stats"), a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"{name} diverged")


@pytest.mark.parametrize("warm_schedule", [(), ((1, 2),)])
def test_adaptive_pos_host_tail_every_chunk_matches_batch(graph,
                                                          warm_schedule):
    """The streaming step with the host tail taken straight after each
    chunk's first segment (threshold = the chunk width) reaches the
    whole-graph forest. A chunk takes the tail exactly when its first
    segment left live constraints, which the same fold without a host
    tail shows by running a second segment (a path's or a star's chunks
    converge in their first)."""
    e, n = graph
    pos, order = _device_order(e, n)
    pos_host = np.asarray(pos[:n])
    whole, _ = elim_ops.build_chunk_step(
        jnp.full(n + 1, n, dtype=jnp.int32), pad_chunk(e, len(e), n),
        pos, order, n)
    P = jnp.full(n + 1, n, dtype=jnp.int32)
    size = 37
    for off in range(0, len(e), size):
        chunk = pad_chunk(e[off:off + size], size, n)
        opts = dict(warm_schedule=warm_schedule, small_size=8)
        device_only: dict = {}
        P_dev, _ = elim_ops.build_chunk_step_adaptive_pos(
            P, chunk, pos, pos_host, n, host_tail=False, stats=device_only,
            **opts)
        stats: dict = {}
        P, _ = elim_ops.build_chunk_step_adaptive_pos(
            P, chunk, pos, pos_host, n, host_tail_threshold=size,
            stats=stats, **opts)
        np.testing.assert_array_equal(np.asarray(P), np.asarray(P_dev))
        assert stats["host_syncs"] == 1
        assert stats.get("host_tails", 0) == \
            (device_only["host_syncs"] > 1)
    np.testing.assert_array_equal(np.asarray(P[pos]), np.asarray(whole))


@pytest.mark.parametrize("host_tail_threshold", [-1, 1, 4, 16])
def test_tpu_backend_matches_oracle(graph, host_tail_threshold):
    """End-to-end backend equality on multi-chunk streams at every
    host-tail handoff: -1 is the platform default (on cpu-jax the auto
    threshold, so each chunk goes to the host after its first segment);
    1, 4 and 16 hand off later, after jump-mode small segments (a
    64-edge chunk is under the driver's small size)."""
    e, n = graph
    es = EdgeStream.from_array(e, n_vertices=n)
    res = TpuBackend(chunk_edges=64,
                     host_tail_threshold=host_tail_threshold).partition(
        es, 4, comm_volume=True)
    ref = pure.partition_arrays(e, 4, n=n)
    np.testing.assert_array_equal(res.assignment, ref.assignment)
    assert res.edge_cut == ref.edge_cut
    assert res.comm_volume == ref.comm_volume
    assert res.diagnostics["small_segments"] > 0


@pytest.mark.parametrize("segment_rounds", [1, 3])
def test_adaptive_pos_segment_rounds_match_batch(graph, segment_rounds,
                                                 monkeypatch):
    """Full segments on the device reach the whole-graph forest in
    both of the driver's exact-descent programs: one round a segment
    runs fold_segment_pos, more than one the hoisted stack."""
    e, n = graph
    pos, order = _device_order(e, n)
    whole, _ = elim_ops.build_chunk_step(
        jnp.full(n + 1, n, dtype=jnp.int32), pad_chunk(e, len(e), n),
        pos, order, n)
    hoisted = []
    real = elim_ops.fold_segment_pos_hoisted

    def spy(*a, **kw):
        hoisted.append(kw["segment_rounds"])
        return real(*a, **kw)

    monkeypatch.setattr(elim_ops, "fold_segment_pos_hoisted", spy)
    P = jnp.full(n + 1, n, dtype=jnp.int32)
    stats: dict = {}
    size = 37
    for off in range(0, len(e), size):
        loP, hiP = elim_ops.orient_edges_pos(
            jnp.asarray(pad_chunk(e[off:off + size], size, n)), pos, n)
        P, _ = elim_ops.fold_edges_adaptive_pos(
            P, loP, hiP, n, segment_rounds=segment_rounds, small_size=8,
            host_tail=False, stats=stats)
    assert stats["full_segments"] > 0
    if segment_rounds == 1:
        assert hoisted == []
    else:
        assert len(hoisted) >= 1
    np.testing.assert_array_equal(np.asarray(P[pos]), np.asarray(whole))


def _unskipped_stale_segment(n, lift_levels, segment_rounds):
    """The hoisted segment as it was before all-sentinel levels were
    skipped: every one of the L-1 squarings and every stale level's
    gather runs. The reference for the skip's bit-identity."""
    import jax
    from jax import lax

    @jax.jit
    def run(P, loP, hiP):
        tables = [P]
        for _ in range(lift_levels - 1):
            tables.append(tables[-1][tables[-1]])

        def body(state):
            lo_, hi_, P_, _, rounds = state
            old_at_lo = P_[lo_]
            newP = P_.at[lo_].min(hi_, mode="drop")
            now = newP[lo_]
            cur = lo_
            for t in reversed(tables[1:]):
                cand = t[cur]
                cur = jnp.where(cand < hi_, cand, cur)
            cand = newP[cur]
            cur = jnp.where(cand < hi_, cand, cur)
            became_loop = cur == hi_
            climb_lo = jnp.where(became_loop, n, cur)
            climb_hi = jnp.where(became_loop, n, hi_)
            retire = hi_ == now
            displaced = retire & (now < old_at_lo) & (old_at_lo < n)
            out_lo = jnp.where(retire, jnp.where(displaced, now, n),
                               climb_lo).astype(jnp.int32)
            out_hi = jnp.where(retire, jnp.where(displaced, old_at_lo, n),
                               climb_hi).astype(jnp.int32)
            changed = jnp.any((out_lo != lo_) | (out_hi != hi_))
            return out_lo, out_hi, newP, changed, rounds + 1

        def cond(state):
            return state[3] & (state[4] < segment_rounds)

        lo_, hi_, P_, changed, rounds = lax.while_loop(
            cond, body, (loP, hiP, P, jnp.bool_(True), jnp.int32(0)))
        return lo_, hi_, P_, jnp.stack([changed.astype(jnp.int32), rounds,
                                        jnp.sum(lo_ != n, dtype=jnp.int32)])

    return run


def test_hoisted_skip_is_bit_identical_segment_by_segment():
    """Skipping the all-sentinel lifting levels (elim.py
    _lift_tables / _pos_round_body_stale) changes no segment's
    output: from the same entry state, the hoisted program returns the
    same (loP, hiP, P) and (changed, rounds, live) as the un-skipped
    stale body, on every segment of a stream of RMAT chunks, and its
    fourth stats entry counts the tables that are not all sentinel."""
    e, n = generators.rmat(10, 8, seed=3), 1024
    pos, _ = _device_order(e, n)
    L = n.bit_length()
    ref = _unskipped_stale_segment(n, L, 2)
    P = jnp.full(n + 1, n, dtype=jnp.int32)
    chunk = 1024
    levels_seen = set()
    for off in range(0, len(e), chunk):
        loP, hiP = elim_ops.orient_edges_pos(
            jnp.asarray(pad_chunk(e[off:off + chunk], chunk, n)), pos, n)
        while True:
            got = elim_ops.fold_segment_pos_hoisted(P, loP, hiP, n,
                                                    segment_rounds=2)
            want = ref(P, loP, hiP)
            for name, x, y in zip(("loP", "hiP", "P", "stats"), got, want):
                np.testing.assert_array_equal(
                    np.asarray(x)[:3] if name == "stats" else np.asarray(x),
                    np.asarray(y), err_msg=f"{name} diverged")
            t = np.asarray(P)
            depth = 0  # tables t_1..t_{L-1} that hold a real ancestor
            for _ in range(L - 1):
                t = t[t]
                depth += bool(np.any(t != n))
            sv = np.asarray(got[3])
            assert sv[3] == depth
            levels_seen.add(int(sv[3]))
            loP, hiP, P = got[:3]
            if not sv[0] or sv[2] == 0:
                break
    assert levels_seen & set(range(1, L - 1)), \
        "the stream must hold segments with some levels skipped, some not"


@pytest.mark.parametrize("segment_rounds", [2, 3, 4])
def test_lift_level_counters_cover_every_full_segment(segment_rounds):
    """The adaptive driver sums the hoisted program's live-level entry:
    live + skipped = full segments x (L-1), and a fold from an empty
    table skips levels (its first stack is all sentinel)."""
    e, n = _cases()["rmat"]
    pos, _ = _device_order(e, n)
    loP, hiP = elim_ops.orient_edges_pos(
        jnp.asarray(pad_chunk(e, len(e), n)), pos, n)
    stats: dict = {}
    elim_ops.fold_edges_adaptive_pos(
        jnp.full(n + 1, n, dtype=jnp.int32), loP, hiP, n,
        segment_rounds=segment_rounds, small_size=8, host_tail=False,
        stats=stats)
    full = stats.get("full_segments", 0)
    assert full > 0
    assert stats["lift_levels_skipped"] > 0
    assert stats["lift_levels_live"] + stats["lift_levels_skipped"] == \
        full * (n.bit_length() - 1)


def _uncollapsed_warm_segment(P, loP, hiP, threshold, n, **kw):
    """The warm segment as it was before it collapsed duplicates: the
    rounds alone, nothing set to sentinel, every live slot counted. The
    reference for the collapse's bit-identity."""
    loP, hiP, P, sv = elim_ops.fold_segment_pos(P, loP, hiP, n, **kw)
    return loP, hiP, P, jnp.concatenate([sv, jnp.zeros(1, jnp.int32)])


@pytest.mark.parametrize("case", ["distinct_under", "distinct_over",
                                  "live_under", "distinct_equal",
                                  "compact"])
def test_warm_collapse_decides_on_the_distinct_count(case, monkeypatch):
    """The warm segment collapses duplicate constraints when more slots
    are live than the host-tail threshold (elim.py
    fold_segment_pos_collapse), and the adaptive driver decides on the
    distinct count. An RMAT stream in chunks of 2^14: its first chunk
    leaves many duplicates after the warm round, a later chunk few.
    distinct_under: distinct <= threshold < live, so the host tail is
    taken straight after the warm segment, where the uncollapsed
    driver ran full segments; distinct_equal: threshold == distinct <
    live, the same at the boundary of the driver's <=; compact:
    a first chunk of 2^15 with threshold < distinct <= C/4 < C/2 <
    live, so the size//2 compaction runs straight after the warm
    segment, sized by the distinct count; distinct_over: a later chunk
    with distinct > threshold runs the full segments it ran before;
    live_under: live <= threshold, so nothing is collapsed. The forest
    is bit-identical to the uncollapsed driver's in every case, and
    ``warm_duplicates`` counts live - distinct."""
    e, n = generators.rmat(12, 16, seed=1), 1 << 12
    pos, _ = _device_order(e, n)
    pos_host = np.asarray(pos[:n])
    c = 1 << (15 if case == "compact" else 14)
    opts = dict(segment_rounds=2, warm_schedule=((1, 8),),
                small_size=1 << 10, pos_host=pos_host)
    P = jnp.full(n + 1, n, dtype=jnp.int32)
    chunk = 0 if case != "distinct_over" else 1
    for i in range(chunk + 1):
        loP, hiP = elim_ops.orient_edges_pos(
            jnp.asarray(pad_chunk(e[i * c:(i + 1) * c], c, n)), pos, n)
        if i < chunk:
            P, _ = elim_ops.fold_edges_adaptive_pos(P, loP, hiP, n, **opts)
    wlo, whi, _, _ = elim_ops.fold_segment_pos(
        P, loP, hiP, n, lift_levels=8, segment_rounds=1, descent="stream")
    live, distinct = (int(x) for x in
                      elim_ops.count_live_distinct(wlo, whi, n))
    threshold = {"distinct_under": (live + distinct) // 2,
                 "distinct_equal": distinct,
                 "compact": distinct // 2,
                 "distinct_over": distinct - 1, "live_under": live}[case]
    if case == "distinct_under":
        assert distinct <= threshold < live
    elif case == "distinct_equal":
        assert distinct == threshold < live
    elif case == "compact":
        assert threshold < distinct <= c // 4 and c // 2 < live
    elif case == "distinct_over":
        assert c // 2 < distinct - 1 == threshold < live

    def fold(stats):
        return elim_ops.fold_edges_adaptive_pos(
            P, loP, hiP, n, host_tail_threshold=threshold, stats=stats,
            **opts)[0]

    got: dict = {}
    compactions = []
    real_compact = elim_ops.compact_actives

    def spy(lo, hi, n, size, **kw):
        compactions.append((lo.shape[0], size))
        return real_compact(lo, hi, n, size, **kw)

    with monkeypatch.context() as m:
        m.setattr(elim_ops, "compact_actives", spy)
        P_got = fold(got)
    want: dict = {}
    with monkeypatch.context() as m:
        m.setattr(elim_ops, "fold_segment_pos_collapse",
                  _uncollapsed_warm_segment)
        P_want = fold(want)
    np.testing.assert_array_equal(np.asarray(P_got), np.asarray(P_want))
    assert want["host_tails"] == 1
    assert got["host_tails"] == 1
    assert got["warm_duplicates"] == \
        (0 if case == "live_under" else live - distinct)
    if case in ("distinct_under", "distinct_equal"):
        assert got.get("full_segments", 0) == 0
        assert want["full_segments"] > 0
    elif case == "compact":
        assert got["full_segments"] > 0
        assert compactions[0] == (c, elim_ops.pow2_at_least(2 * distinct))
    else:
        assert got.get("full_segments", 0) == want.get("full_segments", 0)
    if case == "distinct_equal":
        # the tail's one compaction, sized by the distinct count
        assert compactions == [(c, min(elim_ops.pow2_at_least(
            distinct, floor=1 << 14), c))]
    if case == "distinct_over":
        assert got["full_segments"] > 0


def test_fold_stats_wall_attribution():
    """Every segment kind executed must leave its t_* wall key in
    stats, each key non-negative and summing to (well under) the call's
    own wall — the contract bench.py's 'build wall attribution' line
    and BASELINE.md's round-5 decomposition read from."""
    import time as _time

    e, n = _cases()["rmat"]
    pos, order = _device_order(e, n)
    loP, hiP = elim_ops.orient_edges_pos(
        jnp.asarray(pad_chunk(e, len(e), n)), pos, n)
    stats: dict = {}
    P0 = jnp.full(n + 1, n, dtype=jnp.int32)
    t0 = _time.perf_counter()
    elim_ops.fold_edges_adaptive_pos(
        P0, loP, hiP, n, segment_rounds=2, small_size=8, host_tail=False,
        warm_schedule=((1, 1),), stats=stats)
    wall = _time.perf_counter() - t0
    kinds = {"warm_segments": "t_warm_s", "full_segments": "t_full_s",
             "small_segments": "t_small_s"}
    seen = 0
    for count_key, t_key in kinds.items():
        if stats.get(count_key, 0):
            seen += 1
            assert t_key in stats, f"{count_key} ran but {t_key} missing"
            assert stats[t_key] >= 0
    assert seen > 0, "config must exercise at least one segment kind"
    assert sum(stats.get(t, 0) for t in kinds.values()) <= wall + 1e-6


def test_pipeline_runs_under_debug_nans():
    """SURVEY.md §5 race-detection line: the JAX path is functional/pure,
    so the structural check is that a full partition runs clean under
    jax_debug_nans (plus the cross-backend equivalence suite). The
    pipeline is integer-only; this pins that no float NaN can sneak in
    via scoring/balance math."""
    import jax

    import sheep_tpu
    from sheep_tpu.io import formats, generators

    prev = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", True)
    try:
        import tempfile

        with tempfile.TemporaryDirectory() as d:
            p = f"{d}/k.edges"
            formats.write_edges(p, generators.karate_club())
            res = sheep_tpu.partition(p, 2, backend="tpu")
            assert res.edge_cut > 0
    finally:
        jax.config.update("jax_debug_nans", prev)
