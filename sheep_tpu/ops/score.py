"""Edge assignment + scoring on device (SURVEY.md §2 #8, §3.4).

One gathered pass per chunk: part lookups for both endpoints, predicated
counter reductions. All device arithmetic is int32 (int64 is emulated on
TPU); per-chunk counters are exact because chunks are < 2^31 edges, and
cross-chunk accumulation happens in host Python ints / numpy int64.
Multi-device reductions are a ``psum`` in the sharded pipeline.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("n",))
def score_chunk(edges: jax.Array, assign: jax.Array, n: int):
    """(cut, total) int32 counts for one (C, 2) chunk.

    assign is int32[n+1] (sentinel slot ignored). Padding = endpoints
    outside [0, n)."""
    e = edges.astype(jnp.int32)
    u, v = e[:, 0], e[:, 1]
    valid = (u >= 0) & (u < n) & (v >= 0) & (v < n) & (u != v)
    pu = assign[jnp.clip(u, 0, n)]
    pv = assign[jnp.clip(v, 0, n)]
    cut = jnp.sum(valid & (pu != pv), dtype=jnp.int32)
    total = jnp.sum(valid, dtype=jnp.int32)
    return cut, total


@partial(jax.jit, static_argnames=("n",))
def cut_pairs(edges: jax.Array, assign: jax.Array, n: int):
    """(2C, 2) int32 [vertex, foreign_part] rows for cut edges; non-cut and
    padding rows are the sentinel (n, 0). Comm volume = number of distinct
    non-sentinel rows across all chunks (uniqued host-side in int64)."""
    e = edges.astype(jnp.int32)
    u, v = e[:, 0], e[:, 1]
    valid = (u >= 0) & (u < n) & (v >= 0) & (v < n) & (u != v)
    pu = assign[jnp.clip(u, 0, n)]
    pv = assign[jnp.clip(v, 0, n)]
    is_cut = valid & (pu != pv)
    sent_v = jnp.int32(n)
    row_u = jnp.stack([jnp.where(is_cut, u, sent_v), jnp.where(is_cut, pv, 0)], axis=1)
    row_v = jnp.stack([jnp.where(is_cut, v, sent_v), jnp.where(is_cut, pu, 0)], axis=1)
    return jnp.concatenate([row_u, row_v])


# pending cv keys are compacted (sort+unique) whenever the accumulator
# exceeds this many entries, bounding host memory at O(distinct + cap)
# instead of O(all cut-edge endpoints seen) (VERDICT r1 weak #5)
CV_COMPACT_ENTRIES = 1 << 25  # 256 MiB of int64 keys


def accumulate_cv_keys(cv_chunks: list, keys) -> list:
    """Append a chunk's cv keys; compact in place when the PENDING tail
    (everything after the already-compacted head) exceeds the cap.
    Head-excluded accounting keeps memory at O(distinct + cap) without
    going quadratic when the distinct-key set alone exceeds the cap
    (re-sorting the whole accumulator per chunk)."""
    cv_chunks.append(keys)
    if (len(cv_chunks) > 1
            and sum(len(c) for c in cv_chunks[1:]) > CV_COMPACT_ENTRIES):
        from sheep_tpu.utils.checkpoint import compact_cv_keys

        compacted = compact_cv_keys(cv_chunks)
        cv_chunks.clear()
        cv_chunks.append(compacted)
    return cv_chunks


@partial(jax.jit, static_argnames=("n", "cap"))
def cut_pair_rows_compact(edges: jax.Array, assign: jax.Array, n: int,
                          cap: int):
    """Device-side sorted-unique cut rows, compacted to (cap, 2).

    Returns (rows, distinct_count): rows are the chunk's DISTINCT
    (vertex, foreign_part) pairs padded with the sentinel (n, 0); the
    compaction is valid only when distinct_count <= cap — past that the
    caller falls back to the dense pull. Power-law chunks repeat the same
    hub/part pairs constantly, so the device dedup shrinks the
    host transfer from 2C rows to min(distinct, cap) rows."""
    rows = cut_pairs(edges, assign, n)
    v, p = rows[:, 0], rows[:, 1]
    idx = jnp.lexsort((p, v))
    v2, p2 = v[idx], p[idx]
    first = jnp.concatenate([
        jnp.ones(1, bool), (v2[1:] != v2[:-1]) | (p2[1:] != p2[:-1])])
    keep = first & (v2 < n)
    count = jnp.sum(keep, dtype=jnp.int32)
    # fill slots index an appended sentinel row (same trick as
    # elim.compact_actives), so padding is inert
    sel = jnp.nonzero(keep, size=cap, fill_value=v2.shape[0])[0]
    v3 = jnp.concatenate([v2, jnp.full(1, n, v2.dtype)])[sel]
    p3 = jnp.concatenate([p2, jnp.zeros(1, p2.dtype)])[sel]
    return jnp.stack([v3, p3], axis=1), count


def _compact_cap(c_rows: int) -> int:
    """Device-compaction capacity for a chunk producing c_rows rows."""
    from sheep_tpu.ops.elim import pow2_at_least

    return min(c_rows, pow2_at_least(c_rows >> 3, floor=1 << 16))


def part_loads_accounting(assign, k: int, weights=None,
                          cap: float = None) -> dict:
    """Balance/capacity-freeze accounting of one assignment (ISSUE 13
    cut ledger): per-part load spread plus — when ``cap`` is given (the
    split's ``alpha * total/k`` bag capacity or refine's
    ``alpha * ceil(n/k)`` move cap) — how many parts sit AT/ABOVE it.
    A part at capacity is FROZEN for every capacity-respecting repair
    pass (refine can only shrink it), so cut stuck behind frozen parts
    is attributable to the balance budget, not to the LP signal. Host
    numpy, O(V): callers gate on need (the ledger, a traced split)."""
    import numpy as np

    a = np.asarray(assign)
    if weights is None:
        loads = np.bincount(a, minlength=k).astype(np.float64)
    else:
        loads = np.bincount(a, weights=np.asarray(weights, np.float64),
                            minlength=k)
    total = float(loads.sum())
    mean = total / max(k, 1)
    out = {"balance": float(loads.max() / mean) if mean > 0 else 1.0,
           "max_load": float(loads.max()), "min_load": float(loads.min()),
           "empty_parts": int((loads == 0).sum())}
    if cap is not None:
        at_cap = loads >= float(cap)
        out["cap"] = float(cap)
        out["parts_at_capacity"] = int(at_cap.sum())
        out["frozen_load_fraction"] = round(
            float(loads[at_cap].sum() / total) if total else 0.0, 6)
    return out


def edge_effect_host(edges, assignments: dict, n: int) -> tuple:
    """Host twin of :func:`score_chunk` for O(Δ) delta accounting
    (ISSUE 17 incremental scoring): ``(valid_count, {k: cut_count})``
    of one delta batch under EXISTING assignments. Same validity mask
    as the streamed scorers — endpoints in [0, n), no self-loops — so
    an incrementally maintained (cut, total) stays bit-equal to a full
    ``score_stream`` pass over the mutated survivor multiset."""
    import numpy as np

    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    u, v = e[:, 0], e[:, 1]
    valid = (u >= 0) & (u < n) & (v >= 0) & (v < n) & (u != v)
    cuts = {}
    for k, a in assignments.items():
        uc, vc = u[valid], v[valid]
        cuts[k] = int(np.count_nonzero(a[uc] != a[vc]))
    return int(np.count_nonzero(valid)), cuts


def cut_pair_keys_host(chunk, assign, n: int, k: int):
    """Run cut_pairs on a (C, 2) or (D, C, 2) chunk and return the encoded
    int64 keys (vertex * k + foreign_part) on host — the shared comm-volume
    accumulation used by every backend. Pulls the device-deduped compact
    rows when they fit the capacity, the dense row dump otherwise."""
    import numpy as np

    from sheep_tpu import obs

    arr = obs.pull("cv-chunk", chunk)
    rows_all = []
    for c in arr.reshape(-1, arr.shape[-2], 2) if arr.ndim == 3 else [arr]:
        cap = _compact_cap(2 * c.shape[0])
        if cap < 2 * c.shape[0]:
            compact, count = cut_pair_rows_compact(c, assign, n, cap)
            # designed pulls: this helper IS the host accumulation step
            if int(obs.pull("cv-count", count)) <= cap:
                rows = obs.pull("cv-rows", compact)
                rows = rows[rows[:, 0] < n]
                rows_all.append(rows[:, 0].astype(np.int64) * k + rows[:, 1])
                continue
        rows = obs.pull("cv-rows", cut_pairs(c, assign, n))
        rows = rows[rows[:, 0] < n]
        rows_all.append(rows[:, 0].astype(np.int64) * k + rows[:, 1])
    return np.concatenate(rows_all) if rows_all else np.zeros(0, np.int64)


# -- distributed incremental rescore (ISSUE 19) ------------------------
# One compiled rescore program per (mesh, arc capacity, K): cached here
# so repeat epochs at similar delta sizes never recompile (the sheeplint
# ``fold`` rule's contract for the update path).
_MOVE_RESCORE_CACHE: dict = {}


def _make_move_rescore(mesh):
    """Build the jitted all-k rescore program for ``mesh``: arcs shard
    over the devices, the per-k assignment/mask tables replicate, and
    the per-shard (not-both, both) partial sums ride out through ONE
    psum — the single all-reduce a scored resident epoch pays."""
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sheep_tpu.parallel.mesh import SHARD_AXIS, shard_map

    shard = NamedSharding(mesh, P(SHARD_AXIS))
    repl = NamedSharding(mesh, P())

    @partial(jax.jit,
             in_shardings=(shard, shard, repl, repl, repl),
             out_shardings=repl)
    def rescore(su, du, prev_t, new_t, mask_t):
        def f(s_l, d_l, prev_, new_, mask_):
            keep = mask_[:, s_l]                       # (K, a)
            both = mask_[:, d_l]
            diff = (new_[:, s_l] != new_[:, d_l]).astype(jnp.int32) \
                - (prev_[:, s_l] != prev_[:, d_l]).astype(jnp.int32)
            dk = jnp.where(keep, diff, 0)
            s_nb = jnp.sum(jnp.where(both, 0, dk), axis=1,
                           dtype=jnp.int32)
            s_b = jnp.sum(jnp.where(both, dk, 0), axis=1,
                          dtype=jnp.int32)
            return lax.psum(jnp.stack([s_nb, s_b], axis=1), SHARD_AXIS)
        return shard_map(f, mesh=mesh,
                         in_specs=(P(SHARD_AXIS), P(SHARD_AXIS),
                                   P(), P(), P()),
                         out_specs=P())(su, du, prev_t, new_t, mask_t)

    return rescore


def move_rescore_sharded(src, dst, prevs: dict, news: dict,
                         masks: dict, mesh) -> dict:
    """Distributed twin of :func:`sheep_tpu.ops.refine.move_rescore_host`
    (ISSUE 19 tentpole b): exact per-k edge-cut deltas of a batch of
    part moves, computed from per-shard partial sums all-reduced ONCE
    for every k together.

    Bit-equal to the host scorer by construction: integer addition is
    associative, so sharding the kept arcs and psumming the (not-both,
    both) partials reproduces the host sums exactly; the both-changed
    halving divides only AFTER the global reduction (a per-shard "both"
    partial may be odd — only the global one is guaranteed even by arc
    symmetry, asserted here like the host path). Per-shard counts stay
    int32-exact because each shard sees < 2^31 arcs (the same bound
    :func:`score_chunk` leans on). Sentinel-padded arc slots index the
    tables' sentinel row (mask false) and contribute nothing.

    ``prevs`` / ``news`` / ``masks`` are ``{k: array[V]}`` for the ks
    whose assignment actually moved; returns ``{k: cut_delta}``."""
    import numpy as np

    from sheep_tpu.ops.elim import pow2_at_least

    ks = list(prevs)
    out = {k: 0 for k in ks}
    s = np.asarray(src)
    d = np.asarray(dst)
    if not len(s) or not ks:
        return out
    n = int(len(next(iter(prevs.values()))))
    dev = int(mesh.devices.size)
    cap = pow2_at_least(-(-len(s) // dev), floor=1 << 10) * dev
    su = np.full(cap, n, np.int32)
    du = np.full(cap, n, np.int32)
    su[:len(s)] = s
    du[:len(d)] = d
    kk = len(ks)
    prev_t = np.zeros((kk, n + 1), np.int32)
    new_t = np.zeros((kk, n + 1), np.int32)
    mask_t = np.zeros((kk, n + 1), bool)
    for i, k in enumerate(ks):
        prev_t[i, :n] = prevs[k]
        new_t[i, :n] = news[k]
        mask_t[i, :n] = masks[k]
    fn = _MOVE_RESCORE_CACHE.get(mesh)
    if fn is None:
        fn = _MOVE_RESCORE_CACHE[mesh] = _make_move_rescore(mesh)
    part = np.asarray(  # sheeplint: sync-ok (the one designed pull)
        fn(su, du, prev_t, new_t, mask_t))
    for i, k in enumerate(ks):
        s_nb, s_b = int(part[i, 0]), int(part[i, 1])
        # symmetric arcs: the global both-changed sum is even (the
        # per-shard partials need not be — divide after the psum)
        assert s_b % 2 == 0
        out[k] = s_nb + s_b // 2
    return out
