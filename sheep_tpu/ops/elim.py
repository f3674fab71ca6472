"""Elimination-tree build as a data-parallel fixpoint (SURVEY.md §2 #4-6).

This is the TPU answer to the reference's sequential union-find hot loop
(SURVEY.md §7 hard part #1). Instead of pointer-chasing per edge, the
build is a *constraint-rewriting fixpoint*: the carried forest lives in a
persistent ``minp`` table (minp[x] = elimination position of x's parent,
n = none) and only the chunk's C edges are ever active:

    invariant  pos[lo] < pos[hi] for every active edge (lo, hi)
    round:
      minp[x] <- min(minp[x], pos of hi over active edges at lo=x)
                                                          (scatter-min)
      an active edge (x, v) with pos[v] == minp[x] RETIRES — it is now
      represented by the table. If it improved the table (old parent p
      had pos[p] > pos[v]), the displaced constraint "x ~ p from
      pos[p]" reduces to "v ~ p from pos[p]" (x~v merged strictly
      earlier), so the retiring slot is REUSED in place for (v, p).
      every other active edge (x, v) climbs: rewrite to (m, v) where m
      is x's highest ancestor with pos[m] < pos[v]          (gather)
    fixpoint: all slots dead -> the table is the elimination forest of
    every constraint inserted so far.

This is the vectorized form of the C++ core's incremental insertion
(core/csrc/sheep_core.cpp insert_edge: climb / displace-and-reinsert);
the represented constraint closure is preserved by every rewrite, so the
fixpoint is the unique elimination forest of the inserted multiset,
independent of edge order — which is what makes the build streamable and
the per-shard forests mergeable. Termination: a slot's pos[lo] strictly
increases on every climb AND on displacement spawn (the displaced
constraint's lo is the new parent, later than x), so each slot changes
at most n times; binary lifting makes it near-logarithmic in practice.

Unlike a formulation that re-materializes the carried forest's V tree
edges as active constraints each chunk, the active set here is O(C):
per-chunk transient memory and per-round work are independent of V
(BASELINE.md "HBM budget": single-chip ceiling 2^29 vertices at 16 GiB).

Every operation is a flat gather / scatter-min over static shapes; the
loop is a ``lax.while_loop``. Within each round the climb uses **binary
lifting** (pointer doubling): the parent map is squared ``lift_levels``
times (t_{j+1} = t_j[t_j], each a 2^j-step ancestor table) and every
edge jumps up the tables to its highest ancestor still earlier than
``hi``. Parent chains strictly increase in elimination position, so the
pos-bound predicate is monotone along a chain (measured: 645 -> 22
rounds on RMAT-14).

The round body runs entirely in **position space** (state = elimination
positions, table P[p] = parent position of the vertex at rank p): the
parent table then IS the first lifting table and jump admissibility is
a direct integer compare, cutting the gathers per level per slot from
three to one — and random-gather count is the entire round cost on a
real TPU (measured ~100-150 M gathers/s on v5e regardless of operand
shapes; tools/microbench_fixpoint.py). The public entry points keep the
vertex-space minp contract via exact permutation conversions; the
``*_pos`` variants let the streaming backend carry P across chunks with
zero steady-state conversions.

Two descent schedules, auto-selected by memory footprint:

- **exact** (high-to-low over precomputed tables): one round climbs each
  edge to its true highest admissible ancestor, fewest rounds, but all
  ``lift_levels`` tables are live at once -> O(V log V) working memory.
  Used while that fits ``EXACT_TABLE_BYTES`` (1 GiB default).
- **stream** (low-to-high, squaring interleaved with jumping): only one
  table is live -> O(V + C) memory, ~1.4x the rounds (greedy LSB-first
  jumping is not exact, but every taken jump is a sound rewrite, so the
  fixpoint is unchanged). Used for huge V where the table stack would
  blow HBM.

Sentinel encoding: index ``n`` means "none"; ``pos[n] = n`` acts as +inf,
``order[n] = n``. Inactive/padding edges are (n, n).
"""

from __future__ import annotations

import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from sheep_tpu import obs
from sheep_tpu.analysis import sanitize

NO_PARENT = -1


def pow2_at_least(x: int, floor: int = 1) -> int:
    """Smallest power of two >= max(x, 1), raised to at least ``floor``
    — the shared buffer-sizing rule (compactions, host-tail pulls,
    merge payload capacities): power-of-two sizes keep the set of
    compiled program shapes logarithmic in the starting width."""
    return max(floor, 1 << max(0, (max(int(x), 1) - 1).bit_length()))


@partial(jax.jit, static_argnames=("n",))
def orient_edges(edges: jax.Array, pos: jax.Array, n: int):
    """(C,2) int32 edges -> (lo, hi) with pos[lo] < pos[hi]; self-loops and
    out-of-range/padding endpoints become inactive (n, n)."""
    e = edges.astype(jnp.int32)
    u = jnp.clip(e[:, 0], 0, n)
    v = jnp.clip(e[:, 1], 0, n)
    pu, pv = pos[u], pos[v]
    lo = jnp.where(pu <= pv, u, v)
    hi = jnp.where(pu <= pv, v, u)
    bad = (lo == hi) | (pos[lo] == pos[hi])  # self-loop or both-sentinel
    lo = jnp.where(bad, n, lo)
    hi = jnp.where(bad, n, hi)
    return lo, hi


# exact descent keeps lift_levels ancestor tables of 4*(n+1) bytes live at
# once; beyond this budget the fixpoint switches to the O(V) stream descent
EXACT_TABLE_BYTES = 1 << 30


def _resolve(n: int, lift_levels: int, descent: str):
    if lift_levels <= 0:
        lift_levels = max(1, int(n).bit_length())
    if descent == "auto":
        table_bytes = lift_levels * 4 * (n + 1)
        descent = "exact" if table_bytes <= EXACT_TABLE_BYTES else "stream"
    return lift_levels, descent


def _pos_round_body(n: int, lift_levels: int, descent: str):
    """One fixpoint round as a while_loop body over POSITION-SPACE state
    (loP, hiP, P, changed, rounds) — shared by every entry point so all
    schedules execute identical rounds.

    Position space is the real-chip optimization (BASELINE.md roofline):
    with P[p] = elimination position of the parent of the vertex at rank
    p, the parent table IS the first binary-lifting table (ancestor
    chains strictly increase in position), and jump admissibility is the
    direct integer compare ``cand < hiP``. The vertex-space formulation
    needed three gathers per lifting level per slot (t[new_lo],
    pos[cand], plus the order[...] rewrites); this needs ONE — and XLA
    gather throughput is the whole cost of a round on TPU (measured
    ~100-150 M random gathers/s on v5e, tools/microbench_fixpoint.py).
    The dynamics commute with the pos/order permutation, so slot
    trajectories are bit-identical to the vertex-space form under
    ``order[.]``/``pos[.]`` conjugation."""

    def body(state):
        lo_, hi_, P_, _, rounds = state
        old_at_lo = P_[lo_]  # parent position BEFORE this round
        newP = P_.at[lo_].min(hi_, mode="drop")
        now = newP[lo_]

        # climb for non-retiring slots. t_j[p] = p's 2^j-step ancestor
        # position under the updated table (sentinel n is a fixpoint of
        # every table since P[n] = n); a jump is safe iff it lands
        # strictly earlier than hiP
        t = newP
        cur = lo_
        if descent == "exact":
            tables = [t]
            for _ in range(lift_levels - 1):
                t = t[t]
                tables.append(t)
            for t in reversed(tables):
                cand = t[cur]
                cur = jnp.where(cand < hi_, cand, cur)
        else:  # stream: square in place, only one table live
            for j in range(lift_levels):
                cand = t[cur]
                cur = jnp.where(cand < hi_, cand, cur)
                if j < lift_levels - 1:
                    t = t[t]
        became_loop = cur == hi_  # constraint already implied
        climb_lo = jnp.where(became_loop, n, cur)
        climb_hi = jnp.where(became_loop, n, hi_)

        # retire: this slot's target IS the min at lo (positions are
        # unique, so only duplicates of the same constraint retire
        # together). If it improved on an existing parent p, reuse the
        # slot for the displaced constraint (now, old); else it dies.
        retire = hi_ == now
        displaced = retire & (now < old_at_lo) & (old_at_lo < n)
        out_lo = jnp.where(retire,
                           jnp.where(displaced, now, n),
                           climb_lo).astype(jnp.int32)
        out_hi = jnp.where(retire,
                           jnp.where(displaced, old_at_lo, n),
                           climb_hi).astype(jnp.int32)
        # slots only ever change toward progress (loP strictly
        # increases), so "no slot changed" == fixpoint (table included:
        # the table only changes through a retiring slot)
        changed = jnp.any((out_lo != lo_) | (out_hi != hi_))
        return out_lo, out_hi, newP, changed, rounds + 1

    return body


def _init_state(minp, lo, hi):
    # derive the initial carry scalars from `lo` so their sharding/varying
    # axes match the loop body's outputs (required under shard_map)
    changed0 = lo[0] == lo[0]  # True, with lo's varying axes
    rounds0 = (lo[0] * 0).astype(jnp.int32)
    return (lo.astype(jnp.int32), hi.astype(jnp.int32),
            minp.astype(jnp.int32), changed0, rounds0)


def _run_segment(body, P, loP, hiP, n: int, segment_rounds: int):
    """Shared segment epilogue: bounded while_loop + the packed int32[3]
    stats vector (changed, rounds, live) — the cross-module contract
    read by the adaptive driver (one host pull) and the sharded
    pipeline (sv[0]/sv[2] pmax)."""
    def cond(state):
        _, _, _, changed, rounds = state
        return changed & (rounds < segment_rounds)

    loP, hiP, P, changed, rounds = lax.while_loop(
        cond, body, _init_state(P, loP, hiP))
    stats = jnp.stack([changed.astype(jnp.int32), rounds,
                       jnp.sum(loP != n, dtype=jnp.int32)])
    return loP, hiP, P, stats


def _pos_round_body_stale(n: int, tables: tuple):
    """Round body for :func:`fold_segment_pos_hoisted`: identical
    retire/displace semantics to :func:`_pos_round_body` (exact
    descent), but the lifting tables above level 0 are STALE closures —
    built once per segment — while level 0 is always the CURRENT table.
    Sound because ancestor-ship is permanent (when a parent improves,
    the displaced constraint re-links the old parent above the new
    one), so a stale table's jumps land on genuine — just possibly
    non-maximal — ancestors; any progress missed is caught after the
    next rebuild. Saves (R-1)/R of the L x V squaring gathers per
    segment, the round's dominant V-term (BASELINE.md 'stale lifting
    tables')."""

    def body(state):
        lo_, hi_, P_, _, rounds = state
        old_at_lo = P_[lo_]
        newP = P_.at[lo_].min(hi_, mode="drop")
        now = newP[lo_]

        cur = lo_
        for t in reversed(tables):
            cand = t[cur]
            cur = jnp.where(cand < hi_, cand, cur)
        # level 0 last and CURRENT: guarantees one-step progress per
        # live slot even right after a displacement spawn
        cand = newP[cur]
        cur = jnp.where(cand < hi_, cand, cur)
        became_loop = cur == hi_
        climb_lo = jnp.where(became_loop, n, cur)
        climb_hi = jnp.where(became_loop, n, hi_)

        retire = hi_ == now
        displaced = retire & (now < old_at_lo) & (old_at_lo < n)
        out_lo = jnp.where(retire,
                           jnp.where(displaced, now, n),
                           climb_lo).astype(jnp.int32)
        out_hi = jnp.where(retire,
                           jnp.where(displaced, old_at_lo, n),
                           climb_hi).astype(jnp.int32)
        changed = jnp.any((out_lo != lo_) | (out_hi != hi_))
        return out_lo, out_hi, newP, changed, rounds + 1

    return body


@partial(jax.jit, static_argnames=("n", "lift_levels", "segment_rounds"))
def fold_segment_pos_hoisted(
    P: jax.Array,
    loP: jax.Array,
    hiP: jax.Array,
    n: int,
    lift_levels: int = 0,
    segment_rounds: int = 32,
):
    """:func:`fold_segment_pos` (exact descent) with the lifting-table
    stack HOISTED out of the round loop: tables t_1..t_{L-1} are built
    once from the entry table and stay fixed for the whole segment;
    only level 0 (the table itself) is current inside rounds. Same
    (loP, hiP, P, stats) contract. The final forest is the same unique
    fixpoint (stale jumps are sound, see :func:`_pos_round_body_stale`);
    per-round trajectories may differ from the fresh-table body, so the
    adaptive driver treats round counts as diagnostics, not contracts.

    Fixpoint-exit soundness: the driver loop only stops on a segment
    reporting no change, and every segment starts with tables freshly
    built from its entry table — a first round that changes nothing ran
    with a fully-current view, so 'no change' is a genuine fixpoint."""
    return fold_segment_pos_stale(P, loP, hiP,
                                  build_lift_tables(P, n, lift_levels),
                                  n, segment_rounds=segment_rounds)


@partial(jax.jit, static_argnames=("n", "lift_levels"))
def build_lift_tables(P: jax.Array, n: int, lift_levels: int = 0):
    """The exact-descent lifting stack t_1..t_{L-1} as a standalone
    program, for CROSS-SEGMENT reuse (``stale_reuse`` > 1 in the
    adaptive driver): (L-1) x V squaring gathers once per rebuild
    instead of once per segment."""
    lift_levels, _ = _resolve(n, lift_levels, "exact")
    t = P.astype(jnp.int32)
    tables = []
    for _ in range(lift_levels - 1):
        t = t[t]
        tables.append(t)
    return tuple(tables)


@partial(jax.jit, static_argnames=("n", "segment_rounds"))
def fold_segment_pos_stale(
    P: jax.Array,
    loP: jax.Array,
    hiP: jax.Array,
    tables: tuple,
    n: int,
    segment_rounds: int = 32,
):
    """:func:`fold_segment_pos_hoisted` with the stack passed IN
    (:func:`build_lift_tables`) so the driver can reuse it across
    several segments. Soundness is the stronger form the stale round
    body already satisfies: level 0 is always current (one-step
    progress per live slot, so no livelock — a constraint whose level-0
    jump is inadmissible retires by scatter-min within two rounds), and
    a no-change segment is a genuine fixpoint REGARDLESS of stack
    freshness, because slots only change toward progress and the table
    only changes through a retiring slot (see _pos_round_body). Stale
    jumps land on genuine ancestors (permanence), so the unique
    fixpoint is unchanged; only round counts differ."""
    body = _pos_round_body_stale(n, tuple(tables))
    return _run_segment(body, P, loP, hiP, n, segment_rounds)


@partial(jax.jit, static_argnames=("n", "lift_levels", "segment_rounds",
                                   "descent"))
def fold_segment_pos(
    P: jax.Array,
    loP: jax.Array,
    hiP: jax.Array,
    n: int,
    lift_levels: int = 0,
    segment_rounds: int = 32,
    descent: str = "auto",
):
    """At most ``segment_rounds`` rounds in ONE device execution, entirely
    in position space — the production hot path (no pos/order tables in
    the compiled program at all). Returns (loP, hiP, P, stats) where
    ``stats`` is int32[3] = (changed, rounds, live): packing the three
    control scalars into one vector lets the host driver read them with
    a SINGLE device pull per segment — each pull is a full host/device
    round-trip, and the driver needs all three every segment. Bounding
    rounds per execution keeps accelerator calls short (long single
    executions tripped the TPU worker watchdog in round 2's first bench
    attempt)."""
    lift_levels, descent = _resolve(n, lift_levels, descent)
    body = _pos_round_body(n, lift_levels, descent)
    return _run_segment(body, P, loP, hiP, n, segment_rounds)


def _pos_small_round_body(n: int, jumps: int):
    """Jump-mode round body for SMALL active buffers: identical
    retire/displace semantics to :func:`_pos_round_body`, but the climb is
    ``jumps`` single parent steps via per-element gathers — O(C') work per
    round with NO O(V) lifting-table rebuild. Used for the fixpoint tail,
    where a handful of displacement-chain constraints would otherwise pay
    the full-buffer, full-table cost every round."""

    def body(state):
        lo_, hi_, P_, _, rounds = state
        old_at_lo = P_[lo_]
        newP = P_.at[lo_].min(hi_, mode="drop")
        now = newP[lo_]

        cur = lo_
        for _ in range(jumps):
            cand = newP[cur]
            cur = jnp.where(cand < hi_, cand, cur)
        became_loop = cur == hi_
        climb_lo = jnp.where(became_loop, n, cur)
        climb_hi = jnp.where(became_loop, n, hi_)

        retire = hi_ == now
        displaced = retire & (now < old_at_lo) & (old_at_lo < n)
        out_lo = jnp.where(retire,
                           jnp.where(displaced, now, n),
                           climb_lo).astype(jnp.int32)
        out_hi = jnp.where(retire,
                           jnp.where(displaced, old_at_lo, n),
                           climb_hi).astype(jnp.int32)
        changed = jnp.any((out_lo != lo_) | (out_hi != hi_))
        return out_lo, out_hi, newP, changed, rounds + 1

    return body


@partial(jax.jit, static_argnames=("n", "jumps", "segment_rounds"))
def fold_segment_small_pos(
    P: jax.Array,
    loP: jax.Array,
    hiP: jax.Array,
    n: int,
    jumps: int = 8,
    segment_rounds: int = 64,
):
    """Bounded segment of jump-mode rounds (see _pos_small_round_body).
    Same (loP, hiP, P, stats) contract as :func:`fold_segment_pos`."""
    body = _pos_small_round_body(n, jumps)
    return _run_segment(body, P, loP, hiP, n, segment_rounds)


# ---------------------------------------------------------------------------
# batched segment dispatch (ISSUE 1 tentpole): fold N staged streaming
# segments inside ONE bounded device program. The per-segment driver
# above pays one host round-trip (the sv pull) per bounded segment —
# measured as the dominant build cost through a degraded link (~160 s of
# the 227.8 s round-5 build against a 68 s device floor, VERDICT r5
# item 2). Here the host stages N segments as padded [N, C] position
# blocks, the device runs an outer while_loop that advances segment by
# segment (each segment's rounds are the SAME _pos_round_body), and the
# host pulls one packed stats word per execution: O(segments / N) syncs
# instead of O(segments). The forest is bit-identical — the elimination
# fixpoint is unique given the constraint multiset, independent of how
# the segments are scheduled (tests/test_dispatch_batch.py).
# ---------------------------------------------------------------------------

def batch_segment_fixpoint(
    P: jax.Array,
    loB: jax.Array,
    hiB: jax.Array,
    n: int,
    lift_levels: int = 0,
    descent: str = "auto",
    batch_rounds: int = 0,
):
    """Traceable core of the batched dispatch: advance through the rows
    of the [N, C] active blocks, one fixpoint round per loop step, with
    on-device stop conditions — a segment is done when a round changes
    nothing (the genuine fixpoint, see :func:`_pos_round_body`), the
    program exits when every segment is done or ``batch_rounds`` total
    rounds are spent (watchdog bounding; the host re-dispatches on the
    returned blocks to resume). A converged segment's row is stored
    all-sentinel — its residual live slots are implied by the table —
    so re-entry after a budget exhaustion re-confirms it in one round.

    Returns ``(loB, hiB, P, sv)`` with ``sv`` int32[4] =
    (segments_done, rounds, live, retired) — ONE packed stats word per
    batch. Callable directly under shard_map (the sharded pipeline's
    per-device form); :func:`fold_segments_batch_pos` is the jitted
    single-device entry."""
    N, _ = loB.shape
    lift_levels, descent = _resolve(n, lift_levels, descent)
    if batch_rounds <= 0:
        batch_rounds = 32 * N
    round_body = _pos_round_body(n, lift_levels, descent)
    # derive carried scalars from the block so their sharding/varying
    # axes match the loop outputs (required under shard_map, as in
    # _init_state)
    zero = (loB[0, 0] * 0).astype(jnp.int32)
    dummy_changed = loB[0, 0] == loB[0, 0]

    def load(block, i):
        return lax.dynamic_index_in_dim(block, i, axis=0, keepdims=False)

    def cond(state):
        i, _, _, _, _, _, rounds, _ = state
        return (i < N) & (rounds < batch_rounds)

    def body(state):
        i, lo, hi, loB_, hiB_, P_, rounds, retired = state
        lo2, hi2, P2, changed, _ = round_body(
            (lo, hi, P_, dummy_changed, zero))
        retired = retired + jnp.sum((lo2 == n) & (lo != n),
                                    dtype=jnp.int32)
        seg_done = ~changed
        sent = jnp.full_like(lo2, n)
        # store the working buffer back every round so the blocks always
        # reflect resumable state when the round budget exhausts
        loB_ = lax.dynamic_update_index_in_dim(
            loB_, jnp.where(seg_done, sent, lo2), i, axis=0)
        hiB_ = lax.dynamic_update_index_in_dim(
            hiB_, jnp.where(seg_done, sent, hi2), i, axis=0)
        i2 = jnp.where(seg_done, i + 1, i)
        nxt = jnp.minimum(i2, N - 1)
        lo3 = jnp.where(seg_done, load(loB_, nxt), lo2)
        hi3 = jnp.where(seg_done, load(hiB_, nxt), hi2)
        return (i2, lo3, hi3, loB_, hiB_, P2, rounds + 1, retired)

    state = (zero, load(loB, zero), load(hiB, zero), loB, hiB,
             P.astype(jnp.int32), zero, zero)
    i_f, _, _, loB_f, hiB_f, P_f, rounds_f, retired_f = lax.while_loop(
        cond, body, state)
    live = jnp.sum(loB_f != n, dtype=jnp.int32)
    sv = jnp.stack([i_f, rounds_f, live, retired_f])
    return loB_f, hiB_f, P_f, sv


@partial(jax.jit, static_argnames=("n", "lift_levels", "descent",
                                   "batch_rounds"))
def fold_segments_batch_pos(
    P: jax.Array,
    loB: jax.Array,
    hiB: jax.Array,
    n: int,
    lift_levels: int = 0,
    descent: str = "auto",
    batch_rounds: int = 0,
):
    """Jitted :func:`batch_segment_fixpoint` — the single-device batched
    dispatch program."""
    return batch_segment_fixpoint(P, loB, hiB, n, lift_levels=lift_levels,
                                  descent=descent,
                                  batch_rounds=batch_rounds)


@partial(jax.jit, static_argnames=("n", "lift_levels", "descent",
                                   "batch_rounds"), donate_argnums=(0, 1, 2))
def fold_segments_batch_pos_donated(
    P: jax.Array,
    loB: jax.Array,
    hiB: jax.Array,
    n: int,
    lift_levels: int = 0,
    descent: str = "auto",
    batch_rounds: int = 0,
):
    """:func:`fold_segments_batch_pos` with the carried table and the
    [N, C] staging blocks DONATED: XLA reuses their HBM buffers for the
    execution's outputs instead of allocating a second copy of each,
    so a chain of executions holds one table + one staging block per
    in-flight execution rather than two (ISSUE 4 tentpole;
    utils/membudget.build_phase_bytes models the credit). Inputs are
    INVALIDATED by the call — only for callers that rebind, like the
    re-dispatch loops here."""
    return batch_segment_fixpoint(P, loB, hiB, n, lift_levels=lift_levels,
                                  descent=descent,
                                  batch_rounds=batch_rounds)


@partial(jax.jit, static_argnames=("n",))
def orient_chunks_batch_pos(chunks: jax.Array, pos: jax.Array, n: int):
    """(N, C, 2) stacked padded chunks -> oriented POSITION blocks
    (loB, hiB), each row an independent [C] active buffer — the [N, C]
    staging block of the batched dispatch. Sentinel-padded rows (and the
    per-chunk padding tail) orient to the inert (n, n), which is the
    per-segment live mask: a fully-inert row converges in one round."""
    return jax.vmap(lambda c: orient_edges_pos(c, pos, n))(chunks)



def _resolve_batch_rounds(batch_rounds: int, segment_rounds: int,
                          N: int) -> int:
    """Per-execution round budget of the batched dispatch: default
    ``segment_rounds * N`` (the allowance the per-segment driver would
    spread over N syncs). Every execution restarts the segment cursor
    at 0, and each already-converged segment still costs one
    confirmation round: a per-execution budget below N can stall the
    cursor at the same prefix forever and silently return an
    unconverged forest at the max_rounds backstop — clamp so one
    execution can always cross the whole block."""
    if batch_rounds <= 0:
        batch_rounds = max(1, segment_rounds) * max(N, 1)
    return max(batch_rounds, max(N, 1))


def _t_ms(stats: dict, key: str, dt_s: float) -> None:
    """Accumulate a millisecond counter UNROUNDED (same rule as t_add:
    consumers round at read time so sums never drift past the wall)."""
    stats[key] = stats.get(key, 0.0) + dt_s * 1e3


def _seed_ms_counters(stats: dict) -> None:
    """Pre-seed the overlap counters so every driver run emits all of
    them — a fold that converges before its second execution would
    otherwise never touch ``device_gap_ms``, and the bench contract /
    regression gate treat a missing field as incomparable rather than
    zero. The H2D ingest pair (ISSUE 12) seeds here too: a
    device-stream build stages nothing, and its 0.0s are the
    zero-host-bytes evidence, not an absent measurement."""
    stats.setdefault("host_blocked_ms", 0.0)
    stats.setdefault("device_gap_ms", 0.0)
    stats.setdefault("h2d_staged_ms", 0.0)
    stats.setdefault("h2d_blocked_ms", 0.0)


def fold_segments_batch(
    P: jax.Array,
    loB: jax.Array,
    hiB: jax.Array,
    n: int,
    lift_levels: int = 0,
    segment_rounds: int = 2,
    descent: str = "auto",
    batch_rounds: int = 0,
    max_rounds: int = 1 << 20,
    stats=None,
    donate: bool = False,
):
    """SYNCHRONOUS host driver of the batched dispatch over ONE staged
    block: loop bounded :func:`fold_segments_batch_pos` executions
    until every staged segment reports done — ONE packed-stats pull
    per EXECUTION instead of per segment. The default per-execution
    round budget is ``segment_rounds * N`` (see
    :func:`_resolve_batch_rounds`), so the host sync count drops by ~N
    while no single device execution runs longer than N bounded
    segments back to back (the watchdog envelope scales with the
    staged batch, not with the stream). Returns ``(P, total_rounds)``.

    ``donate`` runs the donated program
    (:func:`fold_segments_batch_pos_donated`): the caller's P/loB/hiB
    are INVALIDATED.

    Implemented as :func:`fold_segments_pipelined` at depth 1 over the
    single block — the pipelined driver's documented degenerate mode
    (same executions in the same order, pinned by
    tests/test_inflight.py) — so there is exactly one dispatch loop to
    maintain. ``host_blocked_ms``/``device_gap_ms`` quantify the
    alternation tax deeper pipelines remove; on the max_rounds
    backstop, ``batch_incomplete_segments`` flags the undrained block
    (key presence is the contract)."""
    return fold_segments_pipelined(
        P, iter([(loB, hiB)]), n, inflight=1, lift_levels=lift_levels,
        segment_rounds=segment_rounds, descent=descent,
        batch_rounds=batch_rounds, max_rounds=max_rounds, donate=donate,
        stats=stats)


# ---------------------------------------------------------------------------
# asynchronous in-flight dispatch pipeline (ISSUE 4 tentpole). The batched
# driver above is still a synchronous lockstep: stage -> execute ->
# BLOCKING packed-stats pull -> decide -> stage next, so the device idles
# through every host read/orient/pad and the host idles through every
# device program. JAX arrays are futures, so the pull is the only forced
# sync — this driver keeps a bounded FIFO (depth D) of issued executions
# whose stats words stay un-pulled, chains each new execution on the
# previous one's (async) output table, and converts sv to host ints
# one-behind. Buffers are donated along the chain, so the staged blocks
# and the carried table are REUSED across executions instead of doubling
# peak HBM (fold_segments_batch_pos_donated).
#
# Speculation + bit-identity: a new staged group is issued assuming the
# executions ahead of it drain their blocks (the common case — the
# per-execution round budget covers the whole block). When a pulled sv
# reveals an execution did NOT drain (budget exhaustion), its leftover
# blocks are re-queued and re-dispatched on the CURRENT chain table;
# that re-orders constraint resolution but cannot change the result,
# because the elimination fixpoint is the unique forest of the inserted
# constraint multiset, independent of fold order (the PR-1 argument, now
# applied across groups instead of within one). At stream end the driver
# speculates the other way — "the last blocks have NOT converged" — and
# issues their re-dispatch before pulling; if the pull says converged,
# the speculative executions are DISCARDED: their svs are never read
# (zero extra syncs) and their output table is the bit-identical
# re-confirmation of the converged one (drained blocks are all-sentinel;
# re-entry re-confirms each row in one round and leaves the table
# untouched), so adopting it IS resuming from the last confirmed carry.
# ---------------------------------------------------------------------------

def fold_segments_pipelined(
    P: jax.Array,
    staged,
    n: int,
    inflight: int = 2,
    lift_levels: int = 0,
    segment_rounds: int = 2,
    descent: str = "auto",
    batch_rounds: int = 0,
    max_rounds: int = 1 << 20,
    donate: bool = True,
    stats=None,
    on_confirm=None,
    on_flush=None,
):
    """Fold a stream of staged [N, C] oriented position blocks with up
    to ``inflight`` device executions in flight (see the block comment
    above for the speculation/discard model).

    ``staged`` yields ``(loB, hiB)`` or ``(loB, hiB, tag)`` blocks
    (:func:`orient_chunks_batch_pos`); blocks are consumed (donated when
    ``donate``). ``on_confirm(tag, rounds, P)`` fires after each stats
    pull — ``tag`` is the staged group's tag for the first execution of
    a group and None for re-dispatches — with the CURRENT chain-tip
    table (an async jax array valid until the next execution is issued;
    read it immediately, do not store it). A truthy return from
    ``on_confirm`` requests a FLUSH BARRIER: the driver stops consuming
    new groups, drains everything already issued (including leftover
    re-dispatches) to completion, then calls ``on_flush(P)`` with a
    table that provably contains the full constraint multiset of every
    confirmed group — the only place a checkpoint cut is sound, because
    mid-pipeline the tip table can UNDER-represent a confirmed group
    whose budget-exhausted leftovers are still queued host-side.
    Returns ``(P, total_rounds)``; ``inflight=1`` degenerates to the
    synchronous execute/pull/decide loop (same executions in the same
    order as :func:`fold_segments_batch` over the group sequence).

    Counters (all absorbed by the obs tracer at span boundaries and
    emitted as bench contract fields): ``host_blocked_ms`` = wall spent
    inside blocking sv pulls; ``device_gap_ms`` = wall from a pull that
    EMPTIED the in-flight queue to the next execution's dispatch (the
    device provably idles through exactly those windows; with D >= 2
    the queue rarely empties and the counter collapses toward 0);
    ``inflight_discards`` = speculative executions whose sv was never
    read. ``max_rounds`` is a backstop, not an exact cap: in-flight
    executions are drained and counted when it trips, and
    ``batch_incomplete_segments`` then reports the staged BLOCKS known
    undrained — a LOWER BOUND: the unconsumed remainder of the stream
    is never staged (counting it would force its H2D uploads), so the
    flag's presence, not its magnitude, is the incompleteness
    contract (as in :func:`fold_segments_batch`)."""
    from collections import deque

    from sheep_tpu.utils import fault

    if inflight < 1:
        raise ValueError("inflight must be >= 1")
    if stats is None:
        stats = {}
    _seed_ms_counters(stats)
    stats.setdefault("inflight_discards", 0)
    fold = fold_segments_batch_pos_donated if donate \
        else fold_segments_batch_pos
    fifo: deque = deque()       # issued, un-pulled executions, FIFO
    leftovers: deque = deque()  # blocks of partially-drained executions
    it = iter(staged)
    t_start = time.perf_counter()

    def pull_group():
        try:
            return next(it)
        except StopIteration:
            return None

    state = {"tipP": P.astype(jnp.int32), "tip": None, "idle_since": None,
             "flushing": False}
    nxt = pull_group()
    total = 0

    def issue(loB, hiB, kind, tag):
        now = time.perf_counter()
        if state["idle_since"] is not None:
            _t_ms(stats, "device_gap_ms", now - state["idle_since"])
            state["idle_since"] = None
        # dispatch-time injection point (ISSUE 9): a fault raised here
        # unwinds the whole driver with the chain un-drained — exactly
        # what a real allocation failure inside fold() does — so the
        # backend-level retry/degrade wrapper sees the production shape
        state["issued"] = state.get("issued", 0) + 1
        fault.maybe_fail("dispatch", state["issued"],
                         kinds=("oom", "device"))
        N = int(loB.shape[0])
        prevP = state["tipP"]
        lo2, hi2, P2, sv = fold(
            prevP, loB, hiB, n, lift_levels=lift_levels,
            descent=descent,
            batch_rounds=_resolve_batch_rounds(batch_rounds,
                                               segment_rounds, N))
        if donate:
            # SHEEP_SANITIZE: the chained inputs must really be
            # poisoned — a silently ignored donation doubles HBM and
            # leaves use-after-donate bugs latent. Touches only
            # is_deleted metadata, never the dead buffers' contents:
            sanitize.check_donated(
                prevP, loB, hiB,  # sheeplint: donate-ok
                origin="fold_segments_batch_pos_donated")
        state["tipP"] = P2
        rec = {"lo": lo2, "hi": hi2, "sv": sv, "kind": kind, "tag": tag,
               "N": N}
        state["tip"] = rec
        fifo.append(rec)

    def confirm(rec):
        """Blocking pull of one execution's stats word; returns done."""
        nonlocal total
        # the ONE designed sync of the pipeline: the one-behind packed
        # stats pull (everything else stays an unread future)
        done, r, live, retired = (int(x) for x in obs.pull(
            "pipelined-sv-pull", rec["sv"], host_blocked=stats))
        now = time.perf_counter()
        stats["host_syncs"] = stats.get("host_syncs", 0) + 1
        stats["batch_execs"] = stats.get("batch_execs", 0) + 1
        stats["batch_retired"] = stats.get("batch_retired", 0) + retired
        stats["device_rounds"] = stats.get("device_rounds", 0) + r
        total += r
        if not fifo:
            # nothing left in flight: the device finished this execution
            # no later than the pull completed and idles until the next
            # dispatch
            state["idle_since"] = now
        drained = done >= rec["N"]
        if drained:
            # any speculative re-dispatches of these (now known-drained)
            # blocks are bit-identical re-confirmations: discard them —
            # never read their svs — and let the chain tip (their
            # output) stand in for the confirmed carry
            while fifo and fifo[0]["kind"] == "spec":
                fifo.popleft()
                stats["inflight_discards"] = \
                    stats.get("inflight_discards", 0) + 1
            if not fifo:
                state["idle_since"] = time.perf_counter()
        elif not (fifo and fifo[0]["kind"] == "spec"):
            # budget exhausted and no speculative continuation already
            # in flight: the leftover constraints live in this
            # execution's output blocks — re-queue them (re-dispatching
            # on the current chain table is sound: the fixpoint is
            # order-independent in the constraint multiset)
            leftovers.append((rec["lo"], rec["hi"]))
        if on_confirm is not None:
            if on_confirm(rec["tag"] if rec["kind"] == "group" else None,
                          r, state["tipP"]):
                state["flushing"] = True
        return drained

    # SHEEP_SANITIZE: arm the stray-sync traps for the whole dispatch
    # chain — between the annotated pulls, every device value must
    # stay an unread future (one stray int()/bool() here silently
    # reverts the pipeline to lockstep; the sanitizer makes it raise)
    with sanitize.guard("dispatch"):
        while True:
            while len(fifo) < inflight:
                if leftovers:
                    lo, hi = leftovers.popleft()
                    issue(lo, hi, "left", None)
                elif state["flushing"]:
                    # flush barrier: no new groups, no speculation —
                    # only drain what is already in flight
                    break
                elif nxt is not None:
                    lo, hi = nxt[0], nxt[1]
                    tag = nxt[2] if len(nxt) > 2 else None
                    # dispatch the staged group BEFORE pulling the next
                    # one: pull_group() can block on the producer's
                    # read/pad (prefetch queue empty on IO-bound
                    # streams), and the device should be folding
                    # through that wall, not waiting behind it
                    issue(lo, hi, "group", tag)
                    nxt = pull_group()
                elif fifo:
                    # stream drained, queue not full: speculate the
                    # newest execution does NOT finish its blocks and
                    # issue its re-dispatch now (discarded unread if
                    # it did)
                    tip = state["tip"]
                    issue(tip["lo"], tip["hi"], "spec", None)
                else:
                    break
            if not fifo:
                if state["flushing"]:
                    # fully drained (the fill loop always re-issues
                    # leftovers before this point): every confirmed
                    # group's constraints are in the tip table — the
                    # sound cut
                    state["flushing"] = False
                    if on_flush is not None:
                        on_flush(state["tipP"])
                    if nxt is not None:
                        continue
                break
            confirm(fifo.popleft())
            if total >= max_rounds:
                # backstop: drain what is already in flight (those
                # rounds ran — counting them keeps the stats honest),
                # then report the undrained remainder instead of
                # exiting silently. A flush barrier requested during
                # this drain is deliberately DROPPED: with leftovers
                # pending there is no sound cut to save, and the run
                # is returning incomplete (and flagged) anyway —
                # resume simply redoes from the previous barrier
                while fifo:
                    confirm(fifo.popleft())
                pending = len(leftovers) + (1 if nxt is not None else 0)
                if pending:
                    stats["batch_incomplete_segments"] = pending
                break
    stats["t_batch_s"] = stats.get("t_batch_s", 0.0) + \
        (time.perf_counter() - t_start)
    return state["tipP"], total


# ---------------------------------------------------------------------------
# sort-merge round prototype (VERDICT r2 item 2): the one primitive class
# not yet tried as the round body. Replaces every random C-from-V table
# gather with a sort-based join so the round rides lax.sort throughput
# instead of the ~100-150 M elem/s XLA gather roofline. Kept bit-identical
# to the jump-mode round (tests/test_tpu_ops.py) so the keep/reject
# decision is purely a measured-throughput question — see BASELINE.md
# "sort-based round" entry for the measured verdict.
# ---------------------------------------------------------------------------

def sorted_lookup(tables, idx: jax.Array, n: int):
    """``[t[idx] for t in tables]`` with NO random gather.

    Mechanism: concatenate the dense key range [0, n] (carrying each
    table's values) with the query indices, one lexicographic
    ``lax.sort`` by (key, is_query) — every query row lands immediately
    after the table row with its key, table keys being dense — then a
    last-valid ``associative_scan`` propagates table values onto query
    rows, and one scatter returns results to slot order. Cost:
    O((V + C) log) sort + streaming scan, vs C random gathers; wins iff
    sort throughput/element beats the gather roofline on the target
    device (the microbench probes exactly this pair)."""
    C = idx.shape[0]
    m = n + 1
    keys = jnp.concatenate([jnp.arange(m, dtype=jnp.int32),
                            idx.astype(jnp.int32)])
    tag = jnp.concatenate([jnp.zeros(m, jnp.int32), jnp.ones(C, jnp.int32)])
    slot = jnp.concatenate([jnp.zeros(m, jnp.int32),
                            jnp.arange(C, dtype=jnp.int32)])
    payloads = tuple(jnp.concatenate([t.astype(jnp.int32),
                                      jnp.zeros(C, jnp.int32)])
                     for t in tables)
    srt = lax.sort((keys, tag, slot) + payloads, num_keys=2)
    st, ss, sp = srt[1], srt[2], srt[3:]
    is_table = st == 0

    def combine(a, b):
        # last-valid: b's payloads win wherever b is a table row
        vals = tuple(jnp.where(b[-1], pb, pa)
                     for pa, pb in zip(a[:-1], b[:-1]))
        return vals + (a[-1] | b[-1],)

    scanned = lax.associative_scan(combine, sp + (is_table,))
    # scatter query rows back to slot order; table rows go to a dump slot
    dump = jnp.where(st == 1, ss, C)
    out = []
    for v in scanned[:-1]:
        buf = jnp.zeros(C + 1, jnp.int32).at[dump].set(v, mode="drop")
        out.append(buf[:C])
    return out


def _pos_sortmerge_round_body(n: int, jumps: int):
    """Jump-mode round with every table *read* through
    :func:`sorted_lookup` — identical retire/displace/climb semantics to
    :func:`_pos_small_round_body` (the scatter-min write stays a
    scatter; it is not the dominant cost and has no sort equivalent
    cheaper than a segmented reduce of the same sorted buffer)."""

    def body(state):
        lo_, hi_, P_, _, rounds = state
        newP = P_.at[lo_].min(hi_, mode="drop")
        old_at_lo, now = sorted_lookup((P_, newP), lo_, n)

        cur = lo_
        for _ in range(jumps):
            cand = sorted_lookup((newP,), cur, n)[0]
            cur = jnp.where(cand < hi_, cand, cur)
        became_loop = cur == hi_
        climb_lo = jnp.where(became_loop, n, cur)
        climb_hi = jnp.where(became_loop, n, hi_)

        retire = hi_ == now
        displaced = retire & (now < old_at_lo) & (old_at_lo < n)
        out_lo = jnp.where(retire,
                           jnp.where(displaced, now, n),
                           climb_lo).astype(jnp.int32)
        out_hi = jnp.where(retire,
                           jnp.where(displaced, old_at_lo, n),
                           climb_hi).astype(jnp.int32)
        changed = jnp.any((out_lo != lo_) | (out_hi != hi_))
        return out_lo, out_hi, newP, changed, rounds + 1

    return body


@partial(jax.jit, static_argnames=("n", "jumps", "segment_rounds"))
def fold_segment_sortmerge_pos(
    P: jax.Array,
    loP: jax.Array,
    hiP: jax.Array,
    n: int,
    jumps: int = 8,
    segment_rounds: int = 64,
):
    """Sort-merge variant of :func:`fold_segment_small_pos` — same
    (loP, hiP, P, stats) contract, bit-identical trajectories (asserted
    by tests), different primitive mix for the microbench decision."""
    body = _pos_sortmerge_round_body(n, jumps)
    return _run_segment(body, P, loP, hiP, n, segment_rounds)


@partial(jax.jit, static_argnames=("n", "lift_levels", "max_rounds", "descent"))
def fold_edges(
    minp: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    pos: jax.Array,
    order: jax.Array,
    n: int,
    lift_levels: int = 0,
    max_rounds: int = 1 << 20,
    descent: str = "auto",
):
    """Fold active constraints (lo, hi) into the carried forest table.

    Returns (minp int32[n+1], rounds int32); minp[x] = elimination
    position of x's parent (n = root/no parent). The active buffer is
    fixed-size: a retiring slot is reused in place by the constraint it
    displaces, so per-round work is O(len(lo)), independent of V.

    Vertex-space contract over the position-space core: inputs convert
    with three gathers (minp[order], pos[lo], pos[hi]), the result with
    one (P[pos]) — exact integer permutations, so results are identical.

    ``lift_levels`` = number of doubled ancestor tables per round
    (0 -> auto: ceil(log2(n+1)), enough to cover any chain in one round).
    ``descent`` = "exact" | "stream" | "auto" (see module docstring).
    """
    lift_levels, descent = _resolve(n, lift_levels, descent)
    body = _pos_round_body(n, lift_levels, descent)

    def cond(state):
        _, _, _, changed, rounds = state
        return changed & (rounds < max_rounds)

    state = _init_state(minp[order], pos[lo], pos[hi])
    _, _, P_f, _, rounds = lax.while_loop(cond, body, state)
    return P_f[pos], rounds


@partial(jax.jit, static_argnames=("n", "lift_levels", "segment_rounds",
                                   "descent"))
def fold_edges_segment(
    minp: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    pos: jax.Array,
    order: jax.Array,
    n: int,
    lift_levels: int = 0,
    segment_rounds: int = 32,
    descent: str = "auto",
):
    """Vertex-space wrapper of :func:`fold_segment_pos` (same state
    contract as before: returns (lo, hi, minp, changed, rounds) with
    vertex ids). The round dynamics commute with the pos/order
    permutation, so the returned state is bit-identical to the historic
    vertex-space implementation."""
    lift_levels, descent = _resolve(n, lift_levels, descent)
    body = _pos_round_body(n, lift_levels, descent)

    def cond(state):
        _, _, _, changed, rounds = state
        return changed & (rounds < segment_rounds)

    state = _init_state(minp[order], pos[lo], pos[hi])
    loP, hiP, P_f, changed, rounds = lax.while_loop(cond, body, state)
    return order[loP], order[hiP], P_f[pos], changed, rounds




@partial(jax.jit, static_argnames=("n", "size", "dedup"))
def compact_actives(lo: jax.Array, hi: jax.Array, n: int, size: int,
                    dedup: bool = False):
    """Pack the live constraints into a (size,) buffer, padding with the
    inert sentinel (n, n). Valid only when the live count <= size (the
    caller checks); slot identity is meaningless — only the SET of
    active constraints matters to the fixpoint (duplicates retire
    together and spawn identical displacements), so compaction and
    dedup are exact.

    ``dedup`` additionally drops duplicate (lo, hi) pairs first via one
    two-key sort: after a few rounds many slots have been rewritten to
    the same (ancestor, hi) constraint. The production driver sizes the
    target from the cheap pre-dedup live count, which every segment
    program returns in its packed stats vector (:func:`fold_segment_pos`)
    — a per-segment distinct count would cost a full-buffer sort each
    segment (measured: seconds at C=2^24 on the v5e). The live count is
    an upper bound on the distinct count, so the size is always
    sufficient. :func:`count_live_distinct` exists for
    diagnostics/tests."""
    if dedup:
        lo, hi = lax.sort((lo, hi), num_keys=2)
        dup = (lo == jnp.roll(lo, 1)) & (hi == jnp.roll(hi, 1))
        dup = dup.at[0].set(False)
        lo = jnp.where(dup, n, lo)
        hi = jnp.where(dup, n, hi)
    c = lo.shape[0]
    # fill slots index an appended sentinel row, so padding is inert
    sel = jnp.nonzero(lo != n, size=size, fill_value=c)[0]
    lo_ext = jnp.concatenate([lo, jnp.full(1, n, lo.dtype)])
    hi_ext = jnp.concatenate([hi, jnp.full(1, n, hi.dtype)])
    return lo_ext[sel], hi_ext[sel]


@partial(jax.jit, static_argnames=("n",))
def count_live_distinct(lo: jax.Array, hi: jax.Array, n: int):
    slo, shi = lax.sort((lo, hi), num_keys=2)
    dup = (slo == jnp.roll(slo, 1)) & (shi == jnp.roll(shi, 1))
    dup = dup.at[0].set(False)
    live = jnp.sum(slo != n)
    return live, live - jnp.sum(dup & (slo != n))




def _order_host(pos_host, n: int):
    """Inverse permutation of pos_host with the sentinel slot appended."""
    order_host = np.empty(n + 1, dtype=np.int64)
    order_host[np.asarray(pos_host)] = np.arange(n, dtype=np.int64)
    order_host[n] = n
    return order_host


def _host_tail_finish_pos(P, loP, hiP, n: int, size: int, pos_host):
    """Finish the fixpoint on HOST via the native core's Liu pass.

    The fixpoint tail is a displacement cascade — inherently sequential
    pointer-chasing that a vector machine resolves one link per round
    (measured: 6.8k tail rounds at RMAT-20 streamed in 4 chunks). The
    native C++ insertion resolves the whole cascade in O(total chain
    length) on host, so once the live count is small we pull the O(V)
    table + the compacted live constraints, extend the forest there, and
    push the table back. Same unique forest (cross-backend bit-identity
    is an existing test invariant)."""
    from sheep_tpu.core import native

    with obs.profiler_span("compact"):
        clo, chi = compact_actives(loP, hiP, n, size, dedup=True)
    # designed host-tail handoff: the compacted live constraints and
    # the O(V) table cross to the host exactly once per tail
    lo_np = obs.pull("host-tail-lo", clo)
    hi_np = obs.pull("host-tail-hi", chi)
    mask = lo_np != n
    pos_host = np.asarray(pos_host)
    order_host = _order_host(pos_host, n)
    edges = np.stack([order_host[lo_np[mask]], order_host[hi_np[mask]]],
                     axis=1)
    P_np = obs.pull("host-tail-table", P)  # the one O(V) pull
    pp = P_np[pos_host]   # vertex-indexed parent positions
    parent = np.where(pp < n, order_host[np.minimum(pp, n)],
                      NO_PARENT).astype(np.int64)
    parent = native.build_elim_tree(edges, pos_host, parent)
    newP = np.full(n + 1, n, dtype=np.int32)
    has = parent >= 0
    newP[pos_host[has]] = pos_host[parent[has]]
    return jnp.asarray(newP)


def host_tail_delta(P_snap, loP, hiP, n: int, pos_host):
    """Resolve a compacted fixpoint tail on HOST and return it as DELTA
    constraints instead of a replacement table.

    Same native Liu pass as :func:`_host_tail_finish_pos`, but the result
    is the set of (position, new_parent_position) pairs whose parent
    CHANGED — exactly the tree edges the resolution added. Injecting
    those pairs as ordinary actives into any later fold yields the same
    unique fixpoint (the forest is a function of the inserted constraint
    multiset; a resolved link is a derived tree edge of a sub-multiset,
    which is what :func:`merge_forests` folds), so the caller can run
    the native pass in a worker thread while the device folds the next
    chunk, and ship an O(changed) delta instead of the O(V) table push.

    Inputs must be HOST-safe snapshots (jax arrays are immutable, so the
    device arrays themselves are safe); everything here is numpy + the
    native core — no jax dispatch — making it executor-thread-friendly
    apart from the initial pulls."""
    from sheep_tpu.core import native

    lo_np = obs.pull("tail-overlap-lo", loP)
    hi_np = obs.pull("tail-overlap-hi", hiP)
    mask = lo_np != n
    pos_host = np.asarray(pos_host)
    order_host = _order_host(pos_host, n)
    edges = np.stack([order_host[lo_np[mask]], order_host[hi_np[mask]]],
                     axis=1)
    # O(V) pull overlapped with device work
    P_np = obs.pull("tail-overlap-table", P_snap)
    pp = P_np[pos_host]
    parent = np.where(pp < n, order_host[np.minimum(pp, n)],
                      NO_PARENT).astype(np.int64)
    # native.build_elim_tree writes into a contiguous int64 parent array
    # IN PLACE (and returns it) — diff against a snapshot, not the alias
    new_parent = native.build_elim_tree(edges, pos_host, parent.copy())
    ch = np.nonzero(new_parent != parent)[0]
    # links are only ever added or improved, never removed
    assert len(ch) == 0 or new_parent[ch].min() >= 0
    dlo = pos_host[ch].astype(np.int32)
    dhi = pos_host[new_parent[ch]].astype(np.int32)
    return dlo, dhi


def pad_actives_pow2(dlo, dhi, n: int, floor: int = 1 << 14):
    """Pad host (dlo, dhi) constraint arrays to a power-of-two length
    with the inert (n, n) sentinel so injected carries come from a small
    set of static shapes (one compile per bucket, not per delta)."""
    size = pow2_at_least(max(1, len(dlo)), floor=floor)
    out_lo = np.full(size, n, dtype=np.int32)
    out_hi = np.full(size, n, dtype=np.int32)
    out_lo[: len(dlo)] = dlo
    out_hi[: len(dhi)] = dhi
    return jnp.asarray(out_lo), jnp.asarray(out_hi)


class TailOverlap:
    """Worker-thread host-tail pipeline shared by the tpu backend and the
    tuning tool: submit compacted tails to :func:`host_tail_delta`, drain
    finished resolutions, and hand them back as padded injection carries.

    Use as a context manager so the single worker thread (and any
    in-flight O(V) pull) is released even when the driving loop raises —
    a leaked non-daemon thread blocks interpreter exit until its pending
    job finishes, which on a wedged device link means a hang instead of
    a fast failure."""

    def __init__(self, n: int, pos_host):
        from concurrent.futures import ThreadPoolExecutor

        self.n = n
        self.pos_host = pos_host
        self._executor = ThreadPoolExecutor(max_workers=1)
        self._pending: list = []   # in-flight futures, FIFO
        self._deltas: list = []    # resolved (dlo, dhi) awaiting injection

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._executor.shutdown(wait=True)
        return False

    def submit(self, P, loP, hiP) -> None:
        """Queue a compacted live tail (device arrays are immutable, so
        the P snapshot is safe to pull from the worker thread). The
        worker runs in the caller's context: its pulls and native wall
        count into the caller's stats scope."""
        import contextvars

        self._pending.append(self._executor.submit(
            contextvars.copy_context().run, host_tail_delta, P, loP, hiP,
            self.n, self.pos_host))

    def drain(self, block: bool) -> None:
        while self._pending and (block or self._pending[0].done()):
            d = self._pending.pop(0).result()
            if len(d[0]):
                self._deltas.append(d)

    def take_inject(self):
        """All drained deltas as one padded (loP, hiP) carry, or None."""
        if not self._deltas:
            return None
        dlo = np.concatenate([d[0] for d in self._deltas])
        dhi = np.concatenate([d[1] for d in self._deltas])
        self._deltas.clear()
        return pad_actives_pow2(dlo, dhi, self.n)


def _fold_adaptive_pos_impl(*args, **kwargs):
    """:func:`_fold_adaptive_pos_impl_body` under the SHEEP_SANITIZE
    stray-sync guard: the adaptive driver's only designed host reads
    are the per-segment packed sv pull and the host-tail handoff —
    any other implicit device->host conversion in the loop raises."""
    with sanitize.guard("adaptive-fold"):
        return _fold_adaptive_pos_impl_body(*args, **kwargs)


def _fold_adaptive_pos_impl_body(
    P: jax.Array,
    loP: jax.Array,
    hiP: jax.Array,
    n: int,
    lift_levels: int,
    segment_rounds: int,
    descent: str,
    max_rounds: int,
    small_size: int,
    small_jumps: int,
    host_tail: bool,
    host_tail_threshold: int,
    warm_schedule: tuple,
    pos_host,
    stats,
    carry_out: bool,
    stale_tables: bool = True,
    stale_reuse: int = 1,
):
    """Shared adaptive-fixpoint loop; returns (P, total, carry) where
    ``carry`` is None (converged / host-finished) or a compacted
    (carry_loP, carry_hiP) of the still-live constraints (carry_out mode,
    see :func:`fold_edges_adaptive_pos_carry`).

    ``stale_reuse`` = full segments per lifting-stack rebuild (exact
    descent with stale_tables only). 1 = the landed per-segment
    hoisting; K > 1 reuses one stack across K segments
    (:func:`fold_segment_pos_stale`), cutting the (L-1) x V squaring
    gathers — the dominant V-term — by a further factor K at the price
    of weaker (never unsound) jumps between rebuilds."""
    from sheep_tpu.core import native

    # the CLI validates R:L >= 1 at parse time; validate the Python API
    # too — _resolve silently promotes levels <= 0 to FULL depth, the
    # opposite of a cheap warm round, so a malformed entry must fail
    # loudly here rather than quietly invert the schedule's intent
    for entry in warm_schedule:
        wr, wl = entry
        if wr < 1 or wl < 1:
            raise ValueError(
                f"warm_schedule entries must be (rounds >= 1, "
                f"lift_levels >= 1); got {tuple(entry)!r}")

    use_host_tail = host_tail and native.available() and pos_host is not None
    if stats is None:
        stats = {}
    _seed_ms_counters(stats)
    total = 0
    size = int(loP.shape[0])
    if host_tail_threshold <= 0:
        # auto: hand off once <= size/8 constraints remain (min 2^16) —
        # the cpu-jax sweet spot; on a real chip device rounds are far
        # cheaper relative to the host pass, so callers may lower it
        host_tail_threshold = max(1 << 16, size // 8)
    warm = list(warm_schedule)
    lift_stack = None
    segs_on_stack = 0

    def t_add(key: str, dt: float) -> None:
        # wall-clock attribution per segment KIND. Dispatches are async,
        # but each loop iteration ends in exactly ONE device pull (the
        # sv sync below), so iteration wall == that segment's true cost
        # — this is what decomposed the round-5 bad-link capture's
        # 227.8 s build (68 s device floor vs per-segment sync/transfer
        # tax; BASELINE.md round-5 capture section). Accumulate
        # UNROUNDED: consumers round at read time — a per-add 3-decimal
        # quantum over hundreds of segments can push sum(t_*) past the
        # measured wall on fast machines
        stats[key] = stats.get(key, 0.0) + dt

    prev_ready = None  # when the previous segment's sv pull completed
    while True:
        t0 = time.perf_counter()
        if prev_ready is not None:
            # host decision window between a stats pull and the next
            # fixpoint dispatch — an upper bound on device idle (the
            # rare dedup compactions dispatch device work inside it).
            # This driver is synchronous by design (its host decisions
            # need the stats); the in-flight batched pipeline is what
            # removes the window
            _t_ms(stats, "device_gap_ms", t0 - prev_ready)
        # the segment's dispatch, as a span on the profiler's trace
        fold_sp = obs.profiler_span("fold").start()
        if warm and size > small_size:
            wrounds, wlevels = warm.pop(0)
            seg = min(wrounds, max_rounds - total)
            loP, hiP, P, sv = fold_segment_pos(
                P, loP, hiP, n, lift_levels=wlevels,
                segment_rounds=seg, descent="stream")
            stats["warm_segments"] = stats.get("warm_segments", 0) + 1
            t_key = "t_warm_s"
        elif size > small_size:
            seg = min(segment_rounds, max_rounds - total)
            rl, rd = _resolve(n, lift_levels, descent)
            if stale_tables and rd == "exact" and seg > 1:
                # exact descent with per-SEGMENT (stale) tables: saves
                # (seg-1)/seg of the L x V squaring gathers — the
                # round's dominant V-term (same unique fixpoint; see
                # fold_segment_pos_hoisted)
                if stale_reuse > 1:
                    if lift_stack is None or segs_on_stack >= stale_reuse:
                        # release the old stack BEFORE building the new
                        # one: both alive at once would transiently
                        # double the (EXACT_TABLE_BYTES-scale) footprint
                        lift_stack = None
                        lift_stack = build_lift_tables(P, n, rl)
                        segs_on_stack = 0
                        stats["stack_rebuilds"] = \
                            stats.get("stack_rebuilds", 0) + 1
                    loP, hiP, P, sv = fold_segment_pos_stale(
                        P, loP, hiP, lift_stack, n, segment_rounds=seg)
                    segs_on_stack += 1
                else:
                    loP, hiP, P, sv = fold_segment_pos_hoisted(
                        P, loP, hiP, n, lift_levels=rl, segment_rounds=seg)
            else:
                loP, hiP, P, sv = fold_segment_pos(
                    P, loP, hiP, n, lift_levels=lift_levels,
                    segment_rounds=seg, descent=descent)
            stats["full_segments"] = stats.get("full_segments", 0) + 1
            t_key = "t_full_s"
        else:
            seg = min(max(segment_rounds, 64), max_rounds - total)
            loP, hiP, P, sv = fold_segment_small_pos(
                P, loP, hiP, n, jumps=small_jumps, segment_rounds=seg)
            stats["small_segments"] = stats.get("small_segments", 0) + 1
            t_key = "t_small_s"
        fold_sp.end()
        # ONE device pull per segment for all three control scalars
        # (each pull is a full host/device round-trip); the
        # duplicate collapse happens inside the dedup compactions, which
        # run rarely — a per-segment distinct count would cost a
        # full-buffer two-key sort every segment (measured: seconds at
        # C=2^24 on the v5e, swamping the rounds it saved)
        changed, r, live = (int(x) for x in obs.pull(
            "adaptive-sv-pull", sv, host_blocked=stats))
        prev_ready = time.perf_counter()
        # dispatch-count attribution: one host->device SYNC per segment
        # is this driver's cost shape (each sv pull is a full link
        # round-trip); the batched dispatch (fold_segments_batch) exists
        # to amortize exactly this counter
        stats["host_syncs"] = stats.get("host_syncs", 0) + 1
        t_add(t_key, time.perf_counter() - t0)
        total += r
        stats["device_rounds"] = stats.get("device_rounds", 0) + r
        # live == 0 is the fixpoint too (the table only changes through
        # a retiring slot): return immediately rather than paying an
        # empty host tail / an all-dead carry buffer / one extra
        # confirming segment
        if not changed or live == 0 or total >= max_rounds:
            return P, total, None
        if live <= host_tail_threshold:
            if carry_out:
                # hand the still-live tail to the NEXT chunk's fold
                # instead of the host: the displaced cascade keeps
                # climbing inside the next chunk's (efficient, wide)
                # rounds, and the per-chunk O(V) table round-trip +
                # sequential native pass disappear. Sound because the
                # fixpoint is a property of the inserted constraint
                # multiset, not of when each constraint resolves.
                stats["carried_tails"] = stats.get("carried_tails", 0) + 1
                stats["carried_live"] = stats.get("carried_live", 0) + live
                cap = min(pow2_at_least(live, floor=1 << 14), size)
                with obs.profiler_span("compact"):
                    carry = compact_actives(loP, hiP, n, cap, dedup=True)
                return P, total, carry
            if use_host_tail:
                stats["host_tails"] = stats.get("host_tails", 0) + 1
                stats["host_tail_live"] = \
                    stats.get("host_tail_live", 0) + live
                # size the pull by the live count, not the threshold:
                # the tail ships two O(size) arrays over the host link
                pull = pow2_at_least(live, floor=1 << 14)
                t0 = time.perf_counter()
                with obs.profiler_span("host_tail"):
                    out = _host_tail_finish_pos(P, loP, hiP, n,
                                                min(pull, size), pos_host)
                t_add("t_host_tail_s", time.perf_counter() - t0)
                return out, total, None
        if size > small_size and live <= size // 2:
            new_size = pow2_at_least(2 * live, floor=small_size)
            if new_size < size:
                with obs.profiler_span("compact"):
                    loP, hiP = compact_actives(loP, hiP, n, new_size,
                                               dedup=True)
                size = new_size
                stats["compactions"] = stats.get("compactions", 0) + 1


def fold_edges_adaptive_pos(
    P: jax.Array,
    loP: jax.Array,
    hiP: jax.Array,
    n: int,
    lift_levels: int = 0,
    segment_rounds: int = 2,
    descent: str = "auto",
    max_rounds: int = 1 << 20,
    small_size: int = 1 << 14,
    small_jumps: int = 16,
    host_tail: bool = True,
    host_tail_threshold: int = 0,
    warm_schedule: tuple = (),
    pos_host=None,
    stats=None,
    stale_tables: bool = True,
    stale_reuse: int = 1,
):
    """Host-driven fixpoint with active-set compaction and a host-finished
    tail — same unique forest as :func:`fold_edges`, far less work.
    Everything stays in position space; callers carry P across chunks and
    convert to the vertex-space minp encoding only at phase boundaries.

    Measured motivation (RMAT-18, cpu-jax): 106 of 122 rounds had < 4k
    live constraints out of a 4.2M buffer, so >85% of build time was
    climbing dead slots and rebuilding lifting tables for them; at
    RMAT-20 the tail cascade alone was 6.8k rounds. Schedule:

    - warm phase: ``warm_schedule`` = ((rounds, lift_levels), ...)
      segments run FIRST with few lifting levels — on the real chip a
      full-buffer round's cost is ~linear in lift_levels x buffer width,
      and the bulk of the buffer retires in the first rounds without
      needing long jumps, so cheap warm rounds + compaction shrink the
      buffer before any full-depth round pays for it
    - full mode: lifting-table segments on the current buffer
    - after each segment, if live count <= size/2, compact the buffer to
      max(small_size, 2*live) rounded up to a power of two (each size is
      one extra compiled program; sizes shrink geometrically, so at most
      ~log4(C) programs exist)
    - once live <= ``host_tail_threshold`` and the native core is
      available, finish on host (:func:`_host_tail_finish_pos`): the
      displacement cascade is sequential work the CPU does in O(chain),
      for one O(V) table round-trip per chunk
    - fallback (no native core): jump-mode rounds at ``small_size`` —
      O(C') gathers per round, independent of V
    """
    P, total, _ = _fold_adaptive_pos_impl(
        P, loP, hiP, n, lift_levels, segment_rounds, descent, max_rounds,
        small_size, small_jumps, host_tail, host_tail_threshold,
        warm_schedule, pos_host, stats, carry_out=False,
        stale_tables=stale_tables, stale_reuse=stale_reuse)
    return P, total


def fold_edges_adaptive_pos_carry(
    P: jax.Array,
    loP: jax.Array,
    hiP: jax.Array,
    n: int,
    **opts,
):
    """Carry-out variant of :func:`fold_edges_adaptive_pos` for
    intermediate stream chunks: instead of host-finishing the tail, the
    still-live constraints are compacted and RETURNED as
    ``(P, rounds, (carry_loP, carry_hiP))`` for the caller to prepend to
    the next chunk's actives (empty carry when converged). Eliminates the
    per-chunk O(V) device->host->device table round-trip and the
    serialized native tail pass; only the stream's FINAL fold (on the
    last carry, via the plain entry point) pays one host tail. The final
    forest is identical — the fixpoint is determined by the inserted
    constraint multiset, not by when each constraint resolves
    (tests/test_tpu_ops.py pins streaming-vs-batch equality with carry
    on)."""
    args = (opts.pop("lift_levels", 0), opts.pop("segment_rounds", 2),
            opts.pop("descent", "auto"), opts.pop("max_rounds", 1 << 20),
            opts.pop("small_size", 1 << 14), opts.pop("small_jumps", 16),
            opts.pop("host_tail", True), opts.pop("host_tail_threshold", 0),
            opts.pop("warm_schedule", ()), opts.pop("pos_host", None),
            opts.pop("stats", None))
    stale = opts.pop("stale_tables", True)
    reuse = opts.pop("stale_reuse", 1)
    if opts:  # reject typos BEFORE the (potentially minutes-long) fold
        raise TypeError(f"unknown options: {sorted(opts)}")
    P, total, carry = _fold_adaptive_pos_impl(P, loP, hiP, n, *args,
                                              carry_out=True,
                                              stale_tables=stale,
                                              stale_reuse=reuse)
    if carry is None:
        carry = (jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.int32))
    return P, total, carry


def fold_edges_adaptive(
    minp: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    pos: jax.Array,
    order: jax.Array,
    n: int,
    lift_levels: int = 0,
    segment_rounds: int = 2,
    descent: str = "auto",
    max_rounds: int = 1 << 20,
    small_size: int = 1 << 14,
    small_jumps: int = 16,
    host_tail: bool = True,
    host_tail_threshold: int = 0,
    warm_schedule: tuple = (),
    pos_host=None,
    stats=None,
):
    """Vertex-space wrapper of :func:`fold_edges_adaptive_pos` (one
    conversion each way; same unique forest)."""
    from sheep_tpu.core import native

    if host_tail and pos_host is None and native.available():
        # only pulled when a host tail can actually run — this is an
        # O(V) d2h transfer
        pos_host = obs.pull("pos-host", pos[:n])
    P, total = fold_edges_adaptive_pos(
        minp[order], pos[lo], pos[hi], n, lift_levels=lift_levels,
        segment_rounds=segment_rounds, descent=descent,
        max_rounds=max_rounds, small_size=small_size,
        small_jumps=small_jumps, host_tail=host_tail,
        host_tail_threshold=host_tail_threshold,
        warm_schedule=warm_schedule, pos_host=pos_host, stats=stats)
    return P[pos], total


def fold_edges_segmented(
    minp: jax.Array,
    lo: jax.Array,
    hi: jax.Array,
    pos: jax.Array,
    order: jax.Array,
    n: int,
    lift_levels: int = 0,
    segment_rounds: int = 32,
    descent: str = "auto",
    max_rounds: int = 1 << 20,
    on_segment=None,
):
    """Host-driven fixpoint: loop :func:`fold_edges_segment` until no slot
    changes. Same result as :func:`fold_edges`; one short device execution
    per ``segment_rounds`` rounds. ``on_segment(total_rounds)`` is called
    after each segment (progress/diagnostics hook)."""
    total = 0
    with sanitize.guard("segmented-fold"):
        while True:
            # never run past max_rounds: the tail segment shrinks to
            # the remaining budget so the result matches
            # fold_edges(max_rounds=...) exactly (one extra compile at
            # most, for the tail size)
            seg = min(segment_rounds, max_rounds - total)
            lo, hi, minp, changed, r = fold_edges_segment(
                minp, lo, hi, pos, order, n, lift_levels=lift_levels,
                segment_rounds=seg, descent=descent)
            # the designed per-segment control pulls of this driver
            total += int(obs.pull("segmented-pull", r))
            done = not bool(obs.pull("segmented-pull", changed))
            if on_segment is not None:
                on_segment(total)
            if done or total >= max_rounds:
                return minp, total


def elim_fixpoint(
    lo: jax.Array,
    hi: jax.Array,
    pos: jax.Array,
    order: jax.Array,
    n: int,
    lift_levels: int = 0,
    max_rounds: int = 1 << 20,
    descent: str = "auto",
):
    """Elimination forest of an oriented constraint set, from scratch —
    :func:`fold_edges` seeded with the empty table."""
    return fold_edges(jnp.full(n + 1, n, dtype=jnp.int32), lo, hi, pos,
                      order, n, lift_levels=lift_levels,
                      max_rounds=max_rounds, descent=descent)


def tree_edges_from_parent(parent_pos: jax.Array, order: jax.Array, n: int):
    """parent_pos (minp) int32[n+1] -> (lo, hi) arrays of the forest edges,
    inactive slots as (n, n). lo = vertex, hi = its parent."""
    v = jnp.arange(n + 1, dtype=jnp.int32)
    has = parent_pos < n
    lo = jnp.where(has, v, n)
    hi = jnp.where(has, order[parent_pos], n)
    return lo, hi


@partial(jax.jit, static_argnames=("n", "lift_levels"))
def build_chunk_step(
    parent_pos: jax.Array,
    chunk: jax.Array,
    pos: jax.Array,
    order: jax.Array,
    n: int,
    lift_levels: int = 0,
):
    """One streaming step: fold a (C, 2) edge chunk into the carried forest.

    parent_pos is the minp encoding (int32[n+1], n = no parent). The
    carried forest stays in the table — only the chunk's C edges are
    active (plus in-place displacement reuse), so per-chunk transients
    are O(C) and per-round work is independent of V. Device memory is
    O(V) tables + O(C) actives plus a bounded lifting-table stack (at
    most ``EXACT_TABLE_BYTES``; past that the stream descent keeps it
    one table) — the edge stream never materializes.
    """
    clo, chi = orient_edges(chunk, pos, n)
    return fold_edges(parent_pos, clo, chi, pos, order, n,
                      lift_levels=lift_levels)


def build_chunk_step_segmented(
    parent_pos: jax.Array,
    chunk: jax.Array,
    pos: jax.Array,
    order: jax.Array,
    n: int,
    lift_levels: int = 0,
    segment_rounds: int = 32,
):
    """:func:`build_chunk_step` with host-bounded device executions
    (:func:`fold_edges_segmented`) — the single-device streaming path uses
    this so no one accelerator call runs unboundedly long."""
    clo, chi = orient_edges(chunk, pos, n)
    return fold_edges_segmented(parent_pos, clo, chi, pos, order, n,
                                lift_levels=lift_levels,
                                segment_rounds=segment_rounds)


def build_chunk_step_adaptive(
    parent_pos: jax.Array,
    chunk: jax.Array,
    pos: jax.Array,
    order: jax.Array,
    n: int,
    lift_levels: int = 0,
    segment_rounds: int = 2,
    warm_schedule: tuple = (),
    pos_host=None,
    stats=None,
    **fold_opts,
):
    """:func:`build_chunk_step` via :func:`fold_edges_adaptive`
    (compaction + host-finished tail) — same unique forest, bounded
    device executions, and the sequential displacement cascade runs on
    host instead of one link per device round."""
    clo, chi = orient_edges(chunk, pos, n)
    return fold_edges_adaptive(parent_pos, clo, chi, pos, order, n,
                               lift_levels=lift_levels,
                               segment_rounds=segment_rounds,
                               warm_schedule=warm_schedule,
                               pos_host=pos_host, stats=stats, **fold_opts)


@partial(jax.jit, static_argnames=("n",))
def orient_edges_pos(edges: jax.Array, pos: jax.Array, n: int):
    """(C,2) int32 edges -> oriented elimination POSITIONS (loP, hiP)
    with loP < hiP; self-loops and out-of-range/padding endpoints become
    the inert sentinel (n, n). pos is injective over vertices with
    pos[n] = n, so equal positions <=> same vertex or both padding."""
    e = edges.astype(jnp.int32)
    u = jnp.clip(e[:, 0], 0, n)
    v = jnp.clip(e[:, 1], 0, n)
    pu, pv = pos[u], pos[v]
    lo = jnp.minimum(pu, pv)
    hi = jnp.maximum(pu, pv)
    bad = lo == hi
    lo = jnp.where(bad, n, lo)
    hi = jnp.where(bad, n, hi)
    return lo, hi


def build_chunk_step_adaptive_pos(
    P: jax.Array,
    chunk: jax.Array,
    pos: jax.Array,
    pos_host,
    n: int,
    lift_levels: int = 0,
    segment_rounds: int = 2,
    warm_schedule: tuple = (),
    stats=None,
    **fold_opts,
):
    """One streaming step on the POSITION-SPACE carried table P — the
    single-device production fold: the backend carries P across chunks
    and converts to/from the vertex-space minp encoding only at phase
    (and checkpoint) boundaries, so the steady-state loop runs zero
    vertex<->position conversions. Extra ``fold_opts`` (e.g.
    host_tail_threshold) forward to :func:`fold_edges_adaptive_pos`.

    ``carry`` = (loP, hiP) actives carried over from the previous
    chunk's fold (prepended to this chunk's oriented actives);
    ``carry_out=True`` selects the carry-returning variant — the step
    then returns (P, rounds, carry) instead of (P, rounds)."""
    carry = fold_opts.pop("carry", None)
    carry_out = fold_opts.pop("carry_out", False)
    loP, hiP = orient_edges_pos(chunk, pos, n)
    if carry is not None and int(carry[0].shape[0]):
        loP = jnp.concatenate([loP, carry[0]])
        hiP = jnp.concatenate([hiP, carry[1]])
    fold = fold_edges_adaptive_pos_carry if carry_out \
        else fold_edges_adaptive_pos
    return fold(P, loP, hiP, n, lift_levels=lift_levels,
                segment_rounds=segment_rounds,
                warm_schedule=warm_schedule,
                pos_host=pos_host, stats=stats,
                **fold_opts)


@partial(jax.jit, static_argnames=("n", "lift_levels"))
def merge_forests(
    a_pos: jax.Array, b_pos: jax.Array, pos: jax.Array, order: jax.Array,
    n: int, lift_levels: int = 0,
):
    """Associative merge of two forests in minp encoding (SURVEY.md §2 #6):
    fold B's tree edges into A's table — T(A ∪ B) = T(T(A) ∪ T(B)).

    This is the cross-shard/device reduction combiner; the butterfly in
    ``parallel/pipeline.py`` ships each forest as either the O(V) table
    or compacted boundary pairs."""
    blo, bhi = tree_edges_from_parent(b_pos, order, n)
    minp, _ = fold_edges(a_pos, blo, bhi, pos, order, n,
                         lift_levels=lift_levels)
    return minp


def minp_to_parent(minp, order, n):
    """minp encoding -> parent array (int64[n], -1 for roots) on host."""
    minp = obs.pull("split-table", minp[:n])
    order = obs.pull("split-order", order)
    parent = np.where(minp < n, order[np.minimum(minp, n)], NO_PARENT)
    return parent.astype(np.int64)


def parent_to_minp(parent, pos, n):
    """parent array (int[n], -1 roots) -> device minp encoding int32[n+1]."""
    parent = np.asarray(parent)
    pos = obs.pull("pos-host", pos)
    minp = np.full(n + 1, n, dtype=np.int32)
    has = parent >= 0
    minp[:n][has] = pos[parent[has]]
    return jnp.asarray(minp)
