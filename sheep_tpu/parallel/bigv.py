"""Vertex-sharded build for graphs whose tables exceed one chip
(SURVEY.md §7 hard part #2; BASELINE.md eval config 5, RMAT-30 class).

The standard sharded pipeline replicates the O(V) pos/order tables and
keeps one forest per device, so 8 chips raise edge throughput but not the
vertex ceiling (2^29 on 16 GiB). This pipeline shards every vertex-indexed
table into contiguous blocks of B = ceil((V+1)/D) rows — device d owns
global rows [dB, (d+1)B) — cutting per-device table memory to O(V/D):
RMAT-30 (V=2^30) fits a v5e-8 slice at ~2.6 GiB/chip.

With the displacement fixpoint (ops/elim.py) the build needs no partial
trees and no merge at all: there is ONE distributed forest table, and all
devices' active constraints fold into it concurrently through routed
collective ops. Like the single-chip path, the fixpoint runs in
POSITION SPACE: the forest table P is indexed by elimination position
(block-sharded by position), actives are (loP, hiP) position pairs, and
a climb step is one routed P-lookup — the vertex-space formulation
needed a second routed order[] lookup per step and carried the vertex
id alongside, so position space halves the climb collectives AND drops
a third of the active-buffer traffic. Per fixpoint round (inside
shard_map over the ``shards`` axis):

  1. routed scatter-min  — all_gather the (loP, hiP) requests; each
     owner folds the requests hitting its block into its P shard and
     answers (pre-round, post-round) parent positions; answers ride one
     all_to_all back and combine with jnp.min (non-owners answer the
     sentinel n = +inf).
  2. routed gather       — P[p] lookups for the climb (``jumps``
     single-step climbs per round instead of the single-chip path's
     binary-lifting tables, which would be V-sized).
  3. local rewrite       — retire / displace-in-place / climb, exactly
     the single-chip displacement rules; liveness is a psum, so the
     while_loop terminates collectively.

The elimination order is computed on HOST (one stable numpy argsort
over the degree table — hosts hold hundreds of GB; one sort per run,
amortized
over the whole stream) and only the pos block shard is pushed to
devices (position space needs no device-side order table). The split
likewise runs on host over the O(V) parent array (native C++).
Degrees accumulate into a block-sharded table via the same
routed scatter pattern, and scoring resolves part lookups against a
block-sharded assignment table with the routed gather — NO vertex-indexed
device state is replicated anywhere in the pipeline, so per-device memory
really is O(V/D) tables + O(D * chunk) routing buffers. (Host memory is
O(V): the degree fold, sort, and split run there by design.)

The fixpoint loop is driven from the HOST in bounded segments
(``segment_rounds`` rounds per device execution): long single accelerator
executions are what crash TPU worker watchdogs, and the collective
``live`` count makes every device (and every process) agree on the
segment boundary, so the lockstep host loop is safe under shard_map.

Everything is static-shape: routing buffers are (D, Q) for Q actives, so
there are no per-destination capacity constants and no overflow paths —
the cost is shipping D*Q words per collective, the standard trade for
hub-skewed (power-law) graphs where per-owner request counts are
unboundedly uneven.
"""

from __future__ import annotations

import os
import time
from collections import OrderedDict
from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from sheep_tpu import obs
from sheep_tpu.analysis import sanitize
from sheep_tpu.io.devicestream import is_device_stream
from sheep_tpu.ops.elim import pow2_at_least
from sheep_tpu.parallel.mesh import SHARD_AXIS, shard_map


class BigVPipeline:
    """Compiled vertex-sharded pipeline for a fixed (n, chunk_edges, mesh).

    ``jumps`` = single-step parent climbs per TAIL-phase round (bulk
    rounds use stream-descent lifting with ``lift_levels`` tables — see
    ``_make_fold_lift``); more tail jumps = fewer tail rounds at ~flat
    collective bytes.
    """

    def __init__(self, n: int, chunk_edges: int, mesh, jumps: int = 128,
                 max_rounds: int = 1 << 20, segment_rounds: int = 16,
                 dedup_compact: bool = True, lift_levels: int = 0,
                 hoist_bytes: Optional[int] = None):
        d = mesh.devices.size
        self.n = n
        self.cs = chunk_edges
        self.mesh = mesh
        self.n_devices = d
        self.jumps = jumps
        self.B = -(-(n + 1) // d)  # owned rows per device
        self.rows = d * self.B      # padded global table length
        self.segment_rounds = segment_rounds
        # multi-host: same collectives ride DCN; this process owns
        # n_local contiguous mesh rows (jax.devices() orders by process),
        # so its local span of any block-sharded table is
        # [proc * n_local * B, (proc+1) * n_local * B)
        self.dedup_compact = dedup_compact
        # bulk-phase stream-descent lifting depth (0 = auto: enough to
        # cover any ancestor chain in one round, like single-chip)
        self.lift_levels = lift_levels if lift_levels > 0 \
            else max(1, int(n).bit_length())
        # hoisted-stack HBM budget per device (stale lifting tables,
        # _make_fold_lift_hoisted): each hoisted level keeps one B-row
        # int32 block alive for the whole segment. Default 0 = per-round
        # squaring: MEASURED at RMAT-16/D=8 (tools/bigv_collectives.py),
        # hoisting LOST — q_rounds 1.06M -> 2.1M, 1540 -> 2651 MB/device
        # — because 16 rounds of stack staleness delay the live-set
        # collapse at bulk width, while the squaring term it amortizes
        # is only V words/round (small next to D*Q lookups when V << Q).
        # The trade can only reverse in the V-dominant regime (B >> Q,
        # the RMAT-30 class); enable there explicitly via hoist_bytes /
        # SHEEP_BIGV_HOIST_BYTES and re-measure (BASELINE.md bigv).
        # env is a fallback for the DEFAULT only; an explicit ctor value
        # always wins (review finding: an exported experiment var must
        # not silently override TpuBigVBackend(hoist_bytes=X))
        import os as _os

        self.hoist_bytes = hoist_bytes if hoist_bytes is not None \
            else int(_os.environ.get("SHEEP_BIGV_HOIST_BYTES", "0"))
        self.hoist_levels = min(self.lift_levels - 1,
                                max(0, self.hoist_bytes // (4 * self.B)))
        self.procs = len({dev.process_index for dev in mesh.devices.flat})
        self.proc = jax.process_index() if self.procs > 1 else 0
        self.n_local = (sum(1 for dev in mesh.devices.flat
                            if dev.process_index == jax.process_index())
                        if self.procs > 1 else d)
        if self.procs > 1 and self.n_local * self.procs != d:
            raise ValueError("uneven devices per process not supported")

        self.shard = NamedSharding(mesh, P(SHARD_AXIS))        # (rows,)
        self.batch_sharding = NamedSharding(mesh, P(SHARD_AXIS, None, None))
        self.repl = NamedSharding(mesh, P())

        n_, B, D = self.n, self.B, d

        # ---- routed primitives (shard_map bodies) ------------------------

        def _lookup(table_local, q):
            """table[q] for arbitrary global ids q (Q,) against a
            block-sharded table; sentinel-safe (answers n for q >= rows
            handled by the ownership mask; q == n hits the padded
            sentinel row, which every shard keeps at value n)."""
            gq = lax.all_gather(q, SHARD_AXIS)          # (D, Q)
            me = lax.axis_index(SHARD_AXIS)
            local = gq - me * B
            ok = (local >= 0) & (local < B)
            part = jnp.where(ok, table_local[jnp.clip(local, 0, B - 1)],
                             jnp.int32(n_))
            mine = lax.all_to_all(part, SHARD_AXIS, 0, 0)
            return jnp.min(mine, axis=0)                # (Q,)

        def _scatter_min(table_local, lo, val):
            """Fold (lo -> val) requests from EVERY device into the
            distributed table; returns (new_table_local, old, new) where
            old/new are the pre-/post-round parent positions at each of
            THIS device's requests."""
            glo = lax.all_gather(lo, SHARD_AXIS)        # (D, Q)
            gval = lax.all_gather(val, SHARD_AXIS)
            me = lax.axis_index(SHARD_AXIS)
            local = glo - me * B
            ok = (local >= 0) & (local < B)
            idx = jnp.where(ok, local, B)               # B = dropped
            new_local = table_local.at[idx.ravel()].min(
                gval.ravel(), mode="drop")
            lidx = jnp.clip(local, 0, B - 1)
            old_part = jnp.where(ok, table_local[lidx], jnp.int32(n_))
            new_part = jnp.where(ok, new_local[lidx], jnp.int32(n_))
            old = jnp.min(lax.all_to_all(old_part, SHARD_AXIS, 0, 0), axis=0)
            new = jnp.min(lax.all_to_all(new_part, SHARD_AXIS, 0, 0), axis=0)
            return new_local, old, new

        # ---- degrees: block-sharded accumulator, routed scatter-add -----
        # (same ownership routing as _scatter_min; semantics match
        # ops/degrees.degree_chunk: clip to [0, n], slot n absorbs padding,
        # self-loops count twice, one scatter per endpoint column — the
        # flattened (2C,) form compiled for 219 s for a described 2x2
        # v5e mesh at V = 2^22, C = 2^20, the columns in 2.5 s; PR 21)
        @partial(jax.jit, out_shardings=self.shard)
        def deg_zeros():
            return jnp.zeros(self.rows, jnp.int32)

        @partial(jax.jit,
                 in_shardings=(self.shard, self.batch_sharding),
                 out_shardings=self.shard)
        def deg_step(deg_sh, batch):
            def f(deg_local, chunk_local):
                me = lax.axis_index(SHARD_AXIS)
                for col in (chunk_local[0][:, 0], chunk_local[0][:, 1]):
                    ids = jnp.clip(col, 0, n_).astype(jnp.int32)
                    local = lax.all_gather(ids, SHARD_AXIS) - me * B
                    idx = jnp.where((local >= 0) & (local < B), local, B)
                    deg_local = deg_local.at[idx.ravel()].add(1,
                                                              mode="drop")
                return deg_local
            return shard_map(f, mesh=mesh,
                             in_specs=(P(SHARD_AXIS),
                                       P(SHARD_AXIS, None, None)),
                             out_specs=P(SHARD_AXIS))(deg_sh, batch)

        # ---- the routed displacement fixpoint ---------------------------
        act = NamedSharding(mesh, P(SHARD_AXIS, None))  # (D, Q) actives

        @partial(jax.jit,
                 in_shardings=(self.shard, self.batch_sharding),
                 out_shardings=(act, act))
        def orient_step(pos_sh, batch):
            """Resolve a batch's endpoints to oriented POSITION-PAIR
            constraints (loP, hiP); loop detection is local
            (loP == hiP -> inert)."""
            def f(pos_local, chunk_local):
                chunk = chunk_local[0]
                u = jnp.clip(chunk[:, 0], 0, n_)
                v = jnp.clip(chunk[:, 1], 0, n_)
                pu = _lookup(pos_local, u)
                pv = _lookup(pos_local, v)
                lo = jnp.minimum(pu, pv).astype(jnp.int32)
                hi = jnp.maximum(pu, pv).astype(jnp.int32)
                bad = (pu == pv) | (pu == n_) | (pv == n_)
                lo = jnp.where(bad, n_, lo)
                hi = jnp.where(bad, n_, hi)
                return lo[None], hi[None]
            return shard_map(
                f, mesh=mesh,
                in_specs=(P(SHARD_AXIS), P(SHARD_AXIS, None, None)),
                out_specs=(P(SHARD_AXIS, None),) * 2)(pos_sh, batch)

        seg_ = self.segment_rounds

        def _make_fold(climb, prepare=None):
            """Segment program factory: at most ``segment_rounds`` routed
            fixpoint rounds in one device execution; the psum'd live
            count is the collective continue signal, identical on every
            device/process, so the host loop segment boundaries stay in
            lockstep. Retire/displace semantics match the single-chip
            _pos_small_round_body with the table lookups routed; the ONE
            varying piece is ``climb(ctx, P_l, cur, hi_) -> cur`` — built
            by :func:`_make_fold_seg` (fixed jump count) or
            :func:`_make_fold_lift` (stream-descent lifting) so the two
            kernels cannot drift apart. ``prepare(P_local) -> ctx`` runs
            ONCE per segment before the round loop (hoisted lifting
            stacks); its outputs enter the while_loop as constants."""

            @partial(jax.jit,
                     in_shardings=(self.shard, act, act),
                     out_shardings=(self.shard, act, act, self.repl,
                                    self.repl, self.repl))
            def fold_seg_step(P_sh, lo_all, hi_all):
                def f(P_local, lo_l, hi_l):
                    lo0, hi0 = lo_l[0], hi_l[0]
                    ctx = prepare(P_local) if prepare is not None else None

                    def body(state):
                        lo_, hi_, P_l, _, rounds = state
                        P_l, old, new = _scatter_min(P_l, lo_, hi_)

                        retire = hi_ == new
                        displaced = retire & (new < old) & (old < n_)

                        # climb: first step from the scatter reply, the
                        # rest from the pluggable climb body
                        can0 = new < hi_
                        cur = jnp.where(can0, new, lo_)
                        cur = climb(ctx, P_l, cur, hi_)
                        became_loop = cur == hi_
                        climb_lo = jnp.where(became_loop, n_, cur)
                        climb_hi = jnp.where(became_loop, n_, hi_)

                        # displaced constraint: (new, old-parent pos)
                        out_lo = jnp.where(
                            retire, jnp.where(displaced, new, n_),
                            climb_lo).astype(jnp.int32)
                        out_hi = jnp.where(
                            retire, jnp.where(displaced, old, n_),
                            climb_hi).astype(jnp.int32)
                        live = lax.psum(jnp.sum(out_lo != n_), SHARD_AXIS)
                        return out_lo, out_hi, P_l, live, rounds + 1

                    def cond(state):
                        _, _, _, live, rounds = state
                        return (live > 0) & (rounds < seg_)

                    live0 = lax.psum(jnp.sum(lo0 != n_), SHARD_AXIS)
                    state = (lo0, hi0, P_local, live0,
                             (live0 * 0).astype(jnp.int32))
                    lo_f, hi_f, P_f, live_f, rounds = \
                        lax.while_loop(cond, body, state)
                    max_live = lax.pmax(jnp.sum(lo_f != n_), SHARD_AXIS)
                    return (P_f, lo_f[None], hi_f[None],
                            live_f, lax.pmax(rounds, SHARD_AXIS), max_live)

                return shard_map(
                    f, mesh=mesh,
                    in_specs=(P(SHARD_AXIS),
                              P(SHARD_AXIS, None), P(SHARD_AXIS, None)),
                    out_specs=(P(SHARD_AXIS), P(SHARD_AXIS, None),
                               P(SHARD_AXIS, None), P(), P(), P()))(
                        P_sh, lo_all, hi_all)

            return fold_seg_step

        def _make_fold_seg(jumps_n: int):
            """Fold program with ``jumps_n`` single-step climbs per round
            — the TAIL regime: a displacement cascade of length l costs
            ~l/j rounds but ~2*l*D*Q collective words regardless of j,
            so at small Q more jumps cut rounds (and per-op collective
            latencies) nearly for free (measured: BASELINE.md bigv
            entry)."""

            def climb(ctx, P_l, cur, hi_):
                for _ in range(jumps_n - 1):
                    p_next = _lookup(P_l, cur)
                    cur = jnp.where(p_next < hi_, p_next, cur)
                return cur

            return _make_fold(climb)

        def _make_fold_lift(levels_n: int):
            """Fold program whose climb uses STREAM-DESCENT BINARY
            LIFTING on the distributed table — the single-chip trick
            (ops/elim.py stream descent: square ONE table in place,
            t <- t[t], interleaved with jumps) carried to the
            block-sharded layout, for the BULK regime. A squaring is a
            routed lookup at the OWNED-rows width B = V/D (D*B = V words
            per device), *cheaper* than one jump collective at full Q —
            and lifting collapses the round count the way it does on one
            chip (measured: 430 jump rounds -> 31 lift rounds at
            RMAT-15/D=8 with ~6x less total traffic, BASELINE.md).
            Memory stays O(V/D): exactly one extra table block lives at
            a time. Every taken jump lands on a genuine ancestor still
            earlier than hi, so each rewrite is sound and the unique
            fixpoint is unchanged."""

            def climb(ctx, P_l, cur, hi_):
                t = P_l
                for j in range(levels_n):
                    cand = _lookup(t, cur)
                    cur = jnp.where(cand < hi_, cand, cur)
                    if j < levels_n - 1:
                        t = _lookup(t, t)   # routed squaring (width B)
                return cur

            return _make_fold(climb)

        def _make_fold_lift_hoisted(levels_n: int, hoist_n: int):
            """:func:`_make_fold_lift` with the squared tables HOISTED
            out of the round loop — the bigv port of the single-chip
            stale-tables trick (ops/elim.py fold_segment_pos_hoisted).
            The per-round climb above re-squares the table every round:
            2*(levels-1) routed B-width collectives shipping ~V words
            per device PER ROUND — the dominant V-term of the bulk
            phase (BASELINE.md bigv entry: 'the remaining question at
            RMAT-30 scale is the V-word squaring term'). Here the stack
            of ``hoist_n`` squared tables is built ONCE per segment
            (stale between rounds; level 0 = the live table stays
            current), so the squaring traffic amortizes over
            ``segment_rounds`` rounds. Sound for the same reason as the
            single-chip variant: ancestor-ship is permanent, so a stale
            jump lands on a genuine (possibly non-maximal) ancestor; the
            fixpoint exit stays exact because the segment loop only
            exits on live == 0 (all constraints retired), which is
            table-freshness-independent. ``hoist_n`` < levels-1 caps the
            stack's HBM at hoist_bytes (scale-30 tables cannot afford a
            full log2(V) stack per device); shorter reach just means a
            long cascade takes extra (cheap, stackless) rounds."""

            def prepare(P_local):
                stack = []
                t = P_local
                for _ in range(hoist_n):
                    t = _lookup(t, t)   # routed squaring (width B)
                    stack.append(t)
                return tuple(stack)

            def climb(stack, P_l, cur, hi_):
                cand = _lookup(P_l, cur)        # level 0: CURRENT table
                cur = jnp.where(cand < hi_, cand, cur)
                for t in stack:                 # stale hoisted levels
                    cand = _lookup(t, cur)
                    cur = jnp.where(cand < hi_, cand, cur)
                # reach beyond the byte-capped stack: keep squaring
                # dynamically from the deepest hoisted table (per-round
                # cost returns, but only for the levels past the cap)
                t = stack[-1] if stack else P_l
                for _ in range(hoist_n + 1, levels_n):
                    t = _lookup(t, t)
                    cand = _lookup(t, cur)
                    cur = jnp.where(cand < hi_, cand, cur)
                return cur

            return _make_fold(climb, prepare=prepare)

        def _make_compact(to_size: int):
            """Dedup + pack each device's live (loP, hiP) actives into a
            (D, to_size) buffer (valid when every device's live count <=
            to_size — the caller checks the pmax). Shrinking Q directly
            shrinks every routed collective: all_gather/all_to_all ship
            D * Q words per round.

            The dedup (drop duplicate (lo, hi) pairs via one 2-key sort,
            exactly like the single-chip ``compact_actives(dedup=True)``)
            is the "dedup requests before the all_gather" lever: after a
            few rounds many slots have been rewritten to the same
            (ancestor, hi) constraint — on hub-skewed graphs MOST of
            them (a star graph's requests all climb to the hub). The
            constraint closure is a SET property (duplicates retire
            together and spawn identical displacements), so dropping
            in-shard duplicates is exact; cross-shard duplicates remain
            (deduping them would need an extra routed pass). Runs only
            at compaction cadence, not per round — a per-round sort was
            measured in seconds at C=2^24 on the v5e (BASELINE.md)."""
            act = NamedSharding(mesh, P(SHARD_AXIS, None))

            dedup = self.dedup_compact

            @partial(jax.jit,
                     in_shardings=(act, act),
                     out_shardings=(act, act))
            def compact_step(lo_all, hi_all):
                def f(lo_l, hi_l):
                    lo0, hi0 = lo_l[0], hi_l[0]
                    if dedup:
                        lo0, hi0 = lax.sort((lo0, hi0), num_keys=2)
                        dup = (lo0 == jnp.roll(lo0, 1)) & \
                            (hi0 == jnp.roll(hi0, 1))
                        dup = dup.at[0].set(False)
                        lo0 = jnp.where(dup, n_, lo0)
                        hi0 = jnp.where(dup, n_, hi0)
                    c = lo0.shape[0]
                    sel = jnp.nonzero(lo0 != n_, size=to_size,
                                      fill_value=c)[0]
                    ext = lambda a: jnp.concatenate(
                        [a, jnp.full(1, n_, a.dtype)])[sel]
                    return (ext(lo0)[None], ext(hi0)[None])
                return shard_map(
                    f, mesh=mesh,
                    in_specs=(P(SHARD_AXIS, None),) * 2,
                    out_specs=(P(SHARD_AXIS, None),) * 2)(
                        lo_all, hi_all)
            return compact_step

        # ---- scoring (block-sharded assignment, routed part lookups;
        # chunk stays sharded — no replicated O(V) state here either) ----
        @partial(jax.jit,
                 in_shardings=(self.batch_sharding, self.shard),
                 out_shardings=self.repl)
        def score_step(batch, assign_sh):
            def f(chunk_local, assign_local):
                chunk = chunk_local[0]
                u = chunk[:, 0].astype(jnp.int32)
                v = chunk[:, 1].astype(jnp.int32)
                valid = (u >= 0) & (u < n_) & (v >= 0) & (v < n_) & (u != v)
                au = _lookup(assign_local, jnp.clip(u, 0, n_))
                av = _lookup(assign_local, jnp.clip(v, 0, n_))
                cut = jnp.sum(valid & (au != av), dtype=jnp.int32)
                total = jnp.sum(valid, dtype=jnp.int32)
                return lax.psum(jnp.stack([cut, total])[None], SHARD_AXIS)
            return shard_map(
                f, mesh=mesh,
                in_specs=(P(SHARD_AXIS, None, None), P(SHARD_AXIS)),
                out_specs=P(SHARD_AXIS, None))(batch, assign_sh)[0]

        self.deg_zeros = deg_zeros
        self.deg_step = deg_step
        self.orient_step = orient_step
        self.score_step = score_step
        self.max_rounds = max_rounds
        self._make_compact = _make_compact
        self._compact_cache: dict = {}
        self._make_fold_seg = _make_fold_seg
        self._fold_seg_cache: dict = {}
        self._make_fold_lift = _make_fold_lift
        self._make_fold_lift_hoisted = _make_fold_lift_hoisted
        self._fold_lift_cache: dict = {}

    # compaction floor: the tail's collective bytes are ~ops x D x Q x
    # rounds, and the tail runs hundreds of rounds at the FLOOR width —
    # measured at RMAT-15/D=8, a 4096 floor put ~3.4 GB of the 4 GB
    # per-device total in the tail; 512 cuts that ~8x for a handful of
    # extra (cached, geometrically-sized) compaction programs
    MIN_Q = 1 << 9
    # once the active width compacts to <= TAIL_Q, switch from the
    # lifting program to a jump program with ``self.jumps`` climb steps
    # per round: the remaining work is displacement cascades (one link
    # per jump), and at small Q the extra lookups per round are far
    # cheaper than the rounds they save
    TAIL_Q = 1 << 13

    def _round_cost(self, q: int, jumps: int, lift: bool):
        """(collective ops, bytes received per device) for ONE fixpoint
        round at active width Q: _scatter_min = 2 all_gather +
        2 all_to_all at Q; a jump round adds (jumps-1) lookup pairs at
        Q; a lift round adds ``lift_levels`` lookup pairs at Q plus the
        NON-hoisted squaring pairs at the owned-rows width B (the
        ``hoist_levels`` hoisted squarings are paid once per SEGMENT —
        :func:`_segment_cost`). Every collective ships (D, width) int32
        — the D*Q-words trade documented in the module docstring, now
        *measured* per chunk (diagnostics) instead of only documented."""
        d = self.n_devices
        if lift:
            L, K = self.lift_levels, self.hoist_levels
            ops = 4 + 2 * L + 2 * (L - 1 - K)
            words = d * (4 * q + 2 * L * q + 2 * (L - 1 - K) * self.B)
        else:
            ops = 4 + 2 * (jumps - 1)
            words = d * ops * q
        return ops, 4 * words

    def _segment_cost(self, lift: bool):
        """(ops, bytes/device) paid once per fold CALL: the hoisted
        lifting stack is built per segment, 2 routed collectives per
        hoisted level at width B."""
        if not lift or not self.hoist_levels:
            return 0, 0
        K = self.hoist_levels
        return 2 * K, 4 * self.n_devices * 2 * K * self.B

    def build_step(self, P_sh, pos_sh, batch_dev, stats=None):
        """Fold one sharded batch into the distributed forest via
        host-bounded segments. Returns (P_sh, total_rounds) — identical
        to running the whole fixpoint in one execution, but no single
        device call exceeds ``segment_rounds`` rounds, and the active
        buffers compact (with in-shard dedup) to the pmax live width as
        the set collapses (every routed collective ships D*Q words, so
        smaller Q = proportionally less ICI/DCN traffic per tail round).

        ``stats``: accumulates collective_ops / collective_bytes /
        compactions / q_rounds (sum of Q over rounds) for the run
        diagnostics, plus the cross-backend O(Δ) cost triple
        (host_syncs / device_rounds / folded_bytes — the counters the
        update-vs-rebuild gate compares)."""
        if stats is None:
            stats = {}
        lo_a, hi_a = self.orient_step(pos_sh, batch_dev)
        size = int(lo_a.shape[-1])
        # orient: 2 routed lookups (u, v) at chunk width
        stats["collective_ops"] = stats.get("collective_ops", 0) + 4
        stats["collective_bytes"] = stats.get("collective_bytes", 0) \
            + 4 * 4 * self.n_devices * size
        stats["folded_bytes"] = stats.get("folded_bytes", 0) \
            + int(batch_dev.size) * 4
        total = 0
        # SHEEP_SANITIZE: stray-sync traps around the routed fold loop
        # (the designed pulls below are the only host reads)
        with sanitize.guard("bigv-fold"):
            while True:
                # bulk: stream-descent lifting (few rounds, +V squaring
                # words/round); tail: many-jump rounds (no V-term at all)
                lift = size > self.TAIL_Q
                if lift:
                    key = (self.lift_levels, self.hoist_levels)
                    fold = self._fold_lift_cache.get(key)
                    if fold is None:
                        fold = self._fold_lift_cache[key] = \
                            self._make_fold_lift_hoisted(
                                self.lift_levels, self.hoist_levels) \
                            if self.hoist_levels else \
                            self._make_fold_lift(self.lift_levels)
                    jumps = 0
                else:
                    jumps = self.jumps
                    fold = self._fold_seg_cache.get(jumps)
                    if fold is None:
                        fold = self._fold_seg_cache[jumps] = \
                            self._make_fold_seg(jumps)
                P_sh, lo_a, hi_a, live, r, max_live = fold(P_sh, lo_a, hi_a)
                # the designed per-segment replicated pull of this driver
                with sanitize.sync_ok("bigv-segment-pull"):
                    r = int(r)  # sheeplint: sync-ok
                    live_i = int(live)  # sheeplint: sync-ok
                    ml = int(max_live)  # sheeplint: sync-ok
                total += r
                stats["host_syncs"] = stats.get("host_syncs", 0) + 1
                stats["device_rounds"] = \
                    stats.get("device_rounds", 0) + r
                ops, byts = self._round_cost(size, jumps, lift)
                seg_ops, seg_bytes = self._segment_cost(lift)
                stats["collective_ops"] += ops * r + seg_ops
                stats["collective_bytes"] += byts * r + seg_bytes
                stats["q_rounds"] = stats.get("q_rounds", 0) + size * r
                if live_i == 0 or total >= self.max_rounds:
                    return P_sh, total
                if size > self.MIN_Q and ml <= size // 2:
                    new_size = pow2_at_least(2 * ml, floor=self.MIN_Q)
                    if new_size < size:
                        fn = self._compact_cache.get(new_size)
                        if fn is None:
                            fn = self._compact_cache[new_size] = \
                                self._make_compact(new_size)
                        lo_a, hi_a = fn(lo_a, hi_a)
                        size = new_size
                        stats["compactions"] = stats.get("compactions", 0) + 1

    # ---- host-side helpers ----------------------------------------------
    def _put(self, sharding, arr):
        """Single process: plain device_put. Multi-host: every process
        passes its process-local rows and JAX assembles the global
        array. A batch already materialized on device (device-stream
        synthesis, single-process — see ``run``'s ingest) relays
        without a host crossing."""
        if isinstance(arr, jax.Array):
            return jax.device_put(arr, sharding)
        if self.procs == 1:
            return jax.device_put(arr, sharding)
        return jax.make_array_from_process_local_data(sharding, arr)

    def _local_span(self):
        """This process's row span of a (rows,) block-sharded table."""
        w = self.n_local * self.B
        return self.proc * w, (self.proc + 1) * w

    def _local_block(self, arr) -> np.ndarray:
        """Host copy of this process's rows of a (rows,) sharded array."""
        if self.procs == 1:
            return np.asarray(arr)
        shards = sorted(arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        return np.concatenate([np.asarray(s.data) for s in shards])

    def _allgather_table(self, local: np.ndarray) -> np.ndarray:
        """Assemble the full (rows,) host table from per-process local
        blocks (one DCN allgather; identical result on every process)."""
        if self.procs == 1:
            return local
        from jax.experimental import multihost_utils

        return np.asarray(
            multihost_utils.process_allgather(local)).reshape(-1)

    def _shard_table(self, host_table: np.ndarray):
        """Pad an int32[n+1] host table to (rows,) with the sentinel and
        place it block-sharded (every process holds the full host table;
        each contributes its local span)."""
        padded = np.full(self.rows, self.n, np.int32)
        padded[: self.n + 1] = host_table
        a, b = self._local_span()
        return self._put(self.shard,
                         padded if self.procs == 1 else padded[a:b])

    def run(self, stream, k: int, alpha: float = 1.0,
            weights: Optional[str] = "unit", comm_volume: bool = False,
            timings: Optional[dict] = None, checkpointer=None,
            resume: bool = False):
        """Full vertex-sharded partition run.

        Checkpoint state is the per-process LOCAL block (deg_local —
        int32 when the stream's edge bound proves no overflow, int64
        otherwise; ptable_local int32 — O(V/P) per process, the bigv
        scaling story carried through to recovery); the cadence/
        fingerprint/reconcile machinery is shared with the other
        backends (utils/checkpoint)."""
        from sheep_tpu.core import pure
        from sheep_tpu.ops import score as score_ops
        from sheep_tpu.ops.split import tree_split_host
        from sheep_tpu.parallel.pipeline import (iter_batches_lockstep,
                                                 use_byte_range)
        from sheep_tpu.utils import checkpoint as ckpt
        from sheep_tpu.utils import retry as retry_mod
        from sheep_tpu.utils import watchdog as wd_mod
        from sheep_tpu.utils.fault import maybe_fail
        from sheep_tpu.utils.prefetch import prefetch

        t = timings if timings is not None else {}
        n, cs, d = self.n, self.cs, self.n_devices

        # fault tolerance (ISSUE 9): bounded per-batch retry, single
        # process only (a one-rank retry would desynchronize the
        # collective schedules; multi-host keeps the checkpoint/
        # kill+resume contract plus the stall watchdog). Sound because
        # no bigv program donates its inputs: the pre-batch tables are
        # intact after any fault, so re-folding the same batch is the
        # identical computation.
        policy = retry_mod.RetryPolicy()
        # the per-chunk build point sits OUTSIDE _guarded (legacy kill
        # semantics), so it must not offer kinds it cannot absorb —
        # recoverable oom injection rides the "dispatch" point INSIDE
        # the guarded step instead
        bkinds = ("kill", "stall")
        okinds = ("oom",) if self.procs == 1 else ()

        def _guarded(fn, where, stats=None):
            if self.procs > 1:
                return fn()
            before = sum(policy.attempts.values())
            out = policy.run(fn, where=where)
            grew = sum(policy.attempts.values()) - before
            if grew and stats is not None:
                stats["dispatch_retries"] = \
                    stats.get("dispatch_retries", 0) + grew
            return out

        def batches(start_chunk=0, src=None):
            # device-stream ingest (ISSUE 12): a counter-hash input
            # (the bigv soak generator class) synthesizes every
            # (rows, C, 2) batch directly in device memory — zero host
            # bytes per chunk; _put relays the pre-placed global array.
            # Pass-through, not prefetch: a worker queue of global
            # device batches would hold unmodeled HBM, and there is no
            # host I/O to overlap. Multi-host keeps the host lockstep
            # path (per-process assembly takes host rows). ``src``
            # substitutes the streamed source (the anchored degrees
            # pass streams the delta log's base segment only).
            src = stream if src is None else src
            if self.procs == 1 and is_device_stream(src):
                from sheep_tpu.parallel.pipeline import (
                    _PassThrough, device_lockstep_batches)

                return _PassThrough(device_lockstep_batches(
                    src, cs, self.n_local, n, self.batch_sharding,
                    start_chunk=start_chunk, stats=build_stats))
            return prefetch(iter_batches_lockstep(
                src, cs, self.n_local, n, self.proc, self.procs,
                start_chunk=start_chunk,
                byte_range=use_byte_range(src, self.procs)))

        # state_format "bigv-pos": the checkpointed table block is now
        # POSITION-indexed; the format bump makes --resume against a
        # checkpoint written by the old vertex-indexed layout raise a
        # fingerprint mismatch (collectively, in multi-host) instead of
        # resuming into silently-wrong state; runs without --resume
        # start fresh as always
        meta = ckpt.stream_meta(stream, k, cs, weights=weights, alpha=alpha,
                                comm_volume=comm_volume,
                                state_format="bigv-pos",
                                devices=d, procs=self.procs,
                                text_byte_range=use_byte_range(
                                    stream, self.procs))
        state = ckpt.resume_state(checkpointer, meta, resume,
                                  raise_on_mismatch=self.procs == 1)
        if self.procs > 1 and checkpointer is not None and resume:
            state = ckpt.reconcile_multihost_resume(checkpointer, state, meta)
        from_phase = ckpt.phase_index(state.phase) if state else 0

        root_sp = obs.begin("partition", backend="tpu-bigv", k=int(k),
                            n=int(n), devices=int(d))
        stats_acc = obs.stats_accumulator()
        m_cheap = stream.num_edges_cheap
        obs.progress(backend="tpu-bigv", k=int(k), edges_total=m_cheap)

        # ONE build-stats record across the streaming passes so the
        # ingest counters (device_stream_chunks, ISSUE 12) accumulate
        # wherever batches are synthesized
        build_stats: dict = {}
        # out-of-core residency plane (ISSUE 20): under an explicit
        # SHEEP_CACHE_BYTES budget, build-pass device batches (keyed by
        # absolute chunk index) serve the score pass and the in-process
        # dispatch retries from HBM instead of re-uploading, with
        # checkpoint boundaries as eviction points. Single-process host
        # streams only — device-synth batches have no upload to save,
        # and multi-host residency would skew the collective lockstep.
        rm = None
        if self.procs == 1 and not is_device_stream(stream):
            from sheep_tpu.utils.residency import manager_from_env
            rm = manager_from_env(stats=build_stats)
        # anchored-order inputs (delta: logs, ISSUE 19): degrees stream
        # the BASE segment only (the anchor), build/score the full
        # surviving multiset — same anchored-order semantics as the
        # single-device backends, same unique fixpoint
        anchored = bool(getattr(stream, "order_anchor", False))
        deg_src = stream.anchor_stream() if anchored else None
        # pass 1: degrees (block-sharded int32 accumulator + host fold of
        # the LOCAL block, int32 when the edge bound proves no overflow;
        # resets are jitted on-device zeros, no
        # host zero uploads; one final allgather assembles the table)
        t0 = time.perf_counter()
        sp = obs.begin("degrees+sort")
        obs.progress(phase="degrees", chunks_done=0, edges_done=0)
        flush_every = max(1, (2**31 - 1) // max(2 * cs * d, 1))
        if state:
            deg_local = state.arrays["deg_local"].copy()
        else:
            # int32 host accumulator when the stream's edge bound proves
            # no vertex can see 2^31 endpoints — at the RMAT-30 class the
            # int64 table alone is 8 GB/process; resume keeps the saved
            # dtype so checkpoints stay self-consistent
            ub = stream.num_edges_upper_bound
            deg_dtype = np.int64 if ub is None or 2 * ub >= 2**31 \
                else np.int32
            deg_local = np.zeros(self.n_local * self.B, dtype=deg_dtype)
        if from_phase == 0:
            start = state.chunk_idx if state else 0
            deg_sh = self.deg_zeros()
            since = nb = 0
            # with-exit = deterministic prefetch-worker cancel on
            # exception unwind (utils/prefetch.py close contract)
            with wd_mod.watched(self.procs, "bigv-degrees",
                                self.proc) as wd, \
                    batches(start, src=deg_src) as pf:
                for batch in pf:
                    deg_sh = self.deg_step(deg_sh, self._put(
                        self.batch_sharding, batch))
                    since += 1
                    nb += 1
                    wd.touch(f"degrees batch {nb}")
                    maybe_fail("degrees", nb, kinds=("kill", "stall"))
                    obs.chunk_progress(nb * d, cs, m_cheap)
                    at_ckpt = (checkpointer is not None and
                               checkpointer.due_span((nb - 1) * d, nb * d))
                    if since >= flush_every or at_ckpt:
                        deg_local += self._local_block(deg_sh).astype(
                            deg_local.dtype)
                        deg_sh = self.deg_zeros()
                        since = 0
                    if at_ckpt:
                        checkpointer.save("degrees", start + nb * d,
                                          {"deg_local": deg_local}, meta)
            deg_local += self._local_block(deg_sh).astype(deg_local.dtype)
            deg_sh = None  # free the block-sharded device accumulator
        deg_host = self._allgather_table(deg_local)[:n]

        # host-side elimination order: one stable argsort over degrees;
        # hosts hold hundreds of GB, and the sort is once per run. Only
        # pos is pushed to devices — position space needs no order table
        # there. Everything host-side is int32 (n < 2^31 is enforced at
        # backend entry): at V=2^30 the old int64 pos/order pair alone
        # was 17 GB.
        pos_np = pure.elimination_order(deg_host, dtype=np.int32)
        order_np = np.full(n + 1, n, dtype=np.int32)
        order_np[pos_np] = np.arange(n, dtype=np.int32)
        pos_pad = np.empty(n + 1, dtype=np.int32)
        pos_pad[:n] = pos_np
        pos_pad[n] = n
        pos_sh = self._shard_table(pos_pad)
        del pos_pad
        t["degrees+sort"] = time.perf_counter() - t0
        sp.end()

        # pass 2: the single distributed forest (position-indexed table)
        t0 = time.perf_counter()
        sp = obs.begin("build")
        obs.progress(phase="build", chunks_done=0, edges_done=0)
        total_rounds = 0
        if state and from_phase >= 2:
            P_sh = self._put(self.shard, state.arrays["ptable_local"])
        else:
            if state and state.phase == "build":
                P_sh = self._put(self.shard, state.arrays["ptable_local"])
                start = state.chunk_idx
            else:
                P_sh = self._shard_table(np.full(n + 1, n, np.int32))
                start = 0
            nb = 0
            with wd_mod.watched(self.procs, "bigv-build",
                                self.proc) as wd, batches(start) as pf:
                for batch in pf:
                    seg_sp = obs.begin("segment", i=nb)

                    def _step(b=batch, i=nb, key=start + nb * d):
                        maybe_fail("dispatch", i + 1, kinds=okinds)
                        dev = rm.get(key) if rm is not None else None
                        if dev is None:
                            dev = self._put(self.batch_sharding, b)
                            if rm is not None:
                                rm.admit(key, dev, int(b.nbytes))
                        return self.build_step(
                            P_sh, pos_sh, dev, stats=build_stats)

                    try:
                        P_sh, rounds = _guarded(_step, "bigv.build",
                                                stats=build_stats)
                        total_rounds += rounds
                        stats_acc.absorb(build_stats)
                        seg_sp.end(rounds=int(rounds))
                    finally:
                        # idempotent: balances the span when a fault
                        # unwinds mid-batch (recovered runs must still
                        # render a complete tree)
                        seg_sp.end()
                    nb += 1
                    wd.touch(f"build batch {nb}")
                    obs.chunk_progress(nb * d, cs, m_cheap)
                    maybe_fail("build", nb, kinds=bkinds)
                    if checkpointer is not None and \
                            checkpointer.due_span((nb - 1) * d, nb * d):
                        checkpointer.save(
                            "build", start + nb * d,
                            {"deg_local": deg_local,
                             "ptable_local": self._local_block(P_sh)},
                            meta)
                        if rm is not None:
                            # checkpoint boundary = eviction point: a
                            # retry never re-reads behind the confirmed
                            # index
                            rm.boundary(start + nb * d)
        P_host = self._allgather_table(
            self._local_block(P_sh))[: n + 1]
        t["build"] = time.perf_counter() - t0
        stats_acc.absorb(build_stats)
        sp.end(fixpoint_rounds=int(total_rounds))

        # split on host over O(V) state (native C++); position-indexed
        # table -> vertex parent array: parent[v] = order[P[pos[v]]]
        t0 = time.perf_counter()
        sp = obs.begin("split")
        pp = P_host[pos_np]
        parent = np.where(pp < n, order_np[np.minimum(pp, n)], -1)
        # the native split upcasts parent/pos to int64 copies; drop the
        # tables it does not take first so the split-time peak at the
        # RMAT-30 class stays below the old all-int64 path's
        del pp, order_np
        w = deg_host.astype(np.float64) if weights == "degree" else None
        assign_host = tree_split_host(parent, pos_np, k, weights=w,
                                      alpha=alpha)
        assign_np = np.concatenate([assign_host.astype(np.int32),
                                    np.zeros(1, np.int32)])
        assign_sh = self._shard_table(assign_np)
        t["split"] = time.perf_counter() - t0
        sp.end()

        # pass 3: scoring (sharded chunks, routed lookups into the
        # block-sharded assignment, psum counters)
        t0 = time.perf_counter()
        sp = obs.begin("score")
        obs.progress(phase="score", chunks_done=0, edges_done=0)
        cut = total = 0
        cv_chunks = []
        start = 0
        if state and state.phase == "score":
            start = state.chunk_idx
            cut = int(state.arrays["cut"])
            total = int(state.arrays["total"])
            if comm_volume:
                cv_chunks.append(state.arrays["cv_keys"])
        nb = 0
        with wd_mod.watched(self.procs, "bigv-score",
                            self.proc) as wd, batches(start) as pf:
            for batch in pf:
                key = start + nb * d
                dev = rm.get(key) if rm is not None else None
                if dev is None:
                    dev = self._put(self.batch_sharding, batch)
                    if rm is not None:
                        rm.admit(key, dev, int(batch.nbytes))
                # designed per-batch score pull (two scalars)
                c, tt = np.asarray(self.score_step(  # sheeplint: sync-ok
                    dev, assign_sh))
                cut += int(c)
                total += int(tt)
                if comm_volume:
                    score_ops.accumulate_cv_keys(
                        cv_chunks,
                        score_ops.cut_pair_keys_host(batch, assign_np,
                                                     n, k))
                nb += 1
                wd.touch(f"score batch {nb}")
                maybe_fail("score", nb, kinds=("kill", "stall"))
                obs.chunk_progress(nb * d, cs, m_cheap)
                if checkpointer is not None and \
                        checkpointer.due_span((nb - 1) * d, nb * d):
                    cv_chunks = ckpt.save_score_state(
                        checkpointer, start + nb * d, cut, total,
                        cv_chunks,
                        {"deg_local": deg_local,
                         "ptable_local": self._local_block(P_sh)}, meta,
                        comm_volume)
                    if rm is not None:
                        rm.boundary(start + nb * d)
        cv = None
        if comm_volume:
            keys = ckpt.compact_cv_keys(cv_chunks)
            if self.procs > 1:
                # each process saw only its shard's cut edges: union the
                # per-host key sets (padded allgather, then host unique)
                from jax.experimental import multihost_utils

                lens = multihost_utils.process_allgather(
                    np.array([len(keys)], np.int64))
                mx = max(1, int(lens.max()))
                pad = np.full(mx, -1, np.int64)
                pad[:len(keys)] = keys
                allk = multihost_utils.process_allgather(pad)
                keys = np.unique(allk[allk >= 0])
            cv = int(len(keys))
        balance = pure.part_balance(
            assign_host, k, deg_host if weights == "degree" else None)
        t["score"] = time.perf_counter() - t0
        sp.end()
        root_sp.end()
        if checkpointer is not None:
            checkpointer.clear()

        return {
            "assignment": assign_host, "parent": parent.astype(np.int64),
            "pos": pos_np, "degrees": deg_host, "edge_cut": cut,
            "total_edges": total, "balance": balance, "comm_volume": cv,
            "k": k, "fixpoint_rounds": total_rounds,
            "build_stats": build_stats,
        }


# ---------------------------------------------------------------------------
# process-wide compiled-pipeline cache (ISSUE 19)
# ---------------------------------------------------------------------------
# Every BigVPipeline() re-traces and re-compiles the whole routed program
# set (deg/orient/fold/compact/score close over n, B and the shardings),
# a flat multi-second XLA tax per instance regardless of graph size. The
# pipeline is stateless across runs — everything mutable lives in the
# tables threaded through build_step/run, and the only instance dicts
# are the lazy program caches we WANT to share — so instances are safe
# to reuse whenever every constructor input matches. Keyed on the full
# constructor signature plus the mesh's device ids; bounded LRU so a
# long-lived process sweeping many shapes doesn't pin dead programs.

_PIPE_CACHE: "OrderedDict[tuple, BigVPipeline]" = OrderedDict()
_PIPE_CACHE_MAX = 16


def cached_pipeline(n: int, chunk_edges: int, mesh, jumps: int = 128,
                    max_rounds: int = 1 << 20, segment_rounds: int = 16,
                    dedup_compact: bool = True, lift_levels: int = 0,
                    hoist_bytes: Optional[int] = None) -> BigVPipeline:
    """BigVPipeline with its compiled programs reused across backend
    instances (one-shot builds, resident epoch folds, compaction
    rebuilds — all hit the same programs for the same shape)."""
    hb = hoist_bytes if hoist_bytes is not None \
        else int(os.environ.get("SHEEP_BIGV_HOIST_BYTES", "0"))
    key = (n, chunk_edges, tuple(d.id for d in mesh.devices.flat),
           jumps, max_rounds, segment_rounds, dedup_compact,
           lift_levels, hb)
    pipe = _PIPE_CACHE.get(key)
    if pipe is None:
        pipe = BigVPipeline(n, chunk_edges, mesh, jumps=jumps,
                            max_rounds=max_rounds,
                            segment_rounds=segment_rounds,
                            dedup_compact=dedup_compact,
                            lift_levels=lift_levels,
                            hoist_bytes=hoist_bytes)
        _PIPE_CACHE[key] = pipe
        while len(_PIPE_CACHE) > _PIPE_CACHE_MAX:
            _PIPE_CACHE.popitem(last=False)
    else:
        _PIPE_CACHE.move_to_end(key)
    return pipe
