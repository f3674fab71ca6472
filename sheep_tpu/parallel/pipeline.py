"""Sharded multi-device partition pipeline (SURVEY.md §2 #9, §3.1).

The comm surface mirrors the reference's three MPI crossings exactly
(SURVEY.md §3.1), as XLA collectives on the ``shards`` mesh axis:

  1. shard scatter     -> host round-robins edge chunks to devices
                          (EdgeStream chunk index % D), device_put with a
                          NamedSharding — no collective, just placement
  2. tree-merge reduce -> butterfly allreduce with *forest merge* as the
                          combiner: log2(D) host-driven ppermute rounds,
                          each device ships compacted boundary pairs (or
                          the dense O(V) table when occupancy is high)
                          over ICI and folds the received constraints
                          with the adaptive elimination fixpoint; after
                          the last round every device holds the global
                          tree (T is associative + commutative, so the
                          butterfly is valid)
  3. score all-reduce  -> psum of (cut, total) counters

Degrees use per-device partial counts summed once at the end (one
all-reduce of an O(V) vector), so the streaming passes are collective-free:
all cross-device traffic is O(V log D + V), independent of E.
"""

from __future__ import annotations

import math
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
from sheep_tpu.io.devicestream import is_device_stream, note_device_chunks
from sheep_tpu.ops import degrees as degrees_ops
from sheep_tpu.ops import elim as elim_ops
from sheep_tpu.ops import order as order_ops
from sheep_tpu.ops import score as score_ops
from sheep_tpu.parallel.mesh import SHARD_AXIS, shard_map


def chunk_batches(stream, chunk_edges: int, n_devices: int, n: int,
                  shard: int = 0, num_shards: int = 1, start_chunk: int = 0,
                  byte_range: bool = False):
    """Group the chunk stream into (D, C, 2) int32 host batches, one chunk
    per device, padded with the sentinel vertex n. Yields (batch, count)."""
    from sheep_tpu.backends.tpu_backend import pad_chunk

    batch = np.full((n_devices, chunk_edges, 2), n, dtype=np.int32)
    filled = 0
    for chunk in stream.chunks(chunk_edges, shard=shard, num_shards=num_shards,
                               start_chunk=start_chunk, byte_range=byte_range):
        batch[filled] = pad_chunk(chunk, chunk_edges, n)
        filled += 1
        if filled == n_devices:
            yield batch, filled
            batch = np.full((n_devices, chunk_edges, 2), n, dtype=np.int32)
            filled = 0
    if filled:
        yield batch, filled


def use_byte_range(stream, procs: int) -> bool:
    """PLAIN text files in multi-process runs shard by byte span so each
    process parses only ~file/P (VERDICT r1 item 7); binary/CSR formats
    already seek in O(1) per chunk, and gzip members are one sequential
    stream (no seeks — EdgeStream serves them round-robin by chunk
    index, the semantics the non-byte_range batch math assumes)."""
    return (procs > 1 and stream.path is not None
            and stream.fmt == "text")


def iter_batches_lockstep(stream, cs: int, rows: int, n: int, proc: int,
                          procs: int, start_chunk: int = 0,
                          byte_range: bool = False):
    """Yield (rows, C, 2) host batches from this process's shard of the
    chunk stream. Multi-host: every process yields the SAME number of
    batches (stragglers pad with all-sentinel batches) so per-batch
    collectives stay in lockstep — the count comes from the stream length
    (binary: O(1); text: each process counts its OWN byte span, then one
    tiny allgather agrees on the max)."""
    gen = (b for b, _ in chunk_batches(
        stream, cs, rows, n, shard=proc, num_shards=procs,
        start_chunk=start_chunk, byte_range=byte_range))
    if procs == 1:
        yield from gen
        return
    if byte_range:
        # per-process local chunk counts differ (spans are byte-, not
        # edge-balanced); allgather them once to agree on the batch
        # count. Local chunk j of process p = global chunk j*P + p, so
        # the start_chunk skip math matches the round-robin case.
        from jax.experimental import multihost_utils

        mine = -(-stream.count_edges_in_span(proc, procs) // cs)
        counts = np.asarray(multihost_utils.process_allgather(
            np.array([mine], dtype=np.int64))).reshape(-1)

        def owned(p):
            done = max(0, (start_chunk - p + procs - 1) // procs)
            return max(0, int(counts[p]) - done)
    else:
        total = -(-stream.num_edges // cs)  # total chunks in stream

        def owned(p):  # chunks i in [start_chunk, total) with i % procs == p
            full = max(0, (total - p + procs - 1) // procs)
            done = max(0, (start_chunk - p + procs - 1) // procs)
            return full - done

    nb = max(-(-owned(p) // rows) for p in range(procs))
    produced = 0
    for b in gen:
        yield b
        produced += 1
    empty = np.full((rows, cs, 2), n, np.int32)
    for _ in range(nb - produced):
        yield empty


def device_lockstep_batches(stream, cs: int, rows: int, n: int, sharding,
                            start_chunk: int = 0, stats=None):
    """(rows, C, 2) int32 GLOBAL device batches synthesized ON DEVICE
    from a :func:`~sheep_tpu.io.devicestream.is_device_stream` input —
    the single-process device twin of :func:`iter_batches_lockstep`:
    batch b row j carries global chunk ``start_chunk + b*rows + j``,
    chunk indices past the stream end synthesize the inert all-sentinel
    chunk, so the batch sequence is bit-identical to the host path's
    padded batches while paying ZERO host bytes per chunk (ISSUE 12;
    the sharded/bigv soak ingest this replaces generated on host and
    re-crossed the link every pass).

    Each row is synthesized by the stream's jitted device kernel ON its
    owning device (``device_chunk_on``; never a host crossing), then
    the global array assembles with
    ``jax.make_array_from_single_device_arrays``. Multi-host callers
    keep the host lockstep path: per-process assembly goes through
    ``make_array_from_process_local_data``, which takes host rows."""
    shape = (rows, cs, 2)
    # device -> owned row index, from the sharding itself (robust to
    # device enumeration order)
    owners = sorted(
        ((idx[0].start or 0, dev)
         for dev, idx in sharding.addressable_devices_indices_map(
             shape).items()),
        key=lambda t: t[0])
    total = stream.num_device_chunks(cs)
    n_batches = max(0, -(-(total - start_chunk) // rows))

    def place(dev, idx):
        # device_chunk_on = the protocol's placement hook (synthesize
        # on the target device — zero host bytes, nothing on device
        # 0). Duck-typed streams without the hook synthesize on the
        # default device and move device-to-device.
        if hasattr(stream, "device_chunk_on"):
            return stream.device_chunk_on(dev, idx, cs, n)
        return jax.device_put(stream.device_chunk(idx, cs, n), dev)

    for b in range(n_batches):
        shards = []
        for j, dev in owners:
            chunk = place(dev, start_chunk + b * rows + j)
            shards.append(chunk[None])
        # count only the REAL chunks of a partial final batch (pad rows
        # are inert sentinels, and the tpu driver's count is exact —
        # the two drivers must report the same ingest telemetry for
        # the same input)
        note_device_chunks(stats,
                           min(rows, total - (start_chunk + b * rows)))
        yield jax.make_array_from_single_device_arrays(
            shape, sharding, shards)


class _PassThrough:
    """The prefetch surface (with/iter/close) over a plain generator,
    for DEVICE-SYNTH batch streams: a worker thread buffering global
    device arrays would hold queue-depth x batch HBM the membudget
    model never counts, and there is no host I/O to overlap anyway
    (synthesis is already-async device work). Host-format streams keep
    the real :func:`~sheep_tpu.utils.prefetch.prefetch`."""

    def __init__(self, gen):
        self._gen = gen

    def __iter__(self):
        return iter(self._gen)

    def close(self) -> None:
        close = getattr(self._gen, "close", None)
        if close is not None:
            close()

    def __enter__(self) -> "_PassThrough":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


def _grouped(iterable, batch: int):
    """Plain (worker-less) grouping into lists of up to ``batch`` items
    — the device-synth twin of prefetch_batched's inner generator."""
    buf: list = []
    for item in iterable:
        buf.append(item)
        if len(buf) == batch:
            yield buf
            buf = []
    if buf:
        yield buf


class ShardedPipeline:
    """Compiled sharded pipeline for a fixed (n, chunk_edges, mesh)."""

    def __init__(self, n: int, chunk_edges: int, mesh, lift_levels: int = 0,
                 segment_rounds: int = 32, warm_schedule=((1, 8),),
                 dispatch_batch: int = 1, inflight: int = 1,
                 donate: bool = False):
        self.n = n
        self.cs = chunk_edges
        self.mesh = mesh
        self.lift_levels = lift_levels
        # batched segment dispatch (ops/elim.py batch_segment_fixpoint):
        # stage N sharded batches as (D, N, C) oriented blocks and fold
        # them per device inside single bounded programs, pulling one
        # replicated packed-stats word per execution instead of one
        # changed/live pair per segment step. 1 = per-segment (the
        # adaptive _fold_actives loop); the merged forest is the same
        # unique fixpoint either way.
        self.dispatch_batch = max(1, int(dispatch_batch))
        # asynchronous dispatch pipeline depth for the batched path
        # (ISSUE 4): keep up to D issued fold_batch_step executions in
        # flight, speculatively re-dispatching the staged blocks before
        # the replicated stats word is pulled, and read the words
        # one-behind — every process runs the same deterministic driver
        # on the same replicated stats, so the collective schedules
        # stay in lockstep (speculative executions are collectives too,
        # issued identically everywhere). Unneeded speculations are
        # discarded unread; their output is the bit-identical
        # re-confirmation of the drained blocks.
        if inflight < 1:
            raise ValueError("inflight must be >= 1")
        self.inflight = int(inflight)
        # donate the per-device tables + staging blocks into each
        # batched execution (ops/elim.py donation rationale); pure
        # buffer aliasing, identical results
        self.donate = bool(donate)
        # fixpoint rounds per device execution in the build phase; the
        # host loops bounded segments so no single accelerator call runs
        # unboundedly long (the TPU worker watchdog kills those)
        self.segment_rounds = segment_rounds
        # low-lift warm rounds before full-depth rounds, as in the
        # single-device adaptive fold: a full-buffer round costs
        # ~lift_levels x width gathers per device and most slots retire
        # early without long jumps (tools/tune_fixpoint.py sweeps)
        self.warm_schedule = tuple(warm_schedule)
        d = mesh.devices.size
        self.n_devices = d
        self.rounds = max(1, math.ceil(math.log2(d))) if d > 1 else 0
        # multi-host layout: this process owns n_local contiguous mesh rows
        # (jax.devices() orders by process); chunks round-robin over
        # *processes* at the stream level and over local rows within one
        self.procs = len({dev.process_index for dev in mesh.devices.flat})
        self.proc = jax.process_index() if self.procs > 1 else 0
        self.n_local = (sum(1 for dev in mesh.devices.flat
                            if dev.process_index == jax.process_index())
                        if self.procs > 1 else d)
        if self.procs > 1 and self.n_local * self.procs != d:
            raise ValueError("uneven devices per process not supported")

        self.batch_sharding = NamedSharding(mesh, P(SHARD_AXIS, None, None))
        self.state_sharding = NamedSharding(mesh, P(SHARD_AXIS, None))
        self.repl_sharding = NamedSharding(mesh, P())

        n_ = self.n
        lift = self.lift_levels

        @partial(jax.jit,
                 in_shardings=(self.state_sharding, self.batch_sharding),
                 out_shardings=self.state_sharding)
        def deg_step(deg_all, batch):
            def f(deg_local, chunk_local):
                return degrees_ops.degree_chunk(
                    deg_local[0], chunk_local[0], n_)[None]
            return shard_map(f, mesh=mesh,
                             in_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS, None, None)),
                             out_specs=P(SHARD_AXIS, None))(deg_all, batch)

        @partial(jax.jit, out_shardings=self.repl_sharding)
        def deg_reduce(deg_all):
            return jnp.sum(deg_all, axis=0, dtype=jnp.int32)

        @partial(jax.jit, out_shardings=(self.repl_sharding, self.repl_sharding))
        def make_order(deg_total):
            return order_ops.elimination_order(deg_total, n_)

        seg_ = self.segment_rounds

        @partial(jax.jit,
                 in_shardings=(self.batch_sharding, self.repl_sharding),
                 out_shardings=(self.state_sharding, self.state_sharding))
        def orient_step(batch, pos):
            def f(chunk_local, pos_):
                lo, hi = elim_ops.orient_edges_pos(chunk_local[0], pos_, n_)
                return lo[None], hi[None]
            return shard_map(
                f, mesh=mesh,
                in_specs=(P(SHARD_AXIS, None, None), P()),
                out_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS, None)))(
                    batch, pos)

        def _make_fold_seg(small: bool, warm_levels: int = 0,
                           warm_rounds: int = 0):
            """Segment step over whatever active-buffer width the inputs
            have (one compiled program per width). Everything is POSITION
            SPACE (tables P[p] = parent position, actives = position
            pairs), so the compiled programs carry no pos/order tables
            and no per-segment conversion gathers — the orient step maps
            in, and the caller converts the merged table out once.
            ``small`` selects jump-mode rounds (no O(V) lifting-table
            rebuild) for the compacted tail. Returns carried state +
            pmax'd any-device-changed flag and max live count,
            replicated, so every device AND process makes the same host
            decision."""
            @partial(jax.jit,
                     in_shardings=(self.state_sharding, self.state_sharding,
                                   self.state_sharding),
                     out_shardings=(self.state_sharding, self.state_sharding,
                                    self.state_sharding, self.repl_sharding,
                                    self.repl_sharding, self.repl_sharding))
            def fold_seg_step(P_all, lo_all, hi_all):
                def f(P_local, lo_local, hi_local):
                    if small:
                        lo2, hi2, Pn, sv = \
                            elim_ops.fold_segment_small_pos(
                                P_local[0], lo_local[0], hi_local[0], n_,
                                segment_rounds=max(seg_, 64))
                    elif warm_levels:
                        lo2, hi2, Pn, sv = \
                            elim_ops.fold_segment_pos(
                                P_local[0], lo_local[0], hi_local[0], n_,
                                lift_levels=warm_levels,
                                segment_rounds=warm_rounds,
                                descent="stream")
                    else:
                        lo2, hi2, Pn, sv = \
                            elim_ops.fold_segment_pos(
                                P_local[0], lo_local[0], hi_local[0], n_,
                                lift_levels=lift, segment_rounds=seg_)
                    # sv = (changed, rounds, live) computed in-program;
                    # rounds ride out pmax'd (lockstep wall = slowest
                    # device) for the O(Δ) update instrumentation
                    any_changed = lax.pmax(sv[0], SHARD_AXIS)
                    max_live = lax.pmax(sv[2], SHARD_AXIS)
                    rounds_mx = lax.pmax(sv[1], SHARD_AXIS)
                    return (Pn[None], lo2[None], hi2[None], any_changed,
                            max_live, rounds_mx)
                return shard_map(
                    f, mesh=mesh,
                    in_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS, None),
                              P(SHARD_AXIS, None)),
                    out_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS, None),
                               P(SHARD_AXIS, None), P(), P(), P()))(
                        P_all, lo_all, hi_all)
            return fold_seg_step

        # pmax'd live count of a (D, W) active buffer — one tiny
        # replicated scalar, no fold. Lets the merge right-size a
        # received buffer BEFORE paying a full-width fold segment (merge
        # buffers are usually nearly empty: O(boundary) pairs in an
        # O(V)-capacity exchange). One instance serves every width: jit
        # caches an executable per input shape.
        @partial(jax.jit, in_shardings=(self.state_sharding,),
                 out_shardings=self.repl_sharding)
        def live_count(lo_all):
            def f(lo_local):
                live = jnp.sum(lo_local[0] != n_, dtype=jnp.int32)
                return lax.pmax(live, SHARD_AXIS)
            return shard_map(
                f, mesh=mesh, in_specs=(P(SHARD_AXIS, None),),
                out_specs=P())(lo_all)

        def _make_compact(to_size: int):
            @partial(jax.jit,
                     in_shardings=(self.state_sharding, self.state_sharding),
                     out_shardings=(self.state_sharding, self.state_sharding))
            def compact_step(lo_all, hi_all):
                def f(lo_local, hi_local):
                    lo2, hi2 = elim_ops.compact_actives(
                        lo_local[0], hi_local[0], n_, to_size)
                    return lo2[None], hi2[None]
                return shard_map(
                    f, mesh=mesh,
                    in_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS, None)),
                    out_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS, None)))(
                        lo_all, hi_all)
            return compact_step

        self.orient_step = orient_step
        self._fold_full = _make_fold_seg(False)
        self._fold_small = _make_fold_seg(True)
        self._live_count = live_count
        self._fold_warm = [
            _make_fold_seg(False, warm_levels=wl, warm_rounds=wr)
            for wr, wl in self.warm_schedule]
        self._make_compact = _make_compact
        self._compact_cache: dict = {}

        d_ = self.n_devices
        r_ = self.rounds

        def _make_exchange(cap0: int, r: int):
            """One butterfly exchange round, as its own jitted step: each
            device ships its forest to its XOR partner and receives the
            partner's as an ACTIVE CONSTRAINT buffer for the host-driven
            adaptive fold. In position space a table entry p -> P[p] IS
            the constraint (loP=p, hiP=P[p]) — no order lookup anywhere.

            ``cap0`` = per-round payload capacity (entries); 0 means dense
            (ship the whole O(V) table). Compact rounds ship
            (position, parent-position) pairs of the non-sentinel entries
            only — SURVEY.md §7 hard part #4's O(boundary) traffic.
            Capacity doubles per round: a merged forest has at most
            count_A + count_B parent entries, so cap0 >= the initial max
            occupancy makes cap0 * 2^r sufficient for round r — checked
            on host before selecting this path. Once 2 * cap is no
            smaller than the table itself, the round ships dense."""
            perm = [(i, i ^ (1 << r)) for i in range(d_)
                    if (i ^ (1 << r)) < d_]
            cap = min(cap0 << r, n_ + 1) if cap0 else n_ + 1
            compact = 2 * cap < n_ + 1

            @partial(jax.jit,
                     in_shardings=(self.state_sharding,),
                     out_shardings=(self.state_sharding, self.state_sharding))
            def exchange(P_all):
                def f(P_local):
                    table = P_local[0]
                    idx = lax.axis_index(SHARD_AXIS)
                    valid = (idx ^ (1 << r)) < d_
                    if compact:
                        sel = jnp.nonzero(table[:n_] != n_, size=cap,
                                          fill_value=n_)[0].astype(jnp.int32)
                        # fill slots index the sentinel: table[n] == n
                        payload = jnp.stack([sel, table[sel]])
                        recv = lax.ppermute(payload, SHARD_AXIS, perm)
                        # out-of-range XOR partners receive zeros;
                        # neutralize to the inert (n, n) pair
                        recv = jnp.where(valid, recv, jnp.int32(n_))
                        lo, hi = recv[0], recv[1]
                        bad = (lo >= n_) | (hi >= n_)
                        lo = jnp.where(bad, n_, lo)
                        hi = jnp.where(bad, n_, hi)
                    else:
                        other = lax.ppermute(table, SHARD_AXIS, perm)
                        other = jnp.where(valid, other, jnp.int32(n_))
                        p = jnp.arange(n_ + 1, dtype=jnp.int32)
                        has = other < n_
                        lo = jnp.where(has, p, n_)
                        hi = jnp.where(has, other, n_)
                    return lo[None], hi[None].astype(jnp.int32)
                return shard_map(
                    f, mesh=mesh,
                    in_specs=(P(SHARD_AXIS, None),),
                    out_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS, None)))(
                        P_all)
            return exchange

        @partial(jax.jit, out_shardings=self.repl_sharding)
        def extract_merged(P_all):
            return P_all[0]

        @partial(jax.jit, out_shardings=self.repl_sharding)
        def to_minp(P_repl, pos):
            """Replicated position-space table -> vertex-space minp (the
            stable checkpoint/result encoding)."""
            return P_repl[pos]

        self._make_exchange = _make_exchange
        self._exchange_cache: dict = {}
        self._extract_merged = extract_merged
        self.to_minp = to_minp

        @partial(jax.jit, out_shardings=self.repl_sharding)
        def max_occupancy(forest_all):
            """Largest per-device count of non-sentinel forest entries —
            one tiny all-reduce, used to pick the compact-merge capacity."""
            return jnp.max(jnp.sum((forest_all[:, :n_] != n_)
                                   .astype(jnp.int32), axis=1))

        self.max_occupancy = max_occupancy

        @partial(jax.jit,
                 in_shardings=(self.batch_sharding, self.repl_sharding),
                 out_shardings=self.repl_sharding)
        def score_step(batch, assign):
            """Per-batch (cut, total) summed over devices (comm point 3)."""
            def f(chunk_local, assign_):
                c, t = score_ops.score_chunk(chunk_local[0], assign_, n_)
                return lax.psum(jnp.stack([c, t])[None], SHARD_AXIS)
            return shard_map(
                f, mesh=mesh,
                in_specs=(P(SHARD_AXIS, None, None), P()),
                out_specs=P(SHARD_AXIS, None))(batch, assign)[0]

        self.deg_step = deg_step
        self.deg_reduce = deg_reduce
        self.make_order = make_order
        self.score_step = score_step

        nb = self.dispatch_batch
        if nb > 1 or self.inflight > 1:
            self.block_sharding = NamedSharding(
                mesh, P(SHARD_AXIS, None, None))
            self.block_edges_sharding = NamedSharding(
                mesh, P(SHARD_AXIS, None, None, None))

            @partial(jax.jit,
                     in_shardings=(self.block_edges_sharding,
                                   self.repl_sharding),
                     out_shardings=(self.block_sharding,
                                    self.block_sharding))
            def orient_batch_step(blocks, pos):
                def f(block_local, pos_):
                    lo, hi = jax.vmap(
                        lambda c: elim_ops.orient_edges_pos(c, pos_, n_))(
                            block_local[0])
                    return lo[None], hi[None]
                return shard_map(
                    f, mesh=mesh,
                    in_specs=(P(SHARD_AXIS, None, None, None), P()),
                    out_specs=(P(SHARD_AXIS, None, None),
                               P(SHARD_AXIS, None, None)))(blocks, pos)

            # per-execution round budget: the same allowance the
            # per-segment loop would spread over nb segment syncs
            br = max(1, seg_) * nb

            def _fold_batch(P_all, loB_all, hiB_all):
                def f(P_local, loB_local, hiB_local):
                    loB2, hiB2, Pn, sv = elim_ops.batch_segment_fixpoint(
                        P_local[0], loB_local[0], hiB_local[0], n_,
                        lift_levels=lift, batch_rounds=br)
                    # lockstep: every device and process re-dispatches
                    # until the SLOWEST device's block is drained (pmin
                    # of segments-done); rounds/live are pmax'd, retires
                    # psum'd — one replicated word, one host pull
                    done_all = lax.pmin(sv[0], SHARD_AXIS)
                    rounds_mx = lax.pmax(sv[1], SHARD_AXIS)
                    live_mx = lax.pmax(sv[2], SHARD_AXIS)
                    ret_sum = lax.psum(sv[3], SHARD_AXIS)
                    return (Pn[None], loB2[None], hiB2[None],
                            jnp.stack([done_all, rounds_mx, live_mx,
                                       ret_sum]))
                return shard_map(
                    f, mesh=mesh,
                    in_specs=(P(SHARD_AXIS, None),
                              P(SHARD_AXIS, None, None),
                              P(SHARD_AXIS, None, None)),
                    out_specs=(P(SHARD_AXIS, None),
                               P(SHARD_AXIS, None, None),
                               P(SHARD_AXIS, None, None), P()))(
                        P_all, loB_all, hiB_all)

            _shardings = dict(
                in_shardings=(self.state_sharding, self.block_sharding,
                              self.block_sharding),
                out_shardings=(self.state_sharding, self.block_sharding,
                               self.block_sharding, self.repl_sharding))

            self.orient_batch_step = orient_batch_step
            self.fold_batch_step = jax.jit(_fold_batch, **_shardings)
            # donated twin: per-device tables + staging blocks alias
            # into the outputs (callers rebind, like the chain driver)
            self.fold_batch_step_donated = jax.jit(
                _fold_batch, donate_argnums=(0, 1, 2), **_shardings)

    SMALL_SIZE = 1 << 14

    def build_step_batch(self, P_all, blocks_dev, pos, stats=None):
        """Fold ``dispatch_batch`` staged sharded batches — a
        (D, N, C, 2) edge block — into the per-device forests with ONE
        replicated stats pull per bounded batched execution (vs one
        ``changed`` pull per segment step in :meth:`build_step`).

        With ``inflight`` > 1, up to that many executions run in flight:
        each speculatively re-dispatches the previous one's output
        blocks before its stats word is pulled (the not-yet-converged
        assumption), and the words are read one-behind. When a pull
        reveals the blocks had drained, the unread speculations are
        discarded — their output is the bit-identical re-confirmation
        of the drained state (all-sentinel rows re-confirm in one round
        each and leave the tables untouched), so adopting the chain tip
        IS resuming from the confirmed carry. Deterministic on the
        replicated word, so every process issues and discards the same
        executions and the collective schedules never skew.

        Scope note: the speculation here is per-GROUP (this method
        still drains before returning), so a group that converges in
        its first execution pays one discarded re-confirm program — a
        deliberate trade: the discard is N cheap all-sentinel rounds,
        the hidden cost is the replicated sv pull's host round-trip
        (the dominant per-group tax when the host link is slow).
        Cross-group chaining as in the single-device
        fold_segments_pipelined would need the lockstep run() loop
        restructured around a shared chain — left for a future PR."""
        import time

        from collections import deque

        from sheep_tpu.ops.elim import _seed_ms_counters, _t_ms
        from sheep_tpu.utils import fault

        loB, hiB = self.orient_batch_step(blocks_dev, pos)
        fold = self.fold_batch_step_donated if self.donate \
            else self.fold_batch_step
        if stats is not None:
            _seed_ms_counters(stats)
            stats["folded_bytes"] = stats.get("folded_bytes", 0) \
                + int(blocks_dev.size) * 4
        tip = (P_all, loB, hiB)
        fifo: deque = deque()
        idle_since = None
        issued = {"n": 0}

        def issue():
            nonlocal tip, idle_since
            # dispatch-time injection point (ISSUE 9): unwinds the whole
            # group with the donated chain un-drained, like a real
            # allocation failure inside fold(); recoverable kinds only
            # single-process (a one-rank retry would skew collectives)
            issued["n"] += 1
            fault.maybe_fail(
                "dispatch", issued["n"],
                kinds=("oom", "device") if self.procs == 1 else ())
            if idle_since is not None and stats is not None:
                _t_ms(stats, "device_gap_ms",
                      time.perf_counter() - idle_since)
            idle_since = None
            prev = tip
            P2, lo2, hi2, sv = fold(*prev)
            if self.donate:
                # SHEEP_SANITIZE: the chained per-device tables and
                # staging blocks must really be poisoned (metadata-only
                # is_deleted probe, never the dead buffers' contents)
                sanitize.check_donated(
                    *prev,  # sheeplint: donate-ok
                    origin="fold_batch_step_donated")
            tip = (P2, lo2, hi2)
            fifo.append(sv)

        # SHEEP_SANITIZE: between the one-behind replicated word pulls
        # every device value must stay an unread future — a stray sync
        # here would also skew the multi-process collective schedules
        with sanitize.guard("sharded-dispatch"):
            while True:
                while len(fifo) < self.inflight:
                    issue()
                sv = fifo.popleft()
                t_pull = time.perf_counter()
                with sanitize.sync_ok("sharded-sv-pull"):
                    done, r, live, ret = \
                        (int(x) for x in np.asarray(sv))  # sheeplint: sync-ok
                now = time.perf_counter()
                if not fifo:
                    idle_since = now
                if stats is not None:
                    _t_ms(stats, "host_blocked_ms", now - t_pull)
                    stats["host_syncs"] = stats.get("host_syncs", 0) + 1
                    stats["batch_execs"] = \
                        stats.get("batch_execs", 0) + 1
                    stats["batch_retired"] = \
                        stats.get("batch_retired", 0) + ret
                    # max over devices: the lockstep wall is the
                    # slowest one
                    stats["device_rounds"] = \
                        stats.get("device_rounds", 0) + r
                if done >= self.dispatch_batch:
                    if fifo and stats is not None:
                        stats["inflight_discards"] = \
                            stats.get("inflight_discards", 0) + len(fifo)
                    fifo.clear()
                    return tip[0]

    def _fold_actives(self, P_all, lo_all, hi_all, skip_warm: bool = False,
                      stats=None):
        """Adaptive host-driven fold of (D, W) active-constraint buffers
        into the per-device forests (same unique forests as a monolithic
        while_loop): compact every device's buffer to the same smaller
        power-of-2 width when the pmax live count collapses, and run the
        compacted tail in jump-mode (O(C') per round, no O(V)
        lifting-table rebuild). The pmax'd flags keep all devices and
        processes in lockstep; a host tail is not used here because the
        forests are per-device (pulling D of them would cost O(V*D)
        transfers) — the jump-mode tail is the sharded equivalent.
        ``skip_warm`` (merge folds): the buffer was already right-sized
        by the caller, go straight to the resolved schedule. ``stats``
        (if given) accumulates the per-segment lockstep pulls
        (``host_syncs``) and the pmax'd device round count
        (``device_rounds``) — the O(Δ) update-cost instrumentation."""
        size = int(lo_all.shape[-1])
        warm = [] if skip_warm else list(self._fold_warm)
        with sanitize.guard("sharded-fold"):
            while True:
                if warm and size > self.SMALL_SIZE:
                    step = warm.pop(0)
                elif size <= self.SMALL_SIZE:
                    step = self._fold_small
                else:
                    step = self._fold_full
                P_all, lo_all, hi_all, changed, max_live, rounds = step(
                    P_all, lo_all, hi_all)
                # the designed per-segment lockstep pull: one
                # replicated (changed, live) pair per bounded segment
                with sanitize.sync_ok("sharded-segment-pull"):
                    done = not int(changed)  # sheeplint: sync-ok
                    live = int(max_live)  # sheeplint: sync-ok
                    if stats is not None:
                        stats["host_syncs"] = \
                            stats.get("host_syncs", 0) + 1
                        stats["device_rounds"] = \
                            stats.get("device_rounds", 0) \
                            + int(rounds)  # sheeplint: sync-ok
                if done:
                    return P_all
                if size > self.SMALL_SIZE and live <= size // 4:
                    lo_all, hi_all, size = self._compact_to(
                        lo_all, hi_all, live, size)

    def _compact_to(self, lo_all, hi_all, live: int, size: int):
        """Compact (D, size) buffers to the cached power-of-2 program for
        ``2 * live`` (no-op when that is not smaller). One home for the
        capacity rule + program cache shared by the chunk fold and the
        merge's pre-fold right-sizing."""
        new_size = elim_ops.pow2_at_least(2 * live, floor=self.SMALL_SIZE)
        if new_size >= size:
            return lo_all, hi_all, size
        fn = self._compact_cache.get(new_size)
        if fn is None:
            fn = self._compact_cache[new_size] = self._make_compact(new_size)
        lo_all, hi_all = fn(lo_all, hi_all)
        return lo_all, hi_all, new_size

    def build_step(self, P_all, batch_dev, pos, stats=None):
        """Fold one sharded batch into the per-device forests. ``stats``
        (if given) accumulates the fold counters (host_syncs /
        device_rounds via :meth:`_fold_actives`) plus the staged edge
        bytes (``folded_bytes``) — the same cost triple the batched
        path reports, so per-segment builds and delta folds are
        comparable against it."""
        lo_all, hi_all = self.orient_step(batch_dev, pos)
        if stats is not None:
            stats["folded_bytes"] = stats.get("folded_bytes", 0) \
                + int(batch_dev.size) * 4
        return self._fold_actives(P_all, lo_all, hi_all, stats=stats)

    # -- host->device placement (multi-host aware) -------------------------
    def _put(self, sharding, arr):
        """Single process: plain device_put. Multi-host: every process
        passes its process-local rows (or the full array for replicated
        shardings) and JAX assembles the global array. A batch that is
        ALREADY a device array (device-stream synthesis,
        :func:`device_lockstep_batches` — single-process only) relays
        through a device-side device_put: a no-op at the right
        sharding, a D2D re-lay otherwise, never a host crossing."""
        if isinstance(arr, jax.Array):
            return jax.device_put(arr, sharding)
        if self.procs == 1:
            return jax.device_put(arr, sharding)
        return jax.make_array_from_process_local_data(sharding, arr)

    # -- adaptive tree merge (comm point 2) --------------------------------
    def merge(self, P_all, stats: Optional[dict] = None):
        """Merge the per-device forests into the global tree (all in
        position space; callers convert via :func:`to_minp` when they
        need the stable vertex-space encoding).

        Host-driven butterfly: log2(D) rounds, each one jitted exchange
        step (ppermute of the forest — compact boundary pairs or the
        dense table) followed by the shared adaptive fold of the received
        constraints. No unbounded device execution anywhere (the old
        all-in-one-program butterfly ran log2(D) full fixpoints in a
        single call — exactly the long-execution shape that crashes TPU
        worker watchdogs).

        Picks compact (boundary-only pairs) vs dense (full table) shipping
        from one tiny occupancy all-reduce: sparse shards move O(boundary)
        bytes over ICI instead of O(V) per round (SURVEY.md §7 hard part
        #4). Exchange programs are cached per (capacity, round), so at
        most log2(V) * log2(D) exist across a whole run. ``stats`` (if
        given) accumulates the payload byte count actually shipped.
        """
        cap0 = 0
        if self.rounds:
            # one tiny designed all-reduce pull to pick compact vs dense
            with sanitize.sync_ok("merge-occupancy"):
                cnt = int(self.max_occupancy(P_all))  # sheeplint: sync-ok
            c = elim_ops.pow2_at_least(cnt, floor=1024)
            if 2 * c < self.n + 1:
                cap0 = c
        for r in range(self.rounds):
            fn = self._exchange_cache.get((cap0, r))
            if fn is None:
                fn = self._exchange_cache[(cap0, r)] = \
                    self._make_exchange(cap0, r)
            lo_all, hi_all = fn(P_all)
            # received buffers are usually nearly empty (O(boundary)
            # pairs in the exchange's power-of-2 capacity): right-size
            # BEFORE the first fold segment instead of paying one
            # full-width round to discover the live count, and skip the
            # chunk-oriented warm schedule (warm rounds earn their keep
            # on fresh C-width chunks, not on a boundary tail)
            with sanitize.sync_ok("merge-live-count"):
                live = int(self._live_count(lo_all))  # sheeplint: sync-ok
            if live == 0:
                continue
            lo_all, hi_all, _ = self._compact_to(
                lo_all, hi_all, live, int(lo_all.shape[-1]))
            P_all = self._fold_actives(P_all, lo_all, hi_all,
                                       skip_warm=True)
        merged = self._extract_merged(P_all)
        if stats is not None:
            total = 0
            for r in range(self.rounds):
                cap = min(cap0 << r, self.n + 1) if cap0 else self.n + 1
                words = 2 * cap if 2 * cap < self.n + 1 else self.n + 1
                links = sum(1 for i in range(self.n_devices)
                            if (i ^ (1 << r)) < self.n_devices)
                total += 4 * words * links
            stats["merge_payload_bytes"] = \
                stats.get("merge_payload_bytes", 0) + total
            stats["merge_mode"] = "compact" if cap0 else "dense"
        return merged

    # -- state constructors ------------------------------------------------
    def init_degrees(self):
        return self._put(self.state_sharding,
                         np.zeros((self.n_local, self.n + 1), np.int32))

    def init_forest(self):
        return self._put(self.state_sharding,
                         np.full((self.n_local, self.n + 1), self.n, np.int32))

    def put_batch(self, batch: np.ndarray):
        return self._put(self.batch_sharding, batch)

    def put_replicated(self, arr):
        return self._put(self.repl_sharding, np.asarray(arr))

    def _use_byte_range(self, stream) -> bool:
        return use_byte_range(stream, self.procs)

    # -- lockstep batch iteration ------------------------------------------
    def _device_synth(self, stream) -> bool:
        """True when this run ingests by on-device synthesis (ISSUE 12):
        a device stream under a single process. Multi-host keeps the
        host lockstep path (per-process global-array assembly takes
        host rows, and every process must agree on the ingest mode)."""
        return self.procs == 1 and is_device_stream(stream)

    def iter_batches(self, stream, start_chunk: int = 0, stats=None):
        """Process-local lockstep batches (see iter_batches_lockstep):
        host (rows, C, 2) arrays, or pre-placed GLOBAL device batches
        when the input is a device stream (``_put`` relays those
        without a host crossing)."""
        if self._device_synth(stream):
            yield from device_lockstep_batches(
                stream, self.cs, self.n_local, self.n,
                self.batch_sharding, start_chunk=start_chunk,
                stats=stats)
            return
        yield from iter_batches_lockstep(
            stream, self.cs, self.n_local, self.n, self.proc, self.procs,
            start_chunk=start_chunk, byte_range=self._use_byte_range(stream))

    def _staged_batches(self, stream, start_chunk: int = 0, stats=None,
                        group: int = 0):
        """Context-managed batch supplier for the streaming loops:
        prefetch for host-format streams (read/parse/pad overlaps
        device work on a worker thread), :class:`_PassThrough` for
        device-synth streams (buffering global device arrays in a
        worker queue would hold unmodeled HBM, and there is no host
        I/O to overlap). ``group`` > 0 yields lists of up to that many
        batches (the batched dispatch's staging unit)."""
        from sheep_tpu.utils.prefetch import prefetch, prefetch_batched

        it = self.iter_batches(stream, start_chunk=start_chunk,
                               stats=stats)
        if self._device_synth(stream):
            return _PassThrough(_grouped(it, group) if group else it)
        return prefetch_batched(it, group) if group else prefetch(it)

    # -- full run (single process; multi-host callers drive the steps) -----
    def run(self, stream, k: int, alpha: float = 1.0,
            weights: Optional[str] = "unit", comm_volume: bool = False,
            timings: Optional[dict] = None, checkpointer=None,
            resume: bool = False):
        """Drive the whole sharded pipeline over the stream.

        This is the single implementation of the streaming loops; backends
        wrap it and convert the result dict. ``timings`` (if given) is
        filled with per-phase seconds. ``checkpointer`` saves O(V) state
        every ``checkpointer.every`` batches; ``resume`` restarts from it.
        """
        import time

        from sheep_tpu.core import pure
        from sheep_tpu.ops import score as score_ops
        from sheep_tpu.ops.split import tree_split_host
        from sheep_tpu.utils import checkpoint as ckpt
        from sheep_tpu.utils import retry as retry_mod
        from sheep_tpu.utils import watchdog as wd_mod
        from sheep_tpu.utils.fault import maybe_fail

        t = timings if timings is not None else {}
        n, cs, d = self.n, self.cs, self.n_devices
        ckpt_degraded0 = ckpt.degraded_events()
        meta = ckpt.stream_meta(stream, k, cs, weights=weights, alpha=alpha,
                                comm_volume=comm_volume,
                                state_format="sharded", devices=d,
                                procs=self.procs,
                                text_byte_range=self._use_byte_range(stream))
        # multi-host: a fingerprint mismatch must NOT raise per-process
        # here — that would strand the other processes in the reconcile
        # allgather; the sentinel makes reconcile raise collectively
        state = ckpt.resume_state(checkpointer, meta, resume,
                                  raise_on_mismatch=self.procs == 1)
        if self.procs > 1 and checkpointer is not None and resume:
            # per-process manifests may be skewed by one save step; agree
            # on a common step or the collective schedules desynchronize
            state = ckpt.reconcile_multihost_resume(checkpointer, state, meta)
        from_phase = ckpt.phase_index(state.phase) if state else 0

        root_sp = obs.begin("partition", backend="tpu-sharded", k=int(k),
                            n=int(n), devices=int(d),
                            dispatch_batch=int(self.dispatch_batch),
                            inflight=int(self.inflight))
        stats_acc = obs.stats_accumulator()
        merge_acc = obs.stats_accumulator()
        m_cheap = stream.num_edges_cheap
        obs.progress(backend="tpu-sharded", k=int(k), edges_total=m_cheap)

        # ONE build-stats record across the streaming passes, so the
        # ingest counters (device_stream_chunks / h2d_staged_bytes,
        # ISSUE 12) accumulate wherever batches are synthesized
        build_stats: dict = {}
        # out-of-core residency plane (ISSUE 20): under an explicit
        # SHEEP_CACHE_BYTES budget, device batches admitted during the
        # build pass (keyed by absolute chunk index) serve the score
        # pass — and intra-attempt retries — from HBM instead of
        # re-uploading, with checkpoint boundaries as eviction points
        # and spill-before-shrink on RESOURCE faults (_on_resource).
        # Single-process host streams only: device-synth batches have
        # no upload to save, and multi-host residency would skew the
        # collective lockstep.
        rm = None
        if self.procs == 1 and not self._device_synth(stream):
            from sheep_tpu.utils.residency import manager_from_env
            rm = manager_from_env(stats=build_stats)
        # anchored-order inputs (delta: logs, ISSUE 19): the degrees
        # pass streams the BASE segment only — the order anchors to the
        # base degrees exactly as on the single-device backends — while
        # build and score stream the full surviving multiset (the
        # fixpoint is order-independent in the constraint multiset, so
        # the anchored order + full multiset reproduce the single-device
        # table bit for bit). A device-stream base keeps the zero-copy
        # ingest path for the anchor pass.
        anchored = bool(getattr(stream, "order_anchor", False))
        deg_stream = stream.anchor_stream() if anchored else stream
        # pass 1: degrees, int32 on device with int64 host flushes so no
        # per-vertex endpoint count can reach 2^31 between flushes
        t0 = time.perf_counter()
        sp = obs.begin("degrees+sort")
        obs.progress(phase="degrees", chunks_done=0, edges_done=0)
        flush_every = max(1, (2**31 - 1) // max(2 * cs * d, 1))
        if state:
            deg_host = state.arrays["deg"].copy()
        else:
            deg_host = np.zeros(n, dtype=np.int64)
        if from_phase == 0:
            start = state.chunk_idx if state else 0
            deg_all = self.init_degrees()
            since = batches = 0
            with wd_mod.watched(self.procs, "sharded-degrees",
                                self.proc) as wd, \
                    self._staged_batches(deg_stream, start,
                                         build_stats) as pf:
                # with-exit = deterministic worker cancel on exception
                # unwind (fault injection, checkpoint IO)
                for batch in pf:
                    deg_all = self.deg_step(deg_all, self.put_batch(batch))
                    since += 1
                    batches += 1
                    wd.touch(f"degrees batch {batches}")
                    maybe_fail("degrees", batches, kinds=("kill", "stall"))
                    obs.chunk_progress(batches * d, cs, m_cheap)
                    # cadence is in *chunks* (one batch = d chunks),
                    # matching the single-device backends and the
                    # --checkpoint-every doc
                    at_ckpt = (checkpointer is not None and
                               checkpointer.due_span((batches - 1) * d,
                                                     batches * d))
                    if since >= flush_every or at_ckpt:
                        deg_host += np.asarray(  # sheeplint: sync-ok
                            self.deg_reduce(deg_all)[:n], dtype=np.int64)
                        deg_all = self.init_degrees()
                        since = 0
                    if at_ckpt:
                        checkpointer.save("degrees", start + batches * d,
                                          {"deg": deg_host}, meta)
            deg_host += np.asarray(  # sheeplint: sync-ok
                self.deg_reduce(deg_all)[:n], dtype=np.int64)
        # positions are ordinal: rank-compress if totals exceed int32
        if deg_host.size and deg_host.max() >= 2**31:
            deg_rank = np.argsort(np.argsort(deg_host, kind="stable"),
                                  kind="stable")
        else:
            deg_rank = deg_host
        deg_total = self.put_replicated(
            np.concatenate([deg_rank, [0]]).astype(np.int32))
        pos, order = self.make_order(deg_total)
        pos.block_until_ready()
        t["degrees+sort"] = time.perf_counter() - t0
        sp.end()

        # pass 2: per-device forests, then butterfly merge (comm point 2).
        # Device state is position-space (P tables); checkpoints and the
        # returned forest keep the stable vertex-space minp encoding, so
        # conversions (one replicated gather each way) happen only at
        # checkpoint/phase boundaries.
        t0 = time.perf_counter()
        sp = obs.begin("build+merge")
        obs.progress(phase="build", chunks_done=0, edges_done=0)
        merge_stats: dict = {}
        # fault kinds the per-batch injection points can absorb: the
        # in-process retry below only runs single-process (a one-rank
        # retry would desynchronize the collective schedules), so chaos
        # only offers the recoverable kinds there; multi-host points
        # offer kill (the PR-8 contract) and stall (the watchdog's prey)
        bkinds = ("kill", "oom", "device", "stall") if self.procs == 1 \
            else ("kill", "stall")
        if state and from_phase >= 2:
            merged_minp = jnp.asarray(state.arrays["merged"])
        else:
            # fault-tolerant build (ISSUE 9): one retryable attempt
            # against an in-memory snapshot — the merged O(V) forest +
            # next chunk index, exactly a checkpoint's payload, banked
            # at every save. Build checkpoints store the O(V) *merged*
            # forest, not the O(V*d) per-device stack; merging is
            # associative and idempotent, so re-seeding one shard with
            # it (others empty) reproduces the identical fixpoint.
            # Multi-host: each process provides its local rows; the
            # merged forest rides in global row 0 (process 0).
            snap = {"idx": 0, "merged": None}
            if state and state.phase == "build":
                snap["idx"] = state.chunk_idx
                snap["merged"] = state.arrays["merged_partial"]

            def _build_attempt():
                rows = self.n_local
                fa = np.full((rows, n + 1), n, np.int32)
                if snap["merged"] is not None and self.proc == 0:
                    # vertex-space snapshot -> position space, host-side
                    # (no device round-trip, no eager op on a global
                    # array)
                    fa[0] = np.asarray(  # sheeplint: sync-ok
                        snap["merged"],
                        dtype=np.int32)[np.asarray(order)]  # sheeplint: sync-ok
                P_all = self._put(self.state_sharding, fa)
                start = snap["idx"]
                batches = 0
                with wd_mod.watched(self.procs, "sharded-build",
                                    self.proc) as wd:
                    if self.dispatch_batch > 1 or self.inflight > 1:
                        # batched segment dispatch: stage dispatch_batch
                        # sharded batches as one (rows, N, C, 2) block
                        # per process — the prefetch worker groups the
                        # lockstep batch stream, so every process stages
                        # identical groups and the pmin'd stats keep the
                        # collective schedules aligned
                        nb = self.dispatch_batch
                        build_stats["dispatch_batch"] = nb
                        build_stats["inflight_depth"] = self.inflight
                        empty = None
                        devsynth = self._device_synth(stream)
                        # with-exit = deterministic worker cancel on an
                        # exception unwind (fault injection, checkpoint
                        # IO), as in _device_chunk_groups
                        with self._staged_batches(stream, start,
                                                  build_stats,
                                                  group=nb) as pf:
                            for group in pf:
                                gl = len(group)
                                if gl < nb:
                                    if empty is None:
                                        # device-synth groups pad with a
                                        # device-resident sentinel batch
                                        # (no host block to upload)
                                        empty = jnp.full(
                                            (self.n_local, cs, 2), n,
                                            jnp.int32) if devsynth \
                                            else np.full(
                                                (self.n_local, cs, 2),
                                                n, np.int32)
                                    group = group + [empty] * (nb - gl)
                                blocks = jnp.stack(group, axis=1) \
                                    if devsynth \
                                    else np.stack(group, axis=1)
                                before = batches
                                dsp = obs.begin("dispatch", i=before,
                                                batches=gl)
                                try:
                                    P_all = self.build_step_batch(
                                        P_all,
                                        self._put(
                                            self.block_edges_sharding,
                                            blocks),
                                        pos, stats=build_stats)
                                finally:
                                    stats_acc.absorb(build_stats)
                                    dsp.end()
                                batches += gl
                                wd.touch(f"build batch {batches}")
                                obs.chunk_progress(batches * d, cs,
                                                   m_cheap)
                                for b in range(before + 1, batches + 1):
                                    maybe_fail("build", b, kinds=bkinds)
                                if checkpointer is not None and \
                                        checkpointer.due_span(
                                            before * d, batches * d):
                                    partial = np.asarray(self.to_minp(  # sheeplint: sync-ok
                                        self.merge(P_all,
                                                   stats=merge_stats),
                                        pos))
                                    snap["idx"] = start + batches * d
                                    snap["merged"] = partial
                                    checkpointer.save(
                                        "build", start + batches * d,
                                        {"deg": deg_host,
                                         "merged_partial": partial},
                                        meta)
                    else:
                        with self._staged_batches(stream, start,
                                                  build_stats) as pf:
                            for batch in pf:
                                seg_sp = obs.begin("segment", i=batches)
                                try:
                                    key = start + batches * d
                                    dev_batch = rm.get(key) \
                                        if rm is not None else None
                                    if dev_batch is None:
                                        dev_batch = self.put_batch(batch)
                                        if rm is not None:
                                            rm.admit(key, dev_batch,
                                                     int(batch.nbytes))
                                    P_all = self.build_step(
                                        P_all, dev_batch,
                                        pos, stats=build_stats)
                                finally:
                                    seg_sp.end()
                                batches += 1
                                wd.touch(f"build batch {batches}")
                                obs.chunk_progress(batches * d, cs,
                                                   m_cheap)
                                maybe_fail("build", batches,
                                           kinds=bkinds)
                                if checkpointer is not None and \
                                        checkpointer.due_span(
                                            (batches - 1) * d,
                                            batches * d):
                                    partial = np.asarray(self.to_minp(  # sheeplint: sync-ok
                                        self.merge(P_all,
                                                   stats=merge_stats),
                                        pos))
                                    snap["idx"] = start + batches * d
                                    snap["merged"] = partial
                                    checkpointer.save(
                                        "build", start + batches * d,
                                        {"deg": deg_host,
                                         "merged_partial": partial},
                                        meta)
                                    if rm is not None:
                                        # checkpoint boundary = eviction
                                        # point: retries never re-read
                                        # behind the confirmed index
                                        rm.boundary(start + batches * d)
                return P_all

            def _on_resource():
                nxt = retry_mod.degrade_dispatch(
                    n, cs, self.dispatch_batch, self.inflight,
                    self.donate, build_stats, snap["idx"],
                    residency=rm)
                if nxt is not None:
                    self.dispatch_batch, self.inflight = nxt

            def _save_snapshot():
                if checkpointer is not None and \
                        snap["merged"] is not None:
                    checkpointer.save(
                        "build", snap["idx"],
                        {"deg": deg_host,
                         "merged_partial": snap["merged"]}, meta)

            def _on_device_loss():
                retry_mod.recover_device_loss(build_stats, snap["idx"],
                                              _save_snapshot)

            policy = retry_mod.RetryPolicy()
            while True:
                try:
                    P_all = _build_attempt()
                    break
                except Exception as exc:
                    if self.procs > 1:
                        # a one-rank in-process retry would skew the
                        # collective schedules: multi-host keeps the
                        # fault->checkpoint->kill+resume contract
                        raise
                    # shared classify/budget/count/backoff protocol
                    # (retry.handle_build_fault); FATAL and exhausted
                    # budgets re-raise inside
                    retry_mod.handle_build_fault(
                        policy, exc, "sharded.build", build_stats,
                        on_resource=_on_resource,
                        on_device_loss=_on_device_loss)
                    stats_acc.absorb(build_stats)
            msp = obs.begin("merge", devices=int(d))
            merged_minp = self.to_minp(
                self.merge(P_all, stats=merge_stats), pos)
            # real completion barrier
            np.asarray(merged_minp[:1])  # sheeplint: sync-ok
            merge_acc.absorb(merge_stats)
            msp.end()
        t["build+merge"] = time.perf_counter() - t0
        stats_acc.absorb(build_stats)
        sp.end()

        # split on host over O(V) state
        t0 = time.perf_counter()
        with obs.span("split"):
            parent = elim_ops.minp_to_parent(merged_minp, order, n)
            pos_host = np.asarray(pos[:n])  # sheeplint: sync-ok
            w = deg_host.astype(np.float64) if weights == "degree" else None
            assign_host = tree_split_host(parent, pos_host, k, weights=w,
                                          alpha=alpha)
            assign = self.put_replicated(
                np.concatenate([assign_host.astype(np.int32),
                                np.zeros(1, np.int32)]))
            t["split"] = time.perf_counter() - t0

        # pass 3: scoring (comm point 3)
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
        batches = 0
        with wd_mod.watched(self.procs, "sharded-score",
                            self.proc) as wd, \
                self._staged_batches(stream, start, build_stats) as pf:
            for batch in pf:
                key = start + batches * d
                dev_batch = rm.get(key) if rm is not None else None
                if dev_batch is None:
                    dev_batch = self.put_batch(batch)
                    if rm is not None:
                        rm.admit(key, dev_batch, int(batch.nbytes))
                c, tt = np.asarray(  # sheeplint: sync-ok
                    self.score_step(dev_batch, assign))
                cut += int(c)
                total += int(tt)
                if comm_volume:
                    score_ops.accumulate_cv_keys(
                        cv_chunks,
                        score_ops.cut_pair_keys_host(batch, assign, n, k))
                batches += 1
                wd.touch(f"score batch {batches}")
                maybe_fail("score", batches, kinds=("kill", "stall"))
                obs.chunk_progress(batches * d, cs, m_cheap)
                if checkpointer is not None and \
                        checkpointer.due_span((batches - 1) * d,
                                              batches * d):
                    cv_chunks = ckpt.save_score_state(
                        checkpointer, start + batches * d, cut, total,
                        cv_chunks,
                        {"deg": deg_host,
                         "merged": np.asarray(merged_minp)},  # sheeplint: sync-ok
                        meta, comm_volume)
                    if rm is not None:
                        rm.boundary(start + batches * d)
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
        balance = pure.part_balance(assign_host, k,
                                    deg_host if weights == "degree" else None)
        t["score"] = time.perf_counter() - t0
        sp.end()
        root_sp.end()
        if checkpointer is not None:
            checkpointer.clear()
        if ckpt.degraded_events() > ckpt_degraded0:
            build_stats["checkpoint_degraded"] = \
                ckpt.degraded_events() - ckpt_degraded0
        return {
            "assignment": assign_host, "parent": parent, "pos": pos_host,
            "degrees": deg_host, "edge_cut": cut, "total_edges": total,
            "balance": balance, "comm_volume": cv, "k": k,
            "merge_stats": merge_stats, "build_stats": build_stats,
        }


# ---------------------------------------------------------------------------
# process-wide compiled-pipeline cache (ISSUE 19)
# ---------------------------------------------------------------------------
# Every ShardedPipeline() re-traces and re-compiles the whole per-shard
# program set (deg/orient/fold/merge/score close over n, the chunk shape
# and the shardings) — ~1.7 s per instance on the 8-way virtual mesh,
# paid per backend instance regardless of graph size. The pipeline is
# stateless across runs except the lazy program caches we WANT to share
# and ONE degrade path: a resource fault inside run() permanently lowers
# self.dispatch_batch/self.inflight, so a cache hit re-checks those
# against the requested shape and rebuilds if a prior run degraded them.
# Keyed on the full constructor signature plus the mesh's device ids;
# bounded LRU so long-lived processes don't pin dead programs.

_PIPE_CACHE: "OrderedDict[tuple, ShardedPipeline]" = OrderedDict()
_PIPE_CACHE_MAX = 24


def cached_pipeline(n: int, chunk_edges: int, mesh, lift_levels: int = 0,
                    segment_rounds: int = 32, warm_schedule=((1, 8),),
                    dispatch_batch: int = 1, inflight: int = 1,
                    donate: bool = False) -> ShardedPipeline:
    """ShardedPipeline with its compiled programs reused across backend
    instances (one-shot builds, resident epoch folds, compaction
    rebuilds — all hit the same programs for the same shape)."""
    key = (n, chunk_edges, tuple(d.id for d in mesh.devices.flat),
           lift_levels, segment_rounds, tuple(warm_schedule),
           max(1, int(dispatch_batch)), int(inflight), bool(donate))
    pipe = _PIPE_CACHE.get(key)
    if pipe is not None and (pipe.dispatch_batch != key[6]
                             or pipe.inflight != key[7]):
        del _PIPE_CACHE[key]  # degraded by a prior run's fault path
        pipe = None
    if pipe is None:
        pipe = ShardedPipeline(n, chunk_edges, mesh,
                               lift_levels=lift_levels,
                               segment_rounds=segment_rounds,
                               warm_schedule=warm_schedule,
                               dispatch_batch=dispatch_batch,
                               inflight=inflight, donate=donate)
        _PIPE_CACHE[key] = pipe
        while len(_PIPE_CACHE) > _PIPE_CACHE_MAX:
            _PIPE_CACHE.popitem(last=False)
    else:
        _PIPE_CACHE.move_to_end(key)
    return pipe
