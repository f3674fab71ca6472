"""JAX/TPU backend, registered as ``tpu`` (SURVEY.md §2, north star).

Single-device streaming pipeline (the sharded multi-device path lives in
``sheep_tpu/parallel``):

  pass 1  degrees        scatter-add per chunk           (device)
  sort    elim order     one int64 key sort              (device)
  pass 2  tree build     constraint-rewrite fixpoint     (device, O(V+C) + capped tables)
  split   tree split     two linear passes over O(V)     (host)
  pass 3  scoring        gathered counters               (device)

All chunk steps are jitted with static shapes (last chunk padded with the
sentinel vertex n), so the whole stream reuses one compiled program per
phase — no recompilation across chunks (SURVEY.md §7 hard part #3).
"""

from __future__ import annotations

import os
import time
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from sheep_tpu import obs
from sheep_tpu.backends.base import Partitioner, register
from sheep_tpu.io.devicestream import is_device_stream, note_device_chunks
from sheep_tpu.ops import degrees as degrees_ops
from sheep_tpu.ops import elim as elim_ops
from sheep_tpu.ops import order as order_ops
from sheep_tpu.ops import score as score_ops
from sheep_tpu.ops import split as split_ops
from sheep_tpu.types import PartitionResult, check_tpu_vertex_range
from sheep_tpu.utils.platform import device_identity
from sheep_tpu.utils.prefetch import H2DRing, prefetch, prefetch_batched
from sheep_tpu.utils.residency import ResidencyManager


def pad_chunk(chunk: np.ndarray, size: int, n: int) -> np.ndarray:
    """Pad a (c, 2) chunk to (size, 2) int32 with the sentinel vertex n.

    The sentinel is inert in every op: degree slot n is dropped, oriented
    edges (n, n) are inactive, scoring treats n as invalid.
    """
    c = np.asarray(chunk, dtype=np.int64)
    if np.any(c >= np.iinfo(np.int32).max):
        # backstop only: partition() rejects n > MAX_TPU_VERTICES up
        # front, so this fires only for ids beyond a user-supplied
        # (too-small) --num-vertices
        raise ValueError("vertex id >= 2^31 in chunk; ids must fit int32 "
                         "on TPU backends (use --backend cpu)")
    out = np.full((size, 2), n, dtype=np.int32)
    out[: len(c)] = c
    return out


class _ChunkCache:
    """Device-resident cache of padded edge chunks, shared by the three
    streaming passes (degrees / build / score).

    The pipeline reads the same chunks once per pass; without a cache
    every pass re-crosses the host->device link, a PCIe crossing per
    pass. Chunks are kept on device while they fit ``budget`` bytes; a
    graph bigger than the budget keeps a cached prefix and streams the
    rest, so the saving degrades gradually. Filling is prefix-ordered
    and exception-safe: chunk i is cached only with chunks [0, i)
    already cached, so a partially-filled cache is always a valid
    prefix of the stream."""

    def __init__(self, budget_bytes: int):
        self.budget = budget_bytes
        self.used = 0
        self.chunks: list = []
        self.complete = False


class _ChunkCacheReader:
    """Read-only view of a :class:`_ChunkCache` held by another job
    (ISSUE 16): serves the filler's cached prefix but never appends.
    A budget of -1 makes :func:`_device_chunks`' grow test false on
    the first chunk, so the prefix-fill invariant keeps exactly one
    writer while any number of interleaved jobs read — the daemon's
    dispatch thread serializes all access, so no further locking is
    needed. A reader that outruns the filler simply streams the rest
    itself (same chunks, no sharing benefit past the prefix)."""

    budget = -1

    def __init__(self, cache: "_ChunkCache"):
        self._cache = cache

    @property
    def chunks(self):
        return self._cache.chunks

    @property
    def used(self):
        return self._cache.used

    @property
    def complete(self):
        return self._cache.complete

    @complete.setter
    def complete(self, value):
        # unreachable via _device_chunks (a reader's grow flag drops
        # on the first chunk); forwarded rather than raising so a
        # future caller setting it stays benign
        self._cache.complete = value


def _upload_chunks(stream, cs: int, n: int, start_chunk: int,
                   ring: int = 1, stats=None):
    """Padded (cs, 2) int32 DEVICE chunks from ``start_chunk`` on.

    Device streams (:mod:`sheep_tpu.io.devicestream` protocol —
    counter-based generators like
    :class:`~sheep_tpu.io.generators.RmatHashStream`) materialize each
    chunk directly in device memory — no host generation, no
    host->device upload, zero host bytes per chunk. File/memory
    streams take the staged path: read + parse + pad of upcoming
    chunks on the prefetch worker, with up to ``ring`` pre-padded
    blocks' device_put transfers issued ahead of the dispatch chain
    (:class:`~sheep_tpu.utils.prefetch.H2DRing`) — the synchronous
    ``jnp.asarray`` this replaces serialized every transfer into the
    dispatch critical path. ``stats`` collects the ingest counters
    (``h2d_staged_ms`` / ``h2d_blocked_ms`` / ``h2d_staged_bytes`` /
    ``device_stream_chunks``)."""
    if is_device_stream(stream):
        for i in range(start_chunk, stream.num_device_chunks(cs)):
            note_device_chunks(stats)
            yield stream.device_chunk(i, cs, n)
        return
    with prefetch(pad_chunk(c, cs, n)
                  for c in stream.chunks(cs, start_chunk=start_chunk)) as pf, \
            H2DRing(pf, depth=max(1, ring), stats=stats) as staged:
        # with-scope = the structural close the resource rule checks:
        # a consumer abandoning this generator closes the ring (its
        # staged HBM drains) and pf deterministically
        for dev in staged:
            yield dev


def _residency_chunks(stream, cs: int, n: int, rm, start_chunk: int,
                      ring: int = 1, stats=None):
    """Serve chunks through the residency manager (ISSUE 20): resident
    ids come straight from HBM; the first miss falls through to the
    stream (the disk tier — every chunk is reconstructible from its
    on-disk bytes), re-uploading and re-offering each chunk for
    residence. The chunk just yielded stays LEASED while it is the
    freshest serve; the lease is dropped just before the NEXT
    admission so the eviction scans that admission may trigger see it
    as reclaimable — dropping the head anchor instead (because the
    tail chunk was pinned) would cost every later pass its prefix
    hits. Correctness never depends on the lease: eviction only drops
    the manager's reference, and a consumer still folding the chunk
    keeps the device buffer alive through its own reference."""
    idx = start_chunk
    leased = None
    try:
        while True:
            ref = rm.get(idx)
            if ref is None:
                break
            rm.lease(idx)
            if leased is not None:
                rm.release(leased)
            leased = idx
            yield ref
            idx += 1
        if not rm.complete:
            for d in _upload_chunks(stream, cs, n, idx, ring, stats):
                if leased is not None:
                    rm.release(leased)
                    leased = None
                rm.admit(idx, d, int(d.size) * 4)
                rm.lease(idx)
                leased = idx
                yield d
                idx += 1
            if start_chunk == 0:
                rm.note_stream_end(idx)
    finally:
        if leased is not None:
            rm.release(leased)


def _device_chunks(stream, cs: int, n: int, cache, start_chunk: int,
                   ring: int = 1, stats=None):
    """Yield padded (cs, 2) int32 chunks as DEVICE arrays, serving and
    filling ``cache`` when iterating from the stream head. ``cache``
    is a legacy prefix :class:`_ChunkCache` (or a reader view), a
    :class:`~sheep_tpu.utils.residency.ResidencyManager` (eviction +
    reload — the out-of-core regime), or None."""
    if isinstance(cache, ResidencyManager):
        yield from _residency_chunks(stream, cs, n, cache, start_chunk,
                                     ring, stats)
        return
    if cache is None or start_chunk != 0:
        yield from _upload_chunks(stream, cs, n, start_chunk, ring, stats)
        return
    yield from cache.chunks
    if cache.complete:
        return
    grow = True
    for d in _upload_chunks(stream, cs, n, len(cache.chunks), ring, stats):
        nb = int(d.size) * 4
        if grow and cache.used + nb <= cache.budget:
            cache.chunks.append(d)
            cache.used += nb
        else:
            grow = False
        yield d
    if grow:
        cache.complete = True


def _device_hbm_bytes(purpose: str = "the chunk cache",
                      override: str = "SHEEP_CACHE_BYTES") -> int:
    """Reported (or generation-inferred) HBM bytes of the default
    device; 0 when nothing trustworthy is known. ``purpose`` and
    ``override`` name the caller's budget and its knob in the note an
    inference prints."""
    dev = jax.local_devices()[0]
    try:
        stats = dev.memory_stats() or {}
        hbm = int(stats.get("bytes_limit", 0))
    except Exception:
        hbm = 0
    if hbm <= 0:
        # no reported limit: infer only from a known device generation;
        # an unknown accelerator gets 0 rather than a guessed budget
        # that could OOM it (SHEEP_CACHE_BYTES overrides). Exact kind
        # match first so a future kind merely *containing* one of these
        # substrings (with different HBM) prefers its own entry, and
        # log the inference so an OOM is traceable to it.
        kind = getattr(dev, "device_kind", "").lower()
        known = {"v5 lite": 16, "v5e": 16, "v4": 32, "v5p": 95, "v6": 32}
        g = known.get(kind) or next(
            (g for key, g in known.items() if key in kind), 0)
        hbm = g << 30
        if hbm:
            import sys

            print(f"note: device reports no bytes_limit; inferring "
                  f"{g} GiB HBM from device_kind {kind!r} for {purpose} "
                  f"(override with {override})",
                  file=sys.stderr)
    return hbm


def _chunk_cache_budget(n: int, chunk_edges: int,
                        dispatch_batch: int = 1, inflight: int = 1,
                        donate: bool = False, h2d_ring: int = 0) -> int:
    """Bytes of HBM safely spendable on cached chunks: the device limit
    minus the build phase's modeled peak (including the batched
    dispatch's [N, C] staging blocks) and a safety margin.

    0 (cache disabled) on cpu-jax — there the "device" IS host RAM, so
    caching would duplicate the stream in memory to save a transfer that
    does not exist — and 0 when the accelerator does not report a real
    bytes_limit (no basis for a budget). An explicit SHEEP_CACHE_BYTES
    wins EVERYWHERE, including cpu-jax: the override is how the
    out-of-core residency plane (ISSUE 20) is engaged and exercised —
    its spill/reload/boundary machinery is platform-independent, and
    the exactness contract (tiny budget == unconstrained oracle, bit
    for bit) must be testable without an accelerator."""
    from sheep_tpu.utils.membudget import build_phase_bytes

    env = os.environ.get("SHEEP_CACHE_BYTES")
    if env is not None:
        return max(0, int(env))
    if jax.default_backend() == "cpu":
        return 0
    hbm = _device_hbm_bytes()
    reserve = build_phase_bytes(
        n, chunk_edges, dispatch_batch=dispatch_batch,
        inflight=inflight, donate=donate,
        h2d_ring=h2d_ring)["total_bytes"] + (1 << 30)
    return max(0, int(0.9 * hbm) - reserve)


# The TPU compiler aborts the whole process (a CHECK failure in
# memory-space assignment, not an exception) when it compiles the
# donated batched fold at N = 2 for V = 2^22, C = 2^23; N = 1, 3, 4, 8
# and 16 compile (AOT for a described v5e, PR 21). Smaller shapes were
# not probed, so no TPU run folds at N = 2: an explicit request is
# refused and the degrade ladder steps over it.
TPU_REFUSED_BATCH = 2


def refused_dispatch_batch() -> Optional[int]:
    """The dispatch batch this platform must never fold at, or None."""
    return TPU_REFUSED_BATCH if jax.default_backend() == "tpu" else None


def check_dispatch_batch(dispatch_batch: int) -> int:
    """``dispatch_batch`` as given, refused where the platform's compiler
    aborts on it (see :data:`TPU_REFUSED_BATCH`). Called where the
    batch is about to run, so a served job fails instead of sheepd."""
    if dispatch_batch == refused_dispatch_batch():
        raise ValueError(
            f"dispatch_batch={dispatch_batch} is refused on "
            f"{jax.default_backend()}: its compiler aborts the process on "
            f"the batched fold at that width; use 1 or >= 3")
    return dispatch_batch


def resolve_h2d_ring(h2d_ring: int) -> int:
    """Auto-sizing rule for the staged H2D ring depth (shared by the
    tpu driver and the served engine): explicit D >= 1 passes through;
    0 (auto) resolves to 2 on accelerators — the transfer of block i+2
    is in flight while block i folds, so ``h2d_blocked_ms`` collapses
    toward 0 the way ``device_gap_ms`` does at inflight >= 2 — and 1
    on cpu-jax, where device_put is a host-memory copy with no link to
    hide (depth 1 still stages one block ahead, and is bit-identical
    at every depth). Device streams never stage, whatever this says."""
    if h2d_ring != 0:
        return max(1, int(h2d_ring))
    return 1 if jax.default_backend() == "cpu" else 2


def _device_chunk_groups(stream, cs: int, n: int, cache, start_chunk: int,
                         batch: int, ring: int = 1, stats=None):
    """Yield lists of up to ``batch`` padded (cs, 2) int32 DEVICE chunks
    — the staged groups of the batched segment dispatch.

    Host-format streams stage a FULL group of parsed + padded chunks on
    the prefetch worker (:func:`prefetch_batched`) and feed the whole
    group through the staged H2D ring — the transfers for ``ring``
    upcoming groups are in flight while the current enlarged device
    execution runs, so neither the N host reads NOR the N uploads of
    the next batched program sit in the dispatch chain;
    device-synthesizing (:func:`is_device_stream`) and cache-served
    chunks group over the plain per-chunk iterator (no host bytes to
    stage, and the cache's prefix-fill invariant stays in one place)."""
    if batch <= 1:
        for d in _device_chunks(stream, cs, n, cache, start_chunk,
                                ring, stats):
            yield [d]
        return
    if cache is None and not is_device_stream(stream):
        # with-exit is the deterministic worker cancel on abandonment
        # (the in-flight pipeline's discard/backstop paths close this
        # generator mid-stream): drain + join — and drop the ring's
        # staged HBM — instead of waiting for the GC
        with prefetch_batched(
                (pad_chunk(c, cs, n)
                 for c in stream.chunks(cs, start_chunk=start_chunk)),
                batch) as pf, \
                H2DRing(pf, depth=max(1, ring), stats=stats) as staged:
            for dev_group in staged:
                yield list(dev_group)
        return
    group: list = []
    for d in _device_chunks(stream, cs, n, cache, start_chunk,
                            ring, stats):
        group.append(d)
        if len(group) == batch:
            yield group
            group = []
    if group:
        yield group


@register
class TpuBackend(Partitioner):
    name = "tpu"
    supports_checkpoint = True
    supports_multidevice = False  # single-device; see sheep_tpu/parallel
    supports_incremental = True   # partition_update via _fold_delta

    def __init__(self, chunk_edges: int = 1 << 22, lift_levels: int = 0,
                 alpha: float = 1.0, segment_rounds: int = 2,
                 warm_schedule=None, cache_chunks: bool = True,
                 host_tail_threshold: int = -1,
                 dispatch_batch: int = 1,
                 inflight: int = 1,
                 donate_buffers: Optional[bool] = None,
                 h2d_ring: int = 0):
        self.chunk_edges = chunk_edges
        self.lift_levels = lift_levels
        self.alpha = alpha
        # fixpoint rounds per device execution; bounding each call keeps
        # accelerator executions short (long single executions tripped the
        # TPU worker watchdog) while staying bit-identical to monolithic
        self.segment_rounds = segment_rounds
        # one cheap 8-level round before any full-depth round: a
        # full-buffer round costs ~lift_levels x width in gathers, most
        # slots retire early without long jumps, and the dedup/compaction
        # it unlocks shrinks every later round. Measured on the v5e
        # (tools/tune_fixpoint.py, RMAT-20): build 44.9s -> 10.5s
        # together with the C/2 host-tail handoff.
        self.warm_schedule = ((1, 8),) if warm_schedule is None \
            else tuple(warm_schedule)
        self.cache_chunks = cache_chunks
        # -1 = platform default: C/2 on an accelerator (device rounds are
        # expensive relative to the native host pass), auto (C/8, min
        # 2^16) on cpu-jax where the measured sweet spot is later handoff
        self.host_tail_threshold = host_tail_threshold
        # batched segment dispatch (ops/elim.py fold_segments_batch):
        # stage N streamed chunks as one padded [N, C] oriented block
        # and fold them in single bounded device programs — one packed
        # stats sync per execution instead of per segment. The default
        # 1 (with inflight 1) runs the adaptive per-segment driver: its
        # compaction + native host tail schedule finished RMAT-18 k=64
        # on a v5e in 14 rounds / 2.6 s warm, where the batched
        # pipeline (N=16, inflight 2) took 313 full-width rounds /
        # 70.5 s (chip run, PR 21). The forest is bit-identical either
        # way (the fixpoint is unique).
        if dispatch_batch < 1:
            raise ValueError("dispatch_batch must be >= 1")
        self.dispatch_batch = dispatch_batch
        # asynchronous dispatch pipeline depth (ops/elim.py
        # fold_segments_pipelined): keep up to D issued batched
        # executions whose stats words are unread futures, converting
        # each to host ints one-behind so the device never waits for a
        # host read/orient/pad and the host never waits for a device
        # program. Default 1 (the adaptive driver, see dispatch_batch);
        # any D yields the bit-identical forest (fixpoint uniqueness —
        # tests/test_inflight.py).
        if inflight < 1:
            raise ValueError("inflight must be >= 1")
        self.inflight = inflight
        # donate the carried table + staging blocks into each batched
        # execution so XLA reuses their buffers for the outputs instead
        # of double-buffering across executions (None = auto: on
        # whenever the batched/pipelined dispatch runs; results are
        # identical either way — donation is pure buffer aliasing)
        self.donate_buffers = donate_buffers
        # staged H2D ring depth (utils/prefetch.H2DRing): keep up to D
        # pre-padded host blocks' device_put transfers issued ahead of
        # the dispatch chain so the upload of block i+D overlaps the
        # fold of block i. 0 = auto (2 on accelerators, 1 on cpu-jax);
        # bit-identical at every depth (the ring changes WHEN transfers
        # are issued, never what bits arrive). Device streams
        # (io/devicestream.py) skip staging entirely.
        if h2d_ring < 0:
            raise ValueError("h2d_ring must be >= 0 (0 = auto)")
        self.h2d_ring = h2d_ring

    def _fold_delta(self, state, edges) -> None:
        """Incremental fold (ISSUE 15): stage the delta batch as
        padded [N, C] blocks and fold them into the converged carried
        table with the EXISTING batched dispatch
        (``ops/elim.py fold_segments_batch``) under the state's
        anchored order — one bounded device program per group, the
        same unique fixpoint any dispatch shape lands on. O(Δ) device
        work; the vertex-space minp crosses to/from position space
        only at the batch boundary."""
        n = state.n
        e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
        if not len(e):
            return
        # power-of-two delta chunk keeps the set of compiled program
        # shapes logarithmic across arbitrary delta sizes
        cs = elim_ops.pow2_at_least(min(len(e), self.chunk_edges),
                                    floor=1 << 10)
        batch_n = check_dispatch_batch(self.dispatch_batch)
        pos_sent = np.concatenate([state.pos.astype(np.int32),
                                   np.asarray([n], np.int32)])
        order_sent = np.concatenate([state.order,
                                     np.asarray([n], np.int64)])
        pos_dev = jnp.asarray(pos_sent)
        P = jnp.asarray(state.minp[order_sent])
        stats = state.stats
        chunks = [pad_chunk(e[off: off + cs], cs, n)
                  for off in range(0, len(e), cs)]
        for g0 in range(0, len(chunks), batch_n):
            group = chunks[g0: g0 + batch_n]
            # designed upload window: delta batches are host arrays by
            # definition (they arrived over a wire/log); one staged
            # transfer per bounded group, off the steady-state path
            block = jnp.asarray(  # sheeplint: h2d-ok
                np.stack(group))
            loB, hiB = elim_ops.orient_chunks_batch_pos(block, pos_dev,
                                                        n)
            P, rounds = elim_ops.fold_segments_batch(
                P, loB, hiB, n, segment_rounds=self.segment_rounds,
                stats=stats, donate=False)
            stats["update_rounds"] = \
                stats.get("update_rounds", 0) + int(rounds)
        # designed pull: the converged table is the update's product
        state.minp = obs.pull("update-table", P[pos_dev])

    def partition(self, stream, k: int, weights: str = "unit",
                  comm_volume: bool = True, checkpointer=None,
                  resume: bool = False, **opts) -> PartitionResult:
        # ONE stats dict across all three streaming passes: the ingest
        # counters (h2d_* / device_stream_chunks) accumulate wherever
        # chunks cross (or don't cross) the link, and the build phase
        # adds the dispatch counters to the same record. It is also the
        # stats scope (obs.stats_scope): pulls, native calls and
        # compiles anywhere below count into it
        build_stats: dict = {}
        with obs.stats_scope(build_stats):
            return self._partition(stream, k, build_stats, weights,
                                   comm_volume, checkpointer, resume,
                                   **opts)

    def _partition(self, stream, k: int, build_stats: dict, weights: str,
                   comm_volume: bool, checkpointer, resume: bool,
                   **opts) -> PartitionResult:
        from sheep_tpu.utils import checkpoint as ckpt
        from sheep_tpu.utils.fault import maybe_fail

        t = {}
        ckpt_degraded0 = ckpt.degraded_events()
        # right-size the chunk for small graphs so a tiny input doesn't
        # pad out to the full default chunk shape
        cs = stream.clamp_chunk_edges(self.chunk_edges)
        t0 = time.perf_counter()
        n = stream.num_vertices
        check_tpu_vertex_range(n, self.name)
        root_sp = obs.begin("partition", backend=self.name, k=int(k),
                            n=int(n), chunk_edges=int(cs))
        stats_acc = obs.stats_accumulator()
        m_cheap = stream.num_edges_cheap
        obs.progress(backend=self.name, k=int(k), edges_total=m_cheap,
                     chunks_total=-(-m_cheap // cs) if m_cheap else None)
        # the fingerprint names the build state's format, so a
        # checkpoint of any other format is refused on resume
        meta = ckpt.stream_meta(stream, k, cs, weights=weights,
                                alpha=self.alpha, comm_volume=comm_volume,
                                state_format="minp")
        state = ckpt.resume_state(checkpointer, meta, resume)
        from_phase = ckpt.phase_index(state.phase) if state else 0

        # Device accumulation is int32; flush to a host int64 accumulator
        # before a vertex could possibly see 2^31 endpoints, so trillion-edge
        # streams cannot overflow (cross-chunk totals live host-side).
        flush_every = degrees_ops.flush_every_for(cs)
        if state:
            deg_host = state.arrays["deg"].copy()
        else:
            deg_host = np.zeros(n, dtype=np.int64)
        inflight_n = self.inflight
        ring_n = resolve_h2d_ring(self.h2d_ring)
        # the membudget model counts ring staging only for streams that
        # actually stage — a device stream synthesizes in place and
        # holds no pre-transferred blocks
        ring_model = 0 if is_device_stream(stream) else ring_n
        donate = True if self.donate_buffers is None else self.donate_buffers
        batch_n = check_dispatch_batch(self.dispatch_batch)
        # the donating fold only runs on the pipelined/batched branch
        # (batch_n == 1 == inflight_n selects the adaptive per-segment
        # driver below); crediting donation to the HBM model on a path
        # that never donates would under-reserve by a full minp table
        if batch_n == 1 and inflight_n == 1:
            donate = False
        cache_budget = _chunk_cache_budget(n, cs, dispatch_batch=batch_n,
                                           inflight=inflight_n,
                                           donate=donate,
                                           h2d_ring=ring_model) \
            if self.cache_chunks else 0
        # residency-managed chunk tier (ISSUE 20): same prefix-cache
        # fast path when the stream fits the budget, spill/reload with
        # checkpoint-boundary eviction when it does not — device memory
        # is a cache over the on-disk stream, not a ceiling. The spill
        # counters land in build_stats -> diagnostics -> bench record.
        cache = ResidencyManager(cache_budget, stats=build_stats) \
            if cache_budget > 0 else None

        def _ckpt_boundary(confirmed_idx: int) -> None:
            # checkpoint boundaries are the residency eviction points:
            # chunks behind the confirmed index can no longer be
            # re-read by any retry (resume starts at confirmed_idx)
            if isinstance(cache, ResidencyManager):
                cache.boundary(confirmed_idx)
        sp = obs.begin("degrees")
        obs.progress(phase="degrees", chunks_done=0, edges_done=0)
        # anchored-order streams (delta: inputs, io/deltalog.py): the
        # elimination order derives from the BASE segment's degrees
        # only — the contract that makes the incremental path
        # bit-identical to this one-shot build. The anchor pass never
        # touches the chunk cache (its chunks are a different stream
        # than the build/score passes'); build fills the cache with
        # the full surviving multiset as usual.
        anchored = bool(getattr(stream, "order_anchor", False))
        if from_phase == 0:
            start = state.chunk_idx if state else 0
            deg = degrees_ops.init_degrees(n)
            since_flush = 0
            idx = start
            # read+parse+pad of chunk i+1 overlaps the device fold of i;
            # the staged ring keeps its H2D transfer off the chain too
            for padded in _device_chunks(
                    stream.anchor_stream() if anchored else stream,
                    cs, n, None if anchored else cache, start,
                    ring_n, build_stats):
                deg = degrees_ops.degree_chunk(deg, padded, n)
                since_flush += 1
                idx += 1
                maybe_fail("degrees", idx - start)
                obs.chunk_progress(idx, cs, m_cheap)
                at_ckpt = checkpointer is not None and checkpointer.due(idx - start)
                if since_flush >= flush_every or at_ckpt:
                    # designed flush sync: int32 device accumulator ->
                    # int64 host totals
                    deg_host += obs.pull("degrees-flush", deg[:n])
                    deg = degrees_ops.init_degrees(n)
                    since_flush = 0
                if at_ckpt:
                    checkpointer.save("degrees", idx, {"deg": deg_host}, meta)
                    _ckpt_boundary(idx)
            deg_host += obs.pull("degrees-flush", deg[:n])
        t["degrees"] = time.perf_counter() - t0
        sp.end()

        t0 = time.perf_counter()
        with obs.span("sort"):
            # positions are int32 ranks; degree values only matter
            # ordinally, so clip the int64 totals into int32 for the
            # device sort via rankdata
            deg_rank = degrees_ops.rank_clip_i32(deg_host)
            deg_dev = jnp.asarray(deg_rank, dtype=jnp.int32)
            pos, order = order_ops.elimination_order(deg_dev, n)
            # tiny host pull as the sort phase's completion barrier
            obs.pull("sort-barrier", pos[:1])
            t["sort"] = time.perf_counter() - t0
        pos_host_cache = None

        t0 = time.perf_counter()
        sp = obs.begin("build")
        obs.progress(phase="build", chunks_done=0, edges_done=0)
        total_rounds = 0
        if state and from_phase >= 2:
            minp = jnp.asarray(state.arrays["minp"])
        else:
            pos_host_cache = obs.pull("pos-host", pos[:n])
            tail_at = self.host_tail_threshold
            if tail_at < 0:
                tail_at = cs // 2 if jax.default_backend() != "cpu" else 0

            # ---- fault-tolerant build (ISSUE 9 tentpole) --------------
            # The whole streaming build runs as one retryable ATTEMPT
            # against ``snap``, an in-memory snapshot of the last
            # confirmed state (vertex-space minp + next chunk index —
            # exactly a checkpoint's payload, banked whenever one is
            # saved). A RESOURCE_EXHAUSTED-class fault degrades the
            # dispatch footprint (membudget.degraded_dispatch halves
            # dispatch_batch/inflight, the chunk cache is dropped) and
            # re-folds from the snapshot; a device-loss-class fault
            # persists the snapshot through the Checkpointer, best-effort
            # reinitializes the device in-process, and re-folds the same
            # way. Bit-identical either way: restart-from-snapshot is the
            # PR-8 resume semantics, and the fixpoint is unique in the
            # constraint multiset regardless of batch/inflight shape.
            # The carried forest lives in POSITION space on device (P);
            # snapshots/checkpoints keep the stable vertex-space minp
            # encoding, so conversions happen only at those boundaries.
            snap = {"idx": 0, "minp": None}
            if state and state.phase == "build":
                snap["idx"] = state.chunk_idx
                snap["minp"] = state.arrays["minp"]
            cfg = {"batch": batch_n, "inflight": inflight_n,
                   "donate": donate, "ring": ring_n}

            def _build_attempt():
                nonlocal total_rounds
                start = snap["idx"]
                idx = start
                if snap["minp"] is not None:
                    P = jnp.asarray(snap["minp"])[order]
                else:
                    P = jnp.full(n + 1, n, dtype=jnp.int32)
                batch_n = cfg["batch"]
                inflight_n = cfg["inflight"]
                ring_n = cfg["ring"]
                if batch_n > 1 or inflight_n > 1:
                    # batched segment dispatch, pipelined (ops/
                    # elim.py fold_segments_pipelined): stage batch_n
                    # chunks as one oriented [N, C] block, fold groups
                    # in bounded multi-segment device programs with up
                    # to inflight_n executions in flight, and pull one
                    # packed stats word per execution ONE-BEHIND — the
                    # host's read/orient/pad overlaps the device
                    # fixpoint instead of alternating with it, and
                    # donation reuses the table/staging buffers across
                    # the chain. Warm schedule / compaction / host tail
                    # are per-segment host decisions and do not apply
                    # here; the forest is the same unique fixpoint
                    # either way.
                    build_stats["dispatch_batch"] = batch_n
                    build_stats["inflight_depth"] = inflight_n
                    groups = _device_chunk_groups(stream, cs, n, cache,
                                                  start, batch_n, ring_n,
                                                  build_stats)

                    def staged_groups():
                        sentinel_chunk = None
                        for group in groups:
                            gl = len(group)
                            if gl < batch_n:
                                if sentinel_chunk is None:
                                    sentinel_chunk = jnp.full(
                                        (cs, 2), n, jnp.int32)
                                group = group + [sentinel_chunk] * \
                                    (batch_n - gl)
                            loB, hiB = elim_ops.orient_chunks_batch_pos(
                                jnp.stack(group), pos, n)
                            yield loB, hiB, gl

                    # rolling dispatch spans tile the pipelined build:
                    # each one covers confirm-to-confirm (the counter
                    # deltas carry the overlap story — host_blocked_ms
                    # / device_gap_ms); issue/confirm interleave across
                    # groups, so per-group spans would no longer nest
                    dsp = obs.begin("dispatch", i=idx)

                    def confirmed(gl, rounds, tipP):
                        # returns True to request a flush barrier when
                        # a checkpoint is due: mid-pipeline the tip
                        # table can UNDER-represent a confirmed group
                        # whose budget-exhausted leftovers are still
                        # queued, so the save itself happens in
                        # flushed(), after the driver drains everything
                        # issued
                        nonlocal idx, dsp
                        stats_acc.absorb(build_stats)
                        dsp.end(rounds=int(rounds))
                        due = False
                        if gl is not None:
                            prev = idx
                            idx += gl
                            obs.chunk_progress(idx, cs, m_cheap)
                            for i in range(prev + 1, idx + 1):
                                maybe_fail("build", i - start,
                                           kinds=("kill", "oom",
                                                  "device"))
                            due = checkpointer is not None and \
                                checkpointer.due_span(prev - start,
                                                      idx - start)
                        dsp = obs.begin("dispatch", i=idx)
                        return due

                    def flushed(tipP):
                        # pipeline fully drained: idx (advanced through
                        # every group confirmed during the drain) and
                        # the table now agree exactly — the sound cut
                        # for both the durable checkpoint and the
                        # in-memory retry snapshot
                        arrays = {
                            "deg": deg_host,
                            "minp": obs.pull("flush-checkpoint",
                                             tipP[pos])}
                        snap["idx"] = idx
                        snap["minp"] = arrays["minp"]
                        if checkpointer is not None:
                            checkpointer.save("build", idx, arrays, meta)
                        # the flushed table IS the confirmed state
                        # (durable or in-memory snapshot): chunks behind
                        # it are eviction-safe either way
                        _ckpt_boundary(idx)

                    staged = staged_groups()
                    try:
                        P, rounds = elim_ops.fold_segments_pipelined(
                            P, staged, n,
                            inflight=inflight_n,
                            lift_levels=self.lift_levels,
                            segment_rounds=self.segment_rounds,
                            donate=cfg["donate"],
                            stats=build_stats,
                            on_confirm=confirmed,
                            on_flush=flushed)
                        total_rounds += int(rounds)
                    finally:
                        # the discard/backstop/fault paths abandon the
                        # staged stream mid-iteration: close BOTH
                        # generators — a for-loop does not close the
                        # iterator it consumes, so staged.close() alone
                        # would leave _device_chunk_groups (and the
                        # prefetch worker its finally cancels) open
                        # until GC
                        staged.close()
                        groups.close()
                        dsp.end()
                    stats_acc.absorb(build_stats)
                    return P
                for padded in _device_chunks(stream, cs, n, cache, start,
                                             ring_n, build_stats):
                    seg_sp = obs.begin("segment", i=idx)
                    try:
                        P, rounds = elim_ops.build_chunk_step_adaptive_pos(
                            P, padded, pos, pos_host_cache, n,
                            lift_levels=self.lift_levels,
                            segment_rounds=self.segment_rounds,
                            warm_schedule=self.warm_schedule,
                            stats=build_stats,
                            host_tail_threshold=tail_at)
                        total_rounds += int(rounds)
                        stats_acc.absorb(build_stats)
                        seg_sp.end(rounds=int(rounds))
                    finally:
                        # idempotent: balances the span when a fault
                        # unwinds mid-chunk so a RECOVERED run still
                        # renders a complete tree
                        seg_sp.end()
                    idx += 1
                    obs.chunk_progress(idx, cs, m_cheap)
                    maybe_fail("build", idx - start,
                               kinds=("kill", "oom", "device"))
                    if checkpointer is not None and \
                            checkpointer.due(idx - start):
                        arrays = {"deg": deg_host,
                                  "minp": obs.pull("checkpoint", P[pos])}
                        snap["idx"] = idx
                        snap["minp"] = arrays["minp"]
                        checkpointer.save("build", idx, arrays, meta)
                        _ckpt_boundary(idx)
                return P

            from sheep_tpu.utils import retry as retry_mod

            def _on_resource():
                # spill before shrink (ISSUE 20): the resident chunks
                # are reclaimable HBM — with spillable bytes the
                # degrade ladder's first rung drops them (and halves
                # the residency budget) with the dispatch knobs
                # UNCHANGED; only a fault with nothing left to spill
                # halves whichever knob the membudget model indicts
                nonlocal cache
                rm = cache if isinstance(cache, ResidencyManager) \
                    else None
                if cache is not None and rm is None:
                    cache.chunks.clear()
                    cache.used = 0
                    cache.complete = False
                    cache.budget = 0
                    cache = None
                nxt = retry_mod.degrade_dispatch(
                    n, cs, cfg["batch"], cfg["inflight"], cfg["donate"],
                    build_stats, snap["idx"],
                    h2d_ring=None if ring_model == 0 else cfg["ring"],
                    residency=rm)
                if rm is not None and rm.budget <= 0:
                    cache = None  # walked to zero: stop probing it
                if nxt is not None:
                    cfg["batch"], cfg["inflight"] = nxt[0], nxt[1]
                    if len(nxt) > 2:
                        cfg["ring"] = nxt[2]

            def _save_snapshot():
                if checkpointer is not None and snap["minp"] is not None:
                    arrays = {"deg": deg_host, "minp": snap["minp"]}
                    checkpointer.save("build", snap["idx"], arrays, meta)

            def _on_device_loss():
                retry_mod.recover_device_loss(build_stats, snap["idx"],
                                              _save_snapshot)

            policy = retry_mod.RetryPolicy()
            while True:
                try:
                    P = _build_attempt()
                    break
                except Exception as exc:
                    # shared classify/budget/count/backoff protocol
                    # (retry.handle_build_fault — the dispatch_retries
                    # trail is gated higher-is-worse by bench_regress);
                    # FATAL and exhausted budgets re-raise inside
                    retry_mod.handle_build_fault(
                        policy, exc, "tpu.build", build_stats,
                        on_resource=_on_resource,
                        on_device_loss=_on_device_loss)
                    stats_acc.absorb(build_stats)
            # an OOM-degraded ring depth carries forward to the score
            # pass: it runs outside the retry harness, so re-staging at
            # the pre-degrade depth on a device that just proved too
            # small would re-OOM unrecovered
            ring_n = cfg["ring"]
            minp = P[pos]
            # real completion barrier (see above)
            obs.pull("build-barrier", minp[:1])
        t["build"] = time.perf_counter() - t0
        stats_acc.absorb(build_stats)
        sp.end(fixpoint_rounds=int(total_rounds))

        t0 = time.perf_counter()
        with obs.span("split"):
            parent = elim_ops.minp_to_parent(minp, order, n)
            pos_host = pos_host_cache if pos_host_cache is not None \
                else obs.pull("pos-host", pos[:n])
            w = deg_host.astype(np.float64) if weights == "degree" else None
            assign_host = split_ops.tree_split_host(parent, pos_host, k,
                                                    weights=w,
                                                    alpha=self.alpha)
            assign = jnp.concatenate(
                [jnp.asarray(assign_host, dtype=jnp.int32),
                 jnp.zeros(1, dtype=jnp.int32)])
            t["split"] = time.perf_counter() - t0

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
        idx = start
        for padded in _device_chunks(stream, cs, n, cache, start,
                                     ring_n, build_stats):
            c, tt = score_ops.score_chunk(padded, assign, n)
            # designed per-chunk score pull (two scalars, one chunk)
            cut += int(obs.pull("score-cut", c))
            total += int(obs.pull("score-total", tt))
            if comm_volume:
                score_ops.accumulate_cv_keys(
                    cv_chunks,
                    score_ops.cut_pair_keys_host(padded, assign, n, k))
            idx += 1
            maybe_fail("score", idx - start)
            obs.chunk_progress(idx, cs, m_cheap)
            if checkpointer is not None and checkpointer.due(idx - start):
                cv_chunks = ckpt.save_score_state(
                    checkpointer, idx, cut, total, cv_chunks,
                    {"deg": deg_host, "minp": obs.pull("checkpoint", minp)},
                    meta,
                    comm_volume)
                _ckpt_boundary(idx)
        cv = int(len(ckpt.compact_cv_keys(cv_chunks))) if comm_volume else None
        # the score pass re-streams (and under a residency budget,
        # re-spills) — absorb its counters so the trace's final totals
        # match the diagnostics instead of stopping at the build phase
        stats_acc.absorb(build_stats)
        from sheep_tpu.core import pure

        balance = pure.part_balance(assign_host, k,
                                    deg_host if weights == "degree" else None)
        t["score"] = time.perf_counter() - t0
        sp.end()
        root_sp.end()
        if checkpointer is not None:
            checkpointer.clear()
        if ckpt.degraded_events() > ckpt_degraded0:
            # lossy recovery happened during THIS run: surface it in
            # the diagnostics so the bench contract / regression gate
            # see the degradation instead of a silently-clean number
            build_stats["checkpoint_degraded"] = \
                ckpt.degraded_events() - ckpt_degraded0

        return PartitionResult(
            assignment=assign_host, k=k, edge_cut=cut, total_edges=total,
            cut_ratio=cut / max(total, 1), balance=balance, comm_volume=cv,
            phase_times=t, backend=self.name,
            # t_* walls and *_ms counters accumulate unrounded (elim.py
            # t_add/_t_ms) and are rounded HERE, at read time, so their
            # sums never drift past the measured wall by per-add
            # rounding quanta
            diagnostics={"fixpoint_rounds": float(total_rounds),
                         **{k: (round(float(v), 3)
                                if k.startswith("t_") or k.endswith("_ms")
                                else float(v))
                            for k, v in build_stats.items()},
                         **device_identity()},
            tree={"parent": np.asarray(parent), "pos": pos_host,
                  "deg": deg_host} if opts.get("keep_tree") else None,
        )
