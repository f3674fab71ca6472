"""Device-memory model for the streaming build (VERDICT r1 item 4,
SURVEY.md §7 hard part #2).

All vertex-indexed state is int32[n+1]; the edge chunk contributes
int32[C]-shaped work arrays. The model below counts the worst-case live
set of ``build_chunk_step`` + the elimination fixpoint, which dominates
every other phase (degrees needs 2 tables; scoring needs 1 table + the
chunk). XLA reuses buffers aggressively, so this is an upper bound on
steady-state HBM after warm-up; the real high-water mark is
profiled on hardware (BASELINE.md "HBM budget").
"""

from __future__ import annotations

from sheep_tpu.ops.elim import EXACT_TABLE_BYTES


def build_phase_bytes(n: int, chunk_edges: int, lift_levels: int = 0,
                      descent: str = "auto", dispatch_batch: int = 1,
                      inflight: int = 1, donate: bool = False,
                      h2d_ring: int = 0, resident_bytes: int = 0) -> dict:
    """Estimated peak device bytes for one build_chunk_step.

    The displacement fixpoint (ops/elim.py fold_edges) keeps the carried
    forest in the persistent minp table and only the chunk's C edges
    active, so transients are O(C), not O(V + C). Live set: pos + order
    (persistent, 2 tables), the minp table double-buffered across the
    while_loop carry (2 tables), ~6 C-sized active/work arrays
    (lo/hi/poshi/old_at_lo/now/new_lo), and the lifting table stack
    (exact descent: lift_levels tables bounded by EXACT_TABLE_BYTES;
    stream descent: 1 table).

    ``dispatch_batch`` > 1 (the batched segment dispatch,
    ops/elim.py fold_segments_batch) additionally stages N segments on
    device at once: the raw (N, C, 2) chunk stack plus the oriented
    [N, C] lo/hi blocks — the O(C) transient invariant becomes O(N*C).

    ``inflight`` > 1 (the asynchronous dispatch pipeline,
    ops/elim.py fold_segments_pipelined) keeps D issued executions'
    staging blocks live at once — staging multiplies by D. ``donate``
    (fold_segments_batch_pos_donated) lets XLA reuse the carried
    table's and each staging block's buffers for the execution outputs
    instead of double-buffering them across the call boundary — it
    credits back one minp table and one staging block's oriented half.

    ``h2d_ring`` (the staged H2D ring, utils/prefetch.H2DRing —
    ISSUE 12) holds up to that many pre-transferred padded blocks in
    device memory awaiting dispatch — ``dispatch_batch`` chunks of
    (C, 2) int32 each per block, so like ``inflight`` it is a
    depth x staging-bytes product. 0 = ring off (device-stream inputs
    synthesize on device and stage nothing; the synchronous path
    uploads in place).

    ``resident_bytes`` (the residency term, ISSUE 20) is the chunk
    bytes the :class:`~sheep_tpu.utils.residency.ResidencyManager`
    currently holds (or budgets) on device — cached chunks are live HBM
    exactly like staging blocks, and a model that ignored them would
    admit builds whose real footprint overflows the instant the cache
    warms. Unlike every other term it is *reclaimable*: the degrade
    ladder spills it before shrinking any dispatch knob (see
    :func:`degraded_dispatch`).
    """
    if lift_levels <= 0:
        lift_levels = max(1, int(n).bit_length())
    table = 4 * (n + 1)
    stack = lift_levels * table
    if descent == "auto":
        descent = "exact" if stack <= EXACT_TABLE_BYTES else "stream"
    lift_bytes = min(stack, EXACT_TABLE_BYTES) if descent == "exact" else table
    persistent = 4 * table  # pos, order, minp x2 (loop carry)
    transient = 6 * 4 * chunk_edges
    # chunk stack (2C words/row) + oriented lo/hi blocks (2C words/row),
    # held once per in-flight execution. The synchronous per-segment
    # driver (dispatch_batch == 1, inflight == 1) stages nothing beyond
    # the counted transients; the pipelined driver stages its [N, C]
    # blocks even at N == 1 (inflight > 1 selects it)
    staging_unit = 4 * 4 * chunk_edges * max(1, dispatch_batch) \
        if dispatch_batch > 1 or inflight > 1 else 0
    staging = staging_unit * max(1, inflight)
    if donate and staging_unit:
        # donated executions alias input buffers into outputs: one minp
        # table (the cross-execution carry copy) and one oriented lo/hi
        # block pair (half a staging unit) come back. Guarded on
        # staging_unit: the synchronous per-segment configuration never
        # runs a donating program, so crediting it there would
        # under-reserve a full table no matter what flag a caller
        # threads through
        persistent -= table
        staging -= staging_unit // 2
    # staged H2D ring: D pre-uploaded (C, 2) int32 blocks (x batch
    # chunks each) live in HBM between transfer and dispatch
    ring_bytes = 4 * 2 * chunk_edges * max(1, dispatch_batch) \
        * max(0, h2d_ring)
    resident = max(0, int(resident_bytes))
    total = persistent + transient + staging + ring_bytes + lift_bytes \
        + resident
    return {
        "persistent_bytes": persistent,
        "transient_bytes": transient,
        "staging_bytes": staging,
        "h2d_ring_bytes": ring_bytes,
        "lift_bytes": lift_bytes,
        "resident_bytes": resident,
        "descent": descent,
        "total_bytes": total,
    }


def degraded_dispatch(n: int, chunk_edges: int, dispatch_batch: int,
                      inflight: int, donate: bool = False,
                      h2d_ring=None, spillable_bytes: int = 0,
                      refused_batch=None):
    """One RESOURCE_EXHAUSTED degradation step for the dispatch drivers
    (ISSUE 9): halve ``dispatch_batch``, ``inflight`` — or, when the
    caller runs a staged H2D ring (``h2d_ring`` given as an int >= 1,
    ISSUE 12), the ring depth — whichever frees MORE modeled bytes per
    the build-phase HBM model above. Returns the new
    ``(dispatch_batch, inflight)`` pair (legacy callers, ``h2d_ring``
    omitted) or the ``(dispatch_batch, inflight, h2d_ring)`` triple,
    or ``None`` when every knob is already 1 (nothing left to shed;
    the caller falls back to a plain retry, then to the
    checkpoint/kill+resume contract).

    **Spill-before-shrink** (ISSUE 20): when the caller holds evictable
    resident chunks (``spillable_bytes`` > 0), the ladder's FIRST rung
    is spilling them — cached chunks are a pure latency optimization
    whose modeled bytes come back for free, while halving a dispatch
    knob permanently costs overlap for the rest of the run. The step is
    then ``("spill", dispatch_batch, inflight[, h2d_ring])``: the knobs
    come back *unchanged* and the caller (utils/retry.degrade_dispatch
    with a residency manager) performs the actual eviction. Only with
    nothing left to spill does the ladder fall through to halving.

    Reusing :func:`build_phase_bytes` instead of a fixed halving order
    keeps the degrade schedule consistent with the HBM model: the knob
    that the model says holds the most staging is the knob an OOM most
    plausibly indicts. Halving the batch steps over ``refused_batch``
    (a width the platform's compiler aborts on, see
    ``backends/tpu_backend.TPU_REFUSED_BATCH``) to the next one down."""
    batch, depth = max(1, int(dispatch_batch)), max(1, int(inflight))
    ring = None if h2d_ring is None else max(1, int(h2d_ring))
    if spillable_bytes > 0:
        step = ("spill", batch, depth)
        return step + (ring,) if ring is not None else step
    if batch <= 1 and depth <= 1 and (ring is None or ring <= 1):
        return None

    def total(b, d, r):
        return build_phase_bytes(n, chunk_edges, dispatch_batch=b,
                                 inflight=d, donate=donate,
                                 h2d_ring=r or 0)["total_bytes"]

    r0 = ring or 0
    cand = []
    if batch > 1:
        half = batch // 2
        if half == refused_batch:
            half = max(1, half // 2)
        cand.append((total(half, depth, r0), (half, depth, r0)))
    if depth > 1:
        cand.append((total(batch, depth // 2, r0),
                     (batch, depth // 2, r0)))
    if ring is not None and ring > 1:
        cand.append((total(batch, depth, ring // 2),
                     (batch, depth, ring // 2)))
    # smallest modeled footprint wins; ties prefer halving the batch
    # (listed first), which keeps the pipeline depth — and its overlap —
    # alive longest
    best = min(cand, key=lambda c: c[0])[1]
    return best if ring is not None else best[:2]


def max_vertices_for(hbm_bytes: int, chunk_edges: int) -> int:
    """Largest power-of-2 vertex count whose build fits ``hbm_bytes``."""
    v = 1
    while build_phase_bytes(2 * v, chunk_edges)["total_bytes"] <= hbm_bytes:
        v *= 2
    return v
