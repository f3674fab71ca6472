"""One served partition job as a cooperative step generator.

The daemon cannot afford one thread blocked per job (a blocked host
thread serializes nothing usefully — device executions already
serialize on the one dispatch chain), so a job is a GENERATOR over the
existing ops: each ``yield`` marks one unit of device work done
(a degrees chunk, a staged build group, a scoring chunk), and the
scheduler round-robins ``next()`` across admitted jobs. That makes the
interleave explicit and deterministic: staged segments from DIFFERENT
jobs alternate on one dispatch chain, each folding into its own
carried table — sound because each job's elimination fixpoint is
order-independent in its own constraint multiset (the PR-1/PR-3
invariant; no job ever reads another's table).

Bit-identity with the cold CLI build is by construction, not by luck:
the degree accumulation (int64 host totals), the rank clip, the
elimination order, the batched fold (unique fixpoint at any batch
shape), the host tree split and the scoring pass are the same ops the
``tpu`` backend drives, in the same vertex spaces.

Fault containment (per job, ISSUE 9 reused): each staged group folds
under the job's own :class:`~sheep_tpu.utils.retry.RetryPolicy` —
an OOM-class fault degrades THAT job's dispatch batch (membudget
model) and re-folds the same staged block bit-identically
(``donate=False`` keeps the inputs valid across the retry); read
faults never even surface here (the edgestream's bounded retry
absorbs them). A fault that exhausts its budget fails the job, not
the daemon.

Cancellation: the scheduler calls ``close()`` on the step generator;
GeneratorExit unwinds through the ``finally`` blocks below, which
close the chunk/group iterators — and through them the prefetch
workers (``Prefetcher.close()``: stop + drain + join) — and end the
job's phase spans, deterministically, before the job is marked
cancelled.

Durability (ISSUE 14): a durable scheduler hands each job a per-job
:class:`~sheep_tpu.utils.checkpoint.Checkpointer` domain (a
subdirectory of the daemon's checkpoint dir keyed by job id). The
engine saves at chunk/group boundaries on the checkpointer's cadence
— each save pulls the carried table to host, which IS the PR-3 flush
barrier (the pulled state is confirmed, nothing in flight can
under-represent it) — and on (re)start resumes from the newest intact
step: degrees resume restores the int64 host totals (exact integer
addition, so early flushes at save points change nothing), build
resume restores the carried table and re-folds the remaining chunks
into it (bit-identical: the same folds in the same order), score
resume restores the per-k counters and the host forest. A resumed
served forest is therefore bit-identical to the uninterrupted served
build, which is itself bit-identical to the cold CLI build.
:meth:`request_checkpoint` arms an off-cadence save at the next
boundary — the graceful-drain hook (``sheepd`` SIGTERM): once the
save lands, ``suspend_ready`` flips and the scheduler parks the job.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Optional

import numpy as np

import jax.numpy as jnp

from sheep_tpu import obs
from sheep_tpu.backends.tpu_backend import (_device_chunk_groups,
                                            _device_chunks,
                                            check_dispatch_batch,
                                            resolve_h2d_ring)
from sheep_tpu.io.devicestream import is_device_stream
from sheep_tpu.io.edgestream import open_input
from sheep_tpu.ops import degrees as degrees_ops
from sheep_tpu.ops import elim as elim_ops
from sheep_tpu.ops import order as order_ops
from sheep_tpu.ops import score as score_ops
from sheep_tpu.ops import split as split_ops
from sheep_tpu.types import PartitionResult, check_tpu_vertex_range
from sheep_tpu.utils import checkpoint as ckpt_mod
from sheep_tpu.utils import retry as retry_mod
from sheep_tpu.utils.platform import device_identity


class JobEngine:
    """Drives one admitted job; see module docstring. ``job`` is a
    :class:`sheep_tpu.server.scheduler.Job`; ``cache`` an optional
    shared device chunk cache (the daemon's, keyed to this input);
    ``checkpointer`` an optional per-job recovery domain, with
    ``resume`` asking for a resume from its newest intact step."""

    def __init__(self, job, cache=None, checkpointer=None,
                 resume: bool = False):
        self.job = job
        self.cache = cache
        self.ckpt = checkpointer
        self.resume = bool(resume)
        # graceful-drain handshake: request_checkpoint() arms an
        # off-cadence save at the next boundary; the save flips
        # suspend_ready and the scheduler parks the job (benign
        # cross-thread bool — armed under the scheduler lock, read by
        # the dispatch thread between steps)
        self._ckpt_request = False
        self.suspend_ready = False
        # live dispatch knobs — the retry layer's degrade hook halves
        # these mid-build; the staging loop restages at the new shape
        self.batch: Optional[int] = None
        self.ring: int = 1
        self._n = 0
        self._cs = 0
        self._build_idx = 0
        self._dev_stream = False

    # -- durability hooks (ISSUE 14) -----------------------------------
    def request_checkpoint(self) -> None:
        """Arm a save at the next chunk/group boundary regardless of
        cadence — the scheduler's graceful-drain hook."""
        if self.ckpt is not None:
            self._ckpt_request = True
        else:
            self.suspend_ready = True  # nothing to save; park now

    def _save(self, phase: str, idx: int, arrays: dict, meta) -> None:
        self.ckpt.save(phase, int(idx), arrays, meta)
        stats = self.job.stats
        stats["ckpt_saves"] = stats.get("ckpt_saves", 0) + 1
        if self._ckpt_request:
            self._ckpt_request = False
            self.suspend_ready = True

    def _save_score(self, idx: int, minp_host, deg_host, cut: dict,
                    total: int, cv_chunks: dict, rounds: int,
                    meta) -> None:
        """Score-phase save: per-k cut counters + the host forest; the
        cv-key accumulators are compacted into the checkpoint and
        carried forward compacted (the save_score_state convention)."""
        arrays = {"minp": np.asarray(minp_host),
                  "deg": np.asarray(deg_host),
                  "total": np.int64(total), "rounds": np.int64(rounds)}
        for k, c in cut.items():
            arrays[f"cut_k{k}"] = np.int64(c)
            if self.job.spec.comm_volume:
                keys = ckpt_mod.compact_cv_keys(cv_chunks[k])
                arrays[f"cv_k{k}"] = keys
                cv_chunks[k] = [keys]
        self._save("score", idx, arrays, meta)

    # -- fault hooks (per job; the daemon survives, the job degrades) --
    def _on_resource(self):
        # DETACH from the shared chunk cache rather than clearing it in
        # place: a suspended _device_chunks iterator may be mid-way
        # through cache.chunks, and emptying the list under it would
        # make it restart the upload stream at 0 (re-folding the prefix
        # — harmless for the fixpoint, but wasted device work and a
        # skewed step count). The cache_shed flag tells the scheduler
        # to drop the whole entry at finalize, so the HBM is released
        # when the engine's references die and future jobs start fresh.
        if self.cache is not None:
            self.cache = None
            self.job.cache_shed = True
        nxt = retry_mod.degrade_dispatch(
            self._n, self._cs, self.batch, 1, False,
            self.job.stats, self._build_idx,
            h2d_ring=None if self._dev_stream else self.ring)
        if nxt is not None:
            self.batch = nxt[0]
            if len(nxt) > 2:
                self.ring = nxt[2]

    def _enter_phase(self, phase: str) -> None:
        # live progress signal (ISSUE 11): the job descriptor's phase
        # field updates at phase ENTRY (the scheduler confirms it from
        # each step's yield value afterward), and the transition lands
        # in the trace + the job's flight-recorder ring
        self.job.phase = phase
        obs.event("job_phase", job=self.job.id, phase=phase)

    def _phase_span(self, name: str):
        # phase spans parent locally to the job span; a propagated
        # trace id (ISSUE 18) rides on each so --stitch can collect a
        # job's whole subtree by trace attr even across files
        tid = getattr(self.job, "trace_id", None)
        return obs.begin_detached(
            name, parent=self.job.span_id,
            **({"trace": tid} if tid else {}))

    def _on_device_loss(self):
        # best-effort in-process runtime reinit (utils/retry, ISSUE 9):
        # THIS job's live device arrays died with the old client, so
        # its own retries usually exhaust and the job FAILS — but the
        # reinit is what keeps the resident daemon able to serve the
        # NEXT job on a fresh runtime instead of failing every request
        # against a dead accelerator forever. (A durable daemon then
        # also resumes the lossy job from its last checkpoint on
        # restart — the served kill+resume contract, ISSUE 14.)
        retry_mod.recover_device_loss(self.job.stats, self._build_idx)

    def steps(self):
        """The step generator (see module docstring); sets
        ``job.results`` before finishing."""
        job = self.job
        spec = job.spec
        stats = job.stats
        stats_acc = obs.stats_accumulator()
        policy = retry_mod.RetryPolicy()
        t_phase: dict = {}
        with open_input(spec.input,
                        n_vertices=spec.num_vertices) as es:
            n = es.num_vertices
            check_tpu_vertex_range(n, "sheepd")
            cs = es.clamp_chunk_edges(spec.chunk_edges)
            self._n, self._cs = n, cs
            # staged H2D ring (ISSUE 12): device-stream inputs
            # (rmat-hash:/sbm-hash: specs) synthesize chunks in
            # accelerator memory — zero host bytes per served chunk;
            # host-format inputs stage through the ring exactly as the
            # CLI's tpu driver does (same _device_chunks supplier)
            self._dev_stream = is_device_stream(es)
            self.ring = resolve_h2d_ring(spec.h2d_ring)
            # in-job pipeline depth (ISSUE 16): D issued executions'
            # staging blocks live at once
            depth = spec.inflight
            self.batch = check_dispatch_batch(spec.dispatch_batch)
            stats["dispatch_batch"] = self.batch
            stats["inflight_depth"] = depth
            job.n_vertices = n

            # ---- durable resume (ISSUE 14) --------------------------
            meta = None
            state = None
            if self.ckpt is not None:
                # every bit-affecting option is in the fingerprint; a
                # mismatch (input changed under the journaled job)
                # raises and FAILS the job — resuming would corrupt it
                meta = ckpt_mod.stream_meta(
                    es, k=int(spec.ks[0]), chunk_edges=cs,
                    weights=spec.weights, alpha=spec.alpha,
                    comm_volume=spec.comm_volume,
                    ks=[int(k) for k in spec.ks],
                    segment_rounds=int(spec.segment_rounds), served=1)
                state = ckpt_mod.resume_state(self.ckpt, meta,
                                              self.resume)
                if state is not None:
                    stats["resume_phase_idx"] = float(
                        ckpt_mod.phase_index(state.phase))
                    stats["resume_chunk_idx"] = float(state.chunk_idx)
            resume_phase = state.phase if state is not None else None

            # ---- degrees --------------------------------------------
            t0 = time.perf_counter()
            deg_start = 0
            deg_host = np.zeros(n, dtype=np.int64)
            if resume_phase == "degrees":
                deg_host = state.arrays["deg"].astype(np.int64)
                deg_start = int(state.chunk_idx)
            if resume_phase in (None, "degrees"):
                self._enter_phase("degrees")
                sp = self._phase_span("degrees")
                deg = degrees_ops.init_degrees(n)
                flush_every = degrees_ops.flush_every_for(cs)
                since = 0
                idx = deg_start
                chunks = _device_chunks(es, cs, n, self.cache,
                                        deg_start, self.ring, stats)
                try:
                    for padded in chunks:
                        deg = degrees_ops.degree_chunk(deg, padded, n)
                        since += 1
                        idx += 1
                        at_ckpt = self.ckpt is not None and (
                            self.ckpt.due(idx - deg_start)
                            or self._ckpt_request)
                        if since >= flush_every or at_ckpt:
                            # early flushes at save points are exact:
                            # integer degree sums are associative
                            deg_host += np.asarray(  # sheeplint: sync-ok
                                deg[:n], dtype=np.int64)
                            deg = degrees_ops.init_degrees(n)
                            since = 0
                        if at_ckpt:
                            self._save("degrees", idx,
                                       {"deg": deg_host}, meta)
                        stats_acc.absorb(stats)
                        yield "degrees"
                finally:
                    chunks.close()
                    sp.end()
                deg_host += np.asarray(deg[:n],  # sheeplint: sync-ok
                                       dtype=np.int64)
            else:
                # build/score resume: the completed degree totals ride
                # in every later-phase checkpoint
                deg_host = state.arrays["deg"].astype(np.int64)
            t_phase["degrees"] = time.perf_counter() - t0

            # ---- sort (one step; recomputed on resume — the order is
            # a pure deterministic function of the degree totals) -----
            t0 = time.perf_counter()
            self._enter_phase("sort")
            sp = self._phase_span("sort")
            try:
                # the rank clip + flush cadence are SHARED with the tpu
                # backend (ops/degrees.py) — the served==CLI bit-identity
                # contract must not rest on two hand-maintained copies
                deg_rank = degrees_ops.rank_clip_i32(deg_host)
                deg_dev = jnp.asarray(deg_rank, dtype=jnp.int32)
                pos, order = order_ops.elimination_order(deg_dev, n)
                # the host needs pos for the split anyway: this pull
                # is also the sort phase's completion barrier
                pos_host = np.asarray(pos[:n])  # sheeplint: sync-ok
            finally:
                sp.end()
            t_phase["sort"] = time.perf_counter() - t0
            yield "sort"

            # ---- build: staged batched dispatch ---------------------
            total_rounds = 0
            if resume_phase == "score":
                # build completed before the save; its confirmed forest
                # rides in the score checkpoint
                minp_host = state.arrays["minp"]
                total_rounds = int(state.arrays.get("rounds", 0))
                t_phase["build"] = 0.0
            else:
                t0 = time.perf_counter()
                self._enter_phase("build")
                sp = self._phase_span("build")
                if resume_phase == "build":
                    P = jnp.asarray(state.arrays["p"], dtype=jnp.int32)
                    self._build_idx = int(state.chunk_idx)
                    total_rounds = int(state.arrays.get("rounds", 0))
                else:
                    P = jnp.full(n + 1, n, dtype=jnp.int32)
                    self._build_idx = 0
                sentinel_chunk = None
                # ---- in-job pipelined dispatch (ISSUE 16): compose
                # the PR-3 depth-D pipeline into the served engine.
                # Each fifo entry is one ISSUED but unconfirmed
                # execution — (p_in, loB, hiB, gl, rounds_dev), with
                # p_in the carried table BEFORE that fold
                # (donate=False keeps it and the staged blocks valid).
                # CONFIRMING pulls the rounds scalar — the only
                # per-group host sync; deferring it depth-1 groups
                # lets the host issue ahead of the device and lets
                # interleaved jobs overlap H2D + compute instead of
                # serializing every step on the dispatch thread. The
                # confirmed table after entry i is entry i+1's p_in
                # (the tip when nothing younger is in flight) — what
                # checkpoints save, so a resume re-folds exactly the
                # unconfirmed groups, bit-identically.
                fifo: deque = deque()
                issued_idx = self._build_idx

                def fold_retrying(p, lo, hi):
                    while True:
                        try:
                            # classify/budget/count/backoff on fault —
                            # degrade THIS job, never the daemon;
                            # donate=False keeps p/lo/hi valid for
                            # the retry
                            return elim_ops.fold_segments_batch(
                                p, lo, hi, n,
                                segment_rounds=spec.segment_rounds,
                                stats=stats, donate=False)
                        except Exception as exc:
                            retry_mod.handle_build_fault(
                                policy, exc, f"sheepd.{job.id}.build",
                                stats,
                                on_resource=self._on_resource,
                                on_device_loss=self._on_device_loss)

                def issue(group, gl):
                    nonlocal P
                    loB, hiB = elim_ops.orient_chunks_batch_pos(
                        jnp.stack(group), pos, n)
                    P2, rounds = fold_retrying(P, loB, hiB)
                    fifo.append((P, loB, hiB, gl, rounds))
                    P = P2

                def confirm():
                    # one confirmed execution. A fault surfacing at
                    # the sync (an async failure materializing late)
                    # re-drives every unconfirmed fold synchronously
                    # from the oldest staged inputs — bit-identical:
                    # the same folds in the same order into the same
                    # confirmed table.
                    nonlocal P, total_rounds
                    p_in, loB, hiB, gl, rounds = fifo.popleft()
                    try:
                        r = int(rounds)
                    except Exception as exc:
                        retry_mod.handle_build_fault(
                            policy, exc, f"sheepd.{job.id}.build",
                            stats, on_resource=self._on_resource,
                            on_device_loss=self._on_device_loss)
                        pending = [(p_in, loB, hiB, gl)]
                        pending += [(e[0], e[1], e[2], e[3])
                                    for e in fifo]
                        fifo.clear()
                        P = pending[0][0]
                        r, gl = 0, 0
                        for _p, lo2, hi2, g2 in pending:
                            P2, rr = fold_retrying(P, lo2, hi2)
                            r += int(rr)
                            P = P2
                            gl += g2
                    total_rounds += r
                    prev_idx = self._build_idx
                    self._build_idx += gl
                    if self.ckpt is not None and (
                            self.ckpt.due_span(prev_idx,
                                               self._build_idx)
                            or self._ckpt_request):
                        # the pull IS the flush barrier: the confirmed
                        # table (the next in-flight entry's input, or
                        # the tip with an empty pipe) syncs only
                        # confirmed work, so the saved table can never
                        # over-represent build_idx (PR-3 semantics)
                        p_conf = fifo[0][0] if fifo else P
                        self._save(
                            "build", self._build_idx,
                            {"p": np.asarray(p_conf),  # sheeplint: sync-ok
                             "deg": deg_host,
                             "rounds": np.int64(total_rounds)},
                            meta)

                try:
                    while True:
                        batch = self.batch
                        ring = self.ring
                        groups = _device_chunk_groups(
                            es, cs, n, self.cache, issued_idx,
                            batch, ring, stats)
                        restage = False
                        try:
                            for group in groups:
                                gl = len(group)
                                if gl < batch:
                                    if sentinel_chunk is None:
                                        sentinel_chunk = jnp.full(
                                            (cs, 2), n, jnp.int32)
                                    group = group + [sentinel_chunk] * \
                                        (batch - gl)
                                issue(group, gl)
                                issued_idx += gl
                                if len(fifo) >= depth:
                                    confirm()
                                stats_acc.absorb(stats)
                                yield "build"
                                if self.batch != batch \
                                        or self.ring != ring:
                                    # degraded mid-stream: restage the
                                    # remainder at the new shape (and
                                    # the abandoned supplier's finally
                                    # drains its staged ring blocks);
                                    # in-flight entries stay in the
                                    # pipe and confirm on later steps
                                    restage = True
                                    break
                        finally:
                            groups.close()
                        if not restage:
                            break
                    while fifo:
                        # drain the pipe: a step stays one confirmed
                        # execution, so the tail confirms one per yield
                        confirm()
                        stats_acc.absorb(stats)
                        yield "build"
                finally:
                    sp.end(rounds=int(total_rounds))
                minp = P[pos]
                minp_host = np.asarray(minp)  # barrier  # sheeplint: sync-ok
                t_phase["build"] = time.perf_counter() - t0
            stats["fixpoint_rounds"] = float(total_rounds)

            # ---- split (host, per k — the multi-k reuse query) ------
            t0 = time.perf_counter()
            self._enter_phase("split")
            sp = self._phase_span("split")
            try:
                parent = elim_ops.minp_to_parent(minp_host, order, n)
                w = deg_host.astype(np.float64) \
                    if spec.weights == "degree" else None
                assigns = {}
                for k in spec.ks:
                    assigns[k] = split_ops.tree_split_host(
                        parent, pos_host, k, weights=w,
                        alpha=spec.alpha)
            finally:
                sp.end()
            t_phase["split"] = time.perf_counter() - t0
            yield "split"

            # ---- score: ONE stream pass for every k -----------------
            t0 = time.perf_counter()
            self._enter_phase("score")
            sp = self._phase_span("score")
            dev_assign = {
                k: jnp.concatenate([jnp.asarray(a, dtype=jnp.int32),
                                    jnp.zeros(1, dtype=jnp.int32)])
                for k, a in assigns.items()}
            cut = {k: 0 for k in assigns}
            cv_chunks: dict = {k: [] for k in assigns}
            total = 0
            score_start = 0
            if resume_phase == "score":
                score_start = int(state.chunk_idx)
                total = int(state.arrays["total"])
                for k in assigns:
                    cut[k] = int(state.arrays[f"cut_k{k}"])
                    if spec.comm_volume:
                        cv_chunks[k] = [state.arrays[f"cv_k{k}"]]
            elif self.ckpt is not None:
                # bank build completion at score entry: a crash before
                # the first cadence save must not re-fold the build
                # tail from an older build checkpoint
                self._save_score(0, minp_host, deg_host, cut, total,
                                 cv_chunks, total_rounds, meta)
            idx = score_start
            chunks = _device_chunks(es, cs, n, self.cache, score_start,
                                    self.ring, stats)
            try:
                for padded in chunks:
                    first = True
                    for k, a_dev in dev_assign.items():
                        c, tt = score_ops.score_chunk(padded, a_dev, n)
                        # designed per-chunk score pull (two scalars)
                        cut[k] += int(c)  # sheeplint: sync-ok
                        if first:
                            total += int(tt)  # sheeplint: sync-ok
                            first = False
                        if spec.comm_volume:
                            score_ops.accumulate_cv_keys(
                                cv_chunks[k],
                                score_ops.cut_pair_keys_host(
                                    padded, a_dev, n, k))
                    idx += 1
                    if self.ckpt is not None and (
                            self.ckpt.due(idx - score_start)
                            or self._ckpt_request):
                        self._save_score(idx, minp_host, deg_host, cut,
                                         total, cv_chunks,
                                         total_rounds, meta)
                    stats_acc.absorb(stats)
                    yield "score"
            finally:
                chunks.close()
                sp.end()
            t_phase["score"] = time.perf_counter() - t0

            if spec.resident:
                # resident partition (ISSUE 15): wrap the finished
                # build's artifacts into an incremental PartitionState
                # — the converged carried table the tenant will stream
                # delta epochs at. A delta: input seeds the state at
                # the log's epoch (state_from_build handles both).
                from sheep_tpu import incremental as inc_mod

                job.incremental_state = inc_mod.state_from_build(
                    es, spec.ks, spec.weights, spec.alpha, cs,
                    "sheepd", pos_host, deg_host, minp_host, total,
                    base_spec=spec.input)
                # seed the incremental score cache from the build's
                # own full scoring pass (ISSUE 17): the tenant's
                # FIRST scored epoch is then O(delta) too, instead of
                # paying a seeding O(E) pass on the update path. Best
                # effort — a failed seed just means refresh() stays
                # on full passes until one seeds it.
                inc_mod._seed_score_cache(
                    job.incremental_state, assigns,
                    {k: (cut[k], total) for k in spec.ks})

        from sheep_tpu.core import pure

        results = []
        for k in spec.ks:
            cv = int(len(ckpt_mod.compact_cv_keys(cv_chunks[k]))) \
                if spec.comm_volume else None
            bal = pure.part_balance(
                assigns[k], k,
                deg_host if spec.weights == "degree" else None)
            results.append(PartitionResult(
                assignment=assigns[k], k=k, edge_cut=cut[k],
                total_edges=total,
                cut_ratio=cut[k] / max(total, 1), balance=bal,
                comm_volume=cv, phase_times=dict(t_phase),
                backend="sheepd",
                diagnostics={kk: (round(float(v), 3)
                                  if str(kk).startswith("t_")
                                  or str(kk).endswith("_ms")
                                  else float(v))
                             for kk, v in stats.items()
                             if isinstance(v, (int, float))}
                | device_identity()))
        for r in results:
            # the quality plane (ISSUE 13): the served job's final
            # scores land in the trace + the job's flight ring the
            # moment they exist; the scheduler turns them into the
            # sheep_quality_* series at finalize
            obs.event("job_quality", job=job.id, k=int(r.k),
                      cut_ratio=round(float(r.cut_ratio), 6),
                      balance=round(float(r.balance), 4),
                      edge_cut=int(r.edge_cut))
        job.results = results
