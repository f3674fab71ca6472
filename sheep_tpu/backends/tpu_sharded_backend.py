"""Multi-device sharded TPU backend, registered as ``tpu-sharded``.

The v5e-8 / multi-host execution strategy (SURVEY.md §2 #9, §7 step 5):
edge chunks round-robin across the ``shards`` mesh axis, per-device partial
forests, butterfly merge over ICI, psum scoring. Thin wrapper around
``ShardedPipeline.run`` (the single implementation of the streaming
loops); falls back gracefully to a 1-device mesh with results identical to
the ``tpu`` backend.
"""

from __future__ import annotations

import numpy as np

from sheep_tpu.backends.base import Partitioner, register
from sheep_tpu.parallel.mesh import shards_mesh
from sheep_tpu.parallel.pipeline import ShardedPipeline, cached_pipeline
from sheep_tpu.types import PartitionResult, check_tpu_vertex_range
from sheep_tpu.utils.platform import device_identity


@register
class TpuShardedBackend(Partitioner):
    name = "tpu-sharded"
    supports_checkpoint = True
    supports_multidevice = True
    # incremental repartitioning (ISSUE 19): delta epochs fold through
    # the lockstep batch machinery (_fold_delta below), scored refreshes
    # rescore device-side with one all-reduce (_move_rescore)
    supports_incremental = True

    def __init__(self, chunk_edges: int = 1 << 22, lift_levels: int = 0,
                 alpha: float = 1.0, n_devices: int | None = None,
                 segment_rounds: int = 32, warm_schedule=((1, 8),),
                 dispatch_batch: int = 1, inflight: int = 1,
                 donate_buffers: bool | None = None):
        self.chunk_edges = chunk_edges
        self.lift_levels = lift_levels
        self.alpha = alpha
        self.n_devices = n_devices
        self.segment_rounds = segment_rounds
        self.warm_schedule = tuple(warm_schedule)
        # batched segment dispatch (see ShardedPipeline): 1 (with
        # inflight 1) = the adaptive per-segment fold, N > 1 = stage N
        # sharded batches per program. The default is per-segment on
        # every platform, as for the tpu backend, whose batched twin
        # lost 25x on a v5e (chip run, PR 21); on the CPU at RMAT-16
        # the batched sharded fold runs about as many rounds (176 vs
        # 184), each N chunks wide
        if dispatch_batch < 1:
            raise ValueError("dispatch_batch must be >= 1")
        self.dispatch_batch = dispatch_batch
        # asynchronous dispatch pipeline depth for the batched path
        # (see ShardedPipeline.build_step_batch): 1 = synchronous
        if inflight < 1:
            raise ValueError("inflight must be >= 1")
        self.inflight = inflight
        # donate per-device tables + staging blocks into the batched
        # executions (None = auto: on for the batched/pipelined path)
        self.donate_buffers = donate_buffers

    def partition(self, stream, k: int, weights: str = "unit",
                  comm_volume: bool = True, checkpointer=None,
                  resume: bool = False, **opts) -> PartitionResult:
        # comm_volume defaults True like every other backend (VERDICT r1
        # weak #5 asked for consistency); pass False to skip the host-side
        # O(cut pairs) accumulator on huge runs
        if getattr(stream, "order_anchor", False):
            import jax

            if jax.process_count() > 1:
                from sheep_tpu.types import UnsupportedGraphError

                raise UnsupportedGraphError(
                    "delta: inputs stream single-shard; a multi-host "
                    "mesh cannot byte-range an anchored log — run the "
                    "delta build on a single-host mesh or --backend "
                    "tpu/cpu")
        n = stream.num_vertices
        check_tpu_vertex_range(n, self.name)
        mesh = shards_mesh(self.n_devices)
        # shrink the chunk so small graphs don't pad (and compile) up to
        # the full default chunk shape; shared helper so the backends'
        # chunk sizing (and checkpoint fingerprints) cannot diverge
        cs = stream.clamp_chunk_edges(self.chunk_edges,
                                      parts=mesh.devices.size)
        from sheep_tpu.backends.tpu_backend import check_dispatch_batch

        inflight = self.inflight
        donate = True if self.donate_buffers is None else self.donate_buffers
        nb = check_dispatch_batch(self.dispatch_batch)
        pipe = cached_pipeline(n, cs, mesh, lift_levels=self.lift_levels,
                               segment_rounds=self.segment_rounds,
                               warm_schedule=self.warm_schedule,
                               dispatch_batch=nb, inflight=inflight,
                               donate=donate)

        timings: dict = {}
        out = pipe.run(stream, k, alpha=self.alpha, weights=weights,
                       comm_volume=comm_volume, timings=timings,
                       checkpointer=checkpointer, resume=resume)
        return PartitionResult(
            assignment=out["assignment"], k=k, edge_cut=out["edge_cut"],
            total_edges=out["total_edges"],
            cut_ratio=out["edge_cut"] / max(out["total_edges"], 1),
            balance=out["balance"], comm_volume=out["comm_volume"],
            phase_times=timings, backend=self.name,
            # t_* walls and *_ms counters accumulate unrounded (elim.py
            # t_add/_t_ms convention) and are rounded here at read
            # time, matching the tpu backend and bench.py so artifacts
            # stay diffable
            diagnostics={k_: (round(v, 3)
                              if (k_.startswith("t_")
                                  or k_.endswith("_ms"))
                              and isinstance(v, float)
                              else v if isinstance(v, (int, float))
                              else str(v))
                         for k_, v in {**out.get("build_stats", {}),
                                       **out.get("merge_stats", {}),
                                       **device_identity()}.items()},
            tree={"parent": np.asarray(out["parent"]), "pos": out["pos"],
                  "deg": out["degrees"]} if opts.get("keep_tree") else None,
        )

    # -- incremental repartitioning (ISSUE 19) -----------------------------
    def _update_pipe(self, n: int, m: int) -> ShardedPipeline:
        """Cached fold pipeline for the resident update path, keyed on
        the pow2-quantized delta chunk width: repeat epochs at similar
        delta sizes reuse every compiled program (the sheeplint ``fold``
        rule's no-per-epoch-recompile contract). The simple per-segment
        dispatch (batch=1, inflight=1) is the right shape here — a delta
        is a handful of chunks, not a streamed epoch of thousands."""
        from sheep_tpu.ops import elim as elim_ops

        cs = elim_ops.pow2_at_least(min(m, self.chunk_edges),
                                    floor=1 << 10)
        cache = getattr(self, "_upd_pipes", None)
        if cache is None:
            cache = self._upd_pipes = {}
        pipe = cache.get((n, cs))
        if pipe is None:
            mesh = shards_mesh(self.n_devices)
            pipe = cache[(n, cs)] = cached_pipeline(
                n, cs, mesh, lift_levels=self.lift_levels,
                segment_rounds=self.segment_rounds,
                warm_schedule=self.warm_schedule,
                dispatch_batch=1, inflight=1, donate=False)
        return pipe

    def _fold_delta(self, state, edges) -> None:
        """Fold one epoch's adds into the carried table through the
        per-shard lockstep batch machinery: re-seed device row 0 with
        the converged table (merging is associative and idempotent —
        the checkpoint-resume idiom of ``ShardedPipeline.run``), fold
        the delta chunks round-robin over the mesh, butterfly-merge
        back. Bit-identical to the single-device fold: same constraint
        multiset under the same anchored order, unique fixpoint."""
        e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
        if not len(e):
            return
        n = state.n
        pipe = self._update_pipe(n, len(e))
        cs, rows = pipe.cs, pipe.n_local
        stats = state.stats
        order_sent = np.concatenate([state.order,
                                     np.asarray([n], np.int64)])
        pos_sent = np.concatenate([state.pos.astype(np.int32),
                                   np.asarray([n], np.int32)])
        fa = np.full((rows, n + 1), n, np.int32)
        if pipe.proc == 0:
            # vertex-space carried table -> position space, into global
            # row 0; the other rows start empty and merge away
            fa[0] = np.asarray(state.minp, np.int32)[order_sent]
        P_all = pipe._put(pipe.state_sharding, fa)
        pos = pipe.put_replicated(pos_sent)
        from sheep_tpu.backends.tpu_backend import pad_chunk

        chunks = [pad_chunk(e[off: off + cs], cs, n)
                  for off in range(0, len(e), cs)]
        sentinel = None
        for g0 in range(0, len(chunks), rows):
            group = chunks[g0: g0 + rows]
            if len(group) < rows:
                if sentinel is None:
                    sentinel = np.full((cs, 2), n, np.int32)
                group = group + [sentinel] * (rows - len(group))
            P_all = pipe.build_step(
                P_all, pipe.put_batch(np.stack(group)), pos,
                stats=stats)
        merged = pipe.merge(P_all, stats=stats)
        state.minp = np.asarray(  # sheeplint: sync-ok
            pipe.to_minp(merged, pos))
        stats["update_folds"] = stats.get("update_folds", 0) + 1

    def _move_rescore(self, src, dst, prevs, news, masks):
        """Distributed rescore hook for the incremental score cache
        (:func:`sheep_tpu.ops.score.move_rescore_sharded`): per-shard
        cut deltas for every k in ONE program, all-reduced once."""
        from sheep_tpu.ops.score import move_rescore_sharded

        return move_rescore_sharded(src, dst, prevs, news, masks,
                                    shards_mesh(self.n_devices))
