"""Vertex-sharded big-V backend, registered as ``tpu-bigv``.

For graphs whose vertex tables exceed one chip's HBM (BASELINE.md eval
config 5, RMAT-30 class): every vertex-indexed table (pos/order/minp,
degrees, assignment) is block-sharded over the device mesh and the
displacement fixpoint runs as ONE distributed forest with routed
collectives (``parallel/bigv.py``). Per-device table memory is O(V/D);
the standard ``tpu-sharded`` backend is faster whenever the replicated
tables fit (V <= 2^29 single-chip), so pick this one only beyond that.
Multi-host works the same way (the mesh spans all processes' devices and
the routed collectives ride DCN); tested against the sequential oracle in
``tests/test_multihost.py``.
"""

from __future__ import annotations

from sheep_tpu.backends.base import Partitioner, register
from sheep_tpu.parallel.bigv import BigVPipeline, cached_pipeline
from sheep_tpu.parallel.mesh import shards_mesh
from sheep_tpu.types import PartitionResult, check_tpu_vertex_range
from sheep_tpu.utils.platform import device_identity


@register
class TpuBigVBackend(Partitioner):
    name = "tpu-bigv"
    supports_multidevice = True
    supports_checkpoint = True
    # incremental repartitioning (ISSUE 19): delta epochs fold into the
    # one distributed forest (_fold_delta), scored refreshes rescore
    # device-side with one all-reduce (_move_rescore)
    supports_incremental = True

    def __init__(self, chunk_edges: int = 1 << 20, alpha: float = 1.0,
                 jumps: int = 128, n_devices: int | None = None,
                 lift_levels: int = 0, segment_rounds: int = 16,
                 hoist_bytes: int | None = None):
        self.chunk_edges = chunk_edges
        self.alpha = alpha
        self.jumps = jumps
        self.n_devices = n_devices
        # memory/speed trade of the routed fixpoint: each lifting level
        # is a (D, B)-shaped routed lookup inside one compiled program
        # (auto depth at V=2^30 OOM-killed a 125 GB virtual-mesh host —
        # tools/bigv_scale30.py), and segment_rounds bounds rounds per
        # device execution the same way. 0 = auto depth.
        self.lift_levels = lift_levels
        self.segment_rounds = segment_rounds
        # per-device HBM budget for the per-segment (stale) lifting
        # stack; default 0 = per-round squaring — hoisting measured
        # WORSE below the V-dominant regime (see BigVPipeline)
        self.hoist_bytes = hoist_bytes

    def partition(self, stream, k: int, weights: str = "unit",
                  comm_volume: bool = True, checkpointer=None,
                  resume: bool = False, **opts) -> PartitionResult:
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
        cs = self.chunk_edges
        m_cheap = stream.num_edges_cheap
        if m_cheap is not None:
            cs = min(cs, max(1024, -(-m_cheap // mesh.devices.size)))
        pipe = cached_pipeline(n, cs, mesh, jumps=self.jumps,
                               lift_levels=self.lift_levels,
                               segment_rounds=self.segment_rounds,
                               hoist_bytes=self.hoist_bytes)

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
            diagnostics={"fixpoint_rounds": float(out["fixpoint_rounds"]),
                         # the clamped value actually run, so artifact
                         # tooling records it instead of re-deriving the
                         # clamp formula (which could silently drift)
                         "chunk_edges_effective": float(cs),
                         **{k_: float(v) for k_, v in
                            out.get("build_stats", {}).items()},
                         **device_identity()},
            tree={"parent": out["parent"], "pos": out["pos"],
                  "deg": out["degrees"]} if opts.get("keep_tree") else None,
        )

    # -- incremental repartitioning (ISSUE 19) -----------------------------
    def _update_pipe(self, n: int, m: int) -> BigVPipeline:
        """Cached fold pipeline for the resident update path, keyed on
        the pow2-quantized delta chunk width so repeat epochs reuse
        every compiled routed-collective program (the sheeplint ``fold``
        rule's no-per-epoch-recompile contract)."""
        from sheep_tpu.ops.elim import pow2_at_least

        cs = pow2_at_least(min(m, self.chunk_edges), floor=1 << 10)
        cache = getattr(self, "_upd_pipes", None)
        if cache is None:
            cache = self._upd_pipes = {}
        pipe = cache.get((n, cs))
        if pipe is None:
            mesh = shards_mesh(self.n_devices)
            pipe = cache[(n, cs)] = cached_pipeline(
                n, cs, mesh, jumps=self.jumps,
                lift_levels=self.lift_levels,
                segment_rounds=self.segment_rounds,
                hoist_bytes=self.hoist_bytes)
        return pipe

    def _fold_delta(self, state, edges) -> None:
        """Fold one epoch's adds into the ONE distributed forest: the
        carried vertex-space table re-enters block-sharded in position
        space, the delta chunks fold through the routed segment
        machinery, and the converged table gathers back. Bit-identical
        to the single-device fold: same constraint multiset under the
        same anchored order, unique fixpoint."""
        import numpy as np

        e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
        if not len(e):
            return
        n = state.n
        pipe = self._update_pipe(n, len(e))
        cs, rows = pipe.cs, pipe.n_local
        stats = state.stats
        order_sent = np.concatenate([state.order,
                                     np.asarray([n], np.int64)])
        pos_pad = np.concatenate([state.pos.astype(np.int32),
                                  np.asarray([n], np.int32)])
        pos_sh = pipe._shard_table(pos_pad)
        P_sh = pipe._shard_table(
            np.asarray(state.minp, np.int32)[order_sent])
        from sheep_tpu.backends.tpu_backend import pad_chunk

        chunks = [pad_chunk(e[off: off + cs], cs, n)
                  for off in range(0, len(e), cs)]
        sentinel = None
        total_rounds = 0
        for g0 in range(0, len(chunks), rows):
            group = chunks[g0: g0 + rows]
            if len(group) < rows:
                if sentinel is None:
                    sentinel = np.full((cs, 2), n, np.int32)
                group = group + [sentinel] * (rows - len(group))
            P_sh, rounds = pipe.build_step(
                P_sh, pos_sh, pipe._put(pipe.batch_sharding,
                                        np.stack(group)),
                stats=stats)
            total_rounds += int(rounds)
        P_host = pipe._allgather_table(pipe._local_block(P_sh))[:n + 1]
        state.minp = P_host[pos_pad]
        stats["update_folds"] = stats.get("update_folds", 0) + 1
        stats["update_rounds"] = \
            stats.get("update_rounds", 0) + total_rounds

    def _move_rescore(self, src, dst, prevs, news, masks):
        """Distributed rescore hook for the incremental score cache
        (:func:`sheep_tpu.ops.score.move_rescore_sharded`): per-shard
        cut deltas for every k in ONE program, all-reduced once."""
        from sheep_tpu.ops.score import move_rescore_sharded

        return move_rescore_sharded(src, dst, prevs, news, masks,
                                    shards_mesh(self.n_devices))
