"""Pallas VMEM-staged gather probe (SURVEY.md §7 step 7; VERDICT r3
weak #3).

CLOSED 2026-08-01: answered on real hardware — Mosaic rejects or
crashes on every gather form larger than one (8, 128) register tile,
probed exhaustively on-chip (tools/pallas_smoke.py --variant 1|2|3;
BASELINE.md
round-5 capture section), so XLA's native gather stands as the
hot-loop primitive by measurement. This module stays as the recorded
artifact of that evaluation and for the interpreter-mode semantics pin
(tests/test_pallas_gather.py); do not reopen without a new Mosaic
toolchain.

The build fixpoint is bound by random int32 gathers from the position
table. XLA's arbitrary-index gather measured ~100-150 M elem/s on the
v5e — ~50x under the HBM roofline — which is precisely the "XLA leaves
throughput on the table" situation SURVEY.md reserves Pallas for. The
open question (BASELINE.md closed it by argument only, which VERDICT r3
rejected): can a kernel that stages the table in VMEM (the P table is
1-17 MB at RMAT-18..22 — VMEM-resident territory, ~16 MB/core) and
gathers from there beat the XLA path >= 2x?

This module is the measurable form of that question. The kernel keeps
the whole table as one VMEM block and lets Mosaic lower the
``jnp.take``; index traffic is blocked over the grid. Two honest
outcomes on real hardware (``tools/microbench_fixpoint.py``
``pallas_vmem_gather_C``):

- it lowers and is faster -> a Pallas round body becomes the first
  credible path to single-chip R >= 1 (BASELINE.md revised thesis);
- Mosaic rejects the arbitrary-index take (the VPU is an 8x128
  elementwise engine without a general cross-VMEM gather) or it is no
  faster -> the gather roofline stands, now with an artifact instead
  of an argument.

``interpret=True`` runs the same kernel in interpreter mode on any
platform — that is what the unit test pins the semantics with.
"""

from __future__ import annotations

import functools


@functools.lru_cache(maxsize=None)
def _build(table_len: int, n_idx: int, block: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(table_ref, idx_ref, out_ref):
        # whole table resident in VMEM; Mosaic decides whether an
        # arbitrary-index take is expressible on the VPU
        out_ref[...] = jnp.take(table_ref[...], idx_ref[...], axis=0,
                                mode="clip")

    def spec(block_shape, index_map):
        # the memory-space constraint is TPU-only; interpret mode
        # runs anywhere
        if interpret:
            return pl.BlockSpec(block_shape, index_map)
        return pl.BlockSpec(block_shape, index_map,
                            memory_space=pltpu.VMEM)

    grid = (n_idx // block,)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            spec((table_len,), lambda i: (0,)),     # full table, every step
            spec((block,), lambda i: (i,)),
        ],
        out_specs=spec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_idx,), jnp.int32),
        interpret=interpret,
    )


def vmem_gather(table, idx, block: int = 8192, interpret: bool = False):
    """``table[idx]`` (clip-mode) with the table staged as one VMEM
    block. ``len(idx)`` must be a multiple of ``block``; the table must
    fit VMEM next to two index blocks (caller sizes it — 2^21 int32
    entries = 8 MB is the probe's cap)."""
    if len(idx) % block:
        raise ValueError(f"len(idx)={len(idx)} not a multiple of "
                         f"block={block}")
    return _build(len(table), len(idx), block, interpret)(table, idx)
