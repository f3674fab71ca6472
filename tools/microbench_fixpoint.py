#!/usr/bin/env python
"""Fixpoint micro-roofline: measure the primitive ops that bound the
build phase, on whatever platform initializes (real TPU or cpu-jax).

The build fixpoint has no MXU work — it is bound by random int32
gathers, scatter-min, and streaming bandwidth (BASELINE.md roofline
note). This tool times each primitive at partition-realistic shapes and
reports effective bytes/sec vs the HBM roofline (v5e ~ 820 GB/s), which
is the data SURVEY.md §7 step 7 requires before deciding XLA-vs-Pallas
for the inner loop: if XLA's gather sustains a healthy fraction of HBM
bandwidth, a hand-written kernel has nothing to win (Pallas TPU has no
vectorized arbitrary-index gather primitive to beat it with — the VPU
is an 8x128 elementwise engine).

Usage:
    python tools/microbench_fixpoint.py [--scale 22] [--chunk-log 24]
        [--profile-dir DIR] [--platform cpu]

One JSON line per measurement on stdout; human summary on stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def emit(**kw):
    print(json.dumps(kw), flush=True)


_CALL_LATENCY = [0.0]


def timeit(fn, *args, reps=5):
    """Median wall seconds of fn(*args), completion forced by pulling a
    4-byte reduction of the output to host, minus the measured per-call
    round-trip latency.

    A host pull of a scalar is an unambiguous completion barrier; its
    round-trip is measured once by :func:`calibrate_latency` and
    subtracted."""
    import numpy as np
    import jax.numpy as jnp

    def pull(out):
        x = out[0] if isinstance(out, tuple) else out
        return np.asarray(jnp.sum(x.ravel()[:8]))  # sheeplint: sync-ok

    pull(fn(*args))  # warm-up/compile
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        pull(fn(*args))
        times.append(time.perf_counter() - t0)
    return max(sorted(times)[len(times) // 2] - _CALL_LATENCY[0], 1e-9)


def calibrate_latency(reps=9):
    """Median round-trip of a trivial call + 4-byte pull (subtracted from
    every measurement)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    tiny = jax.jit(lambda x: x + 1)
    one = jnp.zeros((8,), jnp.int32)
    np.asarray(tiny(one))  # sheeplint: sync-ok
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(jnp.sum(tiny(one)))  # sheeplint: sync-ok
        ts.append(time.perf_counter() - t0)
    _CALL_LATENCY[0] = sorted(ts)[len(ts) // 2]
    return _CALL_LATENCY[0]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=22, help="V = 2^scale")
    ap.add_argument("--chunk-log", type=int, default=24, help="C = 2^this")
    ap.add_argument("--profile-dir", default=None,
                    help="also capture a jax.profiler trace of one "
                         "full fixpoint round")
    ap.add_argument("--platform", default=None,
                    help="pin a platform (e.g. cpu) before jax init")
    ap.add_argument("--hbm-gbps", type=float, default=820.0,
                    help="roofline bandwidth for the ratio column")
    ap.add_argument("--only-gather-conc", action="store_true",
                    help="run ONLY the gather-concurrency leg (VERDICT "
                         "r5 item 8) — the cheap form for one short chip "
                         "call")
    args = ap.parse_args()

    if args.platform:
        from sheep_tpu.utils.platform import pin_platform

        pin_platform(args.platform)

    import jax
    import jax.numpy as jnp
    import numpy as np

    plat = jax.default_backend()
    n = 1 << args.scale
    c = 1 << args.chunk_log
    log(f"platform={plat}  V=2^{args.scale}={n:,}  C=2^{args.chunk_log}={c:,}")
    lat = calibrate_latency()
    emit(bench="call_latency", seconds=round(lat, 6), platform=plat)
    log(f"per-call round-trip latency: {lat * 1e3:.1f} ms (subtracted)")

    def report(name, seconds, bytes_moved, extra=None):
        gbps = bytes_moved / seconds / 1e9
        line = {"bench": name, "seconds": round(seconds, 6),
                "effective_GBps": round(gbps, 2),
                "vs_hbm_roofline": round(gbps / args.hbm_gbps, 4),
                "platform": plat}
        if extra:
            line.update(extra)
        emit(**line)
        log(f"{name:28s} {seconds * 1e3:9.2f} ms   {gbps:8.1f} GB/s "
            f"({100 * gbps / args.hbm_gbps:5.1f}% of roofline)")

    def gather_concurrency_leg():
        """The last falsifiable R probe (VERDICT r5 item 8): XLA's
        ~120 M elem/s gather is 0.2% of HBM roofline — if per-op LATENCY
        (not bandwidth) binds, K independent C-from-V gathers inside one
        XLA program overlap and the K=4 one-program row beats 4x the
        K=1 row; if the rows are flat per gather, R is formally closed.
        Both forms measured: one fused program vs K separate program
        dispatches (completion forced once at the end either way)."""
        for K in (1, 2, 4):
            tabs = [jax.random.randint(jax.random.PRNGKey(10 + j),
                                       (n + 1,), 0, n, dtype=jnp.int32)
                    for j in range(K)]
            idxs = [jax.random.randint(jax.random.PRNGKey(20 + j),
                                       (c,), 0, n, dtype=jnp.int32)
                    for j in range(K)]

            def fused(*ops):
                ts, is_ = ops[:K], ops[K:]
                return sum(jnp.sum(t[i], dtype=jnp.int64)
                           for t, i in zip(ts, is_))

            s = timeit(jax.jit(fused), *tabs, *idxs)  # sheeplint: jit-ok
            report(f"gather_conc_K{K}_one_program", s, 4 * 3 * c * K,
                   {"K": K, "melems_per_s": round(K * c / s / 1e6, 1)})

            g = jax.jit(lambda t, i: jnp.sum(t[i], dtype=jnp.int64))  # sheeplint: jit-ok

            def k_programs():
                acc = None
                for t, i in zip(tabs, idxs):
                    o = g(t, i)
                    acc = o if acc is None else acc + o
                return acc

            s = timeit(k_programs)
            report(f"gather_conc_K{K}_k_programs", s, 4 * 3 * c * K,
                   {"K": K, "melems_per_s": round(K * c / s / 1e6, 1)})

    if args.only_gather_conc:
        gather_concurrency_leg()
        return

    # transfer bandwidth: the h2d/d2h rate bounds every phase
    # that streams chunks from host (64 MiB probes)
    import numpy as np

    host_buf = np.zeros(1 << 24, np.int32)
    t0 = time.perf_counter()
    dev_buf = jax.device_put(host_buf)
    np.asarray(jnp.sum(dev_buf.ravel()[:8]))  # sheeplint: sync-ok
    h2d = time.perf_counter() - t0
    t0 = time.perf_counter()
    np.asarray(dev_buf)
    d2h = time.perf_counter() - t0
    emit(bench="h2d_64MiB", seconds=round(h2d, 4),
         effective_GBps=round(64e-3 / h2d, 3), platform=plat)
    emit(bench="d2h_64MiB", seconds=round(d2h, 4),
         effective_GBps=round(64e-3 / d2h, 3), platform=plat)
    log(f"h2d 64MiB: {h2d:.2f}s ({64 / h2d:.0f} MB/s)   "
        f"d2h 64MiB: {d2h:.2f}s ({64 / d2h:.0f} MB/s)")

    key = jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    table = jax.random.randint(k1, (n + 1,), 0, n, dtype=jnp.int32)
    idx_c = jax.random.randint(k2, (c,), 0, n, dtype=jnp.int32)
    vals = jax.random.randint(k3, (c,), 0, n, dtype=jnp.int32)

    # 1. random gather, C indices into a V-table (the climb's dominant op)
    g = jax.jit(lambda t, i: t[i])
    s = timeit(g, table, idx_c)
    # bytes: C index reads + C random table reads + C writes
    report("gather_C_from_V", s, 4 * (3 * c))

    # 1b. Pallas VMEM-staged gather (SURVEY.md §7 step 7, VERDICT r3
    # weak #3): the XLA gather above runs ~50x under roofline; if
    # staging the table in VMEM wins >= 2x, a Pallas round body is the
    # first credible path to single-chip R >= 1. Table capped at 2^21
    # entries (8 MB; VMEM ~16 MB/core). A Mosaic lowering rejection is
    # ALSO a result — it closes the escape hatch with an artifact.
    try:
        from sheep_tpu.ops.pallas_gather import vmem_gather

        tscale = min(args.scale, 21)
        tn = 1 << tscale
        table_s = jax.lax.slice(table, (0,), (tn,))
        idx_s = jnp.bitwise_and(idx_c, jnp.int32(tn - 1))
        s = timeit(jax.jit(lambda t, i: vmem_gather(t, i)), table_s, idx_s)
        report("pallas_vmem_gather_C", s, 4 * (3 * c),
               {"table_scale": tscale})
        g_ref = jax.jit(lambda t, i: t[i])
        s = timeit(g_ref, table_s, idx_s)
        report("xla_gather_C_matched", s, 4 * (3 * c),
               {"table_scale": tscale})
    except Exception as e:  # lowering rejection or OOM: record, move on
        emit(bench="pallas_vmem_gather_C", error=str(e)[:400],
             platform=plat)
        log(f"pallas_vmem_gather_C FAILED: {str(e)[:200]}")

    # 2. table self-gather t[t] (lifting-table squaring, V-sized)
    g2 = jax.jit(lambda t: t[t])
    s = timeit(g2, table)
    report("gather_V_from_V", s, 4 * (3 * (n + 1)))

    # 3. scatter-min, C updates into a V-table
    sm = jax.jit(lambda t, i, v: t.at[i].min(v, mode="drop"))
    s = timeit(sm, table, idx_c, vals)
    report("scatter_min_C_into_V", s, 4 * (2 * c + 2 * (n + 1)))

    # 3b. sorts at active-buffer shapes — the cost of dedup compaction
    # and of any sort-based alternative to scatter/gather
    srt = jax.jit(lambda i: jax.lax.sort(i))
    s = timeit(srt, idx_c)
    report("sort_C_int32", s, 4 * 2 * c)
    srt2 = jax.jit(lambda a, b: jax.lax.sort((a, b), num_keys=2))
    s = timeit(srt2, idx_c, vals)
    report("sort2key_C_int32", s, 4 * 4 * c)

    # 4. streaming copy baseline (pure-bandwidth reference point)
    cp = jax.jit(lambda t: t + 1)
    big = jnp.zeros(max(n + 1, c), jnp.int32)
    s = timeit(cp, big)
    report("stream_add_V", s, 4 * 2 * big.shape[0])

    # 5. one full lifting fixpoint round at partition-realistic shapes
    from sheep_tpu.ops import elim as elim_ops

    pos = jnp.concatenate([jax.random.permutation(
        k1, jnp.arange(n, dtype=jnp.int32)), jnp.full(1, n, jnp.int32)])
    order = jnp.zeros(n + 1, jnp.int32).at[pos].set(
        jnp.arange(n + 1, dtype=jnp.int32)).at[n].set(n)
    minp = jnp.full(n + 1, n, dtype=jnp.int32)
    lo = jnp.minimum(idx_c, vals)
    hi = jnp.maximum(idx_c, vals)
    lo = jnp.where(lo == hi, n, lo)
    hi = jnp.where(lo == n, n, hi)

    def one_round(minp_, lo_, hi_):
        out = elim_ops.fold_edges_segment(minp_, lo_, hi_, pos, order, n,
                                          segment_rounds=1)
        return out[2]

    s = timeit(jax.jit(one_round), minp, lo, hi)
    levels = max(1, int(n).bit_length())
    # bytes model from BASELINE.md: ~4*L*(V+C) gathered per round
    report("full_fixpoint_round", s, 4 * levels * (n + 1 + c),
           {"lift_levels": levels})

    # 5b. sort-based round prototype vs the gather round it would replace
    # (VERDICT r2 item 2): matched shapes, one round each. sorted_lookup
    # alone vs the plain gather it replaces is the primitive-level pair.
    loP = pos[lo]
    hiP = pos[hi]
    s = timeit(jax.jit(lambda m, l, h: elim_ops.fold_segment_small_pos(
        m, l, h, n, jumps=4, segment_rounds=1)[2]), minp, loP, hiP)
    report("jump_round_C", s, 4 * 4 * 2 * c, {"jumps": 4})
    s = timeit(jax.jit(lambda m, l, h: elim_ops.fold_segment_sortmerge_pos(
        m, l, h, n, jumps=4, segment_rounds=1)[2]), minp, loP, hiP)
    report("sortmerge_round_C", s, 4 * 4 * 2 * c, {"jumps": 4})
    s = timeit(jax.jit(lambda t, i: elim_ops.sorted_lookup((t,), i, n)[0]),
               table, idx_c)
    report("sorted_lookup_C_from_V", s, 4 * 3 * c)

    # 6. one jump-mode round at tail shapes (16k actives) — measured on
    # the position-space core directly, so no O(V) vertex<->position
    # conversion gathers pollute the O(C')-per-round datum
    small = 1 << 14
    s = timeit(jax.jit(lambda m, l, h: elim_ops.fold_segment_small_pos(
        m, l, h, n, segment_rounds=1)[2]),
        minp, pos[lo[:small]], pos[hi[:small]])
    report("jump_round_16k", s, 4 * 16 * 2 * small)

    # 7. gather concurrency (VERDICT r5 item 8) — see the leg's docstring
    gather_concurrency_leg()

    if args.profile_dir:
        with jax.profiler.trace(args.profile_dir):
            for _ in range(3):
                one_round(minp, lo, hi).block_until_ready()
        log(f"trace written to {args.profile_dir}")


if __name__ == "__main__":
    main()
