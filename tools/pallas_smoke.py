#!/usr/bin/env python
"""Mosaic lowering smokes for the Pallas gather lever, all variants in
one tool (the lever is CLOSED per the round-5/6 captures; one probe
file beats three drifting copies).

    python tools/pallas_smoke.py                 # variant 1 (default)
    python tools/pallas_smoke.py --variant 2 [--perf] [--interpret]
    python tools/pallas_smoke.py --variant 3 [--interpret]

Variants (formerly pallas_smoke.py / pallas_smoke2.py /
pallas_smoke3.py — artifacts under tools/out/ keep those names):

1. **1D VMEM gather** (VERDICT r4 weak #6): does the arbitrary-index
   ``jnp.take`` kernel (ops/pallas_gather.vmem_gather) lower through
   Mosaic at all? Measured verdict: NO — "Only 2D gather is
   supported". One JSON
   line; rc 0 on any DECIDED outcome (lowered or rejected), rc 1 when
   undecided (backend init failed — retry next window).

2. **2D gather forms A-E**: row-take / sublane-gather / lane-gather /
   composite scalar / lane-routed bulk, lowered one by one; ``--perf``
   adds the matched-shape throughput A/B vs XLA's 1D take for the
   forms that lower. Verdict: only the single-tile lane gather (C)
   lowers; every multi-row sublane form dies in a Mosaic assertion.

3. **Lane-gather width scaling**: how wide can take_along_axis(axis=1)
   go before Mosaic rejects it (the transposed-table escape hatch
   needs extent R >= 4096). Stops at the first rejection.

Run on the chip through the chip tool; ``--interpret`` exercises
variants 2/3 off-chip for shape/semantics sanity, not lowering truth.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

INTERPRET = False


# ---------------------------------------------------------------------------
# variant 1: the original 1D VMEM-gather lowering probe
# ---------------------------------------------------------------------------

def variant1() -> int:
    out = {"probe": "pallas_lower_smoke", "table_len": 1 << 20,
           "n_idx": 1 << 16, "block": 8192}
    try:
        import jax

        plat = jax.default_backend()
        out["platform"] = plat
        if plat == "cpu":
            out["decided"] = False
            out["error"] = "cpu backend: Mosaic lowering not exercised"
            print(json.dumps(out), flush=True)
            return 1

        import jax.numpy as jnp
        import numpy as np

        from sheep_tpu.ops.pallas_gather import vmem_gather

        table = jnp.arange(out["table_len"], dtype=jnp.int32)
        # build in int64 on host: the Knuth constant overflows int32
        idx = jnp.asarray(
            (np.arange(out["n_idx"], dtype=np.int64) * 2654435761)
            % out["table_len"], dtype=jnp.int32)

        t0 = time.perf_counter()
        try:
            lowered = jax.jit(
                lambda t, i: vmem_gather(t, i, block=out["block"])
            ).lower(table, idx)
            txt = lowered.compile()  # Mosaic runs at compile, not lower
            out["lowered"] = True
            out["compile_s"] = round(time.perf_counter() - t0, 2)
            del txt
        except Exception as e:
            # Only a genuine Mosaic/lowering rejection is a DECIDED
            # outcome. A runtime error (backend init, lost device) must
            # return rc 1 so a rerun retries the leg instead of retiring
            # it on a false "rejected" artifact.
            msg = f"{type(e).__name__}: {str(e)[:800]}"
            out["compile_s"] = round(time.perf_counter() - t0, 2)
            low = msg.lower()
            mosaic = any(s in low for s in
                         ("mosaic", "unimplemented", "unsupported",
                          "cannot lower", "lowering", "internal: mlir",
                          "notimplementederror"))
            transport = any(s in low for s in
                            ("deadline", "unavailable", "connection",
                             "socket", "rpc", "cancelled"))
            if mosaic and not transport:
                out["lowered"] = False
                out["mosaic_error"] = msg
                out["decided"] = True
                print(json.dumps(out), flush=True)
                return 0
            out["decided"] = False
            out["error"] = msg
            print(json.dumps(out), flush=True)
            return 1

        # it compiles: one quick timed A/B vs the XLA take at the same
        # shape (tiny — the full sweep is microbench_fixpoint's job)
        f_pallas = jax.jit(
            lambda t, i: vmem_gather(t, i, block=out["block"]))
        f_xla = jax.jit(lambda t, i: jnp.take(t, i, mode="clip"))
        for name, f in (("pallas_s", f_pallas), ("xla_s", f_xla)):
            _ = np.asarray(f(table, idx)[:1])  # warm + force completion  # sheeplint: sync-ok
            t0 = time.perf_counter()
            for _ in range(5):
                r = f(table, idx)
            _ = np.asarray(r[:1])  # sheeplint: sync-ok
            out[name] = round((time.perf_counter() - t0) / 5, 5)
        out["decided"] = True
        print(json.dumps(out), flush=True)
        return 0
    except Exception as e:
        out["decided"] = False
        out["error"] = f"{type(e).__name__}: {str(e)[:500]}"
        print(json.dumps(out), flush=True)
        return 1


# ---------------------------------------------------------------------------
# variant 2: 2D gather forms A-E (+ --perf A/B)
# ---------------------------------------------------------------------------

def _specs(pl, pltpu, shapes, out_shape):
    kw = {"memory_space": pltpu.VMEM} if pltpu else {}
    in_specs = [pl.BlockSpec(s, lambda i, r=len(s): (0,) * r, **kw)
                for s in shapes]
    out_specs = pl.BlockSpec(out_shape,
                             lambda i, r=len(out_shape): (0,) * r, **kw)
    return in_specs, out_specs


def try_form(name, kernel, in_arrays, out_shape_dtype, check=None):
    import numpy as np

    import jax
    from jax.experimental import pallas as pl

    pltpu = None
    if not INTERPRET:
        try:
            from jax.experimental.pallas import tpu as pltpu
        except Exception:
            pltpu = None

    rec = {"form": name}
    try:
        in_specs, out_specs = _specs(
            pl, pltpu, [a.shape for a in in_arrays], out_shape_dtype.shape)
        call = pl.pallas_call(
            kernel, grid=(1,), in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape_dtype, interpret=INTERPRET)
        t0 = time.perf_counter()
        lowered = jax.jit(call).lower(*in_arrays)
        compiled = lowered.compile()
        rec["lowered"] = True
        rec["compile_s"] = round(time.perf_counter() - t0, 2)
        out = np.asarray(compiled(*in_arrays))
        if check is not None:
            rec["ok"] = bool(check(out))
    except Exception as e:
        msg = f"{type(e).__name__}: {e}".splitlines()[0][:300]
        if rec.get("lowered"):
            # lowering succeeded; the failure is at run time — that is a
            # different (and better) answer than "does not lower"
            rec["run_error"] = msg
        else:
            rec["lowered"] = False
            rec["error"] = msg
    print(json.dumps(rec), flush=True)
    return rec


def variant2(perf: bool) -> int:
    import numpy as np

    import jax
    import jax.numpy as jnp

    plat = jax.devices()[0].platform
    print(json.dumps({"platform": plat,
                      "device": str(jax.devices()[0])}), flush=True)

    R, B = 4096, 1024
    rng = np.random.default_rng(0)
    table2 = jnp.asarray(
        rng.integers(0, 1 << 30, (R, 128), dtype=np.int32))
    tnp = np.asarray(table2)  # sheeplint: sync-ok

    # A: row-take
    idxA = jnp.asarray(rng.integers(0, R, (B,), dtype=np.int32))
    try_form(
        "A_row_take",
        lambda t, i, o: o.__setitem__(
            ..., jnp.take(t[...], i[...], axis=0, mode="clip")),
        [table2, idxA],
        jax.ShapeDtypeStruct((B, 128), jnp.int32),
        check=lambda out: np.array_equal(out, tnp[np.asarray(idxA)]))  # sheeplint: sync-ok

    # B: sublane gather (axis=0), idx same shape as a (8,128) tile
    idxB = jnp.asarray(rng.integers(0, R, (8, 128), dtype=np.int32))
    try_form(
        "B_sublane_gather",
        lambda t, i, o: o.__setitem__(
            ..., jnp.take_along_axis(t[...], i[...], axis=0)),
        [table2, idxB],
        jax.ShapeDtypeStruct((8, 128), jnp.int32),
        check=lambda out: np.array_equal(
            out, np.take_along_axis(tnp, np.asarray(idxB), axis=0)))  # sheeplint: sync-ok

    # C: lane gather (axis=1) on one (8,128) tile
    x8 = jnp.asarray(rng.integers(0, 1 << 30, (8, 128), dtype=np.int32))
    idxC = jnp.asarray(rng.integers(0, 128, (8, 128), dtype=np.int32))
    try_form(
        "C_lane_gather",
        lambda x, i, o: o.__setitem__(
            ..., jnp.take_along_axis(x[...], i[...], axis=1)),
        [x8, idxC],
        jax.ShapeDtypeStruct((8, 128), jnp.int32),
        check=lambda out: np.array_equal(
            out, np.take_along_axis(np.asarray(x8), np.asarray(idxC),  # sheeplint: sync-ok
                                    axis=1)))

    # D: composite arbitrary-index scalar gather, 8 per two 2D gathers.
    # idx (S, 8) int32 in [0, R*128); out (S, 8).
    S = 64
    idxD = jnp.asarray(rng.integers(0, R * 128, (S, 8), dtype=np.int32))

    def kernel_D(t, i, o):
        def one(s, _):
            g = i[s, :]                        # (8,) arbitrary indices
            row = (g >> 7).reshape(8, 1)       # broadcast rows across lanes
            col = (g & 127).reshape(8, 1)
            rows8 = jnp.take_along_axis(
                t[...], jnp.broadcast_to(row, (8, 128)), axis=0)
            z = jnp.take_along_axis(
                rows8, jnp.broadcast_to(col, (8, 128)), axis=1)
            o[s, :] = z[:, 0]
            return _

        import jax.lax as lax

        lax.fori_loop(0, S, one, 0)

    try_form(
        "D_composite_scalar",
        kernel_D,
        [table2, idxD],
        jax.ShapeDtypeStruct((S, 8), jnp.int32),
        check=lambda out: np.array_equal(
            out, tnp.reshape(-1)[np.asarray(idxD)]))  # sheeplint: sync-ok

    # E: lane-routed bulk gather. Indices PRE-ROUTED so lane j only
    # holds indices with (idx & 127) == j (the router is an XLA sort by
    # idx&127 OUTSIDE the kernel); then ONE sublane dynamic gather does
    # a full (SB,128) tile of arbitrary lookups.
    SB = 64
    lanes = np.arange(128, dtype=np.int32)[None, :]
    rowsE = rng.integers(0, R, (SB, 128), dtype=np.int32)
    idxE = jnp.asarray(rowsE * 128 + lanes)    # pre-routed by construction

    def kernel_E(t, i, o):
        o[...] = jnp.take_along_axis(t[...], i[...] >> 7, axis=0)

    try_form(
        "E_lane_routed_bulk",
        kernel_E,
        [table2, idxE],
        jax.ShapeDtypeStruct((SB, 128), jnp.int32),
        check=lambda out: np.array_equal(
            out, tnp.reshape(-1)[np.asarray(idxE)]))  # sheeplint: sync-ok

    if perf and plat == "tpu":
        _perf2(jax, jnp, rng)
    return 0


def _time(f, *a):
    import jax

    jax.block_until_ready(f(*a))               # warm-up / compile
    t0 = time.perf_counter()
    for _ in range(5):
        r = f(*a)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / 5


def _perf2(jax, jnp, rng):
    """Throughput of the variant-2 forms that lowered vs XLA's 1D
    gather, matched shapes: table 2^20 int32 (4 MB — VMEM-resident
    territory), 2^20 lookups per call. Reports M elem/s; the XLA row is
    the ~100-150 M elem/s incumbent the re-negotiation cites."""
    import numpy as np

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, NI = 1 << 13, 1 << 20                   # table (8192,128) = 2^20
    table2 = jnp.asarray(
        rng.integers(0, 1 << 30, (R, 128), dtype=np.int32))
    flat = table2.reshape(-1)
    # balanced residues BY CONSTRUCTION (NI/128 indices per lane class,
    # randomly interleaved): the block-routing reshape below is exact
    # only for balanced counts; arbitrary input would need per-bucket
    # padding, which is an integration concern, not a lowering probe's
    rows1 = rng.integers(0, R, (NI,), dtype=np.int32)
    res1 = np.repeat(np.arange(128, dtype=np.int32), NI // 128)
    rng.shuffle(res1)
    idx1 = jnp.asarray(rows1 * 128 + res1)

    xla = jax.jit(lambda t, i: jnp.take(t, i, mode="clip"))
    s = _time(xla, flat, idx1)
    print(json.dumps({"perf": "xla_take_1d", "n": NI,
                      "melems": round(NI / s / 1e6, 1)}), flush=True)

    # E + its XLA router (sort by idx&127, then in-kernel sublane gather)
    SB = NI // 128
    vm = {"memory_space": pltpu.VMEM}
    callE = pl.pallas_call(
        lambda t, i, o: o.__setitem__(
            ..., jnp.take_along_axis(t[...], i[...] >> 7, axis=0)),
        grid=(1,),
        in_specs=[pl.BlockSpec((R, 128), lambda g: (0, 0), **vm),
                  pl.BlockSpec((SB, 128), lambda g: (0, 0), **vm)],
        out_specs=pl.BlockSpec((SB, 128), lambda g: (0, 0), **vm),
        out_shape=jax.ShapeDtypeStruct((SB, 128), jnp.int32))
    # gate the E legs on the kernel actually lowering (on the 2026-08
    # toolchain it does NOT — multi-row sublane gather asserts in
    # Mosaic; this keeps the perf artifact complete instead of dying
    # mid-run like the first capture did)
    try:
        probeE = jnp.zeros((SB, 128), jnp.int32)
        jax.jit(callE).lower(table2, probeE).compile()
    except Exception as e:
        print(json.dumps({
            "perf": "E_kernel_only", "lowered": False,
            "error": f"{type(e).__name__}: {e}".splitlines()[0][:300]}),
            flush=True)
        return

    # routing: element with residue j must land in LANE j. After the
    # sort the array is contiguous residue blocks; with BALANCED residue
    # counts (true for the synthetic idx below, NOT for arbitrary input
    # — a real integration pads each bucket to the max count) the
    # column-major reshape(128, SB).T puts block j into column j.
    def routed(t2, i):
        order = jnp.argsort(i & 127)           # the router (XLA sort)
        z = callE(t2, i[order].reshape(128, SB).T)
        return z.T.reshape(-1)                 # values in ROUTED order

    def routed_unrouted(t2, i):
        order = jnp.argsort(i & 127)
        z = callE(t2, i[order].reshape(128, SB).T).T.reshape(-1)
        return jnp.zeros_like(z).at[order].set(z)  # original order

    # correctness of kernel-only leg on routed input
    rowsE = rng.integers(0, R, (SB, 128), dtype=np.int32)
    lanes = np.arange(128, dtype=np.int32)[None, :]
    idxE = jnp.asarray(rowsE * 128 + lanes)
    outE = np.asarray(callE(table2, idxE))
    okE = np.array_equal(outE, np.asarray(flat)[np.asarray(idxE)])  # sheeplint: sync-ok
    s = _time(callE, table2, idxE)
    print(json.dumps({"perf": "E_kernel_only", "ok": bool(okE), "n": NI,
                      "melems": round(NI / s / 1e6, 1)}), flush=True)
    okR = np.array_equal(
        np.sort(np.asarray(routed(table2, idx1))),
        np.sort(np.asarray(flat)[np.asarray(idx1)]))  # sheeplint: sync-ok
    s = _time(jax.jit(routed), table2, idx1)
    print(json.dumps({"perf": "E_with_router", "ok": bool(okR), "n": NI,
                      "melems": round(NI / s / 1e6, 1)}), flush=True)
    okU = np.array_equal(np.asarray(routed_unrouted(table2, idx1)),
                         np.asarray(flat)[np.asarray(idx1)])  # sheeplint: sync-ok
    s = _time(jax.jit(routed_unrouted), table2, idx1)
    print(json.dumps({"perf": "E_router_unroute", "ok": bool(okU),
                      "n": NI,
                      "melems": round(NI / s / 1e6, 1)}), flush=True)


# ---------------------------------------------------------------------------
# variant 3: lane-gather width scaling
# ---------------------------------------------------------------------------

def _probe_width(R):
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    rec = {"probe": "lane_gather_width", "lane_extent": R,
           "table_elems": 128 * R,
           "table_mb": round(128 * R * 4 / 2**20, 1)}
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, 1 << 30, (8, R), dtype=np.int32))
    idx = jnp.asarray(rng.integers(0, R, (8, R), dtype=np.int32))

    kw = {}
    if not INTERPRET:
        from jax.experimental.pallas import tpu as pltpu

        kw = {"memory_space": pltpu.VMEM}
    try:
        call = pl.pallas_call(
            lambda xr, ir, o: o.__setitem__(
                ..., jnp.take_along_axis(xr[...], ir[...], axis=1)),
            grid=(1,),
            in_specs=[pl.BlockSpec((8, R), lambda g: (0, 0), **kw),
                      pl.BlockSpec((8, R), lambda g: (0, 0), **kw)],
            out_specs=pl.BlockSpec((8, R), lambda g: (0, 0), **kw),
            out_shape=jax.ShapeDtypeStruct((8, R), jnp.int32),
            interpret=INTERPRET)
        t0 = time.perf_counter()
        compiled = jax.jit(call).lower(x, idx).compile()
        rec["lowered"] = True
        rec["compile_s"] = round(time.perf_counter() - t0, 2)
        out = np.asarray(compiled(x, idx))
        rec["ok"] = bool(np.array_equal(
            out, np.take_along_axis(np.asarray(x), np.asarray(idx),  # sheeplint: sync-ok
                                    axis=1)))
        n = 8 * R
        jax.block_until_ready(compiled(x, idx))
        t0 = time.perf_counter()
        reps = 20
        for _ in range(reps):
            r = compiled(x, idx)
        jax.block_until_ready(r)
        s = (time.perf_counter() - t0) / reps
        rec["melems"] = round(n / s / 1e6, 1)
    except Exception as e:
        msg = f"{type(e).__name__}: {e}".splitlines()[0][:300]
        if rec.get("lowered"):
            rec["run_error"] = msg
        else:
            rec["lowered"] = False
            rec["error"] = msg
    print(json.dumps(rec), flush=True)
    return rec


def variant3() -> int:
    import jax

    print(json.dumps({"platform": jax.devices()[0].platform,
                      "device": str(jax.devices()[0])}), flush=True)
    widths = [128, 256, 512]
    if not INTERPRET:
        widths += [1024, 4096, 8192, 16384, 32768]
    for R in widths:
        rec = _probe_width(R)
        if not rec.get("lowered") and not INTERPRET:
            break  # wider only gets harder; stop at first rejection
    return 0


def main(argv=None) -> int:
    global INTERPRET
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--variant", type=int, default=1, choices=(1, 2, 3))
    ap.add_argument("--perf", action="store_true",
                    help="variant 2: add the throughput A/B legs")
    ap.add_argument("--interpret", action="store_true",
                    help="variants 2/3: interpreter mode (semantics "
                         "only; no Mosaic lowering truth)")
    args = ap.parse_args(argv)
    INTERPRET = args.interpret
    if args.variant == 1:
        return variant1()
    if args.variant == 2:
        return variant2(args.perf)
    return variant3()


if __name__ == "__main__":
    sys.exit(main())
