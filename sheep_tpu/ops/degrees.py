"""Degree accumulation on device (SURVEY.md §2 #3).

Endpoint-count degrees via scatter-add. Padding convention: edges padded
with endpoint == n land in an extra slot that is dropped by the caller.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from functools import partial


@partial(jax.jit, static_argnames=("n",))
def degree_chunk(deg: jax.Array, edges: jax.Array, n: int) -> jax.Array:
    """Accumulate endpoint counts of one (C, 2) chunk into deg (int32[n+1]).

    Slot n absorbs padding; self-loops count twice (matches the CPU core).
    One scatter per endpoint column: scattering the flattened (2C,) ids
    instead takes the TPU compiler minutes at some widths (a v5e, the
    installed libtpu: 227 s at C = 2^20 AOT here, 283 s on the chip for
    sheepd's RMAT-18 build; columns: 1.2 s; PR 21).
    """
    for col in (edges[:, 0], edges[:, 1]):
        deg = deg.at[jnp.clip(col, 0, n)].add(1, mode="drop")
    return deg


def init_degrees(n: int) -> jax.Array:
    return jnp.zeros(n + 1, dtype=jnp.int32)


def flush_every_for(chunk_edges: int) -> int:
    """Chunks between flushes of the int32 device accumulator into the
    int64 host totals: flush BEFORE any vertex could possibly see 2^31
    endpoints, so trillion-edge streams cannot overflow. Shared by the
    tpu backend and the server engine — the served build's degree
    totals must accumulate exactly like the CLI's for the bit-identity
    contract."""
    return max(1, (2**31 - 1) // max(2 * chunk_edges, 1))


# The measured LP signal law (BASELINE.md "SBM quality", hierarchy.py):
# label-propagation refinement recovers community structure only while
# average intra-community degree / k >= ~1 — below it the per-part
# majority is tie-noise and flat refine stalls (0.847 at s22 k=64 vs
# the 0.1252 hierarchical recipe). The advisor prices exactly this
# signal from the degree pass's cheapest statistic (2E/V; for a
# community graph the intra degree is within a small factor of it) and
# picks the hierarchy recipe that keeps EVERY level above threshold —
# the 2PS move: a degree-distribution signal chooses the strategy up
# front instead of after a wasted build.
LP_SIGNAL_THRESHOLD = 1.0

# the measured winning recipe's repair knobs (ROADMAP item 4 / BASELINE
# "SBM quality"): warm-start boundary repair at the full k, and a tight
# balance budget so the repair has headroom without voiding balance
ADVISED_FINAL_REFINE = 10
ADVISED_BALANCE = 1.05


def intra_signal(n: int, m: int, k: int) -> float:
    """The advisor's signal: average degree (2E/V) per part at ``k``."""
    return (2.0 * m / max(n, 1)) / max(k, 1)


def _prime_factors(k: int) -> list:
    out = []
    d = 2
    while d * d <= k:
        while k % d == 0:
            out.append(d)
            k //= d
        d += 1
    if k > 1:
        out.append(k)
    return out


def _equal_factors(k: int, nlevels: int):
    """Split k into ``nlevels`` near-equal integer factors (largest
    first), or None when k has fewer prime factors than levels."""
    primes = _prime_factors(k)
    if len(primes) < nlevels:
        return None
    buckets = [1] * nlevels
    for p in sorted(primes, reverse=True):
        buckets[buckets.index(min(buckets))] *= p
    return sorted(buckets, reverse=True)


def factor_levels(k: int, cap: int):
    """The fewest near-equal levels with every factor <= cap (each
    level's k stays above the signal threshold), or None when no such
    split exists (k prime and above cap). k=64 at cap=32 -> [8, 8] —
    the measured winning split."""
    import math

    if k <= cap:
        return [k]
    if cap < 2:
        cap = 2
    nlevels = max(2, math.ceil(math.log(k) / math.log(cap)))
    while nlevels <= k.bit_length() + 1:
        fac = _equal_factors(k, nlevels)
        if fac is None:
            return None  # fewer prime factors than levels: no split
        if fac[0] <= cap:
            return fac
        nlevels += 1
    return None


def advise_recipe(n: int, m, k: int,
                  threshold: float = LP_SIGNAL_THRESHOLD) -> dict:
    """The quality advisor's verdict for a flat build at ``k``
    (ISSUE 13): ``mode`` is ``"flat"`` (signal healthy — run as asked),
    ``"hier"`` (flat LP will stall; ``k_levels``/``final_refine``/
    ``balance`` carry the recommended recipe), or ``"unknown"`` (the
    edge count is not O(1)-knowable, so the signal isn't either).
    ``m`` may be None (unknown)."""
    if m is None:
        return {"mode": "unknown", "signal": None, "k": int(k)}
    sig = intra_signal(n, m, k)
    out = {"mode": "flat", "signal": round(sig, 4),
           "threshold": threshold, "k": int(k)}
    if k < 4 or sig >= threshold:
        return out
    avg_deg = 2.0 * m / max(n, 1)
    levels = factor_levels(int(k), max(2, int(avg_deg / threshold)))
    if levels is None or len(levels) < 2:
        return out  # no usable split (prime k past the cap): stay flat
    out.update(mode="hier", k_levels=levels,
               final_refine=ADVISED_FINAL_REFINE,
               balance=ADVISED_BALANCE)
    return out


def rank_clip_i32(deg_host):
    """int64 host degree totals -> int32-safe sort keys for the device
    elimination order. Degree values only matter ORDINALLY, so totals
    past int32 range are replaced by their stable ranks (double
    argsort); below it the totals pass through unchanged. Shared by
    the tpu backend and the server engine (same bit-identity argument
    as :func:`flush_every_for`)."""
    import numpy as np

    if deg_host.size == 0 or deg_host.max() < 2**31:
        return deg_host
    return np.argsort(np.argsort(deg_host, kind="stable"),
                      kind="stable")
