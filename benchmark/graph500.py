"""Graph500 Kronecker (R-MAT) edges, the benchmark's own copy.

Edge ``i`` of a graph is a pure function of ``(scale, A, B, C, gen_seed,
i)``: each of the ``scale`` levels hashes the 64-bit edge counter with
murmur3's fmix32 under a per-level key and picks one quadrant of the
adjacency matrix with the Graph500 initiator probabilities (A=0.57,
B=0.19, C=0.19, D=0.05). This is the same counter hash as the program's
``rmat-hash`` stream (a test pins the two equal), kept here so that the
traffic stays fixed while the program changes.

As the Graph500 specification asks, the vertex labels are then permuted
at random and the edge list is shuffled. ``--seed`` draws the shuffle,
and where the traffic says so the permutation too: every seed then
partitions the same graph up to isomorphism, the same sizes and degree
sequence under other names.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

M32 = 0xFFFFFFFF
BLOCK = 1 << 21  # edges hashed per numpy pass (bounds the temporaries)
THREADS = 4


def _mix32(x: int) -> int:
    x &= M32
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & M32
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & M32
    x ^= x >> 16
    return x


def level_keys(scale: int, gen_seed: int) -> list:
    """Two uint32 keys per level, derived from the generator seed."""
    s = _mix32((gen_seed & M32) ^ 0x9E3779B9)
    keys = [_mix32(s + 0x9E3779B9 * (lvl + 1)) for lvl in range(scale)]
    return [(k, _mix32(k ^ 0x7FEB352D)) for k in keys]


def thresholds(a: float, b: float, c: float) -> tuple:
    """16-bit thresholds: P(row bit = 1), P(col bit = 1 | row bit = 0),
    P(col bit = 1 | row bit = 1)."""
    d = 1.0 - a - b - c

    def q(p):
        return min(65535, max(0, round(p * 65536)))

    return q(c + d), q(b / (a + b)), q(d / (c + d))


def _block(scale, start, count, keys, th):
    idx = start + np.arange(count, dtype=np.int64)
    lo = (idx & M32).astype(np.uint32)
    hi = (idx >> 32).astype(np.uint32)
    t_u, t_v0, t_v1 = (np.uint32(t) for t in th)
    u = np.zeros(count, np.uint32)
    v = np.zeros(count, np.uint32)
    for bit, (k1, k2) in enumerate(keys):
        h = lo ^ np.uint32(k1)
        h ^= h >> np.uint32(16)
        h *= np.uint32(0x85EBCA6B)
        h ^= hi ^ np.uint32(k2)
        h ^= h >> np.uint32(13)
        h *= np.uint32(0xC2B2AE35)
        h ^= h >> np.uint32(16)
        ubit = (h >> np.uint32(16)) < t_u
        vbit = (h & np.uint32(0xFFFF)) < np.where(ubit, t_v1, t_v0)
        u |= ubit.astype(np.uint32) << np.uint32(bit)
        v |= vbit.astype(np.uint32) << np.uint32(bit)
    return np.stack([u, v], axis=1).astype(np.int64)


def rmat_range(scale: int, start: int, count: int, a: float, b: float,
               c: float, gen_seed: int) -> np.ndarray:
    """Edges ``[start, start + count)`` of the counter-hash R-MAT stream
    as an ``(count, 2)`` int64 array, before any relabelling."""
    keys = level_keys(scale, gen_seed)
    th = thresholds(a, b, c)
    out = np.empty((count, 2), np.int64)

    def fill(off):
        n = min(BLOCK, count - off)
        out[off:off + n] = _block(scale, start + off, n, keys, th)

    # numpy releases the GIL in these loops: a few threads share them
    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(fill, range(0, count, BLOCK)))
    return out


class Graph500:
    """One configuration's graph as one seed presents it.

    ``base()`` is the first ``edge_factor << scale`` edges, shuffled.
    With ``relabel`` the seed draws the vertex permutation as well;
    without it the permutation comes from the generator's seed, so every
    run seed holds the same graph, in another order."""

    def __init__(self, cfg: dict, seed: int, relabel: bool = True):
        self.scale = int(cfg["scale"])
        self.edge_factor = int(cfg["edge_factor"])
        self.abc = (float(cfg["A"]), float(cfg["B"]), float(cfg["C"]))
        self.gen_seed = int(cfg["gen_seed"])
        self.seed = int(seed) & (2**64 - 1)  # any whole number
        self.n = 1 << self.scale
        self.m = self.edge_factor << self.scale
        labels = self.seed if relabel else self.gen_seed
        self.perm = np.random.default_rng(labels).permutation(self.n)

    def base(self) -> np.ndarray:
        e = self.perm[rmat_range(self.scale, 0, self.m, *self.abc,
                                 self.gen_seed)]
        return e[np.random.default_rng([self.seed, 0]).permutation(self.m)]
