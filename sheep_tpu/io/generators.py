"""Graph generators for tests, golden fixtures and scale benchmarks.

SURVEY.md §4: the Zachary karate club is the first driver eval config and
the golden-test fixture; RMAT is both eval config 5 (scale-30 synthetic)
and the soak-test generator. All generators are deterministic under a seed.
"""

from __future__ import annotations

from functools import partial

import numpy as np

from sheep_tpu.io.devicestream import DeviceStream

# Zachary karate club, 34 vertices / 78 undirected edges (0-indexed).
# Standard public edge list (W. W. Zachary, 1977; same set shipped by
# networkx as karate_club_graph).
_KARATE = [
    (0, 1), (0, 2), (0, 3), (0, 4), (0, 5), (0, 6), (0, 7), (0, 8), (0, 10),
    (0, 11), (0, 12), (0, 13), (0, 17), (0, 19), (0, 21), (0, 31), (1, 2),
    (1, 3), (1, 7), (1, 13), (1, 17), (1, 19), (1, 21), (1, 30), (2, 3),
    (2, 7), (2, 8), (2, 9), (2, 13), (2, 27), (2, 28), (2, 32), (3, 7),
    (3, 12), (3, 13), (4, 6), (4, 10), (5, 6), (5, 10), (5, 16), (6, 16),
    (8, 30), (8, 32), (8, 33), (9, 33), (13, 33), (14, 32), (14, 33),
    (15, 32), (15, 33), (18, 32), (18, 33), (19, 33), (20, 32), (20, 33),
    (22, 32), (22, 33), (23, 25), (23, 27), (23, 29), (23, 32), (23, 33),
    (24, 25), (24, 27), (24, 31), (25, 31), (26, 29), (26, 33), (27, 33),
    (28, 31), (28, 33), (29, 32), (29, 33), (30, 32), (30, 33), (31, 32),
    (31, 33), (32, 33),
]


def karate_club() -> np.ndarray:
    """34 v / 78 e — driver eval config 1 (BASELINE.json)."""
    return np.asarray(_KARATE, dtype=np.int64)


def path_graph(n: int) -> np.ndarray:
    v = np.arange(n - 1, dtype=np.int64)
    return np.stack([v, v + 1], axis=1)


def star_graph(n: int) -> np.ndarray:
    v = np.arange(1, n, dtype=np.int64)
    return np.stack([np.zeros_like(v), v], axis=1)


def grid_graph(rows: int, cols: int) -> np.ndarray:
    idx = np.arange(rows * cols).reshape(rows, cols)
    horiz = np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], axis=1)
    vert = np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], axis=1)
    return np.concatenate([horiz, vert]).astype(np.int64)


def random_graph(n: int, m: int, seed: int = 0, self_loops: bool = False) -> np.ndarray:
    """Erdos-Renyi-ish multigraph: m uniform random edges."""
    rng = np.random.default_rng(seed)
    e = rng.integers(0, n, size=(m, 2), dtype=np.int64)
    if not self_loops:
        loops = e[:, 0] == e[:, 1]
        e[loops, 1] = (e[loops, 1] + 1) % n
    return e


def _rmat_batch(scale: int, cnt: int, rng, a: float, b: float, c: float) -> np.ndarray:
    d = 1.0 - a - b - c
    u = np.zeros(cnt, dtype=np.int64)
    v = np.zeros(cnt, dtype=np.int64)
    for bit in range(scale):
        r1 = rng.random(cnt)
        r2 = rng.random(cnt)
        # recursive quadrant choice: u bit then v bit conditioned on it
        ubit = (r1 > (a + b)).astype(np.int64)
        pv = np.where(ubit == 0, b / (a + b), d / (c + d))
        vbit = (r2 < pv).astype(np.int64)
        u |= ubit << bit
        v |= vbit << bit
    return np.stack([u, v], axis=1)


def rmat(
    scale: int,
    edge_factor: int = 16,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
    seed: int = 0,
    batch: int = 1 << 20,
) -> np.ndarray:
    """R-MAT generator (Chakrabarti et al. 2004), Graph500 parameters.

    2**scale vertices, edge_factor * 2**scale edges. Materializes the full
    (m, 2) output — for graphs that do not fit in RAM (e.g. driver eval
    config 5, scale=30) use :func:`rmat_stream` instead.
    """
    m = edge_factor << scale
    rng = np.random.default_rng(seed)
    out = np.empty((m, 2), dtype=np.int64)
    for off in range(0, m, batch):
        cnt = min(batch, m - off)
        out[off : off + cnt] = _rmat_batch(scale, cnt, rng, a, b, c)
    return out


def rmat_stream(
    scale: int,
    edge_factor: int = 16,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
    seed: int = 0,
    chunk: int = 1 << 22,
):
    """Yield RMAT edges chunk-by-chunk without materializing the graph."""
    m = edge_factor << scale
    for i, off in enumerate(range(0, m, chunk)):
        cnt = min(chunk, m - off)
        rng = np.random.default_rng(np.random.SeedSequence([seed, i]))
        yield _rmat_batch(scale, cnt, rng, a, b, c)


# ---------------------------------------------------------------------------
# Counter-based R-MAT: one stateless hash per (edge index, level), so any
# edge RANGE is computable independently — on host (numpy) or ON DEVICE
# (jnp), bit-identically. This is what lets the TPU backend materialize
# synthetic chunks in HBM instead of generating on host and paying the
# host->device upload for every chunk, and
# what makes RMAT-30-class synthetic streams (eval config 5) feedable at
# HBM rate rather than host-numpy rate.
#
# The recursive quadrant choice matches :func:`_rmat_batch`: per bit
# level, u's bit is 1 with probability c+d, then v's bit is 1 with
# probability b/(a+b) (u bit 0) or d/(c+d) (u bit 1). Here the two
# uniforms are the 16-bit halves of one 32-bit hash and the thresholds
# are integers, so numpy and jnp agree exactly (uint32 wraparound
# arithmetic only — no floats anywhere).
# ---------------------------------------------------------------------------

_M32 = 0xFFFFFFFF


def _mix32_int(x: int) -> int:
    """murmur3 fmix32 on a Python int (key premixing, host side)."""
    x &= _M32
    x ^= x >> 16
    x = (x * 0x85EBCA6B) & _M32
    x ^= x >> 13
    x = (x * 0xC2B2AE35) & _M32
    x ^= x >> 16
    return x


def _rmat_hash_keys(scale: int, seed: int):
    """Per-level uint32 keys derived from the seed (Python ints)."""
    s = _mix32_int((seed & _M32) ^ 0x9E3779B9)
    return [_mix32_int(s + 0x9E3779B9 * (lvl + 1)) for lvl in range(scale)]


def _rmat_hash_keys2(keys):
    """Second per-level constant (folded with the high counter word
    mid-mix) — ONE definition shared by the numpy body, the native
    dispatch, and the tests, so the premix cannot drift between the
    bit-identical implementations."""
    return [_mix32_int(k ^ 0x7FEB352D) for k in keys]


def _rmat_hash_thresholds(a: float, b: float, c: float):
    """16-bit integer thresholds for the quadrant choice."""
    d = 1.0 - a - b - c
    t_u = min(65535, max(0, round((c + d) * 65536)))       # P(ubit = 1)
    t_v0 = min(65535, max(0, round(b / (a + b) * 65536)))  # P(vbit=1 | u=0)
    t_v1 = min(65535, max(0, round(d / (c + d) * 65536)))  # P(vbit=1 | u=1)
    return t_u, t_v0, t_v1


def _rmat_hash_uv(xp, elo, ehi, keys, thresholds, dtype):
    """Shared numpy/jnp body: map edge-counter words (elo, ehi) to (u, v).

    ``xp`` is the array namespace (numpy or jax.numpy); all arithmetic is
    uint32 with wraparound, so both namespaces produce identical bits.
    """
    t_u, t_v0, t_v1 = (xp.uint32(t) for t in thresholds)
    u = xp.zeros(elo.shape, dtype=xp.uint32)
    v = xp.zeros(elo.shape, dtype=xp.uint32)
    one = xp.uint32(1)
    for bit, (key, key2) in enumerate(zip(keys, _rmat_hash_keys2(keys))):
        # murmur3 fmix32 over (elo ^ key), folded with ehi mid-mix so
        # both counter words reach every output bit
        h = elo ^ xp.uint32(key)
        h = h ^ (h >> xp.uint32(16))
        h = h * xp.uint32(0x85EBCA6B)
        h = h ^ (ehi ^ xp.uint32(key2))
        h = h ^ (h >> xp.uint32(13))
        h = h * xp.uint32(0xC2B2AE35)
        h = h ^ (h >> xp.uint32(16))
        hu = h >> xp.uint32(16)          # 16-bit uniform for u's bit
        hv = h & xp.uint32(0xFFFF)       # 16-bit uniform for v's bit
        ubit = (hu < t_u).astype(xp.uint32)
        t_v = xp.where(ubit == one, t_v1, t_v0)
        vbit = (hv < t_v).astype(xp.uint32)
        u = u | (ubit << xp.uint32(bit))
        v = v | (vbit << xp.uint32(bit))
    return u.astype(dtype), v.astype(dtype)


def rmat_hash_range(
    scale: int,
    start: int,
    count: int,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
    seed: int = 0,
) -> np.ndarray:
    """Edges [start, start+count) of the counter-based R-MAT stream, as a
    (count, 2) int64 array (host twin of the device generator).

    Large ranges take the native C loop when the core is built (~100x
    the numpy path, bit-identical — the soak generator's bottleneck was
    host hashing); small ranges and toolchain-less hosts use numpy."""
    keys = _rmat_hash_keys(scale, seed)
    th = _rmat_hash_thresholds(a, b, c)
    if count >= 4096:
        from sheep_tpu.core import native

        if native.available():
            return native.rmat_hash_range(scale, start, count, keys,
                                          _rmat_hash_keys2(keys), th)
    idx = start + np.arange(count, dtype=np.int64)
    elo = (idx & _M32).astype(np.uint32)
    ehi = (idx >> 32).astype(np.uint32)
    u, v = _rmat_hash_uv(np, elo, ehi, keys, th, np.int64)
    return np.stack([u, v], axis=1)


_DEVICE_CHUNK_FN = None


def _device_chunk_fn():
    """The jitted device-chunk kernel, created once — jax.jit caches on
    the wrapper object, so the wrapper must be a module singleton or
    every chunk would retrace + recompile the scale-deep unrolled hash
    (jax stays a lazy import: this module is numpy-first)."""
    global _DEVICE_CHUNK_FN
    if _DEVICE_CHUNK_FN is None:
        import jax
        import jax.numpy as jnp

        @partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
        def _chunk(start_words, count, pad_to, keys, th, n):
            lo0, hi0 = start_words
            i = jnp.arange(pad_to, dtype=jnp.uint32)
            elo = lo0 + i
            ehi = hi0 + (elo < lo0).astype(jnp.uint32)  # 64-bit carry
            u, v = _rmat_hash_uv(jnp, elo, ehi, list(keys), th,
                                 jnp.int32)
            e = jnp.stack([u, v], axis=1)
            return jnp.where((i < jnp.uint32(count))[:, None], e,
                             jnp.int32(n))

        _DEVICE_CHUNK_FN = _chunk
    return _DEVICE_CHUNK_FN


def rmat_hash_chunk_device(
    scale: int,
    start: int,
    count: int,
    pad_to: int,
    n: int,
    a: float = 0.57,
    b: float = 0.19,
    c: float = 0.19,
    seed: int = 0,
):
    """Device twin of :func:`rmat_hash_range`: a (pad_to, 2) int32 chunk
    materialized ON DEVICE (rows past ``count`` hold the sentinel vertex
    ``n``). One compile per (scale, count, pad_to, seed/abc) combination
    — ``start`` is a traced pair of uint32 words (the 64-bit edge
    counter split for 32-bit jax), so streaming a graph reuses one
    compiled program for every full chunk."""
    import jax.numpy as jnp

    keys = tuple(_rmat_hash_keys(scale, seed))
    th = _rmat_hash_thresholds(a, b, c)
    start_words = (jnp.uint32(start & _M32), jnp.uint32(start >> 32))
    return _device_chunk_fn()(start_words, count, pad_to, keys, th, n)


class _CounterHashStream:
    """Shared :class:`~sheep_tpu.io.edgestream.EdgeStream` surface for
    replay-free counter-hash synthetic streams (R-MAT, SBM). Subclasses
    set ``_n``/``_m`` and implement ``_range(start, count)`` (host chunk
    as an int64 (count, 2) array); they may also provide the
    ``device_chunk`` fast path the TPU backend probes for.

    Chunk access is random (any [start, start+count) range hashes
    independently), which also makes checkpoint resume and round-robin
    sharding exact rather than replay-based.
    """

    path = None
    fmt = "generator"

    def _range(self, start: int, count: int) -> np.ndarray:
        raise NotImplementedError

    # -- EdgeStream surface -------------------------------------------------
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    @property
    def num_edges(self) -> int:
        return self._m

    @property
    def num_edges_cheap(self):
        return self._m

    @property
    def num_edges_upper_bound(self):
        return self._m

    @property
    def num_vertices(self) -> int:
        return self._n

    def clamp_chunk_edges(self, chunk_edges: int, parts: int = 1,
                          floor: int = 1024) -> int:
        return min(chunk_edges, max(floor, -(-self._m // parts)))

    def chunks(self, chunk_edges: int = 1 << 22, shard: int = 0,
               num_shards: int = 1, start_chunk: int = 0,
               byte_range: bool = False):
        """Host chunks by direct range hashing (no generator replay: chunk
        i is _range(i*cs, cs), so skipping ahead is O(1))."""
        if not (0 <= shard < num_shards):
            raise ValueError(f"bad shard {shard}/{num_shards}")
        cs = int(chunk_edges)
        n_chunks = -(-self._m // cs) if self._m else 0
        for i in range(start_chunk, n_chunks):
            if (i % num_shards) == shard:
                yield self._range(i * cs, min(cs, self._m - i * cs))

    def read_all(self) -> np.ndarray:
        return self._range(0, self._m)

    def num_device_chunks(self, chunk_edges: int) -> int:
        return -(-self._m // int(chunk_edges))

    def count_edges_in_span(self, shard: int, num_shards: int) -> int:
        """O(1) arithmetic (EdgeStream replays the generator to count;
        here chunk ownership is round-robin over fixed-size chunks, so
        the owned-edge total is pure arithmetic — matching what
        summing len(c) over chunks(DEFAULT, shard, num_shards) yields).

        NOTE: like EdgeStream's version, the count assumes
        DEFAULT_CHUNK_EDGES ownership granularity — the method exists
        for the byte-range text path's lockstep accounting and is
        unreachable for path-less streams today; it keeps exact parity
        with the base class's replay semantics."""
        from sheep_tpu.io.edgestream import DEFAULT_CHUNK_EDGES as cs

        n_chunks = -(-self._m // cs)
        owned = len(range(shard, n_chunks, num_shards))
        total = owned * cs
        last = n_chunks - 1
        if n_chunks and (last % num_shards) == shard:
            total -= n_chunks * cs - self._m  # short final chunk
        return total

    def _fingerprint(self, tag: str) -> str:
        """Cheap stable identity for checkpoint fingerprints: the
        generator parameters plus a hashed 4096-edge prefix (the full
        first-chunk hash the generic generator fallback would pay costs
        a scale-deep pass over a default-size chunk per partition())."""
        import hashlib

        sample = self._range(0, min(4096, self._m))
        return tag + hashlib.sha1(
            np.ascontiguousarray(sample).tobytes()).hexdigest()


class RmatHashStream(DeviceStream, _CounterHashStream):
    """Counter-based R-MAT stream (:func:`rmat_hash_range`), a
    :class:`~sheep_tpu.io.devicestream.DeviceStream`:
    ``device_chunk(idx, cs, n)`` materializes the padded chunk directly
    in accelerator memory (:func:`rmat_hash_chunk_device`),
    bit-identical to the host chunks every other backend reads — so
    cross-backend equality holds while the device-recognizing drivers
    skip host generation AND the host->device upload entirely.
    """

    def __init__(self, scale: int, edge_factor: int = 16, a: float = 0.57,
                 b: float = 0.19, c: float = 0.19, seed: int = 0):
        if not (1 <= scale <= 32):
            # vertex bits accumulate in uint32 (shifts past bit 31 would
            # silently drop); the device path is further gated to < 2^31
            # ids by check_tpu_vertex_range at backend entry
            raise ValueError(f"rmat-hash scale must be 1..32, got {scale}")
        self.scale = int(scale)
        self.edge_factor = int(edge_factor)
        self.abc = (float(a), float(b), float(c))
        self.seed = int(seed)
        self._m = self.edge_factor << self.scale
        self._n = 1 << self.scale

    def _range(self, start: int, count: int) -> np.ndarray:
        return rmat_hash_range(self.scale, start, count, *self.abc,
                               seed=self.seed)

    def content_fingerprint(self) -> str:
        return self._fingerprint(f"rmat_hash/s{self.scale}/"
                                 f"ef{self.edge_factor}/{self.abc}/"
                                 f"{self.seed}/")

    # -- device fast path ---------------------------------------------------
    def device_chunk(self, idx: int, chunk_edges: int, n: int):
        """Padded (chunk_edges, 2) int32 device chunk for global chunk
        ``idx`` — the TPU backend substitutes this for host pad+upload."""
        cs = int(chunk_edges)
        start = idx * cs
        count = max(0, min(cs, self._m - start))
        return rmat_hash_chunk_device(self.scale, start, count, cs, n,
                                      *self.abc, seed=self.seed)


# ---------------------------------------------------------------------------
# Counter-based planted partition (SBM): ground-truth community structure
# at arbitrary scale, replay-free like the R-MAT above. The real eval
# graphs with community structure (LiveJournal/twitter/uk) are
# unreachable in this environment, and R-MAT is an expander (cut ratios
# 93-97% are a property of the GRAPH, not the partitioner) — this stream
# is how "low communication volume" (SURVEY.md §1's defining output
# property) gets at-scale evidence: k planted blocks, an exact
# inter-block edge fraction p_out, and a known optimal cut to compare
# the recovered cut against (VERDICT r3 item 5).
#
# Model (per edge counter i, five independent 32-bit uniforms):
#   cross  = h0 < round(p_out * 2^32)
#   bu     = h1 & (n_blocks - 1)              # blocks are power-of-two
#   bv     = distinct-from-bu pick from h2    # only used when cross
#   u      = bu * block_size + (h3 & (block_size - 1))
#   v      = (cross ? bv : bu) * block_size + (h4 & (block_size - 1))
# so a cross edge NEVER lands inside a block: the planted cut fraction
# is exactly the Bernoulli(p_out) rate, and vertex ids are contiguous
# within blocks (ground truth = v >> block_bits). Intra edges may be
# self-loops with probability 2^-block_bits (harmless: never cut).
# ---------------------------------------------------------------------------


def _sbm_hash_keys(seed: int):
    """Five per-field uint32 keys (decide, bu, bv, uoff, voff)."""
    s = _mix32_int((seed & _M32) ^ 0x2545F491)
    return [_mix32_int(s + 0x9E3779B9 * (f + 1)) for f in range(5)]


def _hash_fields(xp, elo, ehi, keys):
    """Per-key independent 32-bit uniforms for one edge-counter word
    pair — the shared field-hash loop of every counter-hash stream
    (murmur3 fmix32 over elo ^ key, folded with ehi mid-mix). All
    uint32 wraparound arithmetic: numpy and jnp agree bit-exactly."""
    fields = []
    for key, key2 in zip(keys, _rmat_hash_keys2(keys)):
        h = elo ^ xp.uint32(key)
        h = h ^ (h >> xp.uint32(16))
        h = h * xp.uint32(0x85EBCA6B)
        h = h ^ (ehi ^ xp.uint32(key2))
        h = h ^ (h >> xp.uint32(13))
        h = h * xp.uint32(0xC2B2AE35)
        h = h ^ (h >> xp.uint32(16))
        fields.append(h)
    return fields


def _sbm_hash_uv(xp, elo, ehi, keys, t_out, n_blocks, block_bits, dtype):
    """Shared numpy/jnp body: edge-counter words -> (u, v). All uint32
    wraparound arithmetic, so host and device bits agree exactly."""
    h_cross, h_bu, h_bv, h_uo, h_vo = _hash_fields(xp, elo, ehi, keys)
    cross = h_cross < xp.uint32(t_out)
    bu = h_bu & xp.uint32(n_blocks - 1)
    # distinct second block: draw from [0, n_blocks-1) and skip past bu
    # (modulo bias <= (n_blocks-1)/2^32 — immaterial for any usable
    # block count)
    bvr = h_bv % xp.uint32(n_blocks - 1)
    bv = bvr + (bvr >= bu).astype(xp.uint32)
    b2 = xp.where(cross, bv, bu)
    off_mask = xp.uint32((1 << block_bits) - 1)
    u = (bu << xp.uint32(block_bits)) | (h_uo & off_mask)
    v = (b2 << xp.uint32(block_bits)) | (h_vo & off_mask)
    return u.astype(dtype), v.astype(dtype)


def _sbm_t_out(p_out: float) -> int:
    """p_out as a uint32 threshold (clamped; p_out=1.0 maps to 2^32-1,
    i.e. 'all cross' short of one edge in 4 billion)."""
    return min(_M32, max(0, round(float(p_out) * 4294967296.0)))


def sbm_hash_range(scale: int, start: int, count: int, n_blocks: int,
                   p_out: float, seed: int = 0) -> np.ndarray:
    """Edges [start, start+count) of the counter-based planted-partition
    stream, as a (count, 2) int64 array (host twin of the device path).

    Large ranges take the native C loop when the core is built
    (bit-identical, ~100x numpy — at-scale quality runs re-stream the
    graph once per refine round); small ranges and toolchain-less hosts
    use numpy."""
    nb = int(n_blocks)
    # mirror SbmHashStream's check: this is a public entry point too
    # (tests/tools call it directly), and nb=1 is a modulo-by-zero in
    # _sbm_hash_uv (SIGFPE in the native path) while a non-power-of-two
    # silently corrupts the block structure via the (nb-1) mask
    if nb < 2 or nb & (nb - 1) or nb > (1 << scale):
        raise ValueError(f"n_blocks must be a power of two in "
                         f"[2, 2**scale], got {n_blocks}")
    keys = _sbm_hash_keys(seed)
    block_bits = scale - (nb.bit_length() - 1)
    if count >= 4096:
        from sheep_tpu.core import native

        if native.available() and native.has_sbm_hash():
            return native.sbm_hash_range(
                start, count, keys, _rmat_hash_keys2(keys),
                _sbm_t_out(p_out), nb, block_bits)
    idx = start + np.arange(count, dtype=np.int64)
    elo = (idx & _M32).astype(np.uint32)
    ehi = (idx >> 32).astype(np.uint32)
    u, v = _sbm_hash_uv(np, elo, ehi, keys, _sbm_t_out(p_out), nb,
                        block_bits, np.int64)
    return np.stack([u, v], axis=1)


_SBM_DEVICE_CHUNK_FN = None


def _sbm_device_chunk_fn():
    """Jitted device-chunk kernel singleton (same rationale as
    :func:`_device_chunk_fn`: jit caches on the wrapper object)."""
    global _SBM_DEVICE_CHUNK_FN
    if _SBM_DEVICE_CHUNK_FN is None:
        import jax
        import jax.numpy as jnp

        @partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7))
        def _chunk(start_words, count, pad_to, keys, t_out, n_blocks,
                   block_bits, n):
            lo0, hi0 = start_words
            i = jnp.arange(pad_to, dtype=jnp.uint32)
            elo = lo0 + i
            ehi = hi0 + (elo < lo0).astype(jnp.uint32)  # 64-bit carry
            u, v = _sbm_hash_uv(jnp, elo, ehi, list(keys), t_out,
                                n_blocks, block_bits, jnp.int32)
            e = jnp.stack([u, v], axis=1)
            return jnp.where((i < jnp.uint32(count))[:, None], e,
                             jnp.int32(n))

        _SBM_DEVICE_CHUNK_FN = _chunk
    return _SBM_DEVICE_CHUNK_FN


class SbmHashStream(DeviceStream, _CounterHashStream):
    """Planted-partition (stochastic block model) counter-hash stream:
    2**scale vertices in ``n_blocks`` equal contiguous blocks, each edge
    inter-block with probability ``p_out``. Ground truth is
    :meth:`ground_truth`; the planted cut ratio is exactly the Bernoulli
    cross rate, so a partitioner that recovers the blocks at
    k = n_blocks scores cut_ratio ~= p_out.

    A :class:`~sheep_tpu.io.devicestream.DeviceStream` like
    :class:`RmatHashStream` (bit-identical host and device chunks).
    """

    def __init__(self, scale: int, n_blocks: int = 64,
                 p_out: float = 0.05, edge_factor: int = 16,
                 seed: int = 0):
        if not (1 <= scale <= 31):
            # ids must fit int32 on-device (no < 2^31 backend gate can
            # widen a generator that emits 2^31 ids)
            raise ValueError(f"sbm-hash scale must be 1..31, got {scale}")
        nb = int(n_blocks)
        if nb < 2 or nb & (nb - 1) or nb > (1 << scale):
            raise ValueError(f"n_blocks must be a power of two in "
                             f"[2, 2**scale], got {n_blocks}")
        if not (0.0 <= p_out <= 1.0):
            raise ValueError(f"p_out must be in [0, 1], got {p_out}")
        self.scale = int(scale)
        self.n_blocks = nb
        self.block_bits = self.scale - (nb.bit_length() - 1)
        self.p_out = float(p_out)
        self.edge_factor = int(edge_factor)
        self.seed = int(seed)
        self._m = self.edge_factor << self.scale
        self._n = 1 << self.scale

    def _range(self, start: int, count: int) -> np.ndarray:
        return sbm_hash_range(self.scale, start, count, self.n_blocks,
                              self.p_out, seed=self.seed)

    def content_fingerprint(self) -> str:
        return self._fingerprint(
            f"sbm_hash/s{self.scale}/b{self.n_blocks}/p{self.p_out}/"
            f"ef{self.edge_factor}/{self.seed}/")

    def ground_truth(self, k: int | None = None) -> np.ndarray:
        """The planted assignment at ``k`` parts (default: one part per
        block). ``n_blocks`` must be divisible by ``k``: consecutive
        blocks group into a part, preserving the planted cut. O(V)
        memory — ground truth is for scoring, not for streaming."""
        k = self.n_blocks if k is None else int(k)
        if k < 1 or self.n_blocks % k:
            raise ValueError(f"k must divide n_blocks={self.n_blocks}, "
                             f"got {k}")
        per = self.n_blocks // k
        blocks = np.arange(self._n, dtype=np.int64) >> self.block_bits
        return (blocks // per).astype(np.int32)

    def planted_cut_ratio(self, k: int | None = None) -> float:
        """The exact expected cut ratio of the planted partition at
        ``k`` parts (default: one part per block, where cross edges are
        inter-block by construction). At a GROUPED ``k`` (n_blocks/k
        consecutive blocks per part — :meth:`ground_truth`'s grouping) a
        cross edge stays intra-part when its distinct second block lands
        in the same group: probability (per - 1)/(n_blocks - 1), so the
        grouped planted ratio is p * (n_blocks - per)/(n_blocks - 1).
        This is the per-level optimum the cut ledger's level-0 row is
        measured against (ISSUE 13)."""
        p = _sbm_t_out(self.p_out) / 4294967296.0
        if k is None or k == self.n_blocks:
            return p
        if k < 1 or self.n_blocks % k:
            raise ValueError(f"k must divide n_blocks={self.n_blocks}, "
                             f"got {k}")
        per = self.n_blocks // k
        return p * (self.n_blocks - per) / max(self.n_blocks - 1, 1)

    # -- device fast path ---------------------------------------------------
    def device_chunk(self, idx: int, chunk_edges: int, n: int):
        cs = int(chunk_edges)
        start = idx * cs
        count = max(0, min(cs, self._m - start))
        return _sbm_device_chunk_fn()(
            (np.uint32(start & _M32), np.uint32(start >> 32)), count, cs,
            tuple(_sbm_hash_keys(self.seed)), _sbm_t_out(self.p_out),
            self.n_blocks, self.block_bits, n)


# ---------------------------------------------------------------------------
# Quality-scenario streams (ISSUE 13): the quality CI gate sweeps graph
# CLASSES, not one generator — bipartite, near-clique and power-law-
# degree community structure each stress a different partitioner
# behavior (2PS picks its strategy from exactly these degree/structure
# signals). All three are counter-hash streams like the SBM above:
# random-access chunks, deterministic under a seed, planted ground
# truth where one exists.
# ---------------------------------------------------------------------------


class NearCliqueStream(SbmHashStream):
    """Planted NEAR-CLIQUE communities: 2**scale vertices in dense
    blocks of ``2**clique_bits`` vertices, each edge intra-clique with
    probability ``1 - p_out``. Structurally this IS the planted
    partition with n_blocks = 2**(scale - clique_bits) — the point is
    the REGIME: with edge_factor around 2**(clique_bits - 1) each block
    approaches clique density (~ef * 2**clique_bits intra edges against
    ~2**(2*clique_bits - 1) pairs), the near-clique scenario the
    quality gate needs (a partitioner that shatters cliques shows up
    immediately in the cut). Reuses the SBM hash body wholesale, so the
    device fast path and ground truth come for free and stay
    bit-identical to the host chunks."""

    def __init__(self, scale: int, clique_bits: int, p_out: float = 0.01,
                 edge_factor: int = 8, seed: int = 0):
        cb = int(clique_bits)
        if not (1 <= cb < int(scale)):
            raise ValueError(f"clique_bits must be in [1, scale), got "
                             f"{clique_bits}")
        super().__init__(scale, 1 << (int(scale) - cb), p_out,
                         edge_factor, seed=seed)
        self.clique_bits = cb

    def content_fingerprint(self) -> str:
        return self._fingerprint(
            f"nearclique_hash/s{self.scale}/c{self.clique_bits}/"
            f"p{self.p_out}/ef{self.edge_factor}/{self.seed}/")


class PowerlawSbmHashStream(_CounterHashStream):
    """Planted partition with POWER-LAW within-block degrees: block
    choice is the SBM's (cross with probability ``p_out``, distinct
    second block), but the within-block vertex offsets come from the
    R-MAT recursive bit walk over ``block_bits`` levels instead of a
    uniform draw — so every block has Graph500-shaped hubs while the
    planted cut stays exactly Bernoulli(p_out). This is the
    "power-law SBM" scenario of the quality gate: LP refinement sees
    hub-dominated majorities where the flat SBM sees uniform ones, and
    a recipe that only works on flat degree distributions fails here
    first (the 2PS observation, inverted)."""

    def __init__(self, scale: int, n_blocks: int = 16,
                 p_out: float = 0.05, edge_factor: int = 16,
                 seed: int = 0,
                 a: float = 0.57, b: float = 0.19, c: float = 0.19):
        if not (1 <= scale <= 31):
            raise ValueError(f"plsbm-hash scale must be 1..31, got {scale}")
        nb = int(n_blocks)
        if nb < 2 or nb & (nb - 1) or nb > (1 << (scale - 1)):
            # nb == 2**scale would leave block_bits == 0 (no offset
            # walk at all); require at least 2 vertices per block
            raise ValueError(f"n_blocks must be a power of two in "
                             f"[2, 2**(scale-1)], got {n_blocks}")
        if not (0.0 <= p_out <= 1.0):
            raise ValueError(f"p_out must be in [0, 1], got {p_out}")
        self.scale = int(scale)
        self.n_blocks = nb
        self.block_bits = self.scale - (nb.bit_length() - 1)
        self.p_out = float(p_out)
        self.edge_factor = int(edge_factor)
        self.seed = int(seed)
        self.abc = (float(a), float(b), float(c))
        self._m = self.edge_factor << self.scale
        self._n = 1 << self.scale

    def _range(self, start: int, count: int) -> np.ndarray:
        idx = start + np.arange(count, dtype=np.int64)
        elo = (idx & _M32).astype(np.uint32)
        ehi = (idx >> 32).astype(np.uint32)
        # block fields: the SBM draw (seed-distinct from the offset keys)
        keys = _sbm_hash_keys(self.seed)
        h_cross, h_bu, h_bv = _hash_fields(np, elo, ehi, keys[:3])
        cross = h_cross < np.uint32(_sbm_t_out(self.p_out))
        bu = h_bu & np.uint32(self.n_blocks - 1)
        bvr = h_bv % np.uint32(self.n_blocks - 1)
        bv = bvr + (bvr >= bu).astype(np.uint32)
        b2 = np.where(cross, bv, bu)
        # within-block offsets: the R-MAT bit walk over block_bits
        # levels (distinct key schedule so offsets decorrelate from the
        # block fields)
        okeys = _rmat_hash_keys(self.block_bits,
                                _mix32_int(self.seed ^ 0x6A09E667))
        th = _rmat_hash_thresholds(*self.abc)
        uo, vo = _rmat_hash_uv(np, elo, ehi, okeys, th, np.uint32)
        u = (bu << np.uint32(self.block_bits)) | uo
        v = (b2 << np.uint32(self.block_bits)) | vo
        return np.stack([u.astype(np.int64), v.astype(np.int64)], axis=1)

    def content_fingerprint(self) -> str:
        return self._fingerprint(
            f"plsbm_hash/s{self.scale}/b{self.n_blocks}/p{self.p_out}/"
            f"ef{self.edge_factor}/{self.abc}/{self.seed}/")

    ground_truth = SbmHashStream.ground_truth
    planted_cut_ratio = SbmHashStream.planted_cut_ratio


class BipartiteHashStream(_CounterHashStream):
    """Planted BIPARTITE communities: 2**scale vertices split into a
    left half [0, n/2) and a right half [n/2, n); every edge crosses
    the halves (no intra-side edges, ever). ``n_blocks`` planted
    bi-communities each own one contiguous left segment and the
    matching right segment; an edge joins its block's two sides with
    probability ``1 - p_out`` and a distinct block's right side
    otherwise — so the planted cut at k = n_blocks is exactly
    Bernoulli(p_out), like the SBM, but every neighborhood is
    one-sided. This is the quality gate's bipartite scenario: degree
    signals that implicitly assume triangles/within-part edges (an LP
    majority over SAME-side neighbors, for one) get zero help here."""

    def __init__(self, scale: int, n_blocks: int = 8,
                 p_out: float = 0.02, edge_factor: int = 16,
                 seed: int = 0):
        if not (2 <= scale <= 31):
            raise ValueError(f"bipartite-hash scale must be 2..31, "
                             f"got {scale}")
        nb = int(n_blocks)
        half = 1 << (int(scale) - 1)
        if nb < 2 or nb & (nb - 1) or nb > half:
            raise ValueError(f"n_blocks must be a power of two in "
                             f"[2, 2**(scale-1)], got {n_blocks}")
        if not (0.0 <= p_out <= 1.0):
            raise ValueError(f"p_out must be in [0, 1], got {p_out}")
        self.scale = int(scale)
        self.n_blocks = nb
        # per-SIDE block span: half / n_blocks vertices
        self.block_bits = (self.scale - 1) - (nb.bit_length() - 1)
        self.p_out = float(p_out)
        self.edge_factor = int(edge_factor)
        self.seed = int(seed)
        self._m = self.edge_factor << self.scale
        self._n = 1 << self.scale

    def _range(self, start: int, count: int) -> np.ndarray:
        idx = start + np.arange(count, dtype=np.int64)
        elo = (idx & _M32).astype(np.uint32)
        ehi = (idx >> 32).astype(np.uint32)
        keys = _sbm_hash_keys(_mix32_int(self.seed ^ 0x3C6EF372))
        h_cross, h_bu, h_bv, h_uo, h_vo = _hash_fields(np, elo, ehi, keys)
        cross = h_cross < np.uint32(_sbm_t_out(self.p_out))
        bu = h_bu & np.uint32(self.n_blocks - 1)
        bvr = h_bv % np.uint32(self.n_blocks - 1)
        bv = bvr + (bvr >= bu).astype(np.uint32)
        b2 = np.where(cross, bv, bu)
        off_mask = np.uint32((1 << self.block_bits) - 1)
        half = np.int64(self._n >> 1)
        u = (bu.astype(np.int64) << self.block_bits) \
            | (h_uo & off_mask).astype(np.int64)
        v = half + ((b2.astype(np.int64) << self.block_bits)
                    | (h_vo & off_mask).astype(np.int64))
        return np.stack([u, v], axis=1)

    def content_fingerprint(self) -> str:
        return self._fingerprint(
            f"bipartite_hash/s{self.scale}/b{self.n_blocks}/"
            f"p{self.p_out}/ef{self.edge_factor}/{self.seed}/")

    def ground_truth(self, k: int | None = None) -> np.ndarray:
        """Planted assignment at ``k`` parts (default: one per
        bi-community). Each part takes a block's left AND right
        segments, so the planted partition never cuts the half
        boundary structure itself."""
        k = self.n_blocks if k is None else int(k)
        if k < 1 or self.n_blocks % k:
            raise ValueError(f"k must divide n_blocks={self.n_blocks}, "
                             f"got {k}")
        per = self.n_blocks // k
        half = self._n >> 1
        side_off = np.arange(self._n, dtype=np.int64) % half
        blocks = side_off >> self.block_bits
        return (blocks // per).astype(np.int32)

    planted_cut_ratio = SbmHashStream.planted_cut_ratio
