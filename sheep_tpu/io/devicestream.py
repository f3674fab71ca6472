"""Device-resident stream synthesis — the ``DeviceStream`` protocol
(ISSUE 12 tentpole, leg a).

Before this protocol every chunk of a *synthetic* stream paid a host
generate → pad → ``jnp.asarray`` H2D upload before the device could
fold it, even though the counter-hash generators (io/generators.py)
can compute any edge range directly ON DEVICE, bit-identically to the
host twin. This module makes that capability a first-class input
protocol instead of an ad-hoc attribute probe: a
:class:`DeviceStream` materializes each padded ``(C, 2)`` int32 chunk
in accelerator memory, so a build over one pays
**zero host bytes per chunk** — no host generation, no H2D transfer, no
staging ring. The dispatch drivers (tpu backend, sharded pipeline, bigv
pipeline) and the served engine all recognize the protocol through
:func:`is_device_stream`.

Contract (what every implementation must hold):

- ``device_chunk(idx, chunk_edges, n)`` returns the ``(chunk_edges, 2)``
  int32 device array for GLOBAL chunk ``idx``, rows past the real edge
  count holding the sentinel vertex ``n`` — **bit-identical** to
  ``pad_chunk(host_chunk_idx, chunk_edges, n)`` of the same stream's
  host chunks, so cross-backend/oracle equality holds by construction
  (the fixpoint-uniqueness argument needs identical constraint
  multisets, and checkpoint fingerprints hash the host twin).
- ``num_device_chunks(chunk_edges)`` returns the total chunk count;
  ``device_chunk`` past it yields an all-sentinel (inert) chunk, which
  is what lets lockstep multi-device batch iteration pad stragglers
  without a host round-trip.
- Chunk access is RANDOM (any index independently), which keeps
  checkpoint resume, round-robin sharding and the shared chunk cache's
  prefix semantics exact rather than replay-based.

Host-format streams (files, in-memory arrays, replay generators) are
not device streams; they take the staged H2D ring
(:class:`sheep_tpu.utils.prefetch.H2DRing`) instead — leg (b) of the
same ingest overhaul.
"""

from __future__ import annotations


class DeviceStream:
    """Base / marker class for streams whose padded chunks materialize
    directly in device memory (see module docstring for the contract).
    Subclasses implement :meth:`device_chunk`; the EdgeStream surface
    (``chunks``/``num_vertices``/...) comes from the concrete stream
    class (e.g. ``io.generators._CounterHashStream``)."""

    def device_chunk(self, idx: int, chunk_edges: int, n: int):
        """Padded ``(chunk_edges, 2)`` int32 DEVICE chunk for global
        chunk ``idx`` (sentinel ``n`` past the real edge count)."""
        raise NotImplementedError

    def device_chunk_on(self, device, idx: int, chunk_edges: int, n: int):
        """:meth:`device_chunk` placed on ``device`` — the multi-device
        drivers' placement hook. The counter-hash generators compute it
        there (their kernel takes only uncommitted scalars), so no
        generator work or transient buffer lands on device 0; still
        zero host bytes."""
        import jax

        with jax.default_device(device):
            chunk = self.device_chunk(idx, chunk_edges, n)
        # a stream whose synthesis reads committed arrays (a delta
        # log's cached base) computed elsewhere: move it
        return jax.device_put(chunk, device)


def is_device_stream(stream) -> bool:
    """True when ``stream`` can synthesize padded chunks on device:
    a :class:`DeviceStream`, or any object with a callable
    ``device_chunk`` (duck-typed third-party streams keep working)."""
    return isinstance(stream, DeviceStream) or \
        callable(getattr(stream, "device_chunk", None))


def note_device_chunks(stats, count: int = 1) -> None:
    """Account ``count`` device-synthesized chunks in a driver stats
    dict: bumps ``device_stream_chunks`` and pins ``h2d_staged_bytes``
    at its seeded value (0 unless a host-format pass also ran) — the
    trace-visible proof that the path paid zero per-chunk host staging
    bytes."""
    if stats is None:
        return
    stats.setdefault("h2d_staged_bytes", 0)
    stats["device_stream_chunks"] = \
        stats.get("device_stream_chunks", 0) + count
