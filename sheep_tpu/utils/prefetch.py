"""Host-I/O / device-compute overlap (SURVEY.md §2 parallelism table, PP
row: "double-buffering").

A bounded background-thread prefetcher for the streaming loops: while the
device folds chunk i, the worker thread reads + parses + pads chunk i+1
(file reads, np.fromfile and the ctypes text parser all release the GIL,
so the overlap is real). Depth 2 is double-buffering — one item in flight
on the device, one ready on host — which makes the build phase wall
approximately max(io, compute) instead of their sum (VERDICT r1 item 6).

The wrapper preserves item order exactly (checkpoint chunk indices and
fault-injection counters are unaffected) and propagates worker exceptions
to the consumer at the point of `next()` — with the ORIGINAL worker-side
traceback attached, so the consumer's log names the failing reader frame
rather than this module's re-raise. A worker that dies without
delivering its termination sentinel (killed out-of-band) surfaces as a
RuntimeError at the next `next()` instead of an eternal blocking get,
and ``close()`` joins with a timeout, so neither path can hang the
consumer's unwind (ISSUE 9 satellite; regression-tested with an
injected reader fault).

Lifecycle (ISSUE 4 satellite): :func:`prefetch` returns a
:class:`Prefetcher`, an iterator with an explicit :meth:`Prefetcher.close`
that CANCELS the worker — sets the stop event, drains the bounded queue
so a worker blocked on a full ``put`` wakes immediately, and joins the
thread. Consumers that may abandon the stream mid-iteration (the
in-flight dispatch pipeline's discard/backstop paths, exception unwinds)
call it from a ``finally`` so the worker (and whatever file handle or
device transfer it holds) is released deterministically instead of
whenever the GC finalizes a half-consumed generator. Iterating after
``close`` raises ``StopIteration``; ``close`` is idempotent and also runs
on ``with``-exit and finalization.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Iterable, Iterator, TypeVar

from sheep_tpu import obs

T = TypeVar("T")

_END = object()

#: :meth:`Prefetcher.poll_nowait` return when nothing is queued yet —
#: distinct from every real item AND from stream end (StopIteration)
NOT_READY = object()


class _Raised:
    __slots__ = ("exc", "tb")

    def __init__(self, exc: BaseException):
        self.exc = exc
        self.tb = exc.__traceback__  # worker-side frames, re-attached
        #                              at the consumer's re-raise


class Prefetcher(Iterator[T]):
    """Background-thread iterator over ``iterable`` keeping up to
    ``depth`` items ready ahead of the consumer (see module docstring
    for the close/cancel contract)."""

    def __init__(self, iterable: Iterable[T], depth: int = 2):
        if depth < 1:
            raise ValueError("prefetch depth must be >= 1")
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._closed = False
        self._done = False
        # flight-recorder attribution (ISSUE 11): the worker inherits
        # the job context of the thread that CREATED it (thread-locals
        # don't cross threads), so read faults / retries emitted while
        # pre-reading a served job's chunks land in that job's ring,
        # not the global one
        self._flight_job = obs.flight_job()
        # and the stats scope (obs.stats_scope): counters the worker's
        # reads add land in the partition that started it
        self._stats = obs.current_stats()
        self._thread = threading.Thread(
            target=self._worker, args=(iterable,), daemon=True,
            name="sheep-prefetch")
        self._thread.start()

    def _put_until_stop(self, item) -> bool:
        """Bounded put that gives up when the consumer signalled stop;
        returns True when the item was enqueued."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self, iterable) -> None:
        with obs.flight_job_context(self._flight_job), \
                obs.stats_scope(self._stats):
            try:
                for item in iterable:
                    if not self._put_until_stop(item):
                        return
                    if self._stop.is_set():
                        return
            except BaseException as e:  # delivered to the consumer
                self._put_until_stop(_Raised(e))
                return
            self._put_until_stop(_END)

    def __iter__(self) -> "Prefetcher[T]":
        return self

    def __next__(self) -> T:
        if self._closed or self._done:
            raise StopIteration
        while True:
            try:
                item = self._q.get(timeout=0.5)
                break
            except queue.Empty:
                # liveness guard (ISSUE 9 satellite): a worker that died
                # without delivering its end/exception sentinel (thread
                # killed out-of-band, sentinel put failed) must surface
                # as a diagnosis at the consumer, not an eternal
                # blocking get
                if not self._thread.is_alive():
                    # the worker may have delivered its final item or
                    # sentinel BETWEEN the get timeout and the
                    # liveness check — drain once before declaring it
                    # sentinelless, or a legitimate last chunk (or the
                    # real worker exception) would be replaced by the
                    # bogus died-without diagnosis
                    try:
                        item = self._q.get_nowait()
                        break
                    except queue.Empty:
                        pass
                    self._done = True
                    self._stop.set()
                    raise RuntimeError(
                        "prefetch worker died without delivering a "
                        "result or its termination sentinel")
        if item is _END:
            self._done = True
            self._stop.set()
            raise StopIteration
        if isinstance(item, _Raised):
            self._done = True
            self._stop.set()
            # re-raise with the ORIGINAL worker-side traceback attached
            # (explicit, so the frames that name the failing reader
            # survive even if something cleared __traceback__ in
            # transit) — the consumer's log points at the real fault
            raise item.exc.with_traceback(item.tb)
        return item

    def poll_nowait(self):
        """Non-blocking probe: the next item when one is already
        queued, the module sentinel :data:`NOT_READY` otherwise.
        Stream end and worker exceptions surface exactly as in
        :meth:`__next__` (StopIteration / the original error). This is
        the opportunistic-refill hook of :class:`H2DRing`: the ring
        tops itself up with whatever the worker has ready without ever
        blocking the consumer on the producer thread."""
        if self._closed or self._done:
            raise StopIteration
        try:
            item = self._q.get_nowait()
        except queue.Empty:
            return NOT_READY
        if item is _END:
            self._done = True
            self._stop.set()
            raise StopIteration
        if isinstance(item, _Raised):
            self._done = True
            self._stop.set()
            raise item.exc.with_traceback(item.tb)
        return item

    def close(self, timeout: float = 5.0) -> None:
        """Cancel the worker: signal stop, drain the queue (a worker
        blocked on the full bounded queue wakes within one put poll),
        and join the thread. Idempotent; safe from ``finally`` blocks.
        A worker stuck inside the underlying iterable longer than
        ``timeout`` is abandoned (it is a daemon thread) rather than
        hanging the caller's unwind."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        # drain so a put-blocked worker observes the stop event promptly
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=timeout)
        # the worker may have completed one last put between the drain
        # and its stop check; leave nothing referenced
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break

    @property
    def closed(self) -> bool:
        return self._closed

    def __enter__(self) -> "Prefetcher[T]":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def __del__(self):  # best-effort backstop; explicit close preferred
        try:
            self.close(timeout=0.0)
        except Exception:
            pass


def prefetch(iterable: Iterable[T], depth: int = 2) -> Prefetcher[T]:
    """Iterate ``iterable`` on a background thread, keeping up to
    ``depth`` items ready ahead of the consumer.

    Returns a :class:`Prefetcher`; call :meth:`Prefetcher.close` (or use
    ``with``) when abandoning it before exhaustion — early consumer exit
    otherwise stops the worker on the GC backstop only.
    """
    return Prefetcher(iterable, depth=depth)


# ---------------------------------------------------------------------------
# staged H2D ring (ISSUE 12 tentpole, leg b). The prefetcher above hides
# host READ latency; the host->device transfer itself still ran
# synchronously in the dispatch chain (`jnp.asarray(padded)` issued at
# the exact moment the driver needed the block). jax transfers are
# asynchronous once ISSUED, so the only thing needed to take H2D off the
# critical path is issuing each block's device_put D blocks ahead of its
# consumption — while the device folds block i, the transfers for blocks
# i+1..i+D are already in flight.
# ---------------------------------------------------------------------------


def _block_bytes(block) -> int:
    """Host bytes of one staged block (a single array or a list/tuple of
    them — the grouped staging of the batched dispatch)."""
    if isinstance(block, (list, tuple)):
        return sum(_block_bytes(b) for b in block)
    return int(getattr(block, "nbytes", 0))


class H2DRing:
    """Bounded ring of staged host->device transfers over an iterator of
    PRE-PADDED host blocks (a ``(C, 2)`` chunk, or a list of them — any
    pytree ``jax.device_put`` accepts).

    Keeps up to ``depth`` blocks' transfers issued AHEAD of the
    consumer, preserving order exactly; each yielded device array is
    bit-identical to what ``jnp.asarray`` of the same host block yields,
    so every consumer stays on the fixpoint-uniqueness contract.
    Refills are OPPORTUNISTIC when the source is a :class:`Prefetcher`
    (:meth:`Prefetcher.poll_nowait` — the ring never blocks the consumer
    on the producer thread while it still holds staged blocks); plain
    iterables refill eagerly.

    Counters, accumulated UNROUNDED into ``stats`` (read-time rounding,
    like every ``*_ms`` counter):

    - ``h2d_staged_ms``   wall spent *issuing* ahead-of-need transfers
      (async issue cost — the transfer itself overlaps device compute)
    - ``h2d_blocked_ms``  wall the consumer spent waiting for a block
      the ring did not have staged (mid-stream underrun — exactly the
      synchronous-upload tax this class removes; ~0 at depth >= 2 with
      a keeping-up producer, the ``device_gap_ms`` pattern). The
      startup fill is attributed to staged, not blocked: before the
      first block there is no device work to overlap, the same
      convention ``device_gap_ms`` uses for the first dispatch.
    - ``t_chunk_wait_s``  seconds of every blocking fill, startup
      included: the whole time the consumer waited for its input (span
      ``sheep/chunk_wait`` on the profiler's trace)
    - ``h2d_staged_bytes``  host bytes that crossed through the ring
    - ``h2d_ring_depth``    the resolved depth (gauge)

    Lifecycle mirrors :class:`Prefetcher`: ``close()`` drops the staged
    device references (releasing their HBM — the drain the
    checkpoint/fault contract needs when a driver abandons the stream
    mid-flight) and closes a closeable source; idempotent, ``with``
    supported, iteration after close raises StopIteration.
    """

    def __init__(self, source, depth: int = 2, stats=None):
        if depth < 1:
            raise ValueError("h2d ring depth must be >= 1")
        self.depth = int(depth)
        self._src = source if hasattr(source, "__next__") \
            else iter(source)
        self._poll = getattr(self._src, "poll_nowait", None)
        self._ring: deque = deque()
        self._stats = stats if stats is not None else {}
        self._stats.setdefault("h2d_staged_ms", 0.0)
        self._stats.setdefault("h2d_blocked_ms", 0.0)
        self._stats.setdefault("h2d_staged_bytes", 0)
        self._stats.setdefault("t_chunk_wait_s", 0.0)
        self._stats["h2d_ring_depth"] = self.depth
        self._exhausted = False
        self._closed = False
        self._started = False

    def _issue(self, block) -> None:
        """Issue one block's (async) transfer and append it."""
        import jax

        self._stats["h2d_staged_bytes"] += _block_bytes(block)
        self._ring.append(jax.device_put(block))

    def _fill(self, want: int, may_block: bool) -> None:
        """Stage transfers until the ring holds ``want`` blocks or the
        source has nothing (ready, when non-blocking) left."""
        while len(self._ring) < want and not self._exhausted:
            try:
                if self._poll is not None and not may_block:
                    block = self._poll()
                    if block is NOT_READY:
                        return
                else:
                    block = next(self._src)
            except StopIteration:
                self._exhausted = True
                return
            self._issue(block)
            may_block = False  # at most one blocking pull per fill

    def __iter__(self) -> "H2DRing":
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        if not self._ring and not self._exhausted:
            # underrun (or startup): the consumer waits for host + issue
            # in its critical path — the tax the ring exists to hide
            with obs.profiler_span("chunk_wait"):
                t0 = time.perf_counter()
                self._fill(1, may_block=True)
                dt = time.perf_counter() - t0
            key = "h2d_blocked_ms" if self._started else "h2d_staged_ms"
            self._stats[key] += dt * 1e3
            self._stats["t_chunk_wait_s"] += dt
        if not self._ring:
            raise StopIteration
        self._started = True
        out = self._ring.popleft()
        # top back up to depth off the critical path: transfers are
        # issued (async) now, so they run under the consumer's device
        # work on `out`; only the issue cost lands in staged_ms
        t0 = time.perf_counter()
        self._fill(self.depth, may_block=self._poll is None)
        self._stats["h2d_staged_ms"] += (time.perf_counter() - t0) * 1e3
        return out

    def close(self) -> None:
        """Drop staged device references and close a closeable source.
        Idempotent; safe from ``finally`` blocks."""
        if self._closed:
            return
        self._closed = True
        self._ring.clear()
        close = getattr(self._src, "close", None)
        if close is not None:
            close()

    @property
    def closed(self) -> bool:
        return self._closed

    def __enter__(self) -> "H2DRing":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


def prefetch_batched(iterable: Iterable[T], batch: int,
                     depth: int = 2) -> Prefetcher[list]:
    """Group ``iterable`` into lists of up to ``batch`` items on the
    prefetch worker thread — the staging primitive of the batched
    segment dispatch: all N chunks of the NEXT enlarged device program
    are read + parsed + padded while the device runs the current one
    (``depth`` counts staged *groups*, so depth 2 keeps up to 2N items
    in flight). Order, completeness, exception propagation, early
    consumer exit and :meth:`Prefetcher.close` behave exactly as
    :func:`prefetch`; the final group may be shorter than ``batch``."""
    if batch < 1:
        raise ValueError("prefetch batch must be >= 1")

    def grouped():
        buf: list = []
        for item in iterable:
            buf.append(item)
            if len(buf) == batch:
                yield buf
                buf = []
        if buf:
            yield buf

    return prefetch(grouped(), depth=depth)
