"""The profiler sink and the stats scope.

**Profiler sink.** While a JAX profiler session runs
(``jax.profiler.start_trace``: the CLI's ``--profile-dir``, sheepd's
``profile`` verb, a benchmark's traced run), every obs span also opens a
``jax.profiler.TraceAnnotation`` named ``sheep/<name>``, so the
program's span tree lands in the profiler's trace on the same clock as
the device ops, on the line of the thread that ran it. The check is
``TraceAnnotation.is_enabled()``; JAX is never imported for it (a
process that has not imported JAX has no profiler session).

**Stats scope.** :func:`stats_scope` makes a stats dict the target of
the counters that deep call sites add without having the dict passed
down: :func:`pull` (device-to-host reads), :func:`timed` (walls such as
the native core's) and the compile listener. Outside any scope they
count nothing.

Counters (all ``t_*`` in seconds, accumulated unrounded):

- ``pulls``, ``pull_bytes``: designed device-to-host reads and their
  host bytes;
- ``t_ready_wait_s``: wall waiting for the pulled value to be computed
  (the device still busy);
- ``t_transfer_s``, ``t_transfer_max_s``: wall of the copies once the
  value was ready, summed and the slowest single one;
- ``compiles``, ``t_compile_s``: backend compiles (persistent-cache
  loads included) on a thread inside the scope, and their wall;
- ``t_native_s`` (``core/native.py``) and ``t_chunk_wait_s``
  (``utils/prefetch.H2DRing``) are added by their call sites.
"""

from __future__ import annotations

import sys
import threading
import time
from contextlib import contextmanager
from contextvars import ContextVar

from sheep_tpu.obs.tracer import NULL_SPAN

PREFIX = "sheep/"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_ANNOTATION = None  # jax.profiler.TraceAnnotation, once JAX is imported
_SCOPE: ContextVar = ContextVar("sheep_obs_stats", default=None)
# scopes are carried to the prefetch worker thread, so the
# read-modify-write of one counter may race across threads
_LOCK = threading.Lock()
_LISTENING = False

#: what a scope counts, seeded to 0 on entry: a zero is a measured zero,
#: not a missing reading (``t_native_s`` from :func:`timed` in
#: ``core/native.py``, ``t_chunk_wait_s`` from ``utils/prefetch.H2DRing``)
COUNTERS = ("pulls", "pull_bytes", "t_ready_wait_s", "t_transfer_s",
            "t_transfer_max_s", "t_native_s", "t_chunk_wait_s", "compiles",
            "t_compile_s")


def annotation_cls():
    """``TraceAnnotation`` while a profiler session runs, else None."""
    global _ANNOTATION
    cls = _ANNOTATION
    if cls is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation as cls

        _ANNOTATION = cls
    return cls if cls.is_enabled() else None


class ProfiledSpan:
    """An obs span (a tracer ``Span``, or ``NULL_SPAN`` for a
    profiler-only span) that also holds the profiler annotation
    ``sheep/<name>`` from its start to its end. ``end`` closes the
    annotation on the thread that calls it."""

    __slots__ = ("span", "_ann")

    def __init__(self, span, cls, name: str):
        self.span = span
        self._ann = cls(PREFIX + name)

    @property
    def id(self):
        return getattr(self.span, "id", None)

    def start(self) -> "ProfiledSpan":
        self._ann.__enter__()
        self.span.start()
        return self

    def annotate(self, **attrs) -> None:
        self.span.annotate(**attrs)

    def end(self, **extra) -> None:
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        self.span.end(**extra)

    def __enter__(self) -> "ProfiledSpan":
        return self.start()

    def __exit__(self, et, ev, tb) -> bool:
        self.end(**({"error": et.__name__} if et is not None else {}))
        return False


def profiled(span, name: str):
    """``span`` as it should be handed out: wrapped in a
    :class:`ProfiledSpan` while a profiler session runs, else as is."""
    cls = annotation_cls()
    return ProfiledSpan(span, cls, name) if cls is not None else span


def profiler_span(name: str):
    """A span that exists only on the profiler's trace (no JSONL
    record): for intervals too fine for the tracer's tree, such as one
    fold dispatch or one native call. ``NULL_SPAN`` with no session."""
    return profiled(NULL_SPAN, name)


# -- the stats scope --------------------------------------------------------

@contextmanager
def stats_scope(stats: dict):
    """Make ``stats`` the innermost target of :func:`add`, :func:`pull`,
    :func:`timed` and the compile listener for the ``with`` body (and
    for worker threads that carry it, see :func:`current_stats`).
    ``None`` opens no scope, hiding any outer one."""
    _listen_compiles()
    if stats is not None:
        for k in COUNTERS:
            stats.setdefault(k, 0)
    token = _SCOPE.set(stats)
    try:
        yield stats
    finally:
        _SCOPE.reset(token)


def current_stats():
    """The innermost scope's dict (None outside any): capture it before
    starting a worker thread and re-enter it there."""
    return _SCOPE.get()


def add(key: str, v) -> None:
    """Add ``v`` to ``key`` in the innermost scope (no-op outside)."""
    s = _SCOPE.get()
    if s is not None:
        with _LOCK:
            s[key] = s.get(key, 0) + v


@contextmanager
def timed(name: str, key: str):
    """Profiler span ``sheep/<name>`` around the body, and its wall added
    to ``key`` in the innermost scope."""
    with profiler_span(name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            add(key, time.perf_counter() - t0)


def pull(label: str, x, host_blocked=None):
    """Read a device value to the host: the one form of a designed
    device-to-host read on the partition path. Returns ``np.asarray(x)``
    (``x`` itself converted when it is already a host value, which counts
    nothing).

    Opens ``sheep/pull:<label>``, is an annotated sync point for the
    sanitizer, and times the wait for the value
    (``x.block_until_ready()``) apart from the copy. ``host_blocked``, a
    stats dict, also gets the pull's whole wall in ``host_blocked_ms``."""
    import numpy as np

    ready = getattr(x, "block_until_ready", None)
    if ready is None:
        return np.asarray(x)
    from sheep_tpu.analysis import sanitize

    with profiler_span("pull:" + label), sanitize.sync_ok(label):
        t0 = time.perf_counter()
        ready()
        t1 = time.perf_counter()
        out = np.asarray(x)
        t2 = time.perf_counter()
    if host_blocked is not None:
        host_blocked["host_blocked_ms"] = \
            host_blocked.get("host_blocked_ms", 0.0) + (t2 - t0) * 1e3
    s = _SCOPE.get()
    if s is not None:
        with _LOCK:
            s["pulls"] = s.get("pulls", 0) + 1
            s["pull_bytes"] = s.get("pull_bytes", 0) + int(out.nbytes)
            s["t_ready_wait_s"] = s.get("t_ready_wait_s", 0.0) + (t1 - t0)
            s["t_transfer_s"] = s.get("t_transfer_s", 0.0) + (t2 - t1)
            s["t_transfer_max_s"] = max(s.get("t_transfer_max_s", 0.0),
                                        t2 - t1)
    return out


# -- compiles ---------------------------------------------------------------

def _on_duration(event: str, secs: float, **kw) -> None:
    if event != COMPILE_EVENT:
        return
    s = _SCOPE.get()
    if s is not None:
        with _LOCK:
            s["compiles"] = s.get("compiles", 0) + 1
            s["t_compile_s"] = s.get("t_compile_s", 0.0) + secs


def _listen_compiles() -> None:
    """Register the process-wide compile listener once, when a scope is
    first entered in a process that has imported JAX."""
    global _LISTENING
    if _LISTENING or "jax" not in sys.modules:
        return
    with _LOCK:
        if _LISTENING:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_duration)
        _LISTENING = True
