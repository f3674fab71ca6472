"""Hierarchical span tracer + counter registry (the obs core).

A run traced through this module renders as a TREE, not a flat phase
list: every span carries an id and its parent's id, so
``span("build") > span("segment", i=k) > span("dispatch")`` nests in
the JSONL exactly as it nested in time. Two event kinds:

    {"event": "span_start", "ts": ..., "span": "build", "id": 3,
     "parent": 1, ...attrs}
    {"event": "span_end", "ts": ..., "span": "build", "id": 3,
     "parent": 1, "secs": 8.21, "counters": {"host_syncs": 4, ...}}

``counters`` on span_end is the DELTA of the tracer's registry between
span entry and exit — the ad-hoc ``host_syncs``/``device_rounds``/fold
diagnostics become named metrics sampled at span boundaries. A span
that never ends (process killed mid-build) leaves its span_start as
the last word on where the run died — ``tools/trace_report.py`` flags
those, which is how a dead soak is distinguished from a slow one after
the fact (the round-5 s30 soak died silently for lack of exactly
this).

Spans are context managers, but every span also exposes explicit
``start()``/``end()`` so hot loops can bracket work without
re-indenting (``sp = obs.begin("segment", i=k); ...; sp.end()``).
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import IO, Optional, Union

from sheep_tpu.utils.metrics import MetricsWriter


class CounterRegistry(dict):
    """Named counters/gauges. A plain-dict subclass on purpose: the
    existing ad-hoc stats dicts (``stats["host_syncs"] = ...`` in
    ops/elim.py and the pipelines) absorb without adaptation, and
    ``snapshot``/``delta`` give the span tracer and heartbeat a
    queryable view."""

    def inc(self, name: str, v=1) -> None:
        self[name] = self.get(name, 0) + v

    def gauge(self, name: str, v) -> None:
        self[name] = v

    def absorb(self, stats: dict) -> None:
        """Overwrite-merge a CUMULATIVE stats dict. The elim-ops/pipeline
        counters grow monotonically within a run, so overwriting makes
        absorb idempotent — callers may re-absorb the same dict every
        segment and the registry always holds the latest totals."""
        for k, v in stats.items():
            self[k] = v

    def snapshot(self) -> dict:
        return dict(self)

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        """Numeric keys: after - before (omitted when zero). Non-numeric
        keys (mode strings etc.): included when changed."""
        out = {}
        for k, v in after.items():
            v0 = before.get(k, 0 if isinstance(v, (int, float))
                            and not isinstance(v, bool) else None)
            if (isinstance(v, (int, float)) and not isinstance(v, bool)
                    and isinstance(v0, (int, float))
                    and not isinstance(v0, bool)):
                d = v - v0
                if d:
                    out[k] = round(d, 6) if isinstance(d, float) else d
            elif v0 != v:
                out[k] = v
        return out


class StatsAccumulator:
    """Per-run bridge from one CUMULATIVE stats dict into a registry.

    The ad-hoc stats dicts grow monotonically WITHIN one partition
    call, but each call starts a fresh dict — several calls under one
    tracer (hierarchy levels, partition_multi legs, appended CLI runs)
    must SUM into the registry, not overwrite it (overwrite would emit
    negative span deltas and report only the last call's totals).
    Each ``absorb`` adds only the increment since THIS accumulator's
    previous absorb; create one per stats dict, at the start of the
    run that owns it. Non-numeric values (mode strings) overwrite."""

    __slots__ = ("_reg", "_last")

    def __init__(self, registry: CounterRegistry):
        self._reg = registry
        self._last: dict = {}

    def absorb(self, stats: dict) -> None:
        for k, v in stats.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                prev = self._last.get(k, 0)
                if not isinstance(prev, (int, float)) \
                        or isinstance(prev, bool):
                    prev = 0
                d = v - prev
                if d:
                    self._reg[k] = self._reg.get(k, 0) + d
            else:
                self._reg[k] = v
            self._last[k] = v


class NullStatsAccumulator:
    __slots__ = ()

    def absorb(self, stats: dict) -> None:
        pass


NULL_STATS = NullStatsAccumulator()

# sentinel: "no explicit parent given — derive from the thread-local
# stack" (None is a valid explicit parent meaning "root")
_STACK_PARENT = object()


class Span:
    """One traced interval. Usable as a context manager or via explicit
    ``start()``/``end()`` (unbalanced on purpose when the process dies —
    see module docstring).

    ``parent``/``attach``: by default a span parents to the enclosing
    span on ITS thread's stack and joins that stack. A DETACHED span
    (``attach=False``, parent given explicitly) does neither — it is the
    form for interleaved long-lived intervals that do not nest in time
    on any one thread (the server scheduler's per-job spans: job A's
    root must not become the parent of job B's phases just because both
    jobs step on the scheduler thread)."""

    __slots__ = ("_tracer", "name", "attrs", "id", "parent", "_t0",
                 "_snap", "_done", "_parent_arg", "_attach")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict,
                 parent=_STACK_PARENT, attach: bool = True):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.id = None
        self.parent = None
        self._t0 = 0.0
        self._snap: dict = {}
        self._done = False
        self._parent_arg = parent
        self._attach = attach

    def start(self) -> "Span":
        tr = self._tracer
        self.parent = tr._current_id() \
            if self._parent_arg is _STACK_PARENT else self._parent_arg
        self.id = tr._next_id()
        self._snap = tr.counters.snapshot()
        with tr._balance_lock:  # spans may start on worker threads
            tr._open_spans += 1
        if self._attach:
            tr._push(self.id)
        tr.emit("span_start", span=self.name, id=self.id,
                parent=self.parent, **self.attrs)
        self._t0 = time.perf_counter()
        return self

    def annotate(self, **attrs) -> None:
        """Attach attributes to a RUNNING span so its span_end record
        carries them (the span_start already went out). The quality
        ledger's use case (ISSUE 13): the refine span learns its
        starting cut on the first scoring pass, rounds before the span
        closes — annotate-then-end puts the number on the interval it
        belongs to instead of threading it to the end() call site."""
        self.attrs.update(attrs)

    def end(self, **extra) -> None:
        if self._done or self.id is None:
            return
        self._done = True
        tr = self._tracer
        secs = time.perf_counter() - self._t0
        with tr._balance_lock:
            tr._open_spans -= 1
        if self._attach:
            tr._pop(self.id)
        fields = dict(span=self.name, id=self.id, parent=self.parent,
                      secs=round(secs, 6), **self.attrs)
        fields.update(extra)
        delta = CounterRegistry.delta(self._snap, tr.counters)
        if delta:
            fields["counters"] = delta
        tr.emit("span_end", **fields)

    def __enter__(self) -> "Span":
        return self.start()

    def __exit__(self, et, ev, tb) -> bool:
        self.end(**({"error": et.__name__} if et is not None else {}))
        return False


class NullSpan:
    """The disabled-tracing span: every operation is a no-op on a shared
    singleton, so instrumentation left in hot loops costs one global
    read + one attribute call when tracing is off."""

    __slots__ = ()

    def start(self) -> "NullSpan":
        return self

    def annotate(self, **attrs) -> None:
        pass

    def end(self, **extra) -> None:
        pass

    def __enter__(self) -> "NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = NullSpan()


class Tracer:
    """JSONL span/counter/heartbeat sink for one run.

    Thread model: span ids come from an atomic counter and the span
    stack is thread-local (a span opened on a worker thread parents to
    that thread's enclosing span, or to nothing). ``progress`` is a
    plain dict updated racily by the instrumented loops and read by the
    heartbeat thread — single fields only, no cross-field invariants.
    The underlying MetricsWriter serializes concurrent emits."""

    def __init__(self, dest: Union[str, IO]):
        self._mw = MetricsWriter(dest)
        self.counters = CounterRegistry()
        self.progress: dict = {}
        self.heartbeat = None  # owner-managed Heartbeat, if any
        self._local = threading.local()
        self._ids = itertools.count(1)
        self._closed = False
        self._open_spans = 0  # begun minus ended, across all threads
        self._balance_lock = threading.Lock()

    # -- events ------------------------------------------------------------
    def emit(self, event: str, **fields) -> None:
        self._mw.emit(event, **fields)

    # -- spans -------------------------------------------------------------
    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def begin(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs).start()

    def begin_detached(self, name: str, parent=None,
                       remote_parent=None, **attrs) -> Span:
        """Start a DETACHED span: explicit ``parent`` span id (or None
        for a root), never on any thread's span stack — for intervals
        that interleave in time instead of nesting (see Span).

        ``remote_parent`` (ISSUE 18) is a CROSS-PROCESS parent:
        ``{"trace": <hex trace id>, "span": <hex remote span id>}``
        from a propagated wire trace context. The local tree is
        untouched (``parent`` still names the local parent id); the
        span records additionally carry ``trace`` and
        ``remote_parent`` attrs, which is what lets
        ``tools/trace_report.py --stitch`` graft this process's
        subtree under the originating client span in a DIFFERENT
        process's trace file. An all-zero remote span id means "the
        caller had no span of its own" — the trace id still lands."""
        return self.detached(name, parent=parent,
                             remote_parent=remote_parent, **attrs).start()

    def detached(self, name: str, parent=None, remote_parent=None,
                 **attrs) -> Span:
        """The detached span :meth:`begin_detached` starts, not yet
        started."""
        if remote_parent:
            attrs = dict(attrs)
            tid = remote_parent.get("trace")
            if tid:
                attrs.setdefault("trace", tid)
            rp = remote_parent.get("span")
            if rp and set(str(rp)) != {"0"}:
                attrs.setdefault("remote_parent", str(rp))
        return Span(self, name, attrs, parent=parent, attach=False)

    def current_span_id(self) -> Optional[int]:
        """The calling thread's innermost open span id (None at root)
        — what a client stamps into an outgoing wire trace context as
        the remote parent span (ISSUE 18)."""
        return self._current_id()

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _current_id(self) -> Optional[int]:
        st = self._stack()
        return st[-1] if st else None

    def _next_id(self) -> int:
        return next(self._ids)  # itertools.count: atomic under the GIL

    def _push(self, span_id: int) -> None:
        self._stack().append(span_id)

    def _pop(self, span_id: int) -> None:
        st = self._stack()
        # tolerate out-of-order ends (a caller leaking a handle must not
        # corrupt every later parent attribution): pop through to ours
        while st and st[-1] != span_id:
            st.pop()
        if st:
            st.pop()

    # -- lifecycle ---------------------------------------------------------
    def close(self) -> None:
        """Flush the final counter totals (one ``counters`` event — the
        queryable end-state tools read without re-deriving span deltas)
        and close the sink.

        Under ``SHEEP_SANITIZE=1`` a nonzero open-span count here
        raises: an unbalanced span at a CLEAN close is a leaked handle
        (the deliberate unbalanced-on-death case never reaches close,
        so the forensic value of unclosed spans is untouched)."""
        if self._closed:
            return
        self._closed = True
        if self.counters:
            self.emit("counters", **self.counters.snapshot())
        open_spans = self._open_spans
        if open_spans:
            from sheep_tpu.analysis import sanitize

            if sanitize.enabled():
                self._mw.close()
                raise sanitize.SanitizeError(
                    f"tracer closed with {open_spans} span(s) begun "
                    f"but never ended — a leaked span handle (run "
                    f"tools/trace_report.py on the trace to see which)")
        self._mw.close()

    def __enter__(self) -> "Tracer":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
