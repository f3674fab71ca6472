"""sheep_tpu.obs — the observability spine (ISSUE 3 tentpole).

One module-level tracer that everything threads through:

- **spans** — hierarchical timed intervals emitted as JSONL
  (``span_start``/``span_end`` with parent ids), so a run renders as a
  tree (``tools/trace_report.py``) instead of a flat phase list;
- **counters** — a registry the ad-hoc ``host_syncs``/``device_rounds``
  /fold diagnostics absorb into, sampled as deltas at span boundaries
  and live by the heartbeat;
- **heartbeat** — a thread emitting periodic progress records so a
  multi-hour soak is observable while running (and a dead run is
  distinguishable from a slow one);
- **manifest** — config/topology/version/git-SHA provenance on every
  traced run;
- **metrics** (ISSUE 11) — a typed live registry (counters, gauges,
  fixed-bucket histograms) with Prometheus text rendering, the
  scrape-able face of the same numbers (``obs/metrics.py``);
- **flight recorder** (ISSUE 11) — always-on bounded rings of the
  last N events per job, fed by :func:`event` alongside the tracer
  and dumped to the trace sink on failure/fault/shutdown
  (``obs/flightrec.py``);
- **profiler sink** — while a JAX profiler session runs (CLI
  ``--profile-dir``, sheepd's ``profile`` verb, ``jax.profiler.
  start_trace``), every span is also a ``jax.profiler.TraceAnnotation``
  named ``sheep/<name>``, on the device ops' clock; finer spans that
  live only there (``sheep/pull:<label>``, ``sheep/native:<fn>``,
  ``sheep/fold``, ``sheep/compact``, ``sheep/host_tail``,
  ``sheep/chunk_wait``) come from :func:`profiler_span`, :func:`pull`
  and :func:`timed` (``obs/profiler.py``);
- **stats scope** — :func:`stats_scope` makes a partition's stats dict
  the target of the counters deep call sites add (:func:`pull`'s
  ``pulls``/``pull_bytes``/``t_ready_wait_s``/``t_transfer_s``/
  ``t_transfer_max_s``, :func:`timed`'s walls such as ``t_native_s``,
  the compile listener's ``compiles``/``t_compile_s``) without the dict
  being passed down; they reach the result's ``diagnostics``.

Instrumentation calls are UNCONDITIONAL at the call sites (backends,
pipelines, CLI) and near-free when tracing is off: every facade
function reads one module global, asks whether a profiler session
runs, and returns a shared no-op. Install a tracer (CLI ``--trace``, or
:func:`tracing` in tests/tools) and the same call sites produce the
full trace.

    from sheep_tpu import obs

    acc = obs.stats_accumulator()            # one per stats dict
    with obs.span("build"):
        for i, chunk in enumerate(chunks):
            sp = obs.begin("segment", i=i)
            ...fold...
            acc.absorb(build_stats)          # counter increments -> registry
            obs.progress(chunks_done=i + 1)  # heartbeat inputs
            sp.end(rounds=r)
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import IO, Optional, Union

from sheep_tpu.obs.flightrec import FlightRecorder  # noqa: F401
from sheep_tpu.obs.heartbeat import Heartbeat  # noqa: F401
from sheep_tpu.obs.manifest import collect_manifest, emit_manifest  # noqa: F401
from sheep_tpu.obs.metrics import MetricRegistry  # noqa: F401
from sheep_tpu.obs.profiler import (ProfiledSpan, add,  # noqa: F401
                                    current_stats, profiled, profiler_span,
                                    pull, stats_scope, timed)
from sheep_tpu.obs.tracer import (NULL_SPAN, NULL_STATS, CounterRegistry,  # noqa: F401
                                  NullSpan, Span, StatsAccumulator, Tracer)

_TRACER: Optional[Tracer] = None
_FLIGHT: Optional[FlightRecorder] = None


def install_flight(recorder: FlightRecorder) -> FlightRecorder:
    """Make ``recorder`` the process-wide flight recorder: every
    :func:`event` also lands in its bounded rings (ISSUE 11). Unlike
    the tracer this is always-on-capable — it costs one deque append
    per event and performs no I/O until a dump."""
    global _FLIGHT
    _FLIGHT = recorder
    return recorder


def uninstall_flight() -> Optional[FlightRecorder]:
    global _FLIGHT
    fr, _FLIGHT = _FLIGHT, None
    return fr


def get_flight() -> Optional[FlightRecorder]:
    return _FLIGHT


def flight_job() -> Optional[str]:
    """The calling thread's flight-recorder job context (None without
    a recorder or outside any context) — capture this before spawning
    a worker thread, then re-enter it there with
    :func:`flight_job_context`."""
    f = _FLIGHT
    return f.current_job() if f is not None else None


def flight_job_context(job_id: Optional[str]):
    """Enter ``job_id`` as the calling thread's flight context (no-op
    context manager when tracing-by-ring is off or job_id is None)."""
    from contextlib import nullcontext

    f = _FLIGHT
    if f is None or job_id is None:
        return nullcontext()
    return f.job_context(job_id)


def install(tracer: Tracer) -> Tracer:
    """Make ``tracer`` the process-wide active tracer."""
    global _TRACER
    _TRACER = tracer
    return tracer


def uninstall() -> Optional[Tracer]:
    """Deactivate (and return) the active tracer without closing it."""
    global _TRACER
    t, _TRACER = _TRACER, None
    return t


def get_tracer() -> Optional[Tracer]:
    return _TRACER


def enabled() -> bool:
    return _TRACER is not None


def span(name: str, **attrs):
    """Context-manager span under the active tracer, and on the
    profiler's trace while a session runs (shared no-op when neither)."""
    t = _TRACER
    return profiled(t.span(name, **attrs) if t is not None else NULL_SPAN,
                    name)


def begin(name: str, **attrs):
    """Explicitly-started span (``.end()`` when done) — the
    no-reindent form for instrumenting existing phase blocks."""
    return span(name, **attrs).start()


def begin_detached(name: str, parent=None, remote_parent=None, **attrs):
    """Explicitly-started DETACHED span: parented to the given span id
    (or a root when None) instead of the calling thread's span stack,
    and never pushed onto that stack. The form for intervals that
    interleave rather than nest — e.g. per-job spans on the server
    scheduler thread. ``parent`` accepts a Span too (its id is used).
    ``remote_parent`` is a propagated cross-process trace context
    ``{"trace": ..., "span": ...}`` (see Tracer.begin_detached)."""
    t = _TRACER
    if t is None:
        return profiled(NULL_SPAN, name).start()
    if isinstance(parent, (Span, NullSpan, ProfiledSpan)):
        parent = getattr(parent, "id", None)
    return profiled(t.detached(name, parent=parent,
                               remote_parent=remote_parent, **attrs),
                    name).start()


def current_span_id():
    """The calling thread's innermost open span id under the active
    tracer (None when untraced or at root) — the remote-parent half of
    an outgoing wire trace context (ISSUE 18)."""
    t = _TRACER
    return t.current_span_id() if t is not None else None


def absorb(stats: dict) -> None:
    """One-shot overwrite-merge of a stats dict into the registry (see
    CounterRegistry.absorb). For the per-chunk absorption of a RUN's
    cumulative stats dict use :func:`stats_accumulator` — re-absorbing
    fresh dicts from several runs through THIS function would overwrite
    totals instead of summing them."""
    t = _TRACER
    if t is not None:
        t.counters.absorb(stats)


def stats_accumulator():
    """A per-run :class:`StatsAccumulator` bound to the active tracer's
    registry (shared no-op when tracing is off). Create one per
    cumulative stats dict, at the start of the run that owns it."""
    t = _TRACER
    return StatsAccumulator(t.counters) if t is not None else NULL_STATS


def inc(name: str, v=1) -> None:
    t = _TRACER
    if t is not None:
        t.counters.inc(name, v)


def gauge(name: str, v) -> None:
    t = _TRACER
    if t is not None:
        t.counters.gauge(name, v)


def progress(**fields) -> None:
    """Update the heartbeat's progress fields (racy scalar writes)."""
    t = _TRACER
    if t is not None:
        t.progress.update(fields)


def chunk_progress(idx: int, chunk_edges: int, edges_total=None) -> None:
    """The streamed-chunk loops' one-line progress update: chunks done
    plus the approximate edges_done they imply (capped at the stream
    total when one is cheaply known)."""
    t = _TRACER
    if t is None:
        return
    done = idx * chunk_edges
    t.progress.update(chunks_done=idx,
                      edges_done=min(done, edges_total)
                      if edges_total else done)


def event(name: str, **fields) -> None:
    """Emit a free-form event through the active tracer (no-op off)
    AND into the installed flight recorder's bounded rings (no-op
    without one) — the one call site both sinks share."""
    t = _TRACER
    if t is not None:
        t.emit(name, **fields)
    f = _FLIGHT
    if f is not None:
        f.record(name, fields)


@contextmanager
def tracing(dest: Union[str, IO], heartbeat_secs: Optional[float] = None):
    """Scoped tracing for tests/tools: install a fresh Tracer on
    ``dest`` (path or writable handle), optionally with a heartbeat,
    restore the previous tracer and close on exit."""
    global _TRACER
    prev = _TRACER
    t = Tracer(dest)
    _TRACER = t
    hb = Heartbeat(t, heartbeat_secs).start() if heartbeat_secs else None
    try:
        yield t
    finally:
        if hb is not None:
            hb.stop()
        _TRACER = prev
        t.close()
