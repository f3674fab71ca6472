"""Fault classification + bounded retry policy (ISSUE 9 tentpole).

PR 8 made every path *resumable after* a process death; this module is
what keeps the process alive *through* a fault. Every recoverable error
the drivers see is classified into one of four fault classes, and a
:class:`RetryPolicy` decides — per class, with bounded attempts and
exponential backoff + jitter — whether the driver may retry:

    TRANSIENT    flaky I/O, link blips, UNAVAILABLE/DEADLINE_EXCEEDED
                 RPC-layer errors: retry in place, nothing to change.
    RESOURCE     RESOURCE_EXHAUSTED / OOM-class allocation failures:
                 retry only after the caller degrades its memory
                 footprint (the dispatch drivers halve dispatch_batch /
                 inflight via utils/membudget.degraded_dispatch and
                 drop the chunk cache before re-entering).
    DEVICE_LOSS  the accelerator (or its worker) went away: the caller
                 snapshots, reinitializes what it can in-process
                 (:func:`reinit_devices`), and resumes from the last
                 confirmed state.
    FATAL        everything else — bugs, bad input, the legacy
                 SHEEP_FAULT_INJECT kill injections. Never retried.

Classification is string-pattern based on top of exception types because
that is what the JAX/PJRT stack gives us: device errors surface as
``jaxlib.xla_extension.XlaRuntimeError`` whose *message* carries the
gRPC-style status (``RESOURCE_EXHAUSTED: ...``). Injected faults
(utils/fault.py) carry an explicit ``fault_class`` attribute so chaos
runs exercise exactly the production paths.

Knobs (environment, read once per policy construction):

    SHEEP_RETRY_MAX      attempts per fault class (default 3; 0 disables
                         in-process retry entirely — faults propagate,
                         the PR-8 kill+resume contract still applies)
    SHEEP_RETRY_BASE_S   first backoff delay in seconds (default 0.05)
"""

from __future__ import annotations

import os
import random
import time
from typing import Optional

TRANSIENT = "transient"
RESOURCE = "resource"
DEVICE_LOSS = "device_loss"
FATAL = "fatal"

# matched case-insensitively against "TypeName: message"
_RESOURCE_PATTERNS = (
    "resource_exhausted",
    "out of memory",
    "allocation failure",
    "failed to allocate",
    "oom",
)
_DEVICE_LOSS_PATTERNS = (
    "device_lost",
    "device lost",
    "device or resource busy",
    "failed_precondition: device",
    "tpu worker",
    "device is in an invalid state",
    "internal: failed to connect",
)
_TRANSIENT_PATTERNS = (
    "unavailable",
    "deadline_exceeded",
    "connection reset",
    "connection refused",
    "temporarily unavailable",
    "broken pipe",
    "try again",
)


def classify(exc: BaseException) -> str:
    """Fault class of an exception (see module docstring).

    Precedence: an explicit ``fault_class`` attribute (injected faults)
    wins; then exception types with unambiguous meaning; then message
    patterns, RESOURCE/DEVICE_LOSS before TRANSIENT so a message like
    "RESOURCE_EXHAUSTED while connection was open" degrades memory
    instead of spinning in-place retries.
    """
    cls = getattr(exc, "fault_class", None)
    if cls in (TRANSIENT, RESOURCE, DEVICE_LOSS, FATAL):
        return cls
    if isinstance(exc, MemoryError):
        return RESOURCE
    text = f"{type(exc).__name__}: {exc}".lower()
    for pat in _RESOURCE_PATTERNS:
        if pat in text:
            return RESOURCE
    for pat in _DEVICE_LOSS_PATTERNS:
        if pat in text:
            return DEVICE_LOSS
    if isinstance(exc, (OSError, IOError, TimeoutError)):
        # I/O errors without a more specific verdict above are worth one
        # bounded retry round (torn NFS reads, EINTR, transient EIO)
        return TRANSIENT
    for pat in _TRANSIENT_PATTERNS:
        if pat in text:
            return TRANSIENT
    return FATAL


class RetryPolicy:
    """Bounded per-fault-class retry budget with exponential backoff.

    One instance covers one logical operation (a build phase, a chunk
    stream): attempts are counted PER CLASS, so a run that survives two
    OOM degrades can still survive a later transient read blip. The
    jitter is seeded (``seed``) so chaos-soak replays are deterministic;
    production constructions leave it None (entropy-seeded).
    """

    def __init__(self, max_retries: Optional[int] = None,
                 base_delay_s: Optional[float] = None,
                 max_delay_s: float = 5.0, jitter: float = 0.5,
                 seed: Optional[int] = None):
        if max_retries is None:
            max_retries = int(os.environ.get("SHEEP_RETRY_MAX", "3"))
        if base_delay_s is None:
            base_delay_s = float(os.environ.get("SHEEP_RETRY_BASE_S",
                                                "0.05"))
        if max_retries < 0:
            raise ValueError("max_retries must be >= 0")
        self.max_retries = int(max_retries)
        self.base_delay_s = float(base_delay_s)
        self.max_delay_s = float(max_delay_s)
        self.jitter = float(jitter)
        self._rng = random.Random(seed)
        self.attempts = {TRANSIENT: 0, RESOURCE: 0, DEVICE_LOSS: 0}

    def admit(self, fault_class: str) -> bool:
        """True iff the policy has retry budget left for this class."""
        if fault_class not in self.attempts:
            return False  # FATAL (or unknown): never retried
        return self.attempts[fault_class] < self.max_retries

    def delay_s(self, attempt: int) -> float:
        """Backoff for the given 0-based attempt: base * 2^attempt,
        capped, with +/- ``jitter`` fraction randomized so a fleet of
        retrying workers doesn't stampede the same resource in sync."""
        d = min(self.base_delay_s * (2 ** max(0, attempt)),
                self.max_delay_s)
        if self.jitter:
            d *= 1.0 + self.jitter * (2.0 * self._rng.random() - 1.0)
        return max(0.0, d)

    def record(self, fault_class: str, exc: BaseException,
               where: str = "") -> float:
        """Account one admitted fault: bump the class counter, emit the
        ``retry`` trace event (no-op untraced) and a stderr note, and
        return the backoff delay the caller should sleep. Call only
        after :meth:`admit` said yes."""
        import sys

        attempt = self.attempts[fault_class]
        self.attempts[fault_class] = attempt + 1
        d = self.delay_s(attempt)
        from sheep_tpu import obs

        obs.event("retry", fault_class=fault_class, where=where,
                  attempt=attempt + 1, max_retries=self.max_retries,
                  delay_s=round(d, 3),
                  error=f"{type(exc).__name__}: {str(exc)[:200]}")
        print(f"sheep retry: {fault_class} fault in {where or 'run'} "
              f"(attempt {attempt + 1}/{self.max_retries}, "
              f"backoff {d:.2f}s): {type(exc).__name__}: "
              f"{str(exc)[:200]}", file=sys.stderr)
        return d

    def backoff(self, fault_class: str, exc: BaseException,
                where: str = "") -> None:
        """record + sleep in one call (the common retry-loop epilogue)."""
        time.sleep(self.record(fault_class, exc, where=where))

    def run(self, fn, where: str = "", on_retry=None):
        """Call ``fn()`` under this policy: admitted faults back off and
        re-call; ``on_retry(exc, fault_class, attempt)`` (if given) runs
        between the backoff and the re-call — the hook where callers
        degrade buffers / reinitialize devices. Exhausted budgets and
        FATAL faults re-raise the original exception."""
        while True:
            try:
                return fn()
            except Exception as exc:  # noqa: BLE001 — classified below
                cls = classify(exc)
                if not self.admit(cls):
                    raise
                self.backoff(cls, exc, where=where)
                if on_retry is not None:
                    on_retry(exc, cls, self.attempts[cls])


def handle_build_fault(policy: RetryPolicy, exc: BaseException,
                       where: str, stats: dict,
                       on_resource=None, on_device_loss=None) -> str:
    """The ONE fault epilogue of the drivers' build retry loops
    (tpu_backend / sharded pipeline): classify, check the per-class
    budget (re-raising FATAL and exhausted classes), count the retry
    in ``stats["dispatch_retries"]`` (the bench-gated trail), run the
    class-specific recovery hook, then back off. Returns the fault
    class when the caller should retry; never returns otherwise.

    The hooks carry the genuinely driver-specific halves —
    ``on_resource`` (degrade knobs, drop caches) and ``on_device_loss``
    (persist the driver's snapshot shape) — so the protocol itself
    (ordering, counters, events, budgets) lives in exactly one place."""
    cls = classify(exc)
    if not policy.admit(cls):
        raise exc
    stats["dispatch_retries"] = stats.get("dispatch_retries", 0) + 1
    if cls == RESOURCE and on_resource is not None:
        on_resource()
    elif cls == DEVICE_LOSS and on_device_loss is not None:
        on_device_loss()
    policy.backoff(cls, exc, where=where)
    return cls


def degrade_dispatch(n: int, chunk_edges: int, batch: int, inflight: int,
                     donate: bool, stats: dict, resume_chunk: int,
                     h2d_ring=None, residency=None):
    """Shared RESOURCE recovery step: pick the membudget-modeled
    halving of (dispatch_batch, inflight) — plus the staged H2D ring
    depth when the caller runs one (``h2d_ring`` an int, ISSUE 12) —
    record the degraded-knob counters + the ``dispatch_degraded`` trace
    event. Returns the new pair (or triple, mirroring
    ``membudget.degraded_dispatch``), or None when nothing is left to
    shed (the caller then plain-retries and ultimately falls back to
    the kill+resume contract).

    With a :class:`~sheep_tpu.utils.residency.ResidencyManager`
    (``residency``, ISSUE 20) the ladder spills BEFORE it shrinks:
    resident chunks are reclaimable HBM (their bits live on disk), so
    the first RESOURCE fault drops them — and halves the residency
    budget so refill pressure shrinks too — returning the dispatch
    knobs *unchanged*. Only a fault with nothing left to spill reaches
    the halving rungs below, which never land on a batch width the
    platform refuses (``tpu_backend.refused_dispatch_batch``)."""
    from sheep_tpu import obs
    from sheep_tpu.backends.tpu_backend import refused_dispatch_batch
    from sheep_tpu.utils import membudget

    spillable = residency.spillable_bytes() if residency is not None \
        else 0
    nxt = membudget.degraded_dispatch(
        n, chunk_edges, batch, inflight, donate, h2d_ring=h2d_ring,
        spillable_bytes=spillable, refused_batch=refused_dispatch_batch())
    if nxt is not None and nxt[0] == "spill":
        freed = residency.pressure_spill()
        stats["spill_degrades"] = stats.get("spill_degrades", 0) + 1
        obs.event("dispatch_spilled", resume_chunk=int(resume_chunk),
                  freed_bytes=int(freed),
                  residency_budget=int(residency.budget))
        return nxt[1:]
    if nxt is not None:
        stats["degraded_dispatch_batch"] = nxt[0]
        stats["degraded_inflight"] = nxt[1]
        event = {"dispatch_batch": nxt[0], "inflight": nxt[1]}
        if len(nxt) > 2:
            stats["degraded_h2d_ring"] = nxt[2]
            event["h2d_ring"] = nxt[2]
        obs.event("dispatch_degraded", resume_chunk=int(resume_chunk),
                  **event)
    return nxt


def recover_device_loss(stats: dict, resume_chunk: int,
                        save_snapshot=None) -> bool:
    """Shared DEVICE_LOSS recovery step: persist the driver's snapshot
    FIRST (``save_snapshot()`` — even if in-process recovery fails, the
    PR-8 kill+resume contract holds from here), then best-effort
    reinit, with the counter + ``device_reinit`` event trail."""
    from sheep_tpu import obs

    if save_snapshot is not None:
        save_snapshot()
    alive = reinit_devices()
    stats["device_loss_recoveries"] = \
        stats.get("device_loss_recoveries", 0) + 1
    obs.event("device_reinit", alive=bool(alive),
              resume_chunk=int(resume_chunk))
    return alive


def reinit_devices() -> bool:
    """Best-effort in-process device reinitialization after a
    DEVICE_LOSS-class fault: drop every compiled executable and live
    traced constant (they reference the dead client's buffers) so the
    retry re-stages everything fresh against whatever backend
    ``jax.devices()`` resolves next. Returns True when a device answered
    a trivial computation afterwards.

    This cannot resurrect a truly detached PJRT client in-process — for
    that the PR-8 kill+resume contract (checkpoint was saved before this
    call) remains the backstop — but it recovers the recoverable cases
    (worker restart behind the same client, preempted-then-restored
    chips, and every injected device loss in the chaos harness).
    """
    import jax

    try:
        jax.clear_caches()
    except Exception:
        pass
    try:
        import numpy as np

        dev = jax.local_devices()[0]
        probe = jax.device_put(np.int32(1), dev)
        return int(probe) == 1  # sheeplint: sync-ok
    except Exception:
        return False
