"""Runtime sanitizer mode (``SHEEP_SANITIZE=1``) — the executable twin
of the sheeplint static rules.

Three checks, all free when the env var is unset:

- **stray-sync traps**: :func:`guard` arms, for the current thread, a
  region in which any *implicit* device->host conversion of a
  jax.Array (``int()``/``float()``/``bool()``/``__index__``/
  ``.item()``/``.tolist()``) raises :class:`SanitizeError` unless it
  happens inside a :func:`sync_ok` window — the runtime form of the
  ``# sheeplint: sync-ok`` pragma. The backends arm it around the
  fold/dispatch paths, so the invariant "stats words stay unread
  futures except at the annotated one-behind pulls" is enforced, not
  hoped for. Mechanics: the ArrayImpl conversion dunders are wrapped
  once (first armed guard), with a thread-local armed/sync depth pair;
  on real accelerators ``jax.transfer_guard_device_to_host`` is
  layered on top (it catches paths the dunder wrap cannot, e.g.
  ``__array__``), while on cpu-jax the guard never fires — device
  memory IS host memory, there is no transfer — which is exactly why
  the dunder traps exist: they make the sanitizer testable in CI.
  ``np.asarray`` is deliberately NOT trapped: it is the explicit pull
  form (JAX's own transfer guard classifies it as an explicit
  transfer), and the static sync rule already requires it to sit on a
  pragma-annotated line.
- **donation poisoning**: :func:`check_donated` asserts buffers passed
  at donated positions really were invalidated (``is_deleted``), so a
  platform silently ignoring donation — doubling HBM and keeping
  stale-read bugs latent — fails loudly; reading a poisoned buffer
  afterwards raises in jax itself.
- **span balance**: the tracer counts open spans; under sanitize mode
  ``Tracer.close()`` raises when any span was begun but never ended
  (obs/tracer.py), turning a leaked span from a forensic curiosity
  into a test failure.
"""

from __future__ import annotations

import os
import threading
from contextlib import contextmanager

_TLS = threading.local()
_PATCH_LOCK = threading.Lock()
_PATCHED = False

#: conversion dunders that implicitly sync (method name -> human name)
_TRAP_METHODS = ("__bool__", "__int__", "__float__", "__index__",
                 "__complex__", "item", "tolist")


class SanitizeError(RuntimeError):
    """An armed sanitizer invariant was violated."""


def enabled() -> bool:
    return os.environ.get("SHEEP_SANITIZE", "") not in ("", "0")


def _depth(attr: str) -> int:
    return getattr(_TLS, attr, 0)


def in_sync_window() -> bool:
    return _depth("sync") > 0


def _trap(orig, name):
    def wrapper(self, *a, **kw):
        if _depth("armed") > 0 and _depth("sync") == 0:
            raise SanitizeError(
                f"implicit device->host sync via {name} inside a "
                f"sanitized fold/dispatch region; read device values "
                f"only at annotated sync points (wrap the pull in "
                f"sanitize.sync_ok() and mark the line "
                f"'# sheeplint: sync-ok')")
        return orig(self, *a, **kw)
    wrapper.__name__ = name
    wrapper._sheep_sanitize_orig = orig
    return wrapper


def _install_traps() -> None:
    """Wrap the ArrayImpl conversion dunders once per process. The
    wrappers are inert (two thread-local reads) outside armed regions,
    so installation is a one-way, low-cost switch."""
    global _PATCHED
    with _PATCH_LOCK:
        if _PATCHED:
            return
        from jax._src import array as _jarray

        cls = _jarray.ArrayImpl
        for name in _TRAP_METHODS:
            orig = getattr(cls, name, None)
            if orig is None or hasattr(orig, "_sheep_sanitize_orig"):
                continue
            try:
                setattr(cls, name, _trap(orig, name))
            except (AttributeError, TypeError):
                # an unpatchable method (C-level slot): the transfer
                # guard still covers it on real accelerators
                continue
        _PATCHED = True


def _transfer_guard(level: str):
    """``jax.transfer_guard_device_to_host(level)`` when available."""
    try:
        import jax

        return jax.transfer_guard_device_to_host(level)
    except Exception:
        from contextlib import nullcontext

        return nullcontext()


@contextmanager
def guard(region: str = "dispatch"):
    """Arm the stray-sync sanitizer for the calling thread while the
    ``with`` body runs. No-op (one env read) when sanitize mode is
    off; nests freely; other threads (prefetch workers, host-tail
    executors, heartbeat) are unaffected."""
    if not enabled():
        yield
        return
    _install_traps()
    _TLS.armed = _depth("armed") + 1
    try:
        with _transfer_guard("disallow"):
            yield
    finally:
        _TLS.armed = _depth("armed") - 1


@contextmanager
def sync_ok(label: str = ""):
    """An annotated sync point: implicit conversions are allowed for
    the calling thread while the body runs (the runtime twin of the
    ``# sheeplint: sync-ok`` pragma)."""
    if not enabled():
        yield
        return
    _TLS.sync = _depth("sync") + 1
    try:
        with _transfer_guard("allow"):
            yield
    finally:
        _TLS.sync = _depth("sync") - 1


def check_donated(*arrays, origin: str = "donated call") -> None:
    """Assert every array really was invalidated by a donating call.

    jax deletes donated inputs at the API layer on every backend, so a
    live (non-deleted) buffer here means the donation contract was
    dropped somewhere — the caller would silently double HBM and could
    read stale data without the use-after-donate error that makes the
    bug findable. No-op when sanitize mode is off or for non-jax
    values (numpy inputs are never donated)."""
    if not enabled():
        return
    for i, a in enumerate(arrays):
        deleted = getattr(a, "is_deleted", None)
        if deleted is not None and not deleted():
            raise SanitizeError(
                f"buffer {i} passed to {origin} at a donated position "
                f"was not invalidated — donation silently ignored "
                f"(double HBM) or a non-donating twin was called on "
                f"the donating path")
