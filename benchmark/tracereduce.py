"""From a ``jax.profiler`` trace to the numbers the benchmark reports.

Two steps, so that the second can be checked on a small recorded trace:

``extract(path)``
    reads the ``.xplane.pb`` and keeps, per device plane
    (``/device:TPU:<i>``), the events of its "XLA Ops" and "XLA
    Modules" lines, and from the host planes the spans whose names the
    benchmark itself annotates (``window``, ``partition``)
    and the Python frames of the thread that ran most in the window
    (the profiler's Python tracer). Times are nanoseconds on the
    profiler's one clock.

``reduce(events)``
    within the ``window`` span: busy time per chip (the union of the
    intervals in which an op ran; modules where a plane has no op line)
    and the device time of each program (module), both averaged over the
    chips; and the idle gaps between busy intervals, summed by name: the
    benchmark span the gap falls in and the innermost Python frame
    running at its middle (else the program that ran just before it).
"""

from __future__ import annotations

import glob
import os
import re

ANNOTATIONS = ("window", "partition")
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def program_name(name: str) -> str:
    """``jit_fold_segment_pos(12)`` -> ``jit_fold_segment_pos``."""
    return _SUFFIX.sub("", name)


def extract(path: str, annotations=ANNOTATIONS) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, spans, threads = {}, [], []
    for plane in data.planes:
        if _DEVICE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    lines["modules"] = [[ev.name, ev.start_ns,
                                         ev.duration_ns]
                                        for ev in line.events]
                elif line.name == "XLA Ops":  # names are HLO text: unused
                    lines["ops"] = [["", ev.start_ns, ev.duration_ns]
                                    for ev in line.events]
            device[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                frames = []
                for ev in line.events:
                    if ev.name in annotations:
                        spans.append([ev.name, ev.start_ns, ev.duration_ns])
                    elif ev.name.startswith("$"):
                        frames.append([ev.name, ev.start_ns,
                                       ev.duration_ns])
                threads.append(frames)
    win = [(s, s + d) for n, s, d in spans if n == "window"]
    frames = []
    if win:  # the Python thread that ran most inside the window
        w0, w1 = win[0]
        frames = max(threads, default=[], key=lambda fr: sum(
            1 for _, s, d in fr if s < w1 and s + d > w0))
    return {"device": device, "host": spans, "frames": frames}


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, w0, w1):
    for name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            yield name, a, b


def _innermost(frames, times):
    """For each of the sorted ``times``, the deepest Python frame running
    then (None where none is). Frames of one thread nest, so one sweep
    with a stack of open frames answers them all."""
    out, stack, i = [], [], 0
    for t in times:
        while i < len(frames) and frames[i][1] <= t:
            name, s, d = frames[i]
            while stack and stack[-1][1] <= s:
                stack.pop()
            stack.append((name, s + d))
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        out.append(stack[-1][0] if stack else None)
    return out


def reduce(events: dict, top: int = 10) -> dict:
    """See the module docstring. Returns ``busy_s``, ``window_s``,
    ``idle_share`` (0..1), ``programs`` ({name: device s}), and the
    ``device_ops`` and ``idle_gaps`` lists of a ``breakdown``; None
    where the trace has no device plane."""
    wins = [(s, s + d) for name, s, d in events["host"] if name == "window"]
    if len(wins) != 1:
        raise ValueError(f"expected one 'window' span, found {len(wins)}")
    w0, w1 = wins[0]
    spans = sorted(((s, s + d, name) for name, s, d in events["host"]
                    if name != "window"), key=lambda t: t[0])
    chips = [p for p in events["device"].values()
             if p.get("ops") or p.get("modules")]
    if not chips:  # a CPU run: its ops are not a device's
        return None
    frames = sorted(events.get("frames", []), key=lambda f: (f[1], -f[2]))
    busy_total = 0.0
    programs: dict = {}
    gaps = []  # (start, end, program before)
    for plane in chips:
        mods = list(_clip(plane.get("modules", []), w0, w1))
        for name, a, b in mods:
            prog = program_name(name)
            programs[prog] = programs.get(prog, 0.0) + (b - a) / 1e9
        busy = _union([a, b] for _, a, b in
                      _clip(plane.get("ops") or plane.get("modules"), w0,
                            w1))
        busy_total += sum(b - a for a, b in busy) / 1e9
        ends = sorted((b, program_name(n)) for n, a, b in mods)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        j = 0
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            while j < len(ends) and ends[j][0] <= g0:
                j += 1
            gaps.append((g0, g1, ends[j - 1][1] if j else "window start"))
    gaps.sort()
    doing = _innermost(frames, [(g0 + g1) / 2 for g0, g1, _ in gaps])
    named: dict = {}
    for (g0, g1, before), frame in zip(gaps, doing):
        mid = (g0 + g1) / 2
        inside = [name for s, e, name in spans if s <= mid < e]
        key = (f"{inside[-1] if inside else 'window'}: "
               f"{frame or 'after ' + before}")
        named[key] = named.get(key, 0.0) + (g1 - g0) / 1e9
    window_s = (w1 - w0) / 1e9
    busy_s = busy_total / len(chips)
    programs = {n: s / len(chips) for n, s in programs.items()}
    return {
        "busy_s": busy_s, "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "programs": programs,
        "device_ops": sorted(([n, s] for n, s in programs.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": sorted(([n, s / len(chips)] for n, s in named.items()),
                            key=lambda x: -x[1])[:top],
    }
