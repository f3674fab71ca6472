"""Median wall of the build phase (the fixpoint) over the window's
partitions: ``phase_times["build"]``, which ends at a host pull."""

import statistics


def read(layer):
    vals = [p["phase_times"]["build"] for p in layer.get("partitions", [])
            if "build" in p["phase_times"]]
    return statistics.median(vals) if vals else None
