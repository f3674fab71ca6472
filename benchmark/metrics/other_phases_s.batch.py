"""Median wall of every phase but the build (degrees, sort, split and
score) over the window's partitions, from ``phase_times``."""

import statistics

PHASES = ("degrees", "sort", "split", "score")


def read(layer):
    vals = [sum(p["phase_times"].get(k, 0.0) for k in PHASES)
            for p in layer.get("partitions", [])]
    return statistics.median(vals) if vals else None
