"""Median per partition of the lifting levels the hoisted fold skipped
because their table was all sentinel, summed over the partition's full
segments (``diagnostics["lift_levels_skipped"]``)."""

import statistics


def read(layer):
    vals = [p["diagnostics"]["lift_levels_skipped"]
            for p in layer.get("partitions", [])
            if "lift_levels_skipped" in p["diagnostics"]]
    return statistics.median(vals) if vals else None
