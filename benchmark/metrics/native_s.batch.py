"""Median host wall per partition inside the native core's calls
(``diagnostics["t_native_s"]``: the host tail's forest pass and the
split, each timed where it calls the C library)."""

import statistics


def read(layer):
    vals = [p["diagnostics"]["t_native_s"]
            for p in layer.get("partitions", [])
            if "t_native_s" in p["diagnostics"]]
    return statistics.median(vals) if vals else None
