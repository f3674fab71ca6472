"""Median fixpoint rounds per partition, the program's own count
(``diagnostics["fixpoint_rounds"]``)."""

import statistics


def read(layer):
    vals = [p["diagnostics"]["fixpoint_rounds"]
            for p in layer.get("partitions", [])
            if "fixpoint_rounds" in p["diagnostics"]]
    return statistics.median(vals) if vals else None
