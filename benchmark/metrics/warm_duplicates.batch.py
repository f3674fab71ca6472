"""Median per partition of the duplicate constraints the fixpoint's warm
segments set to sentinel, summed over the partition's chunks
(``diagnostics["warm_duplicates"]``)."""

import statistics


def read(layer):
    vals = [p["diagnostics"]["warm_duplicates"]
            for p in layer.get("partitions", [])
            if "warm_duplicates" in p["diagnostics"]]
    return statistics.median(vals) if vals else None
