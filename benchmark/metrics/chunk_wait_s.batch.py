"""Median wall per partition in which the passes waited for their next
input chunk (``diagnostics["t_chunk_wait_s"]``: the H2D ring's blocking
fills, the first of each pass included)."""

import statistics


def read(layer):
    vals = [p["diagnostics"]["t_chunk_wait_s"]
            for p in layer.get("partitions", [])
            if "t_chunk_wait_s" in p["diagnostics"]]
    return statistics.median(vals) if vals else None
