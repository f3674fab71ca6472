"""Median wall per partition of the device-to-host copies of its designed
pulls, once each value was ready (``diagnostics["t_transfer_s"]``)."""

import statistics


def read(layer):
    vals = [p["diagnostics"]["t_transfer_s"]
            for p in layer.get("partitions", [])
            if "t_transfer_s" in p["diagnostics"]]
    return statistics.median(vals) if vals else None
