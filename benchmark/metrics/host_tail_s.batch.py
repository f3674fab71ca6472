"""Median host wall of the fixpoint's native host tail per partition
(``diagnostics["t_host_tail_s"]``); nothing where no tail ran."""

import statistics


def read(layer):
    vals = [p["diagnostics"]["t_host_tail_s"]
            for p in layer.get("partitions", [])
            if "t_host_tail_s" in p["diagnostics"]]
    return statistics.median(vals) if vals else None
