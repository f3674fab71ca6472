"""The slowest single device-to-host copy in the window
(``diagnostics["t_transfer_max_s"]``, the most over its partitions)."""


def read(layer):
    vals = [p["diagnostics"]["t_transfer_max_s"]
            for p in layer.get("partitions", [])
            if "t_transfer_max_s" in p["diagnostics"]]
    return max(vals) if vals else None
