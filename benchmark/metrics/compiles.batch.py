"""Backend compiles the window's partitions counted themselves
(``diagnostics["compiles"]``, summed): the program's count of what
``window_compiles.batch`` counts from outside."""


def read(layer):
    vals = [p["diagnostics"]["compiles"]
            for p in layer.get("partitions", [])
            if "compiles" in p["diagnostics"]]
    return sum(vals) if vals else None
