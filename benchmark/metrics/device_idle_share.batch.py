"""Share of the traced window in which no op ran on the chip, in %."""


def read(layer):
    tr = layer.get("trace")
    return None if not tr else 100.0 * tr["idle_share"]
