"""Backend compiles inside the window (JAX's compile events, persistent
cache loads included); set-up should have compiled everything."""


def read(layer):
    return layer.get("window_compiles")
