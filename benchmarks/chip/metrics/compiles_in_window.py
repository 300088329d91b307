"""compiles_in_window: XLA backend compiles, and programs loaded from the
persistent compilation cache, that JAX reported between the window's
opening and its close. Every shape is warmed in set-up, so a sound run
reads 0."""


def read(record):
    return float(record["compiles"])
