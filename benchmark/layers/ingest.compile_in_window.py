"""Programs compiled (or loaded from the cache) inside the window: 0 where
warm-up covered the mix's shapes."""

from benchmark.lib.readers import compiles_in_window


def read(run):
    return compiles_in_window(run)
