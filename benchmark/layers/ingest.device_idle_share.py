"""Device idle share of the traced window, percent, on the chip that idles
most: how far the host holds the chip back."""

from benchmark.lib.readers import device_idle_share


def read(run):
    return device_idle_share(run)
