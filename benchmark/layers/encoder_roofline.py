"""The fused ingest program's (encoder forward + scatter) share of its
roofline, percent: operations and bytes of the shapes the packer dispatched
in the traced window over the program's device time there."""

from benchmark.lib.readers import encoder_roofline


def read(run):
    return encoder_roofline(run, ("fused_ingest", "encoder"))
