"""The bridge's ``exec_ms`` per resolved leg over the window: host-clocked
leg time, never device time."""

from benchmark.lib.readers import bridge_leg_host_ms_mean


def read(run):
    return bridge_leg_host_ms_mean(run)
