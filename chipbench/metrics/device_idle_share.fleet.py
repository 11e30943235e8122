"""Share of the fleet window in which no operation ran on the device:
1 - (union of device operation intervals / window), in percent."""


def read(trace, facts, device):
    return 100.0 * trace.idle_share
