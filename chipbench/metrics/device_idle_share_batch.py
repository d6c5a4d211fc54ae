"""device_idle_share.batch: per cent of the traced window in which
no operation ran on the device (1 - union of the trace's op intervals)."""
from chipbench.measures import idle_share


def read(run):
    return idle_share(run)
