"""setup_s: process start to the opening of the measured window (host
clock): weights made on the device, table loaded, programs compiled or
loaded from the cache, warm-up traffic."""


def read(run):
    return run.setup_s
