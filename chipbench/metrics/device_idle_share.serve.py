"""Share of the traced window in which no op ran on the device (1 minus
the union of busy intervals over the window), in percent."""


def read(v):
    return 100.0 * v.trace.idle_share()
