"""Share of the traced window in which no operation ran on the device:
1 - (union of the ``XLA Ops`` intervals) / window, averaged over the
chips.  Layer: device."""


def read(run):
    return 100.0 * run.trace.idle_share()
