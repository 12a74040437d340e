"""Share of the traced window in which no op ran on the chip: 1 - (union of
the device ops' intervals) / window, averaged over the chips.  Device
trace."""


def read(run):
    return 100.0 * run.reduced.idle_share()
