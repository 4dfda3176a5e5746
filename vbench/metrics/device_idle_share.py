"""1 - (union of device operation intervals) / traced window, in percent."""

from vbench import readers


def read(run):
    return readers.idle_share(run)
