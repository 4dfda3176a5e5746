"""Self time of the program's `service.` spans (routing, handles, the
host side of each per-model perplexity) over the window, in percent.

Host work only, as far as the program marks its waits: the host's reads of
device values on the path (each served perplexity, `DeviceTimer`'s sync)
are `device.wait` spans nested inside and are not counted here."""

from vbench import phases


def read(run):
    return phases.span_share(run, phases.LAYERS["service"])
