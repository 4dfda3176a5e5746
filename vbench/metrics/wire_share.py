"""Self time of the program's `client.` and `server.` spans (request and
response encoding, parsing, dispatch) over the window, in percent.

Host work: the spans of the layers below, `device.wait` among them, are
nested inside and not counted here."""

from vbench import phases


def read(run):
    return phases.span_share(run, phases.LAYERS["wire"])
