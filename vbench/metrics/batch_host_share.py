"""Self time of the batch engine's `batch.` spans (bucketing, stacking,
launch dispatch, unstacking) over the window, in percent.

Host work: the batch engine reads no device value (its programs are
dispatched asynchronously), and a wait nested in it is a `device.wait`
span, not counted here."""

from vbench import phases


def read(run):
    return phases.span_share(run, phases.LAYERS["batch"])
