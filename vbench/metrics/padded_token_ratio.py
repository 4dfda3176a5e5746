"""(padded + real token slots) / real tokens of the batch engine's launches
in the window, from its counters."""

from vbench import readers


def read(run):
    real = readers.counter(run, "vedalia_batch_real_tokens_total")
    pad = readers.counter(run, "vedalia_batch_padded_tokens_total")
    if real <= 0:
        return None
    return (pad + real) / real
