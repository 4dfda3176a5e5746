"""Request: refit every served model in one `refine_batch` request (the
coalesced refit of a whole catalog)."""

from __future__ import annotations

from vbench.verbs._common import sweep_work, timed


def request(run, spec: dict, i: int):
    sweeps = int(spec["sweeps"])
    token_sweeps, wk = sweep_work(run, range(len(run.groups)), sweeps)
    r = timed("refine_batch", lambda: run.client.refine_batch(
        run.handles, sweeps, backend=spec["backend"],
        seed=run.derive(2, i + 1000)), token_sweeps, wk)
    if r.ok:
        run.capture()
    return r
