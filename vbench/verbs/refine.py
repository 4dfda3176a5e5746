"""Request: continue sampling each served model with `refine`, one request
per model (a corpus-scale model is one handle)."""

from __future__ import annotations

from vbench.verbs._common import sweep_work, timed


def request(run, spec: dict, i: int):
    sweeps = int(spec["sweeps"])
    token_sweeps, wk = sweep_work(run, range(len(run.groups)), sweeps)

    def call():
        for j, h in enumerate(run.handles):
            run.client.refine(h, sweeps, backend=spec.get("backend"),
                              seed=run.derive(2, i + 1000, j))

    r = timed("refine", call, token_sweeps, wk)
    if r.ok:
        run.capture()
    return r
