"""Set-up: fit every group of the corpus as one model, in one `fit_batch`
request (the server prepares each and batches compatible models)."""

from __future__ import annotations

from vbench.verbs._common import model_args, reviews


def setup(run, spec: dict, step: int) -> None:
    fits = run.client.fit_batch(
        [reviews(g) for g in run.groups], backend=spec["backend"],
        num_sweeps=int(spec["sweeps"]), seed=run.derive(1, step),
        **model_args(run.cell.config))
    run.handles = [f.handle_id for f in fits]
    run.backend = fits[0].backend
