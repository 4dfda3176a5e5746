"""Set-up: `prepare` each group on the server, then `fit_prepared` it as
one model by reference to the prepared corpus."""

from __future__ import annotations

from vbench.verbs._common import model_args, reviews


def setup(run, spec: dict, step: int) -> None:
    args = model_args(run.cell.config)
    for i, g in enumerate(run.groups):
        prep = run.client.prepare(
            reviews(g), base_vocab=args["base_vocab"],
            num_topics=args["num_topics"], alpha=args["alpha"],
            beta=args["beta"], w_bits=args["w_bits"])
        fit = run.client.fit_prepared(
            prep.corpus_id, backend=spec["backend"],
            num_sweeps=int(spec["sweeps"]), seed=run.derive(1, step, i))
        run.handles.append(fit.handle_id)
        run.backend = fit.backend
