"""Helpers the verb files share: the program's review records, the model
parameters a configuration states, and the record of one request."""

from __future__ import annotations

import time

import numpy as np

from vbench import work
from vbench.harness import Request


def reviews(group) -> list:
    """A generated group as the program's review records."""
    from repro.core.rlda import Review

    ends = np.cumsum(group.doc_len)
    starts = ends - group.doc_len
    return [Review(tokens=group.tokens[s:e], rating=float(group.rating[d]),
                   user=int(group.user[d]), helpful=int(group.helpful[d]),
                   unhelpful=int(group.unhelpful[d]),
                   writing_quality=float(group.writing_quality[d]))
            for d, (s, e) in enumerate(zip(starts, ends))]


def model_args(config: dict) -> dict:
    m = config["model"]
    return dict(num_topics=int(m["num_topics"]),
                base_vocab=int(config["corpus"]["base_vocab"]),
                alpha=float(m["alpha"]), beta=float(m["beta"]),
                w_bits=m["w_bits"])


def sweep_work(run, group_ids, sweeps: int):
    """(real tokens x sweeps, modelled work) of refitting these groups.

    Word rows touched are counted as the distinct base words: each occurs
    under at least one rating tier, so this is a lower bound on the
    augmented rows a sweep must read and write, and the modelled least
    time never overstates the work."""
    k = int(run.cell.config["model"]["num_topics"])
    total, w = 0.0, work.ZERO
    for g in group_ids:
        grp = run.groups[g]
        words_used = run.notes.setdefault("words_used", {}).get(g)
        if words_used is None:
            words_used = run.notes["words_used"][g] = len(
                np.unique(grp.tokens))
        total += grp.num_tokens * sweeps
        w = w + work.sweep_work(grp.num_tokens, grp.num_docs, words_used,
                                k).scale(sweeps)
    return total, w


def timed(verb: str, fn, token_sweeps=0.0, wk=None) -> Request:
    import jax

    r = Request(verb=verb, sent=time.perf_counter(),
                token_sweeps=token_sweeps, work=wk)
    try:
        with jax.profiler.TraceAnnotation("vbench." + verb):
            fn()
        r.ok = True
    except Exception as e:  # a failed request is recorded, not raised
        r.error = f"{type(e).__name__}: {e}"
    r.done = time.perf_counter()
    return r
