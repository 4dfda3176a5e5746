"""Batched multi-model fit engine: one launch for M product models.

Vedalia's workload is a *zoo* of per-product RLDA models. PRs 1-3 made
every fit and refit a single-model launch, so a shard refitting 50
products paid 50 cold dispatches. This module is the batching layer in
between: it decides which prepared models may share a launch, stacks them,
drives the `batched` sampler backend (`repro.api.backends.BatchedSampler`
over `core.batch` / the model-grid Pallas kernel), and unstacks the
results back into ordinary per-model states.

A bucket costs a few jitted dispatches besides its launch
(`core.batch.stack_bucket`, `core.batch.unstack_states`): one program per
model pads all of its corpus and state leaves to the bucket's shape, one
program stacks them with the keys, and one program per model slices its
state out of the launch's result. The counts the slice returns are those
the launch's last sweep rebuilt; nothing is rebuilt again. Every compile
key names a model's own shape, the bucket's padded shape or the number of
models, never which models a bucket holds, so the arbitrary subsets that
`refine_many` and the stream scheduler coalesce reuse compiled programs.

Bucketing rules (a bucket = one launch):

  * hard compatibility — `core.batch.compat_key`: num_topics, vocab_size,
    alpha, beta, w_bits are compile-time constants of the sweep;
  * padded corpus length — token counts round up to a power-of-two
    multiple of `LENGTH_QUANTUM`, so "similar-sized" corpora share a
    bucket and the jit cache sees a bounded set of shapes;
  * padded document capacity — num_docs rounds up the same way
    (`DOC_QUANTUM`), bounding `(M, D, K)` doc-count tensor shapes;
  * `max_models` bounds a single launch (VMEM/memory ceiling).

Consumers:
  * `VedaliaService.fit_batch` / `refine_many` (the embedded engine),
  * the `fit_batch` / `refine_batch` protocol verbs,
  * `serving.TopicEngine.fit_many` (wave-scheduled client-side batching),
  * `stream.IncrementalScheduler`, which coalesces drift-triggered refits
    landing in the same scheduling window into one `refine_batch` call.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax

from repro.core import batch as batch_lib
from repro.core.types import Corpus, LDAConfig, LDAState
from repro.obs import metrics, trace

#: Padding waste is the honest cost of the power-of-two shape ladder:
#: every padded token slot runs the sweep like a real one. The pair of
#: counters gives the waste fraction without a separate ratio metric.
_BUCKET_MODELS = metrics.histogram(
    "vedalia_batch_bucket_models",
    "Models stacked into each batched launch.",
    labels=(), buckets=metrics.COUNT_BUCKETS)
_PADDED_TOKENS = metrics.counter(
    "vedalia_batch_padded_tokens_total",
    "Token slots spent on padding across batched launches.")
_REAL_TOKENS = metrics.counter(
    "vedalia_batch_real_tokens_total",
    "Real (unpadded) tokens swept by batched launches.")

#: Token-length padding quantum: corpus lengths round up to a power-of-two
#: multiple of this, which also keeps the fused kernel's token blocks full.
LENGTH_QUANTUM = 256

#: Document-capacity padding quantum.
DOC_QUANTUM = 16

#: Default ceiling on models per launch (VMEM / host-memory bound).
MAX_MODELS_PER_LAUNCH = 64


def _round_bucket(n: int, quantum: int) -> int:
    """Round up to quantum, 2*quantum, 4*quantum, ... (power-of-two ladder:
    a bounded family of shapes for the jit cache)."""
    q = max(1, -(-n // quantum))
    b = 1
    while b < q:
        b *= 2
    return b * quantum


def length_bucket(num_tokens: int) -> int:
    return _round_bucket(num_tokens, LENGTH_QUANTUM)


def doc_bucket(num_docs: int) -> int:
    return _round_bucket(num_docs, DOC_QUANTUM)


def bucket_key(cfg: LDAConfig, corpus: Corpus) -> tuple:
    """Models with equal keys stack into one launch."""
    return batch_lib.compat_key(cfg) + (
        length_bucket(corpus.num_tokens), doc_bucket(cfg.num_docs))


def plan_buckets(
    items: Sequence[tuple[LDAConfig, Corpus]],
    max_models: int = MAX_MODELS_PER_LAUNCH,
) -> list[list[int]]:
    """Group item indices into launch buckets (insertion-ordered, each at
    most `max_models` long)."""
    groups: dict[tuple, list[int]] = {}
    for i, (cfg, corpus) in enumerate(items):
        groups.setdefault(bucket_key(cfg, corpus), []).append(i)
    buckets = []
    for idxs in groups.values():
        for j in range(0, len(idxs), max_models):
            buckets.append(idxs[j:j + max_models])
    return buckets


@dataclasses.dataclass(frozen=True)
class BatchStats:
    """What a batched run actually did — surfaced by benches and logs."""

    num_models: int
    num_launches: int

    @property
    def amortization(self) -> float:
        """Models per launch (1.0 means nothing batched)."""
        return self.num_models / max(self.num_launches, 1)


def _run_bucket(
    sampler,
    idxs: Sequence[int],
    cfgs: Sequence[LDAConfig],
    corpora: Sequence[Corpus],
    keys: Sequence[jax.Array],
    num_sweeps: int,
    states: Optional[Sequence[LDAState]],
) -> list[LDAState]:
    b_cfgs = [cfgs[i] for i in idxs]
    b_corps = [corpora[i] for i in idxs]
    n_pad = length_bucket(max(c.num_tokens for c in b_corps))
    d_pad = doc_bucket(max(c.num_docs for c in b_cfgs))
    real_tokens = sum(c.num_tokens for c in b_corps)
    _BUCKET_MODELS.observe(len(idxs))
    _REAL_TOKENS.inc(real_tokens)
    _PADDED_TOKENS.inc(len(idxs) * n_pad - real_tokens)
    with trace.span("batch.stack"):
        bcfg = batch_lib.batch_cfg(b_cfgs, d_pad)
        stacked_c, stacked_s, stacked_k = batch_lib.stack_bucket(
            b_corps, [keys[i] for i in idxs], n_pad, d_pad,
            states=None if states is None else [states[i] for i in idxs])
    with trace.span("batch.launch"):
        out = sampler.run_many(
            bcfg, stacked_c, stacked_k, num_sweeps, states=stacked_s)
    with trace.span("batch.unstack"):
        return batch_lib.unstack_states(b_cfgs, b_corps, out)


def run_batched(
    sampler,
    cfgs: Sequence[LDAConfig],
    corpora: Sequence[Corpus],
    keys: Sequence[jax.Array],
    num_sweeps: int,
    states: Optional[Sequence[LDAState]] = None,
    max_models: int = MAX_MODELS_PER_LAUNCH,
) -> tuple[list[LDAState], BatchStats]:
    """Fit (cold, `states=None`) or refit (warm) M models in as few
    launches as bucketing allows; returns per-model states in input order.

    `sampler` is any object with the `BatchedSampler.run_many` surface.
    Each model consumes its own PRNG key, so results are comparable to M
    sequential runs from the same keys regardless of bucketing.
    """
    if not (len(cfgs) == len(corpora) == len(keys)):
        raise ValueError("cfgs, corpora and keys must align")
    if states is not None and len(states) != len(cfgs):
        raise ValueError("states must align with cfgs when given")
    with trace.span("batch.plan"):
        buckets = plan_buckets(list(zip(cfgs, corpora)),
                               max_models=max_models)
    out: list[Optional[LDAState]] = [None] * len(cfgs)
    for idxs in buckets:
        # vedalint: disable=prng-key-hygiene -- `keys` is the whole per-model
        # key list; buckets index disjoint subsets, so no key is consumed twice
        for i, st in zip(idxs, _run_bucket(
                sampler, idxs, cfgs, corpora, keys, num_sweeps, states)):
            out[i] = st
    return out, BatchStats(num_models=len(cfgs), num_launches=len(buckets))
