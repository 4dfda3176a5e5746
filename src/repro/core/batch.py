"""Batched multi-model Gibbs sweeps: M product models in one launch.

The paper's closing claim — "rapidly compute a large number of specialized
latent variable models", one RLDA model per product — needs the fit path
itself to amortize across models, not just across tokens. This module is
the math layer of that batching: M *compatible* models (same num_topics,
vocab and hyperparameters; corpora padded to a shared token length, count
tensors padded to a shared document capacity) are stacked along a leading
model axis and swept together:

  * `run_many` / `fit_many` — the jnp oracle path: `jax.vmap` over the
    single-model `core.gibbs.sweep`, with all sweeps scanned under ONE jit
    so a batch of M fits costs one XLA dispatch total instead of M;
  * the fused path lives in `repro.kernels.lda_gibbs.ops.sweep_many`
    (model-grid Pallas kernel) and is selected by the `batched` registry
    backend (`repro.api.backends.BatchedSampler`);
  * `stack_bucket` / `unstack_states`, shared by both paths: a bucket is
    stacked by one jitted padding program per model and one jitted stack,
    and unstacked by one jitted slice per model of the counts the last
    sweep already rebuilt.

Stacked pytrees reuse `Corpus` and `LDAState` verbatim with a leading
(M,) axis on every leaf — `jax.vmap` and the kernel BlockSpecs both
understand that layout, and the codec semantics (stored units at the
boundary) are unchanged per model.

Bucketing policy (which models *may* stack) lives one layer up in
`repro.serving.batch_engine`; this module only checks compatibility.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import codec, gibbs
from repro.core.types import Corpus, LDAConfig, LDAState, init_state


def compat_key(cfg: LDAConfig) -> tuple:
    """Models with equal keys may share one batched launch: the sampler's
    compile-time constants (K, V, priors, fixed-point format)."""
    return (cfg.num_topics, cfg.vocab_size, cfg.alpha, cfg.beta, cfg.w_bits)


def batch_cfg(cfgs: Sequence[LDAConfig], num_docs: int) -> LDAConfig:
    """The shared config of a stack: compat-checked, with `num_docs` set to
    the padded per-model document capacity."""
    keys = {compat_key(c) for c in cfgs}
    if len(keys) != 1:
        raise ValueError(
            f"cannot stack incompatible models: {sorted(keys)}")
    if num_docs < max(c.num_docs for c in cfgs):
        raise ValueError(
            f"document capacity {num_docs} below largest model "
            f"({max(c.num_docs for c in cfgs)})")
    import dataclasses

    return dataclasses.replace(cfgs[0], num_docs=num_docs)


def pad_corpus(corpus: Corpus, num_tokens: int) -> Corpus:
    """Pad a corpus to `num_tokens` with weight-0 tokens (doc/word id 0 —
    valid ids whose zero weight keeps them out of every count)."""
    pad = num_tokens - corpus.num_tokens
    if pad < 0:
        raise ValueError(
            f"corpus has {corpus.num_tokens} tokens > pad target {num_tokens}")
    if pad == 0:
        return corpus
    return _pad_corpus(corpus, num_tokens)


def _pad_tokens(x: jax.Array, num_tokens: int) -> jax.Array:
    return jnp.pad(x, (0, num_tokens - x.shape[0]))


def _pad_corpus(corpus: Corpus, num_tokens: int) -> Corpus:
    return Corpus(*(_pad_tokens(x, num_tokens)
                    for x in (corpus.docs, corpus.words, corpus.weights)))


@partial(jax.jit, static_argnums=(2, 3))
def _pad_model(
    corpus: Corpus,
    state: Optional[LDAState],
    num_tokens: int,
    num_docs: int,
) -> tuple[Corpus, Optional[LDAState]]:
    """Every leaf of one model's corpus and warm state (or None) padded to
    the bucket's `(num_tokens, num_docs)` shape in one program.

    Corpus padding is weight-0 (doc/word id 0); z pads with topic 0 and
    n_dt with zero rows, so no padded slot enters any count.
    """
    corpus = _pad_corpus(corpus, num_tokens)
    if state is not None:
        state = LDAState(
            z=_pad_tokens(state.z, num_tokens),
            n_dt=jnp.pad(
                state.n_dt, ((0, num_docs - state.n_dt.shape[0]), (0, 0))),
            n_wt=state.n_wt,
            n_t=state.n_t,
        )
    return corpus, state


@jax.jit
def _stack(models, keys):
    """M padded models of one shape and their keys stacked along a leading
    model axis: one program per `(M, num_tokens, num_docs)`."""
    return (jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *models),
            jnp.stack(keys))


def stack_bucket(
    corpora: Sequence[Corpus],
    keys: Sequence[jax.Array],
    num_tokens: int,
    num_docs: int,
    states: Optional[Sequence[LDAState]] = None,
) -> tuple[Corpus, Optional[LDAState], jax.Array]:
    """Stack M models into one `(M, num_tokens)` batch with `num_docs`
    document rows: one padding program per model, one stacking program per
    bucket. Cold buckets (`states=None`) stack the corpora and keys alone.

    Compile keys name a model's own shape, the padded shape and M, never
    which models a bucket holds, so any subset of served models reuses the
    programs compiled for its shapes.
    """
    for c in corpora:
        if c.num_tokens > num_tokens:
            raise ValueError(
                f"corpus has {c.num_tokens} tokens > pad target {num_tokens}")
    if states is None:
        states = [None] * len(corpora)
    padded = [_pad_model(c, s, num_tokens, num_docs)
              for c, s in zip(corpora, states)]
    (corpus, state), stacked_keys = _stack(padded, list(keys))
    return corpus, state, stacked_keys


@partial(jax.jit, static_argnums=(2, 3))
def _take_model(
    states: LDAState, i: jax.Array, num_tokens: int, num_docs: int
) -> LDAState:
    """Model `i` of a stacked state, trimmed to `(num_tokens, num_docs)`."""
    st = jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_index_in_dim(x, i, keepdims=False), states)
    return LDAState(z=st.z[:num_tokens], n_dt=st.n_dt[:num_docs],
                    n_wt=st.n_wt, n_t=st.n_t)


def unstack_states(
    cfgs: Sequence[LDAConfig],
    corpora: Sequence[Corpus],
    states: LDAState,
) -> list[LDAState]:
    """Model i's slice of a stacked `run_many` result, trimmed to its own
    token count and document rows: one program per model, `i` traced.

    No count is rebuilt. Every `run_many` ends its last sweep in a rebuild
    and encode under the stack's config, whose storage format is each
    model's; padding tokens weigh 0 and padded document rows stay zero, so
    the slice is the model's stored state up to the order of float
    additions.
    """
    return [
        _take_model(states, i, corpus.num_tokens, cfg.num_docs)
        for i, (cfg, corpus) in enumerate(zip(cfgs, corpora))
    ]


# -- batched sweeps -----------------------------------------------------------


def _sweep_batch(cfg, states, corpora, keys, block, path):
    if path == "pallas":
        from repro.kernels.lda_gibbs import ops as kops

        return kops.sweep_many(cfg, states, corpora, keys)
    return jax.vmap(
        lambda st, co, k: gibbs.sweep(cfg, st, co, k, block)
    )(states, corpora, keys)


@partial(jax.jit, static_argnums=(0, 4, 5))
def sweep_batch(
    cfg: LDAConfig,
    states: LDAState,
    corpora: Corpus,
    keys: jax.Array,  # (M, 2)
    block: int = 4096,
    path: str = "jnp",
) -> LDAState:
    """One full sweep over M stacked models; model i consumes keys[i]
    exactly as the single-model `gibbs.sweep`/kernel sweep would."""
    return _sweep_batch(cfg, states, corpora, keys, block, path)


@partial(jax.jit, static_argnums=(0, 4, 5, 6))
def run_many(
    cfg: LDAConfig,
    states: LDAState,  # stacked warm states (stored units)
    corpora: Corpus,  # stacked (M, N)
    keys: jax.Array,  # (M, 2) one key per model
    num_sweeps: int,
    block: int = 4096,
    path: str = "jnp",
) -> LDAState:
    """`num_sweeps` full sweeps over all M stacked models under one jit.

    Key discipline matches `_BaseSampler.run` per model: model i consumes
    `jax.random.split(keys[i], num_sweeps)`, one subkey per sweep, so a
    batched run is comparable to M sequential runs from the same keys.
    """
    sweep_keys = jax.vmap(
        lambda k: jax.random.split(k, num_sweeps))(keys)  # (M, S, 2)
    sweep_keys = jnp.swapaxes(sweep_keys, 0, 1)  # (S, M, 2)

    def body(carry, ks):
        return _sweep_batch(
            cfg, carry, corpora, ks, block, path), None

    states, _ = jax.lax.scan(body, states, sweep_keys)
    return states


@partial(jax.jit, static_argnums=(0,))
def init_many(cfg: LDAConfig, corpora: Corpus, keys: jax.Array) -> LDAState:
    """Stacked cold-start: per-model uniform init + scatter counts, stored
    units (the vmapped equivalent of encode(init_state(...)))."""
    return jax.vmap(
        lambda co, k: codec.encode_state(cfg, init_state(cfg, co, k))
    )(corpora, keys)


def fit_many(
    cfg: LDAConfig,
    corpora: Corpus,
    keys: jax.Array,
    num_sweeps: int,
    states: Optional[LDAState] = None,
    block: int = 4096,
    path: str = "jnp",
) -> LDAState:
    """Cold (or warm, with `states`) batched fit of M stacked models.

    Mirrors `_BaseSampler.run`: on a cold start each model's key splits
    once for init, and the post-split key drives the sweeps.
    """
    if states is None:
        pairs = jax.vmap(jax.random.split)(keys)  # (M, 2, 2)
        keys, subs = pairs[:, 0], pairs[:, 1]
        states = init_many(cfg, corpora, subs)
    return run_many(
        cfg, states, corpora, keys, num_sweeps, block, path)
