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
  * stacking/unstacking and padding helpers shared by both paths.

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
    return Corpus(
        docs=jnp.pad(corpus.docs, (0, pad)),
        words=jnp.pad(corpus.words, (0, pad)),
        weights=jnp.pad(corpus.weights, (0, pad)),
    )


def stack_corpora(corpora: Sequence[Corpus], num_tokens: int) -> Corpus:
    """Stack corpora into one (M, num_tokens) batch (weight-0 padding)."""
    padded = [pad_corpus(c, num_tokens) for c in corpora]
    return Corpus(
        docs=jnp.stack([c.docs for c in padded]),
        words=jnp.stack([c.words for c in padded]),
        weights=jnp.stack([c.weights for c in padded]),
    )


def stack_states(
    bcfg: LDAConfig,
    cfgs: Sequence[LDAConfig],
    states: Sequence[LDAState],
    num_tokens: int,
) -> LDAState:
    """Stack warm per-model states (stored units) to the batch shape.

    z pads with topic 0 (padding tokens have weight 0 and keep their
    assignment), n_dt pads with zero rows up to the document capacity.
    """
    zs, n_dts = [], []
    for cfg, st in zip(cfgs, states):
        zs.append(jnp.pad(st.z, (0, num_tokens - st.z.shape[0])))
        n_dts.append(jnp.pad(
            st.n_dt, ((0, bcfg.num_docs - cfg.num_docs), (0, 0))))
    return LDAState(
        z=jnp.stack(zs),
        n_dt=jnp.stack(n_dts),
        n_wt=jnp.stack([st.n_wt for st in states]),
        n_t=jnp.stack([st.n_t for st in states]),
    )


def unstack_states(
    cfgs: Sequence[LDAConfig],
    corpora: Sequence[Corpus],
    states: LDAState,
) -> list[LDAState]:
    """Trim each model's z back to its true token count and rebuild its
    counts under its own (unpadded) config — stored units, same contract
    as every single-model backend."""
    return [
        codec.rebuild_state(cfg, corpus, states.z[i, : corpus.num_tokens])
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
