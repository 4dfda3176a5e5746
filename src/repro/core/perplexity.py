"""Perplexity evaluation — the currency of Chital's marketplace (§2.5).

Perplexity drives model *selection* (lower wins), the *verification*
probability (Eq. 6 uses the min/max perplexity ratio of the two sellers),
and the convergence test (perplexity deviation after extra Gibbs iterations).

We use the standard point-estimate evaluation: with

    θ̂_dt = (n_dt + α) / (n_d + ᾱ),   φ̂_tw = (n_wt + β) / (n_t + β̄)

perplexity = exp( - Σ_i w_i log Σ_t θ̂_{d_i t} φ̂_{t w_i}  /  Σ_i w_i ).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import codec
from repro.core.types import Corpus, LDAConfig, LDAState

# Decoding stored (possibly fixed-point) counts is shared across backends.
_real_counts = codec.decode_counts


@partial(jax.jit, static_argnums=(0, 3))
def log_likelihood(
    cfg: LDAConfig, state: LDAState, corpus: Corpus, block: int = 8192
) -> jax.Array:
    """Total weighted token log-likelihood under point estimates."""
    with jax.named_scope("perplexity"):
        return _log_likelihood(cfg, state, corpus, block)


def _log_likelihood(cfg, state, corpus, block):
    n_dt, n_wt, n_t = _real_counts(cfg, state)
    alpha_bar = cfg.alpha * cfg.num_topics
    theta = (n_dt + cfg.alpha) / (n_dt.sum(-1, keepdims=True) + alpha_bar)  # (D,K)
    phi_t = (n_wt + cfg.beta) / (n_t[None, :] + cfg.beta_bar)  # (V,K)

    n = corpus.num_tokens
    nblocks = -(-n // block)
    pad = nblocks * block - n
    docs = jnp.pad(corpus.docs, (0, pad)).reshape(nblocks, block)
    words = jnp.pad(corpus.words, (0, pad)).reshape(nblocks, block)
    wts = jnp.pad(corpus.weights, (0, pad)).reshape(nblocks, block)

    def body(args):
        d_b, w_b, wt_b = args
        p = jnp.sum(theta[d_b] * phi_t[w_b], axis=-1)  # (block,)
        return jnp.sum(wt_b * jnp.log(jnp.maximum(p, 1e-30)))

    return jnp.sum(jax.lax.map(body, (docs, words, wts)))


def perplexity(cfg: LDAConfig, state: LDAState, corpus: Corpus) -> float:
    ll = log_likelihood(cfg, state, corpus)
    total_w = jnp.maximum(corpus.weights.sum(), 1e-9)
    return float(jnp.exp(-ll / total_w))
