"""jit'd wrapper around the lda_gibbs kernel: pad, gather, tile, un-pad.

`sweep_resample(cfg, state, corpus, key)` is a drop-in replacement for the
score+sample inner stage of `repro.core.gibbs.sweep`: counts are gathered
(XLA gather — efficient on TPU), the kernel fuses scoring and Gumbel-max
sampling per VMEM tile, and counts are rebuilt outside. The kernel is
compiled by Mosaic on a TPU; on the CPU backend its body runs in
interpret mode.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import codec, quant
from repro.core.types import Corpus, LDAConfig, LDAState
from repro.kernels.lda_gibbs.kernel import (
    gibbs_resample_blocked,
    gibbs_resample_blocked_batched,
    gibbs_resample_blocked_quant,
    pack_halves,
)


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@partial(jax.jit, static_argnums=(0,))
def sweep_resample(
    cfg: LDAConfig,
    state: LDAState,
    corpus: Corpus,
    key: jax.Array,
) -> jax.Array:
    """One full resampling pass; returns new z (counts rebuilt by caller).

    The kernel sizes its token tile by K (`kernels.tiling`). With a
    packed `cfg.quant` spec (int8/int4_packed) the word-topic rows take
    the quantized kernel: the (V, K) table is
    row-quantized once per sweep (counts are sweep-stale by design, so one
    lossy snapshot per sweep is the §4.3 story at table granularity), the
    uint8 code rows are gathered instead of f32/int32 rows, and the tile
    body dequantizes in VMEM.
    """
    spec = cfg.quant_spec
    k = cfg.num_topics
    kp_base = -(-k // 128) * 128  # lane-pad K to 128
    kp = kp_base
    if spec.packed and spec.bits == 4:
        kp = -(-k // 256) * 256  # keep the nibble-packed lane dim at 128

    def padk(x, fill=0):
        return jnp.pad(x, ((0, 0), (0, kp - k)), constant_values=fill)

    # Noise is drawn at the true token count and the mode-independent base
    # width, so a sweep's draws do not depend on the kernel's token tile
    # and a packed sweep consumes the *same* per-topic gumbel columns as
    # the exact sweep from the same key (the int4 lane over-padding only
    # adds -inf columns).
    with jax.named_scope("noise"):
        gumbel = jax.random.gumbel(
            key, (corpus.num_tokens, kp_base), jnp.float32)
        # Padded topics get -inf scores via zero counts + -inf gumbel.
        gumbel = jnp.where(
            jnp.arange(kp_base)[None, :] < k, gumbel, -jnp.inf)
        if kp != kp_base:
            gumbel = jnp.pad(gumbel, ((0, 0), (0, kp - kp_base)),
                             constant_values=-jnp.inf)

    if spec.packed:
        # Quantize the stale table once, gather packed rows per token.
        n_wt_real = codec.decode_array(cfg, state.n_wt)
        codes, scales = quant.quantize_rows_jnp(n_wt_real, spec.bits)
        with jax.named_scope("gather"):
            codes_rows = padk(codes[corpus.words])
            if spec.bits == 4:
                codes_rows = pack_halves(codes_rows)
            scale_rows = scales[corpus.words]
            rows_d = padk(codec.decode_array(cfg, state.n_dt[corpus.docs]))
            n_t = jnp.pad(codec.decode_array(cfg, state.n_t), (0, kp - k))
        return gibbs_resample_blocked_quant(
            codes_rows,
            scale_rows,
            rows_d,
            n_t,
            state.z,
            corpus.weights,
            gumbel,
            alpha=cfg.alpha,
            beta=cfg.beta,
            beta_bar=cfg.beta_bar,
            bits=spec.bits,
            interpret=_interpret(),
        )

    # Fixed-point counts are gathered *as int32* and rescaled inside the
    # kernel (saves the full (D,K)/(V,K) float materialization of from_fixed).
    with jax.named_scope("gather"):
        rows_d = padk(state.n_dt[corpus.docs])  # (N, K) gathers outside
        rows_w = padk(state.n_wt[corpus.words])  # the kernel
        n_t = jnp.pad(state.n_t, (0, kp - k))
    return gibbs_resample_blocked(
        rows_d,
        rows_w,
        n_t,
        state.z,
        corpus.weights,
        gumbel,
        alpha=cfg.alpha,
        beta=cfg.beta,
        beta_bar=cfg.beta_bar,
        w_bits=cfg.w_bits,
        interpret=_interpret(),
    )


@partial(jax.jit, static_argnums=(0,))
def sweep(
    cfg: LDAConfig,
    state: LDAState,
    corpus: Corpus,
    key: jax.Array,
) -> LDAState:
    """Full kernel-path Gibbs sweep (resample + count rebuild)."""
    z_new = sweep_resample(cfg, state, corpus, key)
    return codec.rebuild_state(cfg, corpus, z_new)


@partial(jax.jit, static_argnums=(0,))
def sweep_many(
    cfg: LDAConfig,
    states: LDAState,  # stacked: z (M, N), n_dt (M, D, K), n_wt (M, V, K)
    corpora: Corpus,  # stacked: docs/words/weights (M, N)
    keys: jax.Array,  # (M, 2) one PRNG key per model
) -> LDAState:
    """One fused Gibbs sweep over M stacked models (single kernel launch).

    `cfg` is the shared batch config: every stacked model has the same
    num_topics/vocab/hyperparameters and `cfg.num_docs` is the padded
    per-model document capacity (`serving.batch_engine` buckets and pads).
    Gathers run per model (an (M, N) batched XLA gather), the model-grid
    kernel fuses score+sample for all M models, and counts are rebuilt
    per model by a vmapped scatter-add. Model i draws exactly the noise
    the single-model `sweep` draws from keys[i].
    """
    n = corpora.docs.shape[1]
    k = cfg.num_topics
    kp = -(-k // 128) * 128

    def padk(x, fill=0):
        return jnp.pad(
            x, ((0, 0), (0, 0), (0, kp - k)), constant_values=fill
        )

    with jax.named_scope("gather"):
        rows_d = padk(
            jax.vmap(lambda n_dt, d: n_dt[d])(states.n_dt, corpora.docs))
        rows_w = padk(
            jax.vmap(lambda n_wt, w: n_wt[w])(states.n_wt, corpora.words))
        n_t = jnp.pad(states.n_t, ((0, 0), (0, kp - k)))

    with jax.named_scope("noise"):
        gumbel = jax.vmap(
            lambda kk: jax.random.gumbel(kk, (n, kp), jnp.float32)
        )(keys)
        # Padded topics get -inf scores via zero counts + -inf gumbel.
        gumbel = jnp.where(
            jnp.arange(kp)[None, None, :] < k, gumbel, -jnp.inf)

    z_new = gibbs_resample_blocked_batched(
        rows_d,
        rows_w,
        n_t,
        states.z,
        corpora.weights,
        gumbel,
        alpha=cfg.alpha,
        beta=cfg.beta,
        beta_bar=cfg.beta_bar,
        w_bits=cfg.w_bits,
        interpret=_interpret(),
    )
    return jax.vmap(lambda co, z: codec.rebuild_state(cfg, co, z))(
        corpora, z_new)
