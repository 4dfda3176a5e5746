"""jit'd wrapper around the alias_mh kernel: tables, gather, pad, un-pad.

`mh_sweep(cfg, state, corpus, key)` is a drop-in replacement for
`repro.core.alias.mh_sweep` that speaks *stored* state at the boundary
(the `AliasSampler` backend contract): the stale word- and doc-proposal
alias tables are built outside by the parallel prefix-sum builder
(`core.alias.build_alias_tables` on the decoded counts), count/table rows
are gathered (XLA gather — efficient on TPU), the kernel fuses the cycle
proposal draws plus all `mh_steps` MH rounds per VMEM tile, and counts are
rebuilt outside. The kernel is compiled by Mosaic on a TPU; on the CPU
backend its body runs in interpret mode.

Randomness is precomputed as (S, N) matrices with **exactly** the key
discipline of `core.alias.mh_sweep` (per-round key -> split 3 -> bucket
randint / bucket-vs-alias uniform / accept uniform at the true token
count), which is what makes the fused sweep bit-exact against the jnp
oracle from identical keys.

`mh_sweep_many` is the model-grid batched variant: M stacked compatible
models (the `serving.batch_engine` layout) in one launch, each model
consuming its own key exactly as the single-model sweep would.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import alias as alias_core
from repro.core import codec, quant
from repro.core.types import Corpus, LDAConfig, LDAState
from repro.kernels.alias_mh.kernel import (
    alias_mh_blocked,
    alias_mh_blocked_batched,
)


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _draws(key: jax.Array, n: int, k: int, mh_steps: int):
    """(S, N) random matrices with `core.alias.mh_sweep`'s key discipline:
    one key per MH round, split 3-ways into bucket / alias / accept draws
    at the true token count (padding is appended afterwards)."""
    js, ups, uas = [], [], []
    with jax.named_scope("noise"):
        for k_step in jax.random.split(key, mh_steps):
            kj, ku, ka = jax.random.split(k_step, 3)
            js.append(jax.random.randint(kj, (n,), 0, k))
            ups.append(jax.random.uniform(ku, (n,)))
            uas.append(jax.random.uniform(ka, (n,)))
        return jnp.stack(js), jnp.stack(ups), jnp.stack(uas)


@partial(jax.jit, static_argnums=(0, 4))
def mh_resample(
    cfg: LDAConfig,
    state: LDAState,
    corpus: Corpus,
    key: jax.Array,
    mh_steps: int = 4,
) -> jax.Array:
    """One fused proposal+MH pass; returns new z (counts rebuilt by
    caller). `state` is in stored units (int32 fixed point when
    `cfg.w_bits` is set — rescaled inside the kernel).

    With a packed `cfg.quant` spec the stale word-topic table — and the
    word-proposal alias tables built from it — is row-quantized to the
    spec's width before use (quantize-dequantize: the accuracy model of
    the packed table; stale tables are rebuilt every sweep anyway, so the
    error never accumulates). Doc rows and totals stay exact, and the
    kernel then runs its plain float path (`w_bits=None`) on the already-
    dequantized inputs.
    """
    spec = cfg.quant_spec
    n = corpus.num_tokens
    k = cfg.num_topics
    kp = -(-k // 128) * 128  # lane-pad K to 128

    def padk(x, fill=0):
        return jnp.pad(x, ((0, 0), (0, kp - k)), constant_values=fill)

    # Stale proposal tables (word + doc cycles): built once per sweep from
    # the decoded counts by the parallel prefix-sum builder, then gathered
    # per token like the count rows. Fixed-point count rows are gathered
    # *as int32* and rescaled inside the kernel.
    if spec.packed:
        n_wt_q = quant.fake_quantize_rows(
            codec.decode_array(cfg, state.n_wt), spec.bits)
        thresh_w, alias_w = alias_core.build_alias_tables(n_wt_q + cfg.beta)
        word_table = n_wt_q
        n_t = codec.decode_array(cfg, state.n_t)
        kernel_w_bits = None  # inputs already real-valued
    else:
        thresh_w, alias_w = alias_core.build_alias_tables(
            codec.decode_array(cfg, state.n_wt) + cfg.beta)
        word_table = state.n_wt
        n_t = state.n_t
        kernel_w_bits = cfg.w_bits
    thresh_d, alias_d = alias_core.build_alias_tables(
        codec.decode_array(cfg, state.n_dt) + cfg.alpha)
    with jax.named_scope("gather"):
        rows_d = state.n_dt[corpus.docs]  # (N, K) gathers outside the kernel
        if spec.packed:
            rows_d = codec.decode_array(cfg, rows_d)
        rows = (
            padk(rows_d),
            padk(word_table[corpus.words]),
            jnp.pad(n_t, (0, kp - k)),
            padk(thresh_w[corpus.words], 0.0),
            padk(alias_w[corpus.words]),
            padk(thresh_d[corpus.docs], 0.0),
            padk(alias_d[corpus.docs]),
        )

    j_prop, u_prop, u_acc = _draws(key, n, k, mh_steps)

    return alias_mh_blocked(
        *rows,
        state.z,
        corpus.weights,
        j_prop,
        u_prop,
        u_acc,
        alpha=cfg.alpha,
        beta=cfg.beta,
        beta_bar=cfg.beta_bar,
        w_bits=kernel_w_bits,
        interpret=_interpret(),
    )


@partial(jax.jit, static_argnums=(0, 4))
def mh_sweep(
    cfg: LDAConfig,
    state: LDAState,
    corpus: Corpus,
    key: jax.Array,
    mh_steps: int = 4,
) -> LDAState:
    """Full kernel-path AliasLDA sweep (fused MH + count rebuild), stored
    units in and out."""
    z_new = mh_resample(cfg, state, corpus, key, mh_steps)
    return codec.rebuild_state(cfg, corpus, z_new)


@partial(jax.jit, static_argnums=(0, 4))
def mh_sweep_many(
    cfg: LDAConfig,
    states: LDAState,  # stacked: z (M, N), n_dt (M, D, K), n_wt (M, V, K)
    corpora: Corpus,  # stacked: docs/words/weights (M, N)
    keys: jax.Array,  # (M, 2) one PRNG key per model
    mh_steps: int = 4,
) -> LDAState:
    """One fused AliasLDA sweep over M stacked models (single launch).

    `cfg` is the shared batch config (`serving.batch_engine` buckets and
    pads). Tables build for all M×V rows in one vectorized pass, gathers
    run per model (batched XLA gather), the model-grid kernel fuses the
    proposal+MH rounds for all M models, and counts are rebuilt per model
    by a vmapped scatter-add — bit-exact M independent single-model sweeps.
    """
    n = corpora.docs.shape[1]
    k = cfg.num_topics
    kp = -(-k // 128) * 128

    thresh_w, alias_w = alias_core.build_alias_tables(
        codec.decode_array(cfg, states.n_wt) + cfg.beta)  # (M, V, K)
    thresh_d, alias_d = alias_core.build_alias_tables(
        codec.decode_array(cfg, states.n_dt) + cfg.alpha)  # (M, D, K)

    def padk(x, fill=0):
        return jnp.pad(
            x, ((0, 0), (0, 0), (0, kp - k)), constant_values=fill)

    def rows_of(table, idx):
        return jax.vmap(lambda t, i: t[i])(table, idx)

    with jax.named_scope("gather"):
        rows = (
            padk(rows_of(states.n_dt, corpora.docs)),
            padk(rows_of(states.n_wt, corpora.words)),
            jnp.pad(states.n_t, ((0, 0), (0, kp - k))),
            padk(rows_of(thresh_w, corpora.words), 0.0),
            padk(rows_of(alias_w, corpora.words)),
            padk(rows_of(thresh_d, corpora.docs), 0.0),
            padk(rows_of(alias_d, corpora.docs)),
        )

    j_prop, u_prop, u_acc = jax.vmap(
        lambda kk: _draws(kk, n, k, mh_steps))(keys)  # (M, S, N) each

    z_new = alias_mh_blocked_batched(
        *rows,
        states.z,
        corpora.weights,
        j_prop,
        u_prop,
        u_acc,
        alpha=cfg.alpha,
        beta=cfg.beta,
        beta_bar=cfg.beta_bar,
        w_bits=cfg.w_bits,
        interpret=_interpret(),
    )
    return jax.vmap(lambda co, z: codec.rebuild_state(cfg, co, z))(
        corpora, z_new)
