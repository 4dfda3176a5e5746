"""Pallas TPU kernel: fused AliasLDA proposal draw + Metropolis–Hastings.

The AliasLDA sweep (`repro.core.alias.mh_sweep`) is the auto-selector's
large-fit path, but as pure jnp every MH round re-reads the gathered count
and table rows from HBM: `mh_steps` rounds × 4 (TB, K) tensors. This kernel
loads each token block's rows into VMEM **once** and runs the stale
proposal draw plus *all* `mh_steps` accept/reject rounds in place:

    draw:    prop = j            if u < thresh[j]      (stale alias table)
             prop = alias[j]     otherwise
    accept:  log a = [log p(prop) + log q(z)] - [log p(z) + log q(prop)]
             with p(t) ∝ (n_td - own + α)(n_tw - own + β)/(n_t - own + β̄)
             (exact self-exclusion against the sweep-stale assignment)

Rounds alternate Li et al.'s *cycle* proposals — even rounds draw from the
token's word table with q(t) ∝ n_tw + β, odd rounds from its doc table
with q(t) ∝ n_td + α — so the chain explores both factors of the target.
The round parity is a compile-time constant (the loop is unrolled), so
each round reads only its own table tile. Per-sweep HBM traffic is
6·TB·K·4B in + TB·4B out regardless of `mh_steps`, instead of `mh_steps`×
that with materialized intermediates.
Randomness is precomputed outside as (S, N) matrices (the lda_gibbs Gumbel
pattern): per round a bucket index, a bucket-vs-alias uniform and an accept
uniform, drawn with exactly `core.alias.mh_sweep`'s key discipline so the
fused sweep is bit-exact against the jnp oracle.

Fixed-point counts (paper §4.3 approximate weighting, w_bits) are handled
in-kernel: int32 count rows are scaled by 2^-(w_bits+1) before scoring.

Per-token topic lookups inside a tile use a branch-free masked-iota
reduction over the K lanes (TPU-friendly; no dynamic lane gather).

Layout is `lda_gibbs`'s: per-token vectors are lane-dense (1, N) arrays
with (1, TB) blocks, the random strips (S, N) with (S, TB) blocks, and
the totals (1, K); the wrappers take 1-D vectors and reshape at the
boundary.

Grid: (num_token_blocks,). TB is sized by K (`kernels.tiling`) for 6
(TB, K) tiles (rows_d, rows_w, word/doc thresh + alias), double-buffered:
TB=1024 at K=128, TB=128 at K=1024. Callers may pass any N; the wrappers
pad the token axis with weight-0 tokens, which keep their assignment.

The batched multi-model variant (`alias_mh_blocked_batched`) adds a leading
*model grid dimension* exactly like `lda_gibbs`: M stacked product models
share one `pallas_call` with grid (M, num_token_blocks), each token block's
BlockSpec (model axis squeezed) indexing its own model's rows, tables,
totals and noise, so the fused batch launch is exactly M independent
single-model sweeps.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling
from repro.kernels.tiling import pad_tokens


def _mh_tile(
    rows_d,  # (TB, K) gathered doc-topic count rows
    rows_w,  # (TB, K) gathered word-topic count rows
    tot,  # (1, K) topic totals
    thresh_w,  # (TB, K) gathered word-table alias thresholds
    alias_w,  # (TB, K) gathered word-table alias targets
    thresh_d,  # (TB, K) gathered doc-table alias thresholds
    alias_d,  # (TB, K) gathered doc-table alias targets
    z0,  # (TB,) sweep-stale assignments (self-exclusion anchor)
    w,  # (TB,) fractional token weights (0 = padding)
    j_prop,  # (S, TB) proposal bucket indices per MH round
    u_prop,  # (S, TB) bucket-vs-alias uniforms per MH round
    u_acc,  # (S, TB) accept uniforms per MH round
    *,
    alpha: float,
    beta: float,
    beta_bar: float,
    w_bits: int | None,
):
    """The shared (TB, K) proposal+MH tile body.

    Both the single-model and the model-grid batched kernels call this, so
    a batched launch is bit-for-bit M independent single-model tiles.
    """
    if w_bits is not None:
        scale = 2.0 ** -(w_bits + 1)
        rows_d = rows_d.astype(jnp.float32) * scale
        rows_w = rows_w.astype(jnp.float32) * scale
        tot = tot.astype(jnp.float32) * scale
    else:
        rows_d = rows_d.astype(jnp.float32)
        rows_w = rows_w.astype(jnp.float32)
        tot = tot.astype(jnp.float32)

    tb, k = rows_d.shape
    topic_iota = jax.lax.broadcasted_iota(jnp.int32, (tb, k), 1)

    def take(mat, idx):  # (TB, K) @ (TB,) -> (TB,): branch-free lane select
        sel = topic_iota == idx[:, None]
        return jnp.sum(jnp.where(sel, mat, jnp.zeros_like(mat)), axis=-1)

    def log_p(zt):  # stale target with exact self-exclusion
        sub = jnp.where((zt == z0) & (w > 0.0), w, 0.0)
        ndt = jnp.maximum(take(rows_d, zt) - sub, 0.0)
        nwt = jnp.maximum(take(rows_w, zt) - sub, 0.0)
        nt = jnp.maximum(take(tot, zt) - sub, 1e-9)
        return (jnp.log(ndt + alpha) + jnp.log(nwt + beta)
                - jnp.log(nt + beta_bar))

    def log_q_w(zt):  # stale proposal densities (ratios, no exclusion)
        return jnp.log(take(rows_w, zt) + beta)

    def log_q_d(zt):
        return jnp.log(take(rows_d, zt) + alpha)

    z_cur = z0
    for s in range(j_prop.shape[0]):  # mh_steps is static: unrolled in VMEM
        j = j_prop[s]
        if s % 2 == 0:  # word-proposal round (compile-time parity)
            thresh, alias_t, log_q = thresh_w, alias_w, log_q_w
        else:  # doc-proposal round
            thresh, alias_t, log_q = thresh_d, alias_d, log_q_d
        prop = jnp.where(
            u_prop[s] < take(thresh, j), j, take(alias_t, j)
        ).astype(z0.dtype)
        log_a = (log_p(prop) + log_q(z_cur)) - (log_p(z_cur) + log_q(prop))
        accept = jnp.log(u_acc[s]) < log_a
        z_cur = jnp.where(accept & (w > 0.0), prop, z_cur)
    return z_cur


def _alias_mh_kernel(
    rows_d_ref,
    rows_w_ref,
    tot_ref,
    thresh_w_ref,
    alias_w_ref,
    thresh_d_ref,
    alias_d_ref,
    z_ref,
    w_ref,
    j_ref,
    up_ref,
    ua_ref,
    z_out_ref,
    *,
    alpha: float,
    beta: float,
    beta_bar: float,
    w_bits: int | None,
):
    # The batched kernel's squeezed model axis makes its refs look exactly
    # like these, so one body serves both grids.
    z_out_ref[0] = _mh_tile(
        rows_d_ref[...],
        rows_w_ref[...],
        tot_ref[...],
        thresh_w_ref[...],
        alias_w_ref[...],
        thresh_d_ref[...],
        alias_d_ref[...],
        z_ref[0],
        w_ref[0],
        j_ref[...],
        up_ref[...],
        ua_ref[...],
        alpha=alpha,
        beta=beta,
        beta_bar=beta_bar,
        w_bits=w_bits,
    )


def _tile_args(rows, tot, z, weights, rnd, npad: int, lead: int):
    """Token-pad every operand to `npad` and give the 1-D vectors and the
    totals their lane-dense (1, N) / (1, K) layout. `rows` are the six
    (.., N, K) tiles, `rnd` the three (.., S, N) random strips, and `lead`
    the number of leading model axes (0 or 1). The accept uniforms pad
    with 1 (log 1 = 0, so padding never NaNs the tile)."""
    rows = [pad_tokens(r, npad, lead) for r in rows]
    z = jnp.expand_dims(pad_tokens(z, npad, lead), lead)
    weights = jnp.expand_dims(pad_tokens(weights, npad, lead, 0.0), lead)
    j, up, ua = rnd
    rnd = (pad_tokens(j, npad, lead + 1),
           pad_tokens(up, npad, lead + 1, 0.0),
           pad_tokens(ua, npad, lead + 1, 1.0))
    return (*rows[:2], jnp.expand_dims(tot, lead), *rows[2:], z, weights,
            *rnd)


def alias_mh_blocked(
    rows_d: jax.Array,  # (N, K) gathered doc-topic count rows
    rows_w: jax.Array,  # (N, K) gathered word-topic count rows
    tot: jax.Array,  # (K,)
    thresh_w: jax.Array,  # (N, K) gathered word-table alias thresholds
    alias_w: jax.Array,  # (N, K) gathered word-table alias targets (int32)
    thresh_d: jax.Array,  # (N, K) gathered doc-table alias thresholds
    alias_d: jax.Array,  # (N, K) gathered doc-table alias targets (int32)
    z: jax.Array,  # (N,)
    weights: jax.Array,  # (N,)
    j_prop: jax.Array,  # (S, N) int32 proposal bucket draws
    u_prop: jax.Array,  # (S, N) float32 bucket-vs-alias uniforms
    u_acc: jax.Array,  # (S, N) float32 accept uniforms
    *,
    alpha: float,
    beta: float,
    beta_bar: float,
    interpret: bool,
    w_bits: int | None = None,
    token_block: Optional[int] = None,
) -> jax.Array:
    """Tiled pallas_call over token blocks: all S MH rounds fused per tile.

    K must be a multiple of 128 (caller pads); N is padded here to a
    multiple of the token tile."""
    n, k = rows_d.shape
    s = j_prop.shape[0]
    assert k % 128 == 0, k
    tb = tiling.resolve(token_block, k, 24 * k)
    npad = -(-n // tb) * tb

    kern = functools.partial(
        _alias_mh_kernel, alpha=alpha, beta=beta, beta_bar=beta_bar,
        w_bits=w_bits,
    )
    row_spec = pl.BlockSpec((tb, k), lambda i: (i, 0))
    tok_spec = pl.BlockSpec((1, tb), lambda i: (0, i))
    rnd_spec = pl.BlockSpec((s, tb), lambda i: (0, i))
    out = pl.pallas_call(
        kern,
        grid=(npad // tb,),
        in_specs=[
            row_spec,  # rows_d
            row_spec,  # rows_w
            pl.BlockSpec((1, k), lambda _i: (0, 0)),
            row_spec,  # thresh_w
            row_spec,  # alias_w
            row_spec,  # thresh_d
            row_spec,  # alias_d
            tok_spec,  # z
            tok_spec,  # weights
            rnd_spec,  # j_prop
            rnd_spec,  # u_prop
            rnd_spec,  # u_acc
        ],
        out_specs=tok_spec,
        out_shape=jax.ShapeDtypeStruct((1, npad), z.dtype),
        interpret=interpret,
        name="alias_mh_sweep",
    )(*_tile_args((rows_d, rows_w, thresh_w, alias_w, thresh_d, alias_d),
                  tot, z, weights, (j_prop, u_prop, u_acc), npad, 0))
    return out[0, :n]


def alias_mh_blocked_batched(
    rows_d: jax.Array,  # (M, N, K) per-model gathered doc-topic count rows
    rows_w: jax.Array,  # (M, N, K) per-model gathered word-topic count rows
    tot: jax.Array,  # (M, K) per-model topic totals
    thresh_w: jax.Array,  # (M, N, K) per-model word-table thresholds
    alias_w: jax.Array,  # (M, N, K) per-model word-table alias targets
    thresh_d: jax.Array,  # (M, N, K) per-model doc-table thresholds
    alias_d: jax.Array,  # (M, N, K) per-model doc-table alias targets
    z: jax.Array,  # (M, N)
    weights: jax.Array,  # (M, N)
    j_prop: jax.Array,  # (M, S, N)
    u_prop: jax.Array,  # (M, S, N)
    u_acc: jax.Array,  # (M, S, N)
    *,
    alpha: float,
    beta: float,
    beta_bar: float,
    interpret: bool,
    w_bits: int | None = None,
    token_block: Optional[int] = None,
) -> jax.Array:
    """One kernel launch over M stacked models: grid (M, N // token_block).

    Every model shares the hyperparameters (compile-time kernel constants —
    the batch engine buckets models by them) while each grid step's
    BlockSpecs select that model's rows, tables, totals, assignments and
    noise, so the fused launch preserves exact per-model self-exclusion and
    w_bits fixed-point weighting.
    """
    m, n, k = rows_d.shape
    s = j_prop.shape[1]
    assert k % 128 == 0, k
    tb = tiling.resolve(token_block, k, 24 * k)
    npad = -(-n // tb) * tb

    kern = functools.partial(
        _alias_mh_kernel, alpha=alpha, beta=beta, beta_bar=beta_bar,
        w_bits=w_bits,
    )
    row_spec = pl.BlockSpec((None, tb, k), lambda j, i: (j, i, 0))
    tok_spec = pl.BlockSpec((None, 1, tb), lambda j, i: (j, 0, i))
    rnd_spec = pl.BlockSpec((None, s, tb), lambda j, i: (j, 0, i))
    out = pl.pallas_call(
        kern,
        grid=(m, npad // tb),
        in_specs=[
            row_spec,  # rows_d
            row_spec,  # rows_w
            pl.BlockSpec((None, 1, k), lambda j, _i: (j, 0, 0)),
            row_spec,  # thresh_w
            row_spec,  # alias_w
            row_spec,  # thresh_d
            row_spec,  # alias_d
            tok_spec,  # z
            tok_spec,  # weights
            rnd_spec,  # j_prop
            rnd_spec,  # u_prop
            rnd_spec,  # u_acc
        ],
        out_specs=tok_spec,
        out_shape=jax.ShapeDtypeStruct((m, 1, npad), z.dtype),
        interpret=interpret,
        name="alias_mh_sweep_batched",
    )(*_tile_args((rows_d, rows_w, thresh_w, alias_w, thresh_d, alias_d),
                  tot, z, weights, (j_prop, u_prop, u_acc), npad, 1))
    return out[:, 0, :n]
