"""Pallas TPU kernel: fused collapsed-Gibbs score + Gumbel-max resampling.

The paper's phone-side hot loop is the per-token Gibbs draw (Eq. 5). The
TPU adaptation (DESIGN.md §3) resamples a whole token block against
sweep-stale counts: gathered count rows arrive as dense (TB, K) tiles and
the kernel fuses

    score tile:  log(n_dt - own + α) + log(n_wt - own + β)
                 - log(n_t - own + β̄)          (exact self-exclusion)
    sample:      argmax(score + gumbel)         (Gumbel-max, branch-free)

in VMEM, so the (TB, K) logits never round-trip to HBM — on a v5e the
fused form is memory-bound on the count rows alone (2·TB·K·4B in,
TB·4B out) instead of 3× that with materialized logits.

Fixed-point counts (paper §4.3 approximate weighting, w_bits) are handled
in-kernel: int32 rows are scaled by 2^-(w_bits+1) before scoring.

Layout. Per-token vectors (z, weights, row scales, the output) enter the
kernel as lane-dense (1, N) arrays with (1, TB) blocks, and the topic
totals as (1, K): XLA tiles a 1-D operand by its whole length (T(1024))
while Mosaic tiles a 1-D block by the block, so 1-D operands are refused
by the v5e compiler unless TB happens to equal XLA's tile. The wrappers
take 1-D vectors and reshape at the boundary.

Grid: (num_token_blocks,). TB is sized by K (`kernels.tiling`): 3 (TB, K)
f32 tiles double-buffered plus the tile body's temporaries stay under the
VMEM budget — TB=1024 at K=128, TB=256 at K=1024. Callers may pass any N;
the wrappers pad the token axis to a multiple of TB with weight-0 tokens,
which keep their assignment.

The batched multi-model variant (`gibbs_resample_blocked_batched`) adds a
leading *model grid dimension*: M stacked product models share one
`pallas_call` with grid (M, num_token_blocks), and each token block's
BlockSpec (model axis squeezed) indexes its own model's gathered count
rows and topic totals — self-exclusion and w_bits fixed-point rescaling
are the same tile body, so the fused batch launch is exactly M independent
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


def _resample_tile(
    rows_d,
    rows_w,
    tot,
    z,
    w,
    g,
    *,
    alpha: float,
    beta: float,
    beta_bar: float,
    w_bits: int | None,
):
    """The shared (TB, K) score+Gumbel-max tile body.

    `tot` is (1, K); `z` and `w` are (TB,). Both the single-model and the
    model-grid batched kernels call this, so a batched launch is
    bit-for-bit M independent single-model tiles.
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
    own = jnp.where(topic_iota == z[:, None], w[:, None], 0.0)

    rd = jnp.maximum(rows_d - own, 0.0)
    rw = jnp.maximum(rows_w - own, 0.0)
    tt = jnp.maximum(tot - own, 1e-9)
    logits = jnp.log(rd + alpha) + jnp.log(rw + beta) - jnp.log(tt + beta_bar)
    z_new = jnp.argmax(logits + g, axis=-1).astype(z.dtype)
    return jnp.where(w > 0.0, z_new, z)


def _gibbs_kernel(
    rows_d_ref,
    rows_w_ref,
    tot_ref,
    z_ref,
    w_ref,
    g_ref,
    z_out_ref,
    *,
    alpha: float,
    beta: float,
    beta_bar: float,
    w_bits: int | None,
):
    # The batched kernel's squeezed model axis makes its refs look exactly
    # like these, so one body serves both grids.
    z_out_ref[0] = _resample_tile(
        rows_d_ref[...],
        rows_w_ref[...],
        tot_ref[...],
        z_ref[0],
        w_ref[0],
        g_ref[...],
        alpha=alpha,
        beta=beta,
        beta_bar=beta_bar,
        w_bits=w_bits,
    )


def _dequant_codes(codes, bits: int):
    """uint8 code tile -> f32 rows. The v5e compiler has no uint8 -> f32
    cast, so codes widen through int32. Nibble-packed tiles hold topic t
    in the low nibble of byte t and topic t + K/2 in the high nibble
    (`pack_halves`), so unpacking is two masks and one 128-aligned lane
    concatenation."""
    x = codes.astype(jnp.int32)
    if bits == 4:
        x = jnp.concatenate([x & 0x0F, (x >> 4) & 0x0F], axis=-1)
    return x.astype(jnp.float32)


def pack_halves(codes: jax.Array) -> jax.Array:
    """(N, K) uint8 4-bit codes -> (N, K/2) bytes in the kernel's nibble
    order (low = topic t, high = topic t + K/2; K a multiple of 256)."""
    half = codes.shape[-1] // 2
    return (codes[..., :half] | (codes[..., half:] << 4)).astype(jnp.uint8)


def _gibbs_kernel_quant(
    codes_w_ref,
    scales_w_ref,
    rows_d_ref,
    tot_ref,
    z_ref,
    w_ref,
    g_ref,
    z_out_ref,
    *,
    alpha: float,
    beta: float,
    beta_bar: float,
    bits: int,
):
    """Tile body for *packed* word-topic rows (QuantSpec int8/int4_packed).

    The gathered `n_wt` rows arrive as uint8 codes — nibble-packed for
    bits=4 — plus one float32 scale per token row, and are dequantized
    *inside* the tile: the VMEM (and HBM→VMEM) footprint of the dominant
    input drops 4x/8x vs f32 rows. Doc-topic rows and topic totals stay
    exact f32 (they are small, and exact self-exclusion on `n_dt` is what
    keeps the sampler's per-document bookkeeping honest).
    """
    rows_w = _dequant_codes(codes_w_ref[...], bits) * scales_w_ref[0][:, None]
    z_out_ref[0] = _resample_tile(
        rows_d_ref[...],
        rows_w,
        tot_ref[...],
        z_ref[0],
        w_ref[0],
        g_ref[...],
        alpha=alpha,
        beta=beta,
        beta_bar=beta_bar,
        w_bits=None,  # inputs are already real-valued / dequantized
    )


def gibbs_resample_blocked_quant(
    codes_w: jax.Array,  # (N, K) uint8 codes, or (N, K//2) nibble-packed
    scales_w: jax.Array,  # (N,) float32 per-row dequant scales
    rows_d: jax.Array,  # (N, K) float32 gathered doc-topic rows (exact)
    tot: jax.Array,  # (K,) float32 topic totals (exact)
    z: jax.Array,  # (N,)
    weights: jax.Array,  # (N,)
    gumbel: jax.Array,  # (N, K)
    *,
    alpha: float,
    beta: float,
    beta_bar: float,
    bits: int,
    interpret: bool,
    token_block: Optional[int] = None,
) -> jax.Array:
    """Packed-row variant of `gibbs_resample_blocked`: same grid and
    sampling semantics, but the word-topic input is quantized codes that
    the tile body dequantizes in VMEM. For bits=4 the caller packs two
    codes per byte with `pack_halves` (pad K to a multiple of 256)."""
    n, k = rows_d.shape
    assert k % 128 == 0, k
    kc = codes_w.shape[-1]
    assert kc == (k // 2 if bits == 4 else k), (kc, k, bits)
    tb = tiling.resolve(token_block, k, 8 * k + kc)
    npad = -(-n // tb) * tb

    kern = functools.partial(
        _gibbs_kernel_quant,
        alpha=alpha, beta=beta, beta_bar=beta_bar, bits=bits,
    )
    row = pl.BlockSpec((tb, k), lambda i: (i, 0))
    tok = pl.BlockSpec((1, tb), lambda i: (0, i))
    out = pl.pallas_call(
        kern,
        grid=(npad // tb,),
        in_specs=[
            pl.BlockSpec((tb, kc), lambda i: (i, 0)),
            tok,
            row,
            pl.BlockSpec((1, k), lambda _i: (0, 0)),
            tok,
            tok,
            row,
        ],
        out_specs=tok,
        out_shape=jax.ShapeDtypeStruct((1, npad), z.dtype),
        interpret=interpret,
        name="lda_gibbs_resample_quant",
    )(pad_tokens(codes_w, npad, 0),
      pad_tokens(scales_w, npad, 0)[None],
      pad_tokens(rows_d, npad, 0),
      tot[None],
      pad_tokens(z, npad, 0)[None],
      pad_tokens(weights, npad, 0, 0.0)[None],
      pad_tokens(gumbel, npad, 0, 0.0))
    return out[0, :n]


def gibbs_resample_blocked(
    rows_d: jax.Array,  # (N, K) gathered doc-topic count rows
    rows_w: jax.Array,  # (N, K) gathered word-topic count rows
    tot: jax.Array,  # (K,)
    z: jax.Array,  # (N,)
    weights: jax.Array,  # (N,)
    gumbel: jax.Array,  # (N, K)
    *,
    alpha: float,
    beta: float,
    beta_bar: float,
    interpret: bool,
    w_bits: int | None = None,
    token_block: Optional[int] = None,
) -> jax.Array:
    """Tiled pallas_call over token blocks. K must be a multiple of 128
    (caller pads); N is padded here to a multiple of the token tile."""
    n, k = rows_d.shape
    assert k % 128 == 0, k
    tb = tiling.resolve(token_block, k, 12 * k)
    npad = -(-n // tb) * tb

    kern = functools.partial(
        _gibbs_kernel, alpha=alpha, beta=beta, beta_bar=beta_bar, w_bits=w_bits
    )
    row = pl.BlockSpec((tb, k), lambda i: (i, 0))
    tok = pl.BlockSpec((1, tb), lambda i: (0, i))
    out = pl.pallas_call(
        kern,
        grid=(npad // tb,),
        in_specs=[row, row, pl.BlockSpec((1, k), lambda _i: (0, 0)),
                  tok, tok, row],
        out_specs=tok,
        out_shape=jax.ShapeDtypeStruct((1, npad), z.dtype),
        interpret=interpret,
        name="lda_gibbs_resample",
    )(pad_tokens(rows_d, npad, 0),
      pad_tokens(rows_w, npad, 0),
      tot[None],
      pad_tokens(z, npad, 0)[None],
      pad_tokens(weights, npad, 0, 0.0)[None],
      pad_tokens(gumbel, npad, 0, 0.0))
    return out[0, :n]


def gibbs_resample_blocked_batched(
    rows_d: jax.Array,  # (M, N, K) per-model gathered doc-topic count rows
    rows_w: jax.Array,  # (M, N, K) per-model gathered word-topic count rows
    tot: jax.Array,  # (M, K) per-model topic totals
    z: jax.Array,  # (M, N)
    weights: jax.Array,  # (M, N)
    gumbel: jax.Array,  # (M, N, K)
    *,
    alpha: float,
    beta: float,
    beta_bar: float,
    interpret: bool,
    w_bits: int | None = None,
    token_block: Optional[int] = None,
) -> jax.Array:
    """One kernel launch over M stacked models: grid (M, N // token_block).

    Every model shares the hyperparameters (they are compile-time kernel
    constants — the batch engine buckets models by them) while each grid
    step's BlockSpecs select that model's count rows, totals, assignments
    and noise, so the fused launch preserves exact per-model self-exclusion
    and w_bits fixed-point weighting.
    """
    m, n, k = rows_d.shape
    assert k % 128 == 0, k
    tb = tiling.resolve(token_block, k, 12 * k)
    npad = -(-n // tb) * tb

    kern = functools.partial(
        _gibbs_kernel,
        alpha=alpha, beta=beta, beta_bar=beta_bar, w_bits=w_bits,
    )
    row = pl.BlockSpec((None, tb, k), lambda j, i: (j, i, 0))
    tok = pl.BlockSpec((None, 1, tb), lambda j, i: (j, 0, i))
    out = pl.pallas_call(
        kern,
        grid=(m, npad // tb),
        in_specs=[row, row, pl.BlockSpec((None, 1, k), lambda j, _i: (j, 0, 0)),
                  tok, tok, row],
        out_specs=tok,
        out_shape=jax.ShapeDtypeStruct((m, 1, npad), z.dtype),
        interpret=interpret,
        name="lda_gibbs_resample_batched",
    )(pad_tokens(rows_d, npad, 1),
      pad_tokens(rows_w, npad, 1),
      tot[:, None],
      pad_tokens(z, npad, 1)[:, None],
      pad_tokens(weights, npad, 1, 0.0)[:, None],
      pad_tokens(gumbel, npad, 1, 0.0))
    return out[:, 0, :n]
