"""Token-tile sizing shared by the fused sampler kernels.

Both `lda_gibbs` and `alias_mh` stream (TB, K) row tiles through VMEM,
double-buffered by the Pallas pipeline, and build a few (TB, K) f32
temporaries in the tile body. The tile that fits therefore shrinks as K
grows: a fixed TB that is right at K=128 exhausts a v5e core's scoped
VMEM (16 MiB by default) at K=1024. `token_block_for` picks the largest
power-of-two TB in [128, 1024] whose footprint stays under
`VMEM_BUDGET`; TB stays a multiple of 128 so the lane-dense (1, TB)
per-token blocks keep the layout XLA gives their operands.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

#: Bytes of VMEM one grid step may plan for (headroom under 16 MiB).
VMEM_BUDGET = 12 * 2**20
#: (TB, K) f32 temporaries the tile bodies keep live (own mask, logits...).
_TEMPS = 4
_MIN_TB, _MAX_TB = 128, 1024


def token_block_for(k_pad: int, row_bytes: int) -> int:
    """Largest power-of-two token tile for lane-padded width `k_pad`,
    where `row_bytes` is what one token's (TB, K)-shaped inputs take in
    VMEM (e.g. 3 f32 rows = 12 * k_pad)."""
    per_token = 2 * row_bytes + _TEMPS * 4 * k_pad
    tb = _MAX_TB
    while tb > _MIN_TB and tb * per_token > VMEM_BUDGET:
        tb //= 2
    return tb


def resolve(token_block: Optional[int], k_pad: int, row_bytes: int) -> int:
    """An explicit `token_block` wins; None sizes the tile by K."""
    if token_block is not None:
        return token_block
    return token_block_for(k_pad, row_bytes)


def pad_tokens(x, npad: int, axis: int, fill=0):
    """Pad the token axis of `x` to `npad` (a no-op when aligned)."""
    extra = npad - x.shape[axis]
    if extra == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, extra)
    return jnp.pad(x, widths, constant_values=fill)
