"""Pallas TPU kernel: chunked diagonal-decay linear recurrence.

Shared compute core of RWKV6 ("Finch", vector decay + u-bonus) and Mamba2
(SSD, scalar-per-head decay folded to vector form by the caller):

    S_c+1 = diag(exp(L_C)) · S_c + Σ_i (k_i ⊙ exp(L_C - L_i)) v_iᵀ
    y_t   = (q_t ⊙ d_t ⊙ exp(Lprev_t)) · S_c + Σ_{i<=t} A[t,i] v_i

All decay factors appear as *ratios* exp(L_a - L_b) ≤ 1, so the kernel is
fp32-stable without log-space matmuls. Per grid step the VMEM working set
is 4 (C, dk) tiles + 1 (C, dv) tile + the (dk, dv) state + the (C, C)
intra-chunk matrix — for C=64, dk=dv=64 about 120 KB, far under VMEM; the
two heavy contractions (A·V and K·V) are MXU matmuls.

Grid: (B·H, num_chunks). TPU grids iterate the last axis innermost and
sequentially, so the recurrent state lives in a VMEM scratch carried
across chunk steps — the cross-chunk dependency is expressed by grid
order, not host control flow.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LOG_W_MIN = -20.0


def _chunk_scan_kernel(
    w_ref,  # (C, dk) decay factors in (0, 1]
    k_ref,  # (C, dk)
    v_ref,  # (C, dv)
    q_ref,  # (C, dk)
    u_ref,  # (1, dk) bonus row (zeros when unused)
    s0_ref,  # (dk, dv) initial state for this (b, h)
    y_ref,  # out: (C, dv)
    s_out_ref,  # out: (dk, dv) final state
    state,  # scratch: (dk, dv) f32
    *,
    include_current: bool,
):
    c_idx = pl.program_id(1)

    @pl.when(c_idx == 0)
    def _init():
        state[...] = s0_ref[...].astype(jnp.float32)

    lw = jnp.clip(
        jnp.log(jnp.maximum(w_ref[...].astype(jnp.float32), 1e-30)),
        LOG_W_MIN,
        0.0,
    )
    kt = k_ref[...].astype(jnp.float32)
    vt = v_ref[...].astype(jnp.float32)
    qt = q_ref[...].astype(jnp.float32)
    c, dk = kt.shape

    L = jnp.cumsum(lw, axis=0)  # inclusive cumulative log decay
    Lprev = L - lw
    S = state[...]

    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    if include_current:
        # mamba2: y_t reads S_t (decay applied through L_t), diagonal i == t.
        qs = qt * jnp.exp(L)
        mask = col <= row
        Lq, Lk = L, L
    else:
        # rwkv6: y_t reads S_{t-1}; strict lower triangle; u-bonus diagonal.
        qs = qt * jnp.exp(Lprev)
        mask = col < row
        Lq, Lk = Lprev, L

    # A[t, i] = sum_d q[t] k[i] exp(Lq[t] - Lk[i]); bounded ratio trick:
    # exp(Lq[t] - Lk[i]) = exp(Lq[t]) * exp(-Lk[i]) overflows, so contract
    # per-d with the masked exp computed via a (C, C, dk) tile — at C=64,
    # dk=64 this is a 1 MB fp32 intermediate, VMEM-resident.
    ratio = Lq[:, None, :] - Lk[None, :, :]  # (C, C, dk)
    ratio = jnp.where(mask[:, :, None], ratio, -jnp.inf)
    A = jnp.sum(jnp.exp(ratio) * qt[:, None, :] * kt[None, :, :], axis=-1)

    if not include_current:
        diag = jnp.sum(qt * u_ref[...] * kt, axis=-1)  # (C,)
        A = A + jnp.where(col == row, diag[:, None], 0.0)

    y = qs @ S + A @ vt  # two MXU contractions
    y_ref[...] = y.astype(y_ref.dtype)

    # Cross-chunk state update.
    Lc = L[-1:, :]  # (1, dk) total chunk decay
    k_dec = kt * jnp.exp(Lc - L)
    state[...] = jnp.exp(Lc[0])[:, None] * S + k_dec.T @ vt

    @pl.when(c_idx == pl.num_programs(1) - 1)
    def _fin():
        s_out_ref[...] = state[...]


def chunk_scan_pallas(
    w: jax.Array,  # (B, S, H, dk)
    k: jax.Array,
    v: jax.Array,  # (B, S, H, dv)
    q: jax.Array,
    u: jax.Array | None,  # (H, dk) or None
    *,
    include_current: bool,
    chunk: int = 64,
    s0: jax.Array | None = None,  # (B, H, dk, dv)
    interpret: bool,
):
    b, s, h, dk = k.shape
    dv = v.shape[-1]
    if s % chunk:
        chunk = max(c for c in range(1, min(chunk, s) + 1) if s % c == 0)
    n = s // chunk

    # (B*H, S, d) layout: one grid row per (batch, head).
    def mix(x, d):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    wf, kf, qf = mix(w, dk), mix(k, dk), mix(q, dk)
    vf = mix(v, dv)
    if u is None:
        uf = jnp.zeros((h, 1, dk), jnp.float32)
    else:
        uf = u.astype(jnp.float32).reshape(h, 1, dk)
    uf = jnp.tile(uf, (b, 1, 1)).reshape(b * h, 1, dk)
    if s0 is None:
        s0 = jnp.zeros((b, h, dk, dv), jnp.float32)
    s0f = s0.reshape(b * h, dk, dv).astype(jnp.float32)

    kern = functools.partial(_chunk_scan_kernel, include_current=include_current)
    y, s_fin = pl.pallas_call(
        kern,
        grid=(b * h, n),
        in_specs=[
            pl.BlockSpec((None, chunk, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, chunk, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, chunk, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, chunk, dk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, dk), lambda i, _j: (i, 0, 0)),
            pl.BlockSpec((None, dk, dv), lambda i, _j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, chunk, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, dk, dv), lambda i, _j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, dv), v.dtype),
            jax.ShapeDtypeStruct((b * h, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
        name="chunk_scan",
    )(wf, kf, vf, qf, uf, s0f)

    y = y.reshape(b, h, s, dv).transpose(0, 2, 1, 3)
    return y, s_fin.reshape(b, h, dk, dv)
