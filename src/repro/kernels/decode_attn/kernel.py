"""Pallas TPU kernel: flash-decode for GQA serving (one token vs KV cache).

The serving hot path: a single query token attends over a long KV cache.
The kernel streams the cache through VMEM in (C, hd) tiles with online
softmax, so HBM traffic is exactly one pass over K and V — the roofline
floor for decode — instead of materializing (Hq, S) scores. Supports GQA
grouping (q block of G = Hq/Hkv query heads per kv head rides the MXU),
gemma2 logit soft-capping, sliding windows, and ring-buffer caches.

Grid: (B, Hkv, S/C). The last axis is TPU-sequential, so the online-softmax
running (m, l, acc) state lives in VMEM scratch across cache tiles.
VMEM per step at C=512, hd=128, G=8: k/v tiles 512 KB + acc ~4 KB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_attn_kernel(
    meta_ref,  # (2,) i32: [pos, length]
    q_ref,  # (G, hd)
    k_ref,  # (C, hd)
    v_ref,  # (C, hd)
    o_ref,  # out (G, hd)
    m_scr,  # scratch (G, 1) f32
    l_scr,  # scratch (G, 1) f32
    acc_scr,  # scratch (G, hd) f32
    *,
    kv_block: int,
    cache_len: int,
    window: int,
    ring: bool,
    cap: float,
    scale: float,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = meta_ref[0]
    length = meta_ref[1]

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (G, C)
    s = s * scale
    if cap > 0.0:
        s = cap * jnp.tanh(s / cap)

    idx = j * kv_block + jax.lax.broadcasted_iota(jnp.int32, (1, kv_block), 1)
    if ring:
        written = jnp.minimum(length, cache_len)
        wp = pos % cache_len
        age = (wp - idx) % cache_len
        abs_pos = pos - age
        valid = (age < written) & (abs_pos >= 0)
        if window > 0:
            valid &= abs_pos > pos - window
    else:
        valid = idx < length
        if window > 0:
            valid &= idx > pos - window

    s = jnp.where(valid, s, NEG_INF)

    m_run = m_scr[...]  # (G, 1)
    m_new = jnp.maximum(m_run, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_run - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _fin():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(
            o_ref.dtype
        )


def decode_attention_pallas(
    q: jax.Array,  # (B, Hq, hd)
    k_cache: jax.Array,  # (B, S, Hkv, hd)
    v_cache: jax.Array,
    *,
    length,
    pos,
    window: int = 0,
    ring: bool = False,
    cap: float = 0.0,
    kv_block: int = 512,
    interpret: bool,
) -> jax.Array:
    b, s, hkv, hd = k_cache.shape
    hq = q.shape[1]
    g = hq // hkv
    if s % kv_block:
        kv_block = max(c for c in range(1, min(kv_block, s) + 1) if s % c == 0)
    n = s // kv_block

    qg = q.reshape(b, hkv, g, hd)
    meta = jnp.stack(
        [jnp.asarray(pos, jnp.int32), jnp.asarray(length, jnp.int32)]
    )

    kern = functools.partial(
        _decode_attn_kernel,
        kv_block=kv_block,
        cache_len=s,
        window=window,
        ring=ring,
        cap=cap,
        scale=hd**-0.5,
    )
    out = pl.pallas_call(
        kern,
        grid=(b, hkv, n),
        in_specs=[
            pl.BlockSpec((2,), lambda _bi, _hi, _j: (0,)),
            pl.BlockSpec((None, None, g, hd), lambda bi, hi, _j: (bi, hi, 0, 0)),
            pl.BlockSpec((None, kv_block, None, hd), lambda bi, hi, j: (bi, j, hi, 0)),
            pl.BlockSpec((None, kv_block, None, hd), lambda bi, hi, j: (bi, j, hi, 0)),
        ],
        out_specs=pl.BlockSpec(
            (None, None, g, hd), lambda bi, hi, _j: (bi, hi, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
        interpret=interpret,
        name="decode_attn",
    )(meta, qg, k_cache, v_cache)
    return out.reshape(b, hq, hd)
