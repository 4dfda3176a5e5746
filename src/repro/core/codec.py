"""Shared state codec (paper §4.3) for sampler backends, built on `QuantSpec`.

Every sampler — the pure-jnp sweep, the Pallas kernel wrapper, the
client/server distributed sweep — and every consumer of counts (perplexity,
views, incremental update) needs the same two conversions:

  decode:  stored counts -> real-valued counts
           (int32 fixed point / 2^(w_bits+1) on the ``fixed`` live mode,
            identity on the float32 path);
  encode:  real-valued counts -> stored counts (round to nearest).

Before this module each call site re-implemented the ``if cfg.w_bits``
branch; now the branch exists exactly once, inside :class:`StateCodec`,
which is constructed from a single `repro.core.quant.QuantSpec`. The
legacy module-level functions (`decode_counts`, `encode_state`, ...) are
thin wrappers over ``codec_for(cfg)`` so all backends keep speaking
"stored state" at the boundary unchanged.

Representation cheat sheet (see `repro.core.quant`):

  * live mutable state (what samplers scatter-add): ``f32`` or ``fixed``
    — `StateCodec.encode_state`/`decode_state`;
  * read-only packed tables (wire payloads, snapshots, kernel-fed
    sweep-stale rows): ``int8`` / ``int4_packed`` codes + per-row scales
    — `StateCodec.pack_table`/`unpack_table`.

The implementation lives in core (it depends only on `quant`, `fractional`
and `types`, and the samplers sit above it); the public surface is
re-exported as `repro.api.codec` — the one documented home of both this
state codec and the wire array codec of `repro.api.protocol`.
"""

from __future__ import annotations

import jax
import numpy as np

from repro.core import fractional, quant
from repro.core.quant import QuantSpec, spec_for
from repro.core.types import Corpus, LDAConfig, LDAState, build_counts

__all__ = [
    "QuantSpec",
    "StateCodec",
    "codec_for",
    "spec_for",
    "decode_array",
    "decode_array_np",
    "decode_counts",
    "decode_counts_np",
    "decode_state",
    "encode_state",
    "rebuild_state",
]


class StateCodec:
    """All stored-state conversions for one :class:`QuantSpec`.

    Construct directly from a spec, or resolve from a config with
    :func:`codec_for`. Methods mirror the legacy module functions minus
    the `cfg` threading (the spec already knows the representation); the
    count-rebuild helper still takes `(cfg, corpus, z)` because the
    scatter shapes live on the config.
    """

    def __init__(self, spec: QuantSpec):
        self.spec = spec

    def __repr__(self):
        return f"StateCodec({self.spec!r})"

    # -- live state: stored units <-> real units ----------------------------

    def decode_array(self, x):
        """One stored count array -> real units (cheap single-array decode
        for call sites that don't need the whole state)."""
        if self.spec.live_fixed:
            return fractional.from_fixed(x, self.spec.w_bits)
        return x

    def decode_array_np(self, x) -> np.ndarray:
        """One stored count array -> float64 numpy (host-side serving)."""
        out = np.asarray(x, np.float64)
        if self.spec.live_fixed:
            out = out / float(fractional.scale(self.spec.w_bits))
        return out

    def encode_array(self, x):
        """One real-valued count array -> stored units."""
        if self.spec.live_fixed:
            return fractional.to_fixed(x, self.spec.w_bits)
        return x

    def decode_counts(self, state: LDAState):
        """Stored ``(n_dt, n_wt, n_t)`` -> real-valued float32 arrays."""
        return (
            self.decode_array(state.n_dt),
            self.decode_array(state.n_wt),
            self.decode_array(state.n_t),
        )

    def decode_counts_np(self, state: LDAState):
        """Stored counts -> float64 numpy arrays (the view/serving path,
        which does its aggregation host-side)."""
        return (
            self.decode_array_np(state.n_dt),
            self.decode_array_np(state.n_wt),
            self.decode_array_np(state.n_t),
        )

    def decode_state(self, state: LDAState) -> LDAState:
        """Full state with counts in real units (z passes through)."""
        n_dt, n_wt, n_t = self.decode_counts(state)
        return LDAState(z=state.z, n_dt=n_dt, n_wt=n_wt, n_t=n_t)

    def encode_state(self, state: LDAState) -> LDAState:
        """Real-valued state -> stored representation."""
        if not self.spec.live_fixed:
            return state
        return LDAState(
            z=state.z,
            n_dt=self.encode_array(state.n_dt),
            n_wt=self.encode_array(state.n_wt),
            n_t=self.encode_array(state.n_t),
        )

    def rebuild_state(self, cfg: LDAConfig, corpus: Corpus, z) -> LDAState:
        """Scatter-rebuild counts from assignments and store (the
        post-sweep pattern shared by all backends: rebuild in real units,
        encode once)."""
        with jax.named_scope("count_rebuild"):
            return self.encode_state(build_counts(cfg, corpus, z))

    # -- read-only packed tables (int8 / int4_packed modes) -----------------

    def pack_table(self, x) -> tuple[np.ndarray, np.ndarray]:
        """A *real-valued* table -> (codes, per-row scales) in this spec's
        packed width (requires a packed mode)."""
        return quant.quantize_rows(np.asarray(x, np.float32), self.spec.bits)

    def unpack_table(self, codes, scales, k: int) -> np.ndarray:
        """(codes, scales) -> real-valued float32 table."""
        return quant.dequantize_rows(codes, scales, self.spec.bits, k)


_F32_CODEC = StateCodec(QuantSpec.f32())
_CODEC_CACHE: dict[QuantSpec, StateCodec] = {}


def codec_for(cfg) -> StateCodec:
    """The (cached) `StateCodec` of a config's resolved `QuantSpec`."""
    spec = spec_for(cfg)
    got = _CODEC_CACHE.get(spec)
    if got is None:
        got = _CODEC_CACHE[spec] = StateCodec(spec)
    return got


# -- legacy cfg-threading wrappers (the stable sampler-facing names) ----------


def decode_array(cfg: LDAConfig, x):
    """One stored count array -> real units (see `StateCodec`)."""
    return codec_for(cfg).decode_array(x)


def decode_array_np(cfg: LDAConfig, x) -> np.ndarray:
    """One stored count array -> float64 numpy.

    Deprecated spelling: prefer ``codec_for(cfg).decode_array_np`` (or
    `decode_counts_np` when all three count arrays are needed) — kept as
    a wrapper because serving paths predating `StateCodec` call it.
    """
    return codec_for(cfg).decode_array_np(x)


def decode_counts(cfg: LDAConfig, state: LDAState):
    """Stored ``(n_dt, n_wt, n_t)`` -> real-valued float32 arrays."""
    return codec_for(cfg).decode_counts(state)


def decode_state(cfg: LDAConfig, state: LDAState) -> LDAState:
    """Full state with counts in real units (z passes through)."""
    return codec_for(cfg).decode_state(state)


def encode_state(cfg: LDAConfig, state: LDAState) -> LDAState:
    """Real-valued state -> stored representation."""
    return codec_for(cfg).encode_state(state)


def rebuild_state(cfg: LDAConfig, corpus: Corpus, z) -> LDAState:
    """Scatter-rebuild counts from assignments and store."""
    return codec_for(cfg).rebuild_state(cfg, corpus, z)


def decode_counts_np(cfg: LDAConfig, state: LDAState):
    """Stored counts -> float64 numpy arrays."""
    return codec_for(cfg).decode_counts_np(state)
