"""Ahead-of-time compiles of the sampler kernels for a described TPU v5e.

Interpret mode runs a kernel body as plain jnp on the CPU, so it cannot
see what Mosaic refuses: operand layouts XLA and Mosaic tile differently,
blocks that break the (8, 128) rule, casts the chip lacks, tiles that
overflow VMEM. These tests lower and compile every main-path kernel
variant against one chip of a described `v5e:2x2` topology, at K=128 and
K=1024 with N padded from a 300k-token corpus, and the alias-table build
at the NYTimes vocabulary. Nothing runs: shapes only.

The sampler programs name their phases with `jax.named_scope` (gather,
noise, alias tables, count rebuild, perplexity), which a profiler trace
reads back from each operation's metadata: the last tests check that each
program names its phases, and that the scopes change the compiled program
in its metadata alone.

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""

from __future__ import annotations

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import alias as alias_core
from repro.core import perplexity
from repro.core.quant import QuantSpec
from repro.core.types import Corpus, LDAConfig, LDAState
from repro.kernels.alias_mh import ops as alias_ops
from repro.kernels.alias_mh.kernel import (
    alias_mh_blocked,
    alias_mh_blocked_batched,
)
from repro.kernels.lda_gibbs import ops as gibbs_ops
from repro.kernels.lda_gibbs.kernel import (
    gibbs_resample_blocked,
    gibbs_resample_blocked_batched,
    gibbs_resample_blocked_quant,
)

N_TOKENS = 300_000  # a 5,000-review product at ~60 tokens per review
N_PER_MODEL = 65_536  # the length bucket of a ~1,000-review product
#: Models per coalesced batch: 16 at K=128; at K=1024 the six gathered
#: (M, N, K) alias tiles of 16 models would not fit one chip's HBM.
M_MODELS = {128: 16, 1024: 4}
MH_STEPS = 4
HYPER = dict(alpha=0.1, beta=0.01, beta_bar=100.0)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep it out of the cache.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


F32, I32, U8 = jnp.float32, jnp.int32, jnp.uint8


@pytest.mark.parametrize("k", [128, 1024])
@pytest.mark.parametrize("w_bits", [None, 8])
def test_gibbs_compiles(one_chip, k, w_bits):
    dt = F32 if w_bits is None else I32
    n = N_TOKENS

    def fn(rd, rw, tot, z, w, g):
        return gibbs_resample_blocked(
            rd, rw, tot, z, w, g, **HYPER, w_bits=w_bits, interpret=False)

    _compile(fn, one_chip, ((n, k), dt), ((n, k), dt), ((k,), dt),
             ((n,), I32), ((n,), F32), ((n, k), F32))


@pytest.mark.parametrize("k", [128, 1024])
@pytest.mark.parametrize("bits", [8, 4])
def test_gibbs_quant_compiles(one_chip, k, bits):
    n = N_TOKENS
    kp = k if bits == 8 else -(-k // 256) * 256
    kc = kp if bits == 8 else kp // 2

    def fn(codes, scales, rd, tot, z, w, g):
        return gibbs_resample_blocked_quant(
            codes, scales, rd, tot, z, w, g, **HYPER, bits=bits,
            interpret=False)

    _compile(fn, one_chip, ((n, kc), U8), ((n,), F32), ((n, kp), F32),
             ((kp,), F32), ((n,), I32), ((n,), F32), ((n, kp), F32))


@pytest.mark.parametrize("k", [128, 1024])
def test_gibbs_batched_compiles(one_chip, k):
    m, n = M_MODELS[k], N_PER_MODEL

    def fn(rd, rw, tot, z, w, g):
        return gibbs_resample_blocked_batched(
            rd, rw, tot, z, w, g, **HYPER, interpret=False)

    _compile(fn, one_chip, ((m, n, k), F32), ((m, n, k), F32),
             ((m, k), F32), ((m, n), I32), ((m, n), F32), ((m, n, k), F32))


def _alias_shapes(lead, n, k):
    row = (*lead, n, k)
    rnd = (*lead, MH_STEPS, n)
    return (
        (row, F32), (row, F32), ((*lead, k), F32),
        (row, F32), (row, I32), (row, F32), (row, I32),
        ((*lead, n), I32), ((*lead, n), F32),
        (rnd, I32), (rnd, F32), (rnd, F32),
    )


@pytest.mark.parametrize("k", [128, 1024])
def test_alias_mh_compiles(one_chip, k):
    def fn(*args):
        return alias_mh_blocked(*args, **HYPER, interpret=False)

    _compile(fn, one_chip, *_alias_shapes((), N_TOKENS, k))


@pytest.mark.parametrize("k", [128, 1024])
def test_alias_mh_batched_compiles(one_chip, k):
    def fn(*args):
        return alias_mh_blocked_batched(*args, **HYPER, interpret=False)

    _compile(fn, one_chip, *_alias_shapes((M_MODELS[k],), N_PER_MODEL, k))


#: The gather-based table builder's temporaries on one v5e at the NYTimes
#: vocabulary (102,660 words): the blocked builder must stay below them.
ALIAS_TABLE_TEMP_BYTES = {256: 1.00e9, 1024: 3.68e9}


@pytest.mark.parametrize("k", sorted(ALIAS_TABLE_TEMP_BYTES))
def test_alias_tables_compile_without_gathers(one_chip, k):
    """The alias-table build has no per-element gather or scatter on the
    chip (each cost a memory access per entry of the V x K table), and
    its row blocks keep its temporaries below the gather builder's."""
    arg = jax.ShapeDtypeStruct((102_660, k), F32, sharding=one_chip)
    compiled = jax.jit(alias_core.build_alias_tables).lower(arg).compile()
    text = compiled.as_text()
    assert not re.search(r"\b(gather|scatter)\(", text)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= ALIAS_TABLE_TEMP_BYTES[k], temp


# -- named phases -------------------------------------------------------------

PHASES = ("gather", "noise", "alias_tables", "count_rebuild", "perplexity")
SAMPLER = ("gather", "noise", "count_rebuild")
#: A tiny coalesced batch: (models, tokens, topics, vocabulary, documents).
TINY = (2, 2048, 12, 256, 32)


def _lower(name, sharding=None):
    m, n, k, v, d = TINY
    # `mh_sweep_int8` is `mh_sweep` with int8 word-topic rows: the packed
    # branch of `mh_resample`.
    quant = QuantSpec.int8() if name.endswith("_int8") else None
    cfg = LDAConfig(num_topics=k, vocab_size=v, num_docs=d, quant=quant)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def args(*lead):
        state = LDAState(
            z=spec((*lead, n), I32), n_dt=spec((*lead, d, k), F32),
            n_wt=spec((*lead, v, k), F32), n_t=spec((*lead, k), F32))
        corpus = Corpus(spec((*lead, n), I32), spec((*lead, n), I32),
                        spec((*lead, n), F32))
        return state, corpus, spec((*lead, 2), jnp.uint32)

    if name == "log_likelihood":
        return perplexity.log_likelihood.lower(cfg, *args()[:2])
    if name == "sweep_resample":
        return gibbs_ops.sweep_resample.lower(cfg, *args())
    if name == "sweep_many":
        return gibbs_ops.sweep_many.lower(cfg, *args(m))
    if name in ("mh_sweep", "mh_sweep_int8"):
        return alias_ops.mh_sweep.lower(cfg, *args())
    return alias_ops.mh_sweep_many.lower(cfg, *args(m))


PROGRAMS = {"sweep_resample": {"gather", "noise"},  # returns z: no rebuild
            "sweep_many": set(SAMPLER),
            "mh_sweep": {*SAMPLER, "alias_tables"},
            "mh_sweep_int8": {*SAMPLER, "alias_tables"},
            "mh_sweep_many": {*SAMPLER, "alias_tables"},
            "log_likelihood": {"perplexity"}}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_sampler_program_names_its_phases(name):
    """Each phase scope reaches the lowered program's locations (a scope
    under `vmap` reads `vmap(<scope>)`), so a refactor cannot silently
    empty a phase of the trace."""
    text = _lower(name).as_text(debug_info=True)
    named = {p for p in PHASES if re.search(rf'[/("]{p}[/)]', text)}
    assert named == PROGRAMS[name]


@contextlib.contextmanager
def _no_scope(_name):
    yield


def _compiled_text(name, sharding, scoped):
    real = jax.named_scope
    if not scoped:
        jax.named_scope = _no_scope
    try:
        jax.clear_caches()  # retrace: the jaxpr cache ignores the scopes
        return _lower(name, sharding).compile().as_text()
    finally:
        jax.named_scope = real


def _strip_metadata(text):
    return re.sub(r", metadata=\{[^}]*\}", "", text)


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_phase_scopes_change_only_metadata(one_chip, monkeypatch, name):
    """The chip's optimised program (Pallas kernels through Mosaic) is the
    same with and without the phase scopes once metadata is stripped. Both
    compiles run from one call site: the metadata also records the Python
    stack that traced each operation."""
    monkeypatch.setattr(gibbs_ops, "_interpret", lambda: False)
    monkeypatch.setattr(alias_ops, "_interpret", lambda: False)
    scoped, bare = [_compiled_text(name, one_chip, s) for s in (True, False)]
    assert scoped != bare  # the scopes are in the metadata
    assert _strip_metadata(scoped) == _strip_metadata(bare)
