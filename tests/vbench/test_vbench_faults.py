"""Each fault a fit cell can have makes `correct` false, and so does the
control (the reference in bfloat16 in the program's place).

The runs drive the whole harness at a small size on the CPU, with the
program's timed path broken underneath; the chip's own check of a run is
skipped (there is no chip here)."""

import dataclasses

import pytest

import _paths  # noqa: F401
from repro.api import service as service_mod
from repro.serving import batch_engine
from vbench import control, harness

SMALL = {"config": {"corpus": {"groups": 3,
                               "docs_per_group": {"law": "fixed", "value": 30},
                               "base_vocab": 300}}}
SMALL_ALIAS = {"config": {"corpus": SMALL["config"]["corpus"],
                          "model": {"num_topics": 128}},
               "traffic": {"setup": [{"verb": "fit_prepared",
                                      "backend": "alias", "sweeps": 2}]}}


def _run(cell, overrides, seed=5):
    out = harness.run_cell(cell, seed, 1.0, False, require_tpu=False,
                           overrides=overrides)
    return out["correct"], {c["name"]: c for c in out["checks"]}


def _over(c):
    return c["value"] > c["limit"]


@pytest.mark.parametrize("cell", ["amazon.refit", "nytimes.fit"])
def test_sound_run_compares_every_number(cell):
    ok, checks = _run(cell, SMALL if cell == "amazon.refit" else SMALL_ALIAS)
    assert set(checks) == {"count_err", "move_gap", "calib_gap"}
    assert not _over(checks["count_err"])


def _unchanged(_self, handles, *_args, **_kw):
    return handles


def test_state_returned_unchanged(monkeypatch):
    monkeypatch.setattr(service_mod.VedaliaService, "refine_many",
                        _unchanged)
    ok, checks = _run("amazon.refit", SMALL)
    assert not ok and _over(checks["move_gap"])


def test_half_the_batch_left_out(monkeypatch):
    real = batch_engine.run_batched

    def half(sampler, cfgs, corpora, keys, num_sweeps, states=None, **kw):
        out, stats = real(sampler, cfgs, corpora, keys, num_sweeps,
                          states=states, **kw)
        if states is not None:
            keep = len(out) // 2 + 1
            out = list(out[:keep]) + list(states[keep:])
        return out, stats

    monkeypatch.setattr(batch_engine, "run_batched", half)
    ok, checks = _run("amazon.refit", SMALL)
    assert not ok and _over(checks["move_gap"])


def test_answer_altered_where_produced(monkeypatch):
    real = service_mod.VedaliaService.refine_many

    def altered(self, handles, num_sweeps, **kw):
        out = real(self, handles, num_sweeps, **kw)
        st = handles[0].model.state
        k = handles[0].cfg.num_topics
        handles[0].model.state = dataclasses.replace(
            st, z=st.z.at[0].set((st.z[0] + 1) % k))
        return out

    monkeypatch.setattr(service_mod.VedaliaService, "refine_many", altered)
    ok, checks = _run("amazon.refit", SMALL)
    assert not ok and _over(checks["count_err"])


def test_alias_half_the_documents_left_out(monkeypatch):
    import jax.numpy as jnp

    from repro.api import backends
    from repro.core import codec

    real = backends.AliasSampler.sweep

    def half(self, cfg, state, corpus, key):
        out = real(self, cfg, state, corpus, key)
        keep = corpus.docs < cfg.num_docs // 2
        return codec.rebuild_state(cfg, corpus,
                                   jnp.where(keep, out.z, state.z))

    monkeypatch.setattr(backends.AliasSampler, "sweep", half)
    ok, checks = _run("nytimes.fit", SMALL_ALIAS)
    assert not ok and _over(checks["move_gap"])


def test_alias_answer_altered_where_produced(monkeypatch):
    real = service_mod.VedaliaService.refine

    def altered(self, handle, num_sweeps, **kw):
        out = real(self, handle, num_sweeps, **kw)
        st = handle.model.state
        handle.model.state = dataclasses.replace(
            st, z=st.z.at[0].set((st.z[0] + 1) % handle.cfg.num_topics))
        return out

    monkeypatch.setattr(service_mod.VedaliaService, "refine", altered)
    ok, checks = _run("nytimes.fit", SMALL_ALIAS)
    assert not ok and _over(checks["count_err"])


def test_alias_state_returned_unchanged(monkeypatch):
    monkeypatch.setattr(service_mod.VedaliaService, "refine", _unchanged)
    ok, checks = _run("nytimes.fit", SMALL_ALIAS)
    assert not ok and _over(checks["move_gap"])


@pytest.mark.parametrize("cell", ["amazon.refit", "nytimes.fit"])
def test_control_fails(cell):
    over = SMALL if cell == "amazon.refit" else {
        "config": SMALL_ALIAS["config"]}
    lines = control.run_seed(cell, 9, ["control"], over)
    limits = harness.find_cell(cell).limits
    assert lines[0]["count_err"] > limits["count_err"]
