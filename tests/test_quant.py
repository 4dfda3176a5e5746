"""QuantSpec codec redesign: packing properties, wire interop, views.

Covers the quantized state & wire format acceptance gates:
  * int8/int4 row packing round-trips within the scale/2 bound (all-zero
    rows exact, per-row scale extremes, odd-length nibble packing);
  * `fixed` mode is bit-exact against the pre-QuantSpec `w_bits` path
    from identical keys (live state never packs);
  * wire interop: a quantized-capable client against a pre-quant server
    (raw form unchanged) and quantized payloads decoding on request;
  * `view_version` round-trip + typed `ViewVersionError` resync;
  * quantized view / export / spot-check / adopt end-to-end through
    `VedaliaClient`;
  * the packed kernel paths (gibbs + alias MH) run and land near the
    unquantized sweep.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.api import VedaliaClient, codec as api_codec, protocol
from repro.core import codec, gibbs, quant
from repro.core.quant import QuantSpec
from repro.core.types import Corpus, LDAConfig, init_state
from repro.core.views import (
    ModelView,
    TopicView,
    ViewVersionError,
    VIEW_VERSION,
)
from repro.data import reviews


def _corpus(n=2000, v=96, d=30, k=8, w_bits=None, quant_spec=None, seed=0):
    rng = np.random.default_rng(seed)
    cfg = LDAConfig(num_topics=k, vocab_size=v, num_docs=d, w_bits=w_bits,
                    quant=quant_spec)
    corpus = Corpus(
        docs=jnp.asarray(rng.integers(0, d, n), jnp.int32),
        words=jnp.asarray(rng.integers(0, v, n), jnp.int32),
        weights=jnp.asarray(rng.random(n), jnp.float32),
    )
    return cfg, corpus


def _reviews(n=60, vocab=120, seed=0):
    return reviews.generate(reviews.SyntheticSpec(
        num_reviews=n, vocab_size=vocab, num_topics=4, mean_tokens=30,
        seed=seed)).reviews


# -- QuantSpec semantics ------------------------------------------------------


def test_spec_validation_and_properties():
    assert QuantSpec.f32().live_mode == "f32"
    assert QuantSpec.fixed(8).live_fixed
    assert QuantSpec.int8().bits == 8
    assert QuantSpec.int4(w_bits=8).bits == 4
    assert QuantSpec.int4(w_bits=8).live_fixed  # packed + fixed live state
    with pytest.raises(ValueError, match="unknown quant mode"):
        QuantSpec(mode="bf16")
    with pytest.raises(ValueError, match="requires w_bits"):
        QuantSpec(mode="fixed")
    with pytest.raises(ValueError, match="must not carry"):
        QuantSpec(mode="f32", w_bits=4)
    with pytest.raises(ValueError, match="wire quant mode"):
        QuantSpec.from_wire("fixed")
    assert QuantSpec.from_w_bits(None) == QuantSpec.f32()
    assert QuantSpec.from_w_bits(8) == QuantSpec.fixed(8)


def test_spec_is_hashable_and_cfg_stays_static():
    # The spec rides inside LDAConfig through jit static args.
    cfg = LDAConfig(num_topics=4, vocab_size=16, num_docs=4,
                    quant=QuantSpec.int8())
    assert hash(cfg) == hash(cfg)
    assert cfg.quant_spec is cfg.quant
    legacy = LDAConfig(num_topics=4, vocab_size=16, num_docs=4, w_bits=6)
    assert legacy.quant_spec == QuantSpec.fixed(6)


def test_codec_for_caches_per_spec():
    cfg_a = LDAConfig(num_topics=4, vocab_size=16, num_docs=4, w_bits=8)
    cfg_b = LDAConfig(num_topics=8, vocab_size=32, num_docs=8, w_bits=8)
    assert codec.codec_for(cfg_a) is codec.codec_for(cfg_b)
    assert codec.codec_for(cfg_a).spec == QuantSpec.fixed(8)


# -- packing round-trip properties -------------------------------------------


@given(
    bits=st.integers(min_value=0, max_value=1),
    k=st.integers(min_value=1, max_value=33),
    rows=st.integers(min_value=1, max_value=8),
    scale=st.floats(min_value=1e-6, max_value=1e6, allow_nan=False),
)
@settings(max_examples=100, deadline=None)
def test_roundtrip_error_within_half_scale(bits, k, rows, scale):
    bits = 4 if bits else 8
    rng = np.random.default_rng(k * 1000 + rows)
    x = (rng.random((rows, k)) * scale).astype(np.float32)
    codes, scales = quant.quantize_rows(x, bits)
    back = quant.dequantize_rows(codes, scales, bits, k)
    assert back.shape == x.shape
    # rint can land half a step away; float32 rounding adds a hair more.
    tol = scales[:, None] * 0.5 + 1e-5 * np.abs(x) + 1e-30
    assert np.all(np.abs(back - x) <= tol)


def test_all_zero_rows_decode_exactly():
    x = np.zeros((3, 7), np.float32)
    for bits in (4, 8):
        codes, scales = quant.quantize_rows(x, bits)
        assert np.all(scales == 0.0)
        assert np.array_equal(
            quant.dequantize_rows(codes, scales, bits, 7), x)
    # Mixed: one live row between zero rows keeps its own scale.
    x[1, 3] = 5.0
    codes, scales = quant.quantize_rows(x, 8)
    back = quant.dequantize_rows(codes, scales, 8, 7)
    assert np.array_equal(back[0], np.zeros(7))
    assert np.array_equal(back[2], np.zeros(7))
    assert abs(back[1, 3] - 5.0) <= scales[1] / 2 + 1e-6


def test_rowmax_is_exact_per_row():
    # The top entry of every row hits code == levels, decoding to rowmax.
    rng = np.random.default_rng(3)
    x = rng.random((5, 12)).astype(np.float32) * np.asarray(
        [1e-5, 1.0, 37.0, 1e4, 2.5e6], np.float32)[:, None]
    for bits in (4, 8):
        codes, scales = quant.quantize_rows(x, bits)
        back = quant.dequantize_rows(codes, scales, bits, 12)
        np.testing.assert_allclose(
            back.max(axis=-1), x.max(axis=-1), rtol=1e-6)


@given(k=st.integers(min_value=1, max_value=40))
@settings(max_examples=60, deadline=None)
def test_nibble_packing_roundtrip_odd_lengths(k):
    rng = np.random.default_rng(k)
    codes = rng.integers(0, 16, (3, k)).astype(np.uint8)
    packed = quant.pack_nibbles(codes)
    assert packed.shape[-1] == (k + 1) // 2
    assert np.array_equal(quant.unpack_nibbles(packed, k), codes)


def test_jnp_twins_match_numpy():
    rng = np.random.default_rng(9)
    x = (rng.random((6, 11)) * 40).astype(np.float32)
    for bits in (4, 8):
        codes_np, scales_np = quant.quantize_rows(x, bits)
        codes_j, scales_j = quant.quantize_rows_jnp(jnp.asarray(x), bits)
        np.testing.assert_allclose(np.asarray(scales_j), scales_np,
                                   rtol=1e-6)
        packed_j = np.asarray(codes_j)
        if bits == 4:
            packed_j = quant.pack_nibbles(packed_j)
        assert np.array_equal(packed_j, codes_np)
    fq = quant.fake_quantize_rows(x, 8)
    fq_j = np.asarray(quant.fake_quantize_rows(jnp.asarray(x), 8))
    np.testing.assert_allclose(fq_j, fq, rtol=1e-5, atol=1e-5)


# -- fixed mode bit-exactness -------------------------------------------------


def test_fixed_mode_is_bit_exact_vs_legacy_w_bits():
    cfg_old, corpus = _corpus(w_bits=8)
    cfg_new = LDAConfig(num_topics=cfg_old.num_topics,
                        vocab_size=cfg_old.vocab_size,
                        num_docs=cfg_old.num_docs, w_bits=8,
                        quant=QuantSpec.fixed(8))
    out_old = gibbs.run(cfg_old, corpus, jax.random.PRNGKey(0),
                        num_sweeps=3)
    out_new = gibbs.run(cfg_new, corpus, jax.random.PRNGKey(0),
                        num_sweeps=3)
    assert np.array_equal(np.asarray(out_old.z), np.asarray(out_new.z))
    assert np.array_equal(np.asarray(out_old.n_wt), np.asarray(out_new.n_wt))


# -- wire array codec ---------------------------------------------------------


def test_raw_wire_form_unchanged_without_spec():
    # A pre-quant decoder must keep parsing what we emit by default.
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    d = protocol.encode_array(x)
    assert set(d) == {"dtype", "shape", "b64"}
    assert "enc" not in d
    assert np.array_equal(protocol.decode_array(d), x)


@pytest.mark.parametrize("mode", ["int8", "int4_packed"])
def test_quantized_wire_roundtrip(mode):
    spec = QuantSpec.from_wire(mode)
    rng = np.random.default_rng(5)
    x = (rng.random((20, 16)) * 100).astype(np.float32)
    d = protocol.encode_array(x, spec=spec)
    assert d["enc"] == "q" and d["mode"] == mode
    back = protocol.decode_array(d)
    assert back.dtype == x.dtype and back.shape == x.shape
    _, scales = quant.quantize_rows(x, spec.bits)
    assert np.all(np.abs(back - x) <= scales[:, None] / 2 + 1e-4)
    # int dtypes round back to integers on dequant.
    xi = (x * 4).astype(np.int32)
    di = protocol.encode_array(xi, spec=spec)
    bi = protocol.decode_array(di)
    assert bi.dtype == np.int32


def test_quantized_wire_is_smaller():
    x = np.random.default_rng(0).random((64, 32)).astype(np.float32) * 50
    raw = len(protocol.encode_array(x)["b64"])
    q8 = len(protocol.encode_array(x, spec=QuantSpec.int8())["b64"])
    q4 = len(protocol.encode_array(x, spec=QuantSpec.int4())["b64"])
    assert q8 < raw / 3  # ~4x minus scale overhead (scales ride separately)
    assert q4 < q8


def test_state_arrays_pack_only_count_tables():
    cfg, corpus = _corpus(w_bits=None)
    state = init_state(cfg, corpus, jax.random.PRNGKey(0))
    d = protocol.encode_state_arrays(state, spec=QuantSpec.int8())
    assert d["z"].get("enc") is None  # ground truth ships raw
    assert d["n_t"].get("enc") is None
    assert d["n_dt"]["enc"] == "q" and d["n_wt"]["enc"] == "q"
    assert protocol.state_arrays_quantized(d)
    assert not protocol.state_arrays_quantized(
        protocol.encode_state_arrays(state))
    arrays = protocol.decode_state_arrays(d)
    assert np.array_equal(arrays["z"], np.asarray(state.z))


def test_api_codec_is_the_documented_home():
    # Both codecs import from one surface, under distinct names.
    assert api_codec.codec_for is codec.codec_for
    assert api_codec.QuantSpec is QuantSpec
    assert api_codec.encode_wire_array is protocol.encode_array
    assert api_codec.decode_wire_array is protocol.decode_array
    assert api_codec.QUANT_STATE_FIELDS == ("n_dt", "n_wt")


# -- view versioning ----------------------------------------------------------


def _view():
    return ModelView(topics=[
        TopicView(topic_id=3, probability=0.25, expected_rating=4.1,
                  expected_helpful=0.6, expected_unhelpful=0.1,
                  top_words=[5, 9, 2], top_word_weights=[7.0, 3.5, 1.25]),
        TopicView(topic_id=1, probability=0.75, expected_rating=2.0,
                  expected_helpful=0.0, expected_unhelpful=0.0,
                  top_words=[4], top_word_weights=[0.0]),
    ])


def test_view_v1_serialization_is_plain_list():
    import json

    v = _view()
    s = v.to_json()
    assert isinstance(json.loads(s), list)  # pre-quant contract holds
    back = ModelView.from_json(s)
    assert back.topics[0].to_dict() == v.topics[0].to_dict()


@pytest.mark.parametrize("mode", ["int8", "int4_packed"])
def test_view_v2_quantized_roundtrip(mode):
    import json

    v = _view()
    spec = QuantSpec.from_wire(mode)
    s = v.to_json(quant_spec=spec)
    obj = json.loads(s)
    assert obj["view_version"] == VIEW_VERSION and obj["quant"] == mode
    back = ModelView.from_json(s)
    for t_in, t_out in zip(v.topics, back.topics):
        assert t_out.topic_id == t_in.topic_id
        assert t_out.top_words == t_in.top_words
        w_in = np.asarray(t_in.top_word_weights)
        w_out = np.asarray(t_out.top_word_weights)
        step = w_in.max() / (2 ** spec.bits - 1) if w_in.max() else 0.0
        assert np.all(np.abs(w_out - w_in) <= step / 2 + 1e-6)
    assert len(s) < len(v.to_json())  # compact form is actually smaller


def test_future_view_version_raises_typed_resync():
    import json

    s = json.dumps({"view_version": VIEW_VERSION + 1, "topics": []})
    with pytest.raises(ViewVersionError) as ei:
        ModelView.from_json(s)
    assert ei.value.resync is True
    assert ei.value.got == VIEW_VERSION + 1
    assert isinstance(ei.value, ValueError)  # old catch-sites still catch


# -- end-to-end through the client -------------------------------------------


@pytest.fixture(scope="module")
def fitted():
    client = VedaliaClient(backend="jnp", num_sweeps=6, update_sweeps=1)
    fit = client.fit(_reviews(), num_topics=8, base_vocab=120, w_bits=8,
                     seed=0)
    return client, fit.handle_id


def test_hello_advertises_quant(fitted):
    client, _ = fitted
    hello = client._call("hello", {})
    assert list(quant.PACKED_MODES) == hello["quant_modes"]
    assert hello["view_version"] == VIEW_VERSION


def test_quantized_view_matches_unquantized_topics(fitted):
    client, hid = fitted
    plain = client.view(hid, top_n=8)
    q = client.view(hid, top_n=8, quant="int8")
    assert q.payload_bytes < plain.payload_bytes
    assert [t.topic_id for t in q.topics] == [
        t.topic_id for t in plain.topics]
    for tp, tq in zip(plain.topics, q.topics):
        assert tp.top_words == tq.top_words
        w = np.asarray(tp.top_word_weights)
        step = (w.max() / 255) if w.size and w.max() else 0.0
        assert np.all(np.abs(np.asarray(tq.top_word_weights) - w)
                      <= step / 2 + 1e-6)


def test_quantized_delta_view_same_topic_set(fitted):
    client, hid = fitted
    full = client.sync_view(hid, top_n=8)
    client.update(hid, _reviews(n=8, seed=91), seed=3)
    delta = client.view(hid, since=full.cursor, top_n=8)
    delta_q = client.view(hid, since=full.cursor, top_n=8, quant="int8")
    # Cursor signatures come from the unquantized view on both syncs, so
    # the re-sent topic set is identical; only the encoding shrinks.
    assert ([t.topic_id for t in delta_q.topics]
            == [t.topic_id for t in delta.topics])
    if delta.topics:
        assert delta_q.payload_bytes < delta.payload_bytes


@pytest.mark.parametrize("mode", ["int8", "int4_packed"])
def test_quantized_export_rebuilds_exact_state(fitted, mode):
    client, hid = fitted
    exact = client.export_model(hid)
    packed = client.export_model(hid, quant=mode)
    assert np.array_equal(np.asarray(packed.state.z),
                          np.asarray(exact.state.z))
    # Counts rebuilt from raw z are bit-exact despite the lossy download.
    assert np.array_equal(np.asarray(packed.state.n_wt),
                          np.asarray(exact.state.n_wt))
    assert np.array_equal(np.asarray(packed.state.n_dt),
                          np.asarray(exact.state.n_dt))


def test_quantized_spot_check_and_adopt(fitted):
    client, hid = fitted
    exp = client.export_model(hid)
    res = client.spot_check(hid, exp.state, num_sweeps=1, seed=5,
                            quant="int8")
    assert res.valid, res.reason
    adopted = client.adopt_state(hid, exp.state, sweeps_run=exp.sweeps_run,
                                 quant="int8")
    assert adopted.handle_id == hid


def test_quantized_upload_of_phony_claim_still_fails(fitted):
    client, hid = fitted
    exp = client.export_model(hid)
    # Quantized uploads rebuild counts from z, so count *fabrication* is
    # erased by construction — the surviving attack is a phony quality
    # claim on a degenerate state, and the claim check must still catch
    # it after the rebuild.
    bad_z = jnp.zeros_like(exp.state.z)
    bad = type(exp.state)(z=bad_z, n_dt=exp.state.n_dt,
                          n_wt=exp.state.n_wt, n_t=exp.state.n_t)
    res = client.spot_check(hid, bad, claimed_perplexity=1.0,
                            num_sweeps=1, seed=5, quant="int8")
    assert not res.valid


def test_raw_upload_of_inconsistent_counts_still_fails(fitted):
    client, hid = fitted
    exp = client.export_model(hid)
    # Unquantized uploads keep the original defense: counts that disagree
    # with their own assignments fail structural validation unchanged.
    bad = type(exp.state)(z=exp.state.z, n_dt=exp.state.n_dt,
                          n_wt=exp.state.n_wt * 3, n_t=exp.state.n_t)
    res = client.spot_check(hid, bad, num_sweeps=0, seed=5)
    assert not res.valid


# -- packed kernel paths ------------------------------------------------------


@pytest.mark.parametrize("spec", [QuantSpec.int8(w_bits=8),
                                  QuantSpec.int4(w_bits=8)])
def test_packed_gibbs_kernel_sweep_runs(spec):
    from repro.kernels.lda_gibbs import ops

    cfg_ref, corpus = _corpus(n=1500, w_bits=8)
    cfg_q = LDAConfig(num_topics=cfg_ref.num_topics,
                      vocab_size=cfg_ref.vocab_size,
                      num_docs=cfg_ref.num_docs, w_bits=8, quant=spec)
    state = codec.encode_state(
        cfg_ref, init_state(cfg_ref, corpus, jax.random.PRNGKey(1)))
    z_ref = ops.sweep_resample(cfg_ref, state, corpus,
                               jax.random.PRNGKey(2))
    z_q = ops.sweep_resample(cfg_q, state, corpus, jax.random.PRNGKey(2))
    assert z_q.shape == z_ref.shape
    assert int(jnp.min(z_q)) >= 0
    assert int(jnp.max(z_q)) < cfg_q.num_topics
    # The packed table is a scale/2-perturbed score surface; most tokens
    # must still land where the exact sweep lands them.
    agree = float(jnp.mean((z_q == z_ref).astype(jnp.float32)))
    assert agree > 0.8, f"packed sweep diverged: agreement {agree:.2%}"


@pytest.mark.parametrize("spec", [QuantSpec.int8(w_bits=8),
                                  QuantSpec.int4(w_bits=8)])
def test_packed_alias_kernel_sweep_runs(spec):
    from repro.kernels.alias_mh import ops

    cfg_ref, corpus = _corpus(n=1500, w_bits=8)
    cfg_q = LDAConfig(num_topics=cfg_ref.num_topics,
                      vocab_size=cfg_ref.vocab_size,
                      num_docs=cfg_ref.num_docs, w_bits=8, quant=spec)
    state = codec.encode_state(
        cfg_ref, init_state(cfg_ref, corpus, jax.random.PRNGKey(1)))
    z_ref = ops.mh_resample(cfg_ref, state, corpus, jax.random.PRNGKey(2))
    z_q = ops.mh_resample(cfg_q, state, corpus, jax.random.PRNGKey(2))
    assert z_q.shape == z_ref.shape
    assert int(jnp.min(z_q)) >= 0
    assert int(jnp.max(z_q)) < cfg_q.num_topics
    agree = float(jnp.mean((z_q == z_ref).astype(jnp.float32)))
    assert agree > 0.8, f"packed MH sweep diverged: agreement {agree:.2%}"
