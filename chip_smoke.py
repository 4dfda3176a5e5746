"""Chip smoke test: Vedalia's served fit and view path on a TPU.

    python chip_smoke.py [--seed N]            # one chip, phases A-D
    python chip_smoke.py --chips 4 [--seed N]  # the pserver 2x2 phase only

Every phase goes through the entry points a user calls: `VedaliaClient`
-> `VedaliaServer` -> `VedaliaService` -> registry backend, on review
corpora generated from `--seed` (`repro.data.reviews`; nothing is
downloaded). One chip:

  A. a large single-product fit (5,000 reviews, ~300k tokens, K=128)
     routed by `backend="auto"`, which must resolve to the alias backend
     on its Pallas path;
  B. kernel parity on the same corpus: the fused Gibbs kernel with f32
     counts, with w_bits=8 fixed point, and with an int8-packed word-topic
     table, each against the `core.gibbs` jnp oracle from the same state
     and key (argmax agreement of one sweep; held-out perplexity within 2%
     after a few sweeps);
  C. a coalesced fit of 16 products (~1,000 reviews each, K=12, the
     service's per-product default) through the `batched` backend on its
     Pallas path, each model's held-out perplexity within 2% of a
     sequential jnp fit;
  D. an incremental update with 200 reviews, two cursor-tracked view
     syncs (the second must re-send 0 topics), top reviews, perplexity.

`--chips 4` runs one pserver fit on a 2x2 ("data", "model") mesh (local
Pallas sweeps, staleness 2) over ~4M tokens with the UCI NYTimes
vocabulary width (V = 102,660), compares it with the jnp oracle on one
device, and checks that the vocab-sharded word-topic table lives on all
four devices.

Each phase prints its wall and compile seconds. Any failure exits
non-zero; no phase failure is caught. With no TPU the script exits 1
before any phase. The last stdout line on success is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import VedaliaClient  # noqa: E402
from repro.api.backends import get_backend  # noqa: E402
from repro.core import codec, gibbs, quant  # noqa: E402
from repro.core.types import Corpus, LDAState, init_state  # noqa: E402
from repro.data import reviews  # noqa: E402
from repro.kernels.alias_mh import ops as alias_ops  # noqa: E402
from repro.kernels.lda_gibbs import ops as gibbs_ops  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

PPX_TOL = 0.02  # held-out perplexity gap vs the jnp oracle (alias_bench)
AGREE_MIN = 0.99  # one-sweep argmax agreement with the oracle, same noise


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Corpus and fit sizes of the phases (the defaults are the chip's)."""

    reviews: int = 5_000  # phase A/B product
    heldout: int = 500
    base_vocab: int = 10_000  # RLDA vocabulary = 5 rating tiers x this
    topics: int = 128
    sweeps: int = 30  # phase A fit
    parity_sweeps: int = 15  # phase B fits
    products: int = 16  # phase C batch
    product_reviews: int = 1_000
    product_heldout: int = 100
    product_topics: int = 12  # the service's per-product default
    batch_sweeps: int = 30
    new_reviews: int = 200  # phase D update
    pod_reviews: int = 66_700  # --chips 4: ~4M tokens
    pod_heldout: int = 2_000
    pod_base_vocab: int = 20_532  # x 5 tiers = 102,660 (UCI NYTimes V)
    pod_sweeps: int = 20


# -- bookkeeping ---------------------------------------------------------------

_COMPILE_S = [0.0]


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event.startswith("/jax/core/compile/"):
        _COMPILE_S[0] += duration


def _phase(name: str, fn):
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    out = fn()
    print(f"[{name}] wall {time.perf_counter() - t0:.3f} s, "
          f"compile {_COMPILE_S[0] - c0:.3f} s", flush=True)
    return out


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _gap(ppx: float, ref: float) -> float:
    return abs(ppx - ref) / ref


def _sds(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _check_mosaic(jitted, *args) -> None:
    """The program the backend runs lowers its Pallas call to a Mosaic
    kernel (`tpu_custom_call`), not to interpret-mode jnp ops."""
    text = jitted.lower(*args).as_text()
    _check("tpu_custom_call" in text,
           f"{getattr(jitted, '__name__', jitted)} has no Mosaic kernel")


def _product(seed: int, n: int, heldout: int, base_vocab: int, topics: int):
    corp = reviews.generate(reviews.SyntheticSpec(
        num_reviews=n + heldout, vocab_size=base_vocab, num_topics=topics,
        seed=seed))
    return corp.reviews[:n], corp.reviews[n:]


# -- one chip ------------------------------------------------------------------


def phase_a(client: VedaliaClient, sz: Sizes, seed: int) -> dict:
    train, held = _product(seed, sz.reviews, sz.heldout, sz.base_vocab,
                           sz.topics)
    prep = client.prepare(train, base_vocab=sz.base_vocab,
                          num_topics=sz.topics)
    fit = client.fit_prepared(prep.corpus_id, backend="auto",
                              num_sweeps=sz.sweeps, seed=seed)
    service = client.server.service
    path = service.sampler(fit.backend)._path()
    print(f"  A: {prep.num_tokens} tokens, auto -> backend={fit.backend} "
          f"path={path}")
    _check((fit.backend, path) == ("alias", "pallas"),
           f"auto resolved to {fit.backend}/{path}, expected alias/pallas")
    rp = client.server.preps[prep.corpus_id]
    state = service.handles[fit.handle_id].state
    _check_mosaic(alias_ops.mh_sweep, rp.cfg, _sds(state),
                  _sds(rp.corpus), _sds(jax.random.PRNGKey(0)))
    ppx = client.perplexity(fit.handle_id, held)
    print(f"  A: train perplexity {fit.perplexity:.3f}, "
          f"held-out {ppx:.3f}")
    _check(bool(np.isfinite(ppx)), "phase A held-out perplexity not finite")
    return dict(train=train, held=held, prep=prep, handle=fit.handle_id)


@functools.partial(jax.jit, static_argnums=0)
def _oracle_resample(cfg, state, corpus, key):
    """`core.gibbs` scores on the kernel's exact noise (same key, same
    (N, K_pad) draw), so argmax agreement isolates the kernel."""
    k = cfg.num_topics
    kp = -(-k // 128) * 128
    n_dt, n_wt, n_t = codec.decode_counts(cfg, state)
    if cfg.quant_spec.packed:
        n_wt = quant.fake_quantize_rows(n_wt, cfg.quant_spec.bits)
    g = jax.random.gumbel(key, (corpus.num_tokens, kp), jnp.float32)[:, :k]
    return gibbs.resample_block(cfg, corpus.docs, corpus.words, state.z,
                                corpus.weights, n_dt, n_wt, n_t, g)


def phase_b(client: VedaliaClient, sz: Sizes, seed: int, a: dict) -> None:
    prep_f32 = client.prepare(a["train"], base_vocab=sz.base_vocab,
                              num_topics=sz.topics, w_bits=None)
    rp_f32 = client.server.preps[prep_f32.corpus_id]
    rp_w8 = client.server.preps[a["prep"].corpus_id]
    modes = [
        ("f32", rp_f32.cfg, prep_f32.corpus_id, rp_f32),
        ("w_bits=8", rp_w8.cfg, a["prep"].corpus_id, rp_w8),
        ("int8", dataclasses.replace(
            rp_w8.cfg, quant=quant.QuantSpec.int8(w_bits=8)),
         a["prep"].corpus_id, rp_w8),
    ]
    kernel, oracle = get_backend("pallas"), get_backend("jnp")
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    for name, cfg, cid, rp in modes:
        corpus = rp.corpus
        state = codec.encode_state(cfg, init_state(cfg, corpus, k0))
        _check_mosaic(gibbs_ops.sweep, cfg, _sds(state), _sds(corpus),
                      _sds(k1))
        z_k = gibbs_ops.sweep_resample(cfg, state, corpus, k1)
        z_o = _oracle_resample(cfg, state, corpus, k1)
        agree = float(jnp.mean((z_k == z_o).astype(jnp.float32)))
        st_k = kernel.run(cfg, corpus, k2, sz.parity_sweeps, state=state)
        st_o = oracle.run(rp.cfg, corpus, k2, sz.parity_sweeps, state=state)
        ppx = []
        for st in (st_k, st_o):
            h = client.adopt(cid, st, sweeps_run=sz.parity_sweeps)
            ppx.append(client.perplexity(h.handle_id, a["held"]))
            client.release(h.handle_id)
        gap = _gap(ppx[0], ppx[1])
        print(f"  B[{name}]: one-sweep argmax agreement {agree:.6f}; "
              f"held-out perplexity kernel {ppx[0]:.3f} vs jnp "
              f"{ppx[1]:.3f} (gap {gap:.4%})")
        _check(agree >= AGREE_MIN, f"B[{name}] agreement {agree}")
        _check(gap <= PPX_TOL, f"B[{name}] perplexity gap {gap:.4%}")
    client.release_corpus(prep_f32.corpus_id)


def phase_c(client: VedaliaClient, sz: Sizes, seed: int) -> None:
    sets = [_product(seed * 1000 + 17 + i, sz.product_reviews,
                     sz.product_heldout, sz.base_vocab, sz.product_topics)
            for i in range(sz.products)]
    fits = client.fit_batch([t for t, _ in sets],
                            num_topics=sz.product_topics,
                            base_vocab=sz.base_vocab, backend="batched",
                            num_sweeps=sz.batch_sweeps, seed=seed)
    path = client.server.service.sampler("batched")._path()
    print(f"  C: {len(fits)} products, backend={fits[0].backend} "
          f"path={path}")
    _check(path == "pallas" and all(f.backend == "batched" for f in fits),
           f"batched fit ran {fits[0].backend}/{path}")
    service = client.server.service
    states = [service.handles[f.handle_id].state for f in fits]
    cfg = service.handles[fits[0].handle_id].cfg
    m, n = 2, max(int(s.z.shape[0]) for s in states)
    i32, f32 = jnp.int32, jnp.float32
    stacked = LDAState(
        z=jax.ShapeDtypeStruct((m, n), i32),
        n_dt=jax.ShapeDtypeStruct((m, cfg.num_docs, cfg.num_topics), i32),
        n_wt=jax.ShapeDtypeStruct((m, cfg.vocab_size, cfg.num_topics), i32),
        n_t=jax.ShapeDtypeStruct((m, cfg.num_topics), i32))
    corpora = Corpus(docs=jax.ShapeDtypeStruct((m, n), i32),
                     words=jax.ShapeDtypeStruct((m, n), i32),
                     weights=jax.ShapeDtypeStruct((m, n), f32))
    _check_mosaic(gibbs_ops.sweep_many, cfg, stacked, corpora,
                  jax.ShapeDtypeStruct((m, 2), jnp.uint32))
    worst = 0.0
    for i, (f, (train, held)) in enumerate(zip(fits, sets)):
        ref = client.fit(train, num_topics=sz.product_topics,
                         base_vocab=sz.base_vocab, backend="jnp",
                         num_sweeps=sz.batch_sweeps, seed=seed + 100 + i)
        p_b = client.perplexity(f.handle_id, held)
        p_o = client.perplexity(ref.handle_id, held)
        gap = _gap(p_b, p_o)
        worst = max(worst, gap)
        print(f"  C[{i}]: held-out perplexity batched {p_b:.3f} vs "
              f"sequential jnp {p_o:.3f} (gap {gap:.4%})")
        _check(gap <= PPX_TOL, f"C[{i}] perplexity gap {gap:.4%}")
        client.release(f.handle_id)
        client.release(ref.handle_id)
    print(f"  C: worst per-model gap {worst:.4%}")


def phase_d(client: VedaliaClient, sz: Sizes, seed: int, a: dict) -> None:
    new, _ = _product(seed + 7, sz.new_reviews, 0, sz.base_vocab, sz.topics)
    upd = client.update(a["handle"], new, seed=seed)
    print(f"  D: update +{upd.num_new_reviews} reviews ({upd.kind}), "
          f"perplexity {upd.perplexity:.3f}")
    full = client.sync_view(a["handle"], max_topics=8)
    again = client.sync_view(a["handle"], max_topics=8)
    print(f"  D: full view {len(full.topics)} topics "
          f"{full.payload_bytes} B; unchanged delta {len(again.topics)} "
          f"topics {again.payload_bytes} B")
    _check(full.valid and len(full.topics) > 0, "full view invalid/empty")
    _check(len(again.topics) == 0,
           f"unchanged delta re-sent {len(again.topics)} topics")
    top = client.top_reviews(a["handle"], full.topic_ids[0], n=5)
    ppx = client.perplexity(a["handle"])
    print(f"  D: top reviews of topic {top.topic_id}: {top.review_ids}; "
          f"perplexity {ppx:.3f}")
    _check(len(top.review_ids) > 0 and bool(np.isfinite(ppx)),
           "top reviews / perplexity")


def run_one_chip(sz: Sizes, seed: int) -> None:
    client = VedaliaClient()
    a = _phase("A large fit", lambda: phase_a(client, sz, seed))
    _phase("B kernel parity", lambda: phase_b(client, sz, seed, a))
    _phase("C batched fit", lambda: phase_c(client, sz, seed))
    _phase("D update+view", lambda: phase_d(client, sz, seed, a))


# -- four chips ----------------------------------------------------------------


def phase_pod(sz: Sizes, seed: int) -> None:
    _check(jax.device_count() >= 4,
           f"--chips 4 needs 4 devices, JAX sees {jax.device_count()}")
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
    client = VedaliaClient(backend_opts={"pserver": dict(
        mesh=mesh, staleness=2, local="pallas")})
    train, held = _product(seed, sz.pod_reviews, sz.pod_heldout,
                           sz.pod_base_vocab, sz.topics)
    # f32 counts: the fused multi-sweep pserver program (and its
    # staleness window) serves the float path.
    prep = client.prepare(train, base_vocab=sz.pod_base_vocab,
                          num_topics=sz.topics, w_bits=None)
    cfg = client.server.preps[prep.corpus_id].cfg
    print(f"  P: {prep.num_tokens} tokens, V={cfg.vocab_size}, "
          f"K={cfg.num_topics}, mesh {dict(mesh.shape)}")
    fit = client.fit_prepared(prep.corpus_id, backend="pserver",
                              num_sweeps=sz.pod_sweeps, seed=seed)
    n_wt = client.server.service.handles[fit.handle_id].state.n_wt
    shards = sorted((s.device.id, tuple(s.data.shape))
                    for s in n_wt.addressable_shards)
    print(f"  P: n_wt {tuple(n_wt.shape)} sharding {n_wt.sharding}; "
          f"per-device shards {shards}")
    _check(len(n_wt.sharding.device_set) == 4,
           f"n_wt lives on {len(n_wt.sharding.device_set)} device(s)")
    _check(all(shape[0] < cfg.vocab_size for _, shape in shards),
           "n_wt is not vocab-sharded")
    ref = client.fit_prepared(prep.corpus_id, backend="jnp",
                              num_sweeps=sz.pod_sweeps, seed=seed)
    p_p = client.perplexity(fit.handle_id, held)
    p_o = client.perplexity(ref.handle_id, held)
    gap = _gap(p_p, p_o)
    print(f"  P: held-out perplexity pserver {p_p:.3f} vs one-device jnp "
          f"{p_o:.3f} (gap {gap:.4%})")
    _check(gap <= PPX_TOL, f"pserver perplexity gap {gap:.4%}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {dev.platform}); "
              "nothing was run", file=sys.stderr)
        return 1
    cache = compile_cache.enable()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(f"chip_smoke: {jax.device_count()} x {dev.device_kind}, "
          f"jax {jax.__version__}, compile cache {cache}", flush=True)

    sz = Sizes()
    if args.chips == 4:
        _phase("P pserver 2x2", lambda: phase_pod(sz, args.seed))
    else:
        run_one_chip(sz, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
