"""The `batched` route on the chip: the model-grid Pallas path, whose
lowered sweep holds the fused kernel (`tpu_custom_call`)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def check(run, route: dict) -> None:
    from repro.core.types import Corpus, LDAState
    from repro.kernels.lda_gibbs import ops as gibbs_ops
    from repro.serving import batch_engine

    service = run.service
    path = service.sampler("batched")._path()
    if path != route["path"]:
        raise AssertionError(f"batched path is {path}, expected "
                             f"{route['path']}")
    h = service.handles[run.handles[0]]
    cfg = h.cfg
    m = 2
    n = batch_engine.length_bucket(h.model.corpus.num_tokens)
    d = batch_engine.doc_bucket(cfg.num_docs)
    bcfg = dataclasses.replace(cfg, num_docs=d)
    i32, f32 = jnp.int32, jnp.float32
    sds = jax.ShapeDtypeStruct
    states = LDAState(z=sds((m, n), i32), n_dt=sds((m, d, cfg.num_topics), i32),
                      n_wt=sds((m, cfg.vocab_size, cfg.num_topics), i32),
                      n_t=sds((m, cfg.num_topics), i32))
    corpora = Corpus(docs=sds((m, n), i32), words=sds((m, n), i32),
                     weights=sds((m, n), f32))
    text = gibbs_ops.sweep_many.lower(
        bcfg, states, corpora, sds((m, 2), jnp.uint32)).as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("the batched sweep has no Mosaic kernel")
