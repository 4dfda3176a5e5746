"""The `alias` route on the chip: the fused proposal + Metropolis-Hastings
Pallas path, whose lowered sweep holds the kernel (`tpu_custom_call`)."""

from __future__ import annotations

import jax


def check(run, route: dict) -> None:
    from repro.kernels.alias_mh import ops as alias_ops

    service = run.service
    sampler = service.sampler("alias")
    path = sampler._path()
    if path != route["path"]:
        raise AssertionError(f"alias path is {path}, expected "
                             f"{route['path']}")
    steps = int(run.cell.config["model"]["mh_steps"])
    if sampler.mh_steps != steps:
        raise AssertionError(f"alias sampler runs {sampler.mh_steps} MH "
                             f"rounds a sweep; the configuration states "
                             f"{steps}")
    h = service.handles[run.handles[0]]

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    text = alias_ops.mh_sweep.lower(
        h.cfg, sds(h.state), sds(h.model.corpus),
        sds(jax.random.PRNGKey(0)), sampler.mh_steps).as_text()
    if "tpu_custom_call" not in text:
        raise AssertionError("the alias sweep has no Mosaic kernel")
