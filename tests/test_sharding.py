"""Sharding rules + 1-device-mesh jit integration (the CPU-runnable slice
of the distribution layer; the 256/512-chip path is covered by dryrun)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.models.params import PDef, partition_specs
from repro.sharding import specs as S
from repro.train.optim import OptConfig, make_optimizer
from repro.train.step import make_train_step


class FakeMesh:
    def __init__(self, sizes):
        self.axis_names = tuple(sizes)
        import numpy as _np

        self.devices = _np.empty(tuple(sizes.values()))


def test_build_rules_drops_non_divisible_axes():
    cfg = configs.get("qwen2-7b")  # vocab 152064, heads 28*128=3584
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = S.build_rules(cfg, mesh)
    assert rules["embed"] == "data"  # 3584 % 16 == 0
    assert rules["qkv"] == "model"  # 3584 % 16 == 0
    assert rules["vocab"] == "model"  # 152064 % 16 == 0
    # a mesh the dims don't divide -> replicate (3584 = 7*512 divides 7,
    # so use 13 which divides neither d_model nor the vocab)
    mesh_odd = FakeMesh({"data": 13, "model": 13})
    rules_odd = S.build_rules(cfg, mesh_odd)
    assert rules_odd["embed"] is None and rules_odd["vocab"] is None


def test_all_full_configs_shard_on_production_mesh():
    """Every assigned arch's weight dims divide the (16,16) mesh (or are
    explicitly replicated by the rules) — partition_specs never errors."""
    mesh = FakeMesh({"data": 16, "model": 16})
    for name in configs.ASSIGNED + ["gemma2-9b-sw"]:
        cfg = configs.get(name)
        rules = S.build_rules(cfg, mesh)
        pspecs = partition_specs(M.build_schema(cfg), rules)
        # sharded dims must divide 16
        for pdef, spec in zip(
            jax.tree.leaves(M.build_schema(cfg),
                            is_leaf=lambda x: isinstance(x, PDef)),
            jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P)),
        ):
            for dim, axis in zip(pdef.shape, spec):
                if axis == "data" or axis == "model":
                    assert dim % 16 == 0, (name, pdef.shape, spec)


def test_activation_specs_batch_fallback():
    cfg = configs.get("qwen2-7b")
    mesh = FakeMesh({"data": 16, "model": 16})
    act = S.activation_specs(cfg, mesh, "decode", global_batch=1)
    # batch of 1 cannot shard over 16 devices -> replicated batch dim
    assert act["residual"][0] is None
    act2 = S.activation_specs(cfg, mesh, "decode", global_batch=128)
    assert act2["residual"][0] == "data"
    # decode KV cache shards its sequence dim over 'model'
    assert act2["kv_cache"][1] == "model"


def test_constrain_noop_outside_context():
    x = jnp.ones((4, 4))
    assert S.constrain(x, "residual") is x


def test_jit_train_step_on_1x1_mesh():
    """Full sharded-jit path (in_shardings from the same code the dry-run
    uses) on the dry-run's 1x1 host mesh — numerics must match the
    unsharded step."""
    cfg = configs.get("gemma2-9b").reduced()
    mesh = make_host_mesh()
    pspecs = M.model_pspecs(cfg, mesh)
    named = lambda t: jax.tree.map(
        lambda s: NamedSharding(mesh, s), t,
        is_leaf=lambda x: isinstance(x, P))
    params = M.init_model(cfg, jax.random.PRNGKey(0))
    opt = make_optimizer(OptConfig(lr=1e-3, warmup_steps=0, decay_steps=10))
    opt_state = opt.init(params)
    batch = {k: jnp.asarray(v) for k, v in
             M.real_batch(cfg, "train", 4, 32, jax.random.PRNGKey(1)).items()}
    step = make_train_step(cfg, opt)

    act = S.activation_specs(cfg, mesh, "train", global_batch=4)
    with mesh, S.use_activation_specs(act):
        fn = jax.jit(
            step,
            in_shardings=(named(pspecs), named(opt.state_pspecs(pspecs)),
                          named(M.batch_pspecs(cfg, mesh, "train", 4)),
                          NamedSharding(mesh, P())),
            out_shardings=(named(pspecs), named(opt.state_pspecs(pspecs)),
                           None),
        )
        p1, o1, m1 = fn(params, opt_state, batch, jnp.int32(0))

    p2, o2, m2 = jax.jit(step)(params, opt_state, batch, jnp.int32(0))
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-3)


def test_cache_pspecs_structure_matches_cache():
    for name in ("qwen2-7b", "gemma2-9b", "zamba2-2.7b", "rwkv6-1.6b",
                 "whisper-base", "llama-3.2-vision-90b"):
        cfg = configs.get(name)
        mesh = FakeMesh({"data": 16, "model": 16})
        cache = M.abstract_cache(cfg, 128, 32768)
        cspecs = M.cache_pspecs(cfg, mesh, 128, 32768, kind="decode")
        assert set(cache) == set(cspecs)
        for k in cache:
            assert len(cspecs[k]) == len(cache[k].shape), (name, k)
            for dim, ax in zip(cache[k].shape, cspecs[k]):
                if ax in ("data", "model"):
                    assert dim % 16 == 0, (name, k)
