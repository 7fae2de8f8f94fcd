"""Mesh, sharding, and device-collective tests on the virtual 8-device CPU
mesh (the load-bearing multi-chip test mechanism, SURVEY §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel import MeshConfig, build_mesh, local_mesh
from ray_tpu.parallel import collectives as col

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")


def test_mesh_config_resolve():
    assert MeshConfig(dp=-1).resolve(8)["dp"] == 8
    sizes = MeshConfig(dp=2, tp=2, sp=2).resolve(8)
    assert sizes == {"dp": 2, "fsdp": 1, "pp": 1, "sp": 2, "tp": 2, "ep": 1}
    with pytest.raises(ValueError):
        MeshConfig(dp=3).resolve(8)


def test_build_mesh_axes():
    mesh = build_mesh(MeshConfig(dp=2, tp=4))
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4
    assert mesh.devices.size == 8


def test_psum_shard_map():
    mesh = local_mesh(8, axis="dp")
    x = np.arange(8, dtype=np.float32)
    out = col.mesh_allreduce(mesh, x, axis_name="dp")
    np.testing.assert_allclose(np.asarray(out), np.full(1, x.sum()))


def test_all_gather_and_ppermute():
    mesh = local_mesh(8, axis="sp")

    def body(x):
        g = col.all_gather(x, "sp", axis=0)
        r = col.ppermute_ring(x, "sp", mesh, shift=1)
        return g, r

    fn = col.shard_map(body, mesh=mesh, in_specs=P("sp"), out_specs=(P(), P("sp")))
    x = np.arange(8, dtype=np.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("sp")))
    gathered, rotated = jax.jit(fn)(xs)
    np.testing.assert_allclose(np.asarray(gathered), x)
    # shift=1 sends shard i to position i+1: rotated[i] = x[i-1]
    np.testing.assert_allclose(np.asarray(rotated), np.roll(x, 1))


def test_all_to_all():
    mesh = local_mesh(8, axis="ep")

    def body(x):  # x local: [1, 8] -> transpose-ish exchange
        return col.all_to_all(x, "ep", split_axis=1, concat_axis=0)

    # Tiled all_to_all is a global identity that RESHARDS: row-sharded in,
    # column-sharded out (the Ulysses sequence<->head redistribution
    # primitive). Each device i ends up holding column i.
    fn = col.shard_map(body, mesh=mesh, in_specs=P("ep", None), out_specs=P(None, "ep"))
    x = np.arange(64, dtype=np.float32).reshape(8, 8)
    xs = jax.device_put(x, NamedSharding(mesh, P("ep", None)))
    out = jax.jit(fn)(xs)
    np.testing.assert_allclose(np.asarray(out), x)
    assert out.sharding.spec == P(None, "ep")


def test_transformer_sharded_matches_single_device():
    import optax

    from ray_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                                n_kv_heads=4, d_ff=172, max_seq=32, dtype=jnp.float32)
    model = tfm.Transformer(cfg)
    rng = jax.random.PRNGKey(1)
    tokens = jax.random.randint(rng, (4, 17), 0, cfg.vocab_size, dtype=jnp.int32)
    params = model.init(rng, tokens[:, :-1])

    ref_loss = float(tfm.loss_fn(model, params, tokens))

    mesh = build_mesh(MeshConfig(dp=2, sp=2, tp=2))
    pspecs = tfm.param_specs(params)
    shardings = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs)
    params_s = jax.tree_util.tree_map(jax.device_put, params, shardings)
    tokens_s = jax.device_put(tokens, NamedSharding(mesh, P(("dp", "fsdp"), None)))
    with jax.set_mesh(mesh):
        loss = float(jax.jit(lambda p, t: tfm.loss_fn(model, p, t))(params_s, tokens_s))
    assert abs(loss - ref_loss) < 1e-4


@pytest.mark.parametrize("enter", [jax.set_mesh, lambda mesh: mesh],
                         ids=["set_mesh", "with_mesh"])
def test_seq_shard_constraint_is_applied(enter):
    """The sequence-parallel constraint between blocks must really be there
    under a dp/sp mesh, entered either way JAX 0.9.0 allows. A dropped
    constraint leaves every loss equal, so the loss tests cannot see it:
    this one looks at the sharding and at the traced program."""
    from ray_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                                n_kv_heads=4, d_ff=172, max_seq=32, dtype=jnp.float32)
    model = tfm.Transformer(cfg)
    tokens = jnp.zeros((4, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)
    mesh = build_mesh(MeshConfig(dp=2, sp=2, tp=2))
    with enter(mesh):
        out = jax.jit(tfm._seq_shard)(jnp.zeros((4, 16, 64)))
        jaxpr = jax.make_jaxpr(lambda p, t: model.apply(p, t))(params, tokens)
    # fsdp has size 1 here, so XLA's normalised spec leaves it out.
    assert out.sharding.spec == P("dp", "sp")
    assert out.sharding.shard_shape(out.shape) == (2, 8, 64)
    assert str(jaxpr).count("sharding_constraint") == cfg.n_layers


def test_seq_shard_skips_by_rule_without_the_axes():
    """No mesh, or a mesh without dp/fsdp/sp (a tp-only serving mesh): the
    constraint is skipped by an explicit test, not by a swallowed error."""
    from ray_tpu.models import transformer as tfm

    x = jnp.zeros((4, 16, 64))
    assert "sharding_constraint" not in str(jax.make_jaxpr(tfm._seq_shard)(x))
    with jax.set_mesh(local_mesh(8, axis="tp")):
        assert "sharding_constraint" not in str(jax.make_jaxpr(tfm._seq_shard)(x))


def test_gqa_attention_matches_mha_expansion():
    from ray_tpu.ops import dot_product_attention

    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (2, 16, 8, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 2, 16))
    out_gqa = dot_product_attention(q, k, v, causal=True, use_pallas=False)
    k_full = jnp.repeat(k, 4, axis=2)
    v_full = jnp.repeat(v, 4, axis=2)
    out_full = dot_product_attention(q, k_full, v_full, causal=True, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out_gqa), np.asarray(out_full), atol=1e-5)


def test_dryrun_multichip_entrypoint():
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, "-c",
         "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)"],
        capture_output=True, text=True, timeout=300,
        cwd=__import__("os").path.dirname(__import__("os").path.dirname(__file__)))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_pipeline_matches_sequential():
    """GPipe over pp=2: loss AND grads through the microbatched ring must
    equal the single-device sequential apply (backward pipeline via the
    autodiff transpose of ppermute)."""
    from jax.sharding import Mesh

    from ray_tpu.parallel.pipeline import (
        PipelineConfig, init_params, pipeline_loss_fn, reference_loss)

    cfg = PipelineConfig(vocab_size=128, d_model=64, n_layers=4, n_heads=4,
                         d_ff=128, n_microbatches=4)
    params = init_params(cfg)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (8, 17)), jnp.int32)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("pp",))
    loss_fn = pipeline_loss_fn(cfg, mesh)
    with jax.set_mesh(mesh):
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, tokens)
    ref_loss = float(reference_loss(cfg, params, tokens))
    assert abs(float(loss) - ref_loss) < 1e-5
    ref_grads = jax.jit(jax.grad(
        lambda p, t: reference_loss(cfg, p, t)))(params, tokens)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(ref_grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_moe_ep_sharding_matches_single_device():
    """Top-2 MoE with experts sharded over ep=2: loss equals the unsharded
    forward (dense dispatch is deterministic)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import transformer as tfm
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    cfg = tfm.TransformerConfig(vocab_size=128, d_model=64, n_layers=2,
                                n_heads=4, n_kv_heads=4, d_ff=96, max_seq=32,
                                dtype=jnp.float32, moe_experts=4)
    model = tfm.Transformer(cfg)
    tokens = jnp.asarray(
        np.random.RandomState(1).randint(0, cfg.vocab_size, (4, 17)), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens[:, :-1])
    ref = float(tfm.loss_fn(model, params, tokens))

    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, ep=2), devices=jax.devices()[:8])
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tfm.param_specs(params))
    params_s = jax.tree_util.tree_map(jax.device_put, params, shardings)
    tokens_s = jax.device_put(tokens, NamedSharding(mesh, P(("dp", "fsdp"), None)))
    with jax.set_mesh(mesh):
        loss = float(jax.jit(
            lambda p, t: tfm.loss_fn(model, p, t))(params_s, tokens_s))
    assert abs(loss - ref) < 1e-4
