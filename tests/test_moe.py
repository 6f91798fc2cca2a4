"""Mixture-of-Experts + expert parallelism (VERDICT missing #10; reference
has no in-tree MoE — vLLM delegation — so the contract here is the public
GShard/Switch semantics: top-k capacity routing, aux losses, EP sharding)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, moe
from ray_tpu.models.training import (OptimizerConfig, init_train_state,
                                     make_train_step)
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.parallel.sharding import ShardingRules, set_mesh


@pytest.fixture(scope="module")
def cfg():
    return moe.CONFIGS["debug"]


def _batch(cfg, batch=4, seq=32, seed=0):
    rng = np.random.default_rng(seed)
    return {"tokens": jnp.asarray(rng.integers(
        0, cfg.base.vocab_size, (batch, seq), dtype=np.int32))}


def test_forward_shapes_and_finite(cfg):
    params = moe.init_params(cfg, jax.random.key(0))
    batch = _batch(cfg)
    logits, metrics = moe.forward(params, batch["tokens"], cfg)
    assert logits.shape == (4, 32, cfg.base.vocab_size)
    assert jnp.isfinite(logits).all()
    assert float(metrics["dropped"]) < 0.5
    assert float(metrics["aux"]) > 0


def test_single_expert_equals_dense_mlp(cfg):
    """E=1, K=1, capacity ≥ tokens: MoE must reduce EXACTLY to the dense
    FFN (routing weight normalizes to 1, nothing dropped) — validates the
    dispatch/combine einsum algebra against llama's mlp."""
    base = cfg.base
    one = moe.MoEConfig(base=base, n_experts=1, top_k=1,
                        capacity_factor=2.0)
    params = moe.init_params(one, jax.random.key(1))
    dense_params = llama.init_params(base, jax.random.key(1))
    # transplant the single expert's weights into the dense model
    dense_layers = dict(dense_params["layers"])
    dense_layers["w_gate"] = params["layers"]["we_gate"][:, 0]
    dense_layers["w_up"] = params["layers"]["we_up"][:, 0]
    dense_layers["w_down"] = params["layers"]["we_down"][:, 0]
    # align the rest of the tree (attention/norm/embed weights)
    for name in ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm"):
        dense_layers[name] = params["layers"][name]
    dense_params = {**params}
    dense_params.pop("lm_head", None)
    dense_params = {k: v for k, v in params.items() if k != "layers"}
    dense_params["layers"] = {k: v for k, v in dense_layers.items()
                              if k not in ("router", "we_gate", "we_up",
                                           "we_down")}
    tokens = _batch(one)["tokens"]
    got, metrics = moe.forward(params, tokens, one)
    want = llama.forward(dense_params, tokens, base)
    assert float(metrics["dropped"]) == 0.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_capacity_dropping_is_graceful(cfg):
    """Starved capacity must drop tokens (metric > 0) but keep the loss
    finite — dropped tokens ride the residual stream."""
    tight = dataclasses.replace(cfg, capacity_factor=0.25)
    params = moe.init_params(tight, jax.random.key(2))
    batch = _batch(tight)
    loss, metrics = moe.loss_fn(params, batch, tight)
    assert jnp.isfinite(loss)
    assert float(metrics["dropped_frac"]) > 0.0


def test_aux_loss_near_one_at_uniform(cfg):
    """Switch aux = E·Σ f_e·p_e ≈ 1 when routing is uniform (fresh router
    ≈ uniform); heavy collapse pushes it toward E."""
    params = moe.init_params(cfg, jax.random.key(3))
    _, metrics = moe.forward(params, _batch(cfg)["tokens"], cfg)
    assert 0.8 < float(metrics["aux"]) < 1.5


def test_grads_reach_experts_and_router(cfg):
    params = moe.init_params(cfg, jax.random.key(4))
    grads = jax.grad(
        lambda p, b: moe.loss_fn(p, b, cfg)[0])(params, _batch(cfg))
    g_router = np.abs(np.asarray(grads["layers"]["router"])).max()
    g_exp = np.abs(np.asarray(grads["layers"]["we_gate"])).max()
    assert g_router > 0 and g_exp > 0
    assert np.isfinite(jax.tree.reduce(
        lambda a, l: a + float(np.sum(np.square(l))),
        grads, 0.0))


def test_ep_sharded_train_step_matches_single_device(cfg):
    """The full SPMD train step on the 8-device mesh (experts sharded over
    fsdp per the rule table) must produce the same loss as single-device
    execution — GSPMD resharding (all-to-all) is a layout change, not math."""
    mesh = make_mesh(MeshConfig(dp=2, fsdp=4), devices=jax.devices())
    rules = ShardingRules(heads=None, kv_heads=None, mlp="fsdp", vocab=None,
                          embed_fsdp="fsdp")
    opt = OptimizerConfig(warmup_steps=1, decay_steps=10).make()
    batch = _batch(cfg, batch=8, seq=32)

    with set_mesh(mesh):
        state, _ = init_train_state(
            lambda k: moe.init_params(cfg, k), moe.param_logical_axes(cfg),
            opt, mesh, rules, jax.random.key(5))
        # expert tensors must actually be sharded over the ep axes
        spec = state.params["layers"]["we_gate"].sharding.spec
        assert "fsdp" in str(spec)
        step = make_train_step(
            lambda p, b: moe.loss_fn(p, b, cfg, rules, mesh=mesh),
            opt, mesh, rules)
        state1, metrics = step(state, batch)
        sharded_loss = float(metrics["loss"])
        # loss decreases over a few more steps (training works end-to-end)
        for _ in range(5):
            state1, metrics = step(state1, batch)
        assert float(metrics["loss"]) < sharded_loss

    # single-device oracle
    params = moe.init_params(cfg, jax.random.key(5))
    oracle, _ = moe.loss_fn(params, batch, cfg)
    # init is sharded-from-birth with identical seed/key → same params
    np.testing.assert_allclose(sharded_loss, float(oracle), rtol=2e-4)


def test_param_counts():
    cfg = moe.CONFIGS["debug"]
    params = moe.init_params(cfg, jax.random.key(0))
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    assert n == cfg.num_params()
    assert cfg.active_params() < cfg.num_params()
    assert cfg.flops_per_token(128) > 0
