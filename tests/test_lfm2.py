"""``ray_tpu.models.lfm2`` on the train path (PR 57): the model against
the plain reference (``benchmark/reference/lfm2.py``, which imports
nothing of the program) in float32 at tiny sizes, loss and EVERY leaf's
gradient, uncut and as one chip's share; what the equations fix (the
convolution is causal and starts from zeros, the split is ``B, C, z``,
the q/k norms come before the rotary); the choice bias as a buffer; and
the normal path, ``JaxTrainer`` -> ``make_train_step`` -> ``loss_fn``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2 as reference
from ray_tpu.models import lfm2, moe
from ray_tpu.models.training import (OptimizerConfig, init_train_state,
                                     make_train_step)
from ray_tpu.ops.short_conv import short_conv
from ray_tpu.parallel.mesh import MeshConfig, make_mesh
from ray_tpu.parallel.sharding import ShardingRules

F32 = jnp.float32
WHOLE = lfm2.Lfm2Config(dtype=F32)
# one chip of four a layer: experts 2-3 of 8, the router 8 wide
SHARE = dataclasses.replace(WHOLE, experts_held=(2, 2))


def spec_of(c: lfm2.Lfm2Config) -> dict:
    """The configuration file the reference reads, from the program's
    config (the adapter's ``program_config`` the other way round)."""
    return {"layer_types": list(c.layer_types),
            "num_hidden_layers": c.n_layers,
            "num_dense_layers": c.num_dense_layers,
            "num_experts": c.experts_held[1],
            "experts_first": c.experts_held[0],
            "num_experts_per_tok": c.top_k,
            "routed_scaling_factor": c.route_scale, "norm_eps": c.norm_eps,
            "rope_parameters": {"rope_theta": c.rope_theta}}


def seeded(c, seed=0):
    """Weights with norms and a choice bias that are not zeros, so that
    a dropped ``1 + w`` or an ignored bias shows."""
    params = lfm2.init_params(c, jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 100), 64))

    def draw(path, leaf):
        name = path[-1].key
        if name.endswith("_norm"):
            return jax.random.normal(next(keys), leaf.shape, F32) * 0.1
        if name == "router_bias":
            return jax.random.normal(next(keys), leaf.shape, F32) * 0.05
        return leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def tokens_of(c, n=24, seed=1):
    return jax.random.randint(jax.random.key(seed), (n,), 0, c.vocab_size)


@pytest.mark.parametrize("config", [WHOLE, SHARE], ids=["held-all", "share"])
def test_loss_and_every_leafs_gradient_are_the_references(config):
    """float32 on both sides under the highest matmul precision: what
    differs is the order of sums (the flash backward's blocks, the row
    buffer's tiles), so 2e-4 of a leaf's norm is the tolerance."""
    params, toks = seeded(config), tokens_of(config)
    with jax.default_matmul_precision("highest"):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: lfm2.loss_fn(p, {"tokens": toks[None]}, config),
            has_aux=True)(params)
        want_loss, want = reference.loss_and_grads(params, toks,
                                                   spec_of(config))
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    for (path, got), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want)):
        name = jax.tree_util.keystr(path)
        if path[-1].key == "router_bias":
            assert not np.asarray(got).any() and not np.asarray(ref).any()
            continue
        assert reference.rel_err(got, ref) < 2e-4, name
    assert float(metrics["moe"]["expert_pairs_dropped"]) == 0.0
    assert float(metrics["moe"]["expert_layer_calls"]) == 4.0


def test_the_convolution_is_causal_and_starts_from_zeros():
    v = jax.random.normal(jax.random.key(0), (2, 12, 8), F32)
    k = jax.random.normal(jax.random.key(1), (8, 3), F32)
    out = short_conv(v, k)
    later = short_conv(v.at[:, 7].add(5.0), k)
    assert np.array_equal(out[:, :7], later[:, :7])         # t < 7 unmoved
    assert not np.allclose(out[:, 7:10], later[:, 7:10])    # t, t+1, t+2
    assert np.array_equal(out[:, 10:], later[:, 10:])       # three taps
    # position 0 sees itself alone, position 1 itself and one before
    np.testing.assert_allclose(out[:, 0], v[:, 0] * k[:, 2], rtol=1e-6)
    np.testing.assert_allclose(
        out[:, 1], v[:, 1] * k[:, 2] + v[:, 0] * k[:, 1], rtol=1e-6)
    np.testing.assert_allclose(
        out[0], reference.short_conv(v[0], k), rtol=1e-6, atol=1e-6)


def test_a_tokens_logits_do_not_see_the_tokens_after_it():
    params, toks = seeded(WHOLE), tokens_of(WHOLE)
    base, _ = lfm2.forward(params, toks[None], WHOLE)
    moved, _ = lfm2.forward(
        params, toks.at[15].set((toks[15] + 1) % WHOLE.vocab_size)[None],
        WHOLE)
    np.testing.assert_allclose(base[0, :15], moved[0, :15], atol=1e-5)
    assert not np.allclose(base[0, 15:], moved[0, 15:], atol=1e-3)


@pytest.mark.parametrize("order", ["BzC", "CBz", "zCB"])
def test_the_split_is_b_c_z(order):
    """``B * z`` commutes, so ``zCB`` is the same mixer; any order that
    moves ``C`` is another."""
    layer = seeded(WHOLE)["layers"][0]
    u = jax.random.normal(jax.random.key(2), (1, 16, WHOLE.hidden), F32)
    with jax.default_matmul_precision("highest"):
        got = lfm2.conv_mixer(u, layer)[0]
        mixers = {o: reference.conv_mixer(
            u[0], layer["w_in"], layer["conv_k"], layer["w_out"], order=o)
            for o in ("BCz", order)}
    assert reference.rel_err(got, mixers["BCz"]) < 1e-5
    assert (reference.rel_err(got, mixers[order]) > 0.1) == (order != "zCB")


def test_the_qk_norms_come_before_the_rotary():
    config = WHOLE
    layer = seeded(config)["layers"][1]
    u = jax.random.normal(jax.random.key(3), (1, 16, config.hidden), F32)
    from ray_tpu.ops.rope import rope_frequencies

    cos, sin = rope_frequencies(config.head_dim, 16, config.rope_theta)
    keys = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
    with jax.default_matmul_precision("highest"):
        got = lfm2.attention_mixer(u, layer, cos, sin, config,
                                   ShardingRules())[0]
        first, after = (reference.attention_mixer(
            u[0], {k: layer[k] for k in keys}, theta=config.rope_theta,
            eps=config.norm_eps, norm_first=f) for f in (True, False))
    assert reference.rel_err(got, first) < 1e-5
    assert reference.rel_err(got, after) > 1e-2


def test_the_choice_bias_chooses_and_is_no_parameter():
    """It moves the loss through the choice, gets zeros for a gradient,
    and under ``frozen_buffers`` no update (not the weight decay's
    either) and no optimizer state."""
    import optax

    params, toks = seeded(WHOLE), tokens_of(WHOLE)
    batch = {"tokens": toks[None]}
    tilted = jax.tree_util.tree_map_with_path(
        lambda p, a: a.at[0].set(10.0) if p[-1].key == "router_bias" else a,
        params)
    loss = lambda p: lfm2.loss_fn(p, batch, WHOLE)[0]
    assert abs(float(loss(params)) - float(loss(tilted))) > 1e-4
    grads = jax.grad(loss)(tilted)
    opt = lfm2.frozen_buffers(
        OptimizerConfig(warmup_steps=1, weight_decay=0.5).make(), params)
    state = opt.init(tilted)
    held = sum(a.size for a in jax.tree.leaves(state)
               if hasattr(a, "size") and a.ndim)
    biases = sum(l["router_bias"].size for l in params["layers"]
                 if "router_bias" in l)
    total = sum(a.size for a in jax.tree.leaves(params))
    assert held == 2 * (total - biases)                 # mu and nu, no more
    for _ in range(2):                                  # step 0 is rate 0
        updates, state = opt.update(grads, state, tilted)
    new = optax.apply_updates(tilted, updates)
    for old, layer, g in zip(tilted["layers"], new["layers"],
                             grads["layers"]):
        if "router_bias" in layer:
            assert not np.asarray(g["router_bias"]).any()
            assert np.array_equal(layer["router_bias"], old["router_bias"])
            assert not np.array_equal(layer["router"], old["router"])


def test_the_axes_and_shapes_trees_are_the_params():
    for config in (WHOLE, SHARE):
        params = lfm2.init_params(config, jax.random.key(0))
        shapes = lfm2.param_shapes(config)
        axes = lfm2.param_logical_axes(config)
        is_leaf = lambda t: isinstance(t, tuple)
        assert jax.tree.structure(params) == jax.tree.structure(
            shapes, is_leaf=is_leaf) == jax.tree.structure(
                axes, is_leaf=is_leaf)
        for a, s, ax in zip(jax.tree.leaves(params),
                            jax.tree.leaves(shapes, is_leaf=is_leaf),
                            jax.tree.leaves(axes, is_leaf=is_leaf)):
            assert a.shape == s and len(ax) == len(s)
    with pytest.raises(ValueError, match="layer_types"):
        lfm2.Lfm2Config(layer_types=("conv", "mamba"))


def test_remat_changes_no_number():
    params, toks = seeded(WHOLE), tokens_of(WHOLE)
    f = lambda c: jax.value_and_grad(
        lambda p: lfm2.loss_fn(p, {"tokens": toks[None]}, c)[0])(params)
    (la, ga), (lb, gb) = f(WHOLE), f(dataclasses.replace(WHOLE, remat=False))
    assert float(la) == pytest.approx(float(lb), rel=1e-6)
    for a, b in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


# ------------------------------------------------------------ the normal path
def _train_losses(config, steps, seed):
    """``make_train_step`` around ``lfm2.loss_fn`` on one CPU device, as
    the benchmark's adapter builds it."""
    mesh = make_mesh(MeshConfig(dp=1), devices=jax.devices()[:1])
    rules = ShardingRules()
    opt = lfm2.frozen_buffers(
        OptimizerConfig(learning_rate=3e-3, warmup_steps=2).make(),
        lfm2.param_shapes(config))
    with jax.sharding.set_mesh(mesh):
        state, _ = init_train_state(
            lambda k: lfm2.init_params(config, k),
            lfm2.param_logical_axes(config), opt, mesh, rules,
            jax.random.key(seed))
        step = make_train_step(
            lambda p, b: lfm2.loss_fn(p, b, config, rules), opt, mesh, rules)
        rng = np.random.default_rng(seed)
        data = rng.integers(0, config.vocab_size, (4, 32), dtype=np.int32)
        losses, counters = [], []
        for _ in range(steps):
            state, m = step(state, {"tokens": jnp.asarray(data)})
            losses.append(float(m["loss"]))
            counters.append({k: float(v) for k, v in m["moe"].items()})
    return losses, counters


def test_jax_trainer_trains_the_model_end_to_end(tmp_path):
    """``JaxTrainer`` -> ``make_train_step`` -> ``lfm2.loss_fn`` on the
    CPU: the loss falls over 20 steps on a batch seen again and again,
    the same seed gives the same losses bit for bit, and the step's
    counters say ``tokens * top_k * held / experts`` pairs a routed
    layer within the routing's spread, none dropped."""
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def loop(config):
        from ray_tpu import train
        from tests.test_lfm2 import SHARE, _train_losses

        losses, counters = _train_losses(SHARE, 20, config["seed"])
        train.report({"losses": losses, "counters": counters})

    ray_tpu.init(num_cpus=2, num_tpus=0)
    try:
        runs = [JaxTrainer(
            loop, train_loop_config={"seed": 5},
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name=f"lfm2-{i}",
                                 storage_path=str(tmp_path))
        ).fit(timeout_s=600).metrics for i in range(2)]
    finally:
        ray_tpu.shutdown()
    losses = runs[0]["losses"]
    assert losses == runs[1]["losses"]                   # bit for bit
    assert np.mean(losses[-3:]) < np.mean(losses[:3]) - 0.5
    tokens, c = 4 * 32, SHARE
    expected = tokens * c.top_k * c.experts_held[1] / c.n_experts
    for row in runs[0]["counters"]:
        calls = row["expert_layer_calls"]
        assert calls == sum(c.routed(i) for i in range(c.n_layers))
        assert row["expert_pairs_dropped"] == 0.0
        # a quarter of the experts gets a quarter of the pairs, give or
        # take what eight random router columns make of it
        assert 0.4 * expected < row["expert_pairs"] / calls < 2.0 * expected
        assert row["experts_hit"] <= calls * c.experts_held[1]
    assert set(runs[0]["counters"][0]) == set(moe.COUNTERS)
