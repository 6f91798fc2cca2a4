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


# ---------------------------------------------------------------------
# The expert layer by share (serving). PR 39 widened ``experts_by_share``
# (what is routed and what is multiplied may be two arrays; an expert
# without a gate matrix is relu^2 of two): the three ways the SwiGLU
# models call it must give what they gave.

def _held_layer(seed, E=8, h=16, m=8, bias=True, first=2, count=4):
    ks = jax.random.split(jax.random.key(seed), 5)
    n = lambda k, s, std: jax.random.normal(k, s, jnp.float32) * std  # noqa: E731
    layer = {"router": n(ks[0], (h, E), h ** -0.5),
             "we_gate": n(ks[1], (E, h, m), h ** -0.5)[first:first + count],
             "we_up": n(ks[2], (E, h, m), h ** -0.5)[first:first + count],
             "we_down": n(ks[3], (E, m, h), m ** -0.5)[first:first + count]}
    if bias:
        layer["router_bias"] = n(ks[4], (E,), 0.05)
    return layer


# y[:, :4], y.sum() and the counters of the PARENT's experts_by_share
# (ed2e22b, computed there; the change gave the same bits on that
# machine: CHANGES.md, PR 39; the sixth, ``expert_extra_passes``, is PR
# 58's and 0 where the buffer holds the worst case). Rows 4 or 5 are
# masked by ``valid`` or routed to experts that are not held.
PINNED = {
    "sigmoid-bias": (dict(score="sigmoid"), 40, [
        [0.044440378, -0.78733015, 0.14460886, -0.19048885],
        [-0.74927342, -0.57796228, 0.81099027, 0.76159883],
        [-0.16568334, -0.29388976, 0.14310952, 0.24648732],
        [0.17422789, 0.36128876, 0.61535853, 0.39945549],
        [0.0, 0.0, 0.0, 0.0], [0.0, 0.0, 0.0, 0.0]],
        9.5898094, [1.0, 6.0, 3.0, 2.6666667, 0.0, 0.0]),
    "sigmoid-grouped": (dict(score="sigmoid", n_group=4, topk_group=2), 41, [
        [0.83885831, 0.64135939, -0.63467962, -0.35841301],
        [0.71907121, 0.50111294, -0.40957868, -0.64344692],
        [0.021582253, -0.056922551, -0.091307327, -0.37537974],
        [0.0, 0.0, 0.0, 0.0],
        [-0.10283951, 0.19438042, 0.41202286, -0.17959227],
        [0.0, 0.0, 0.0, 0.0]],
        -3.6533864, [1.0, 5.0, 4.0, 1.6, 0.0, 0.0]),
    "softmax": (dict(score="softmax"), 42, [
        [0.80854398, -0.088838473, -0.11247842, 0.20547146],
        [-0.21342658, -0.4947252, 0.222248, 2.1686323],
        [0.46644819, 1.3073367, -1.8853381, -1.787852],
        [0.60256702, 0.93020451, -0.32830495, 1.0740455],
        [-0.18605082, 0.27420703, -0.056373611, 0.07928504],
        [0.0, 0.0, 0.0, 0.0]],
        -3.4974942, [1.0, 6.0, 4.0, 1.3333334, 0.0, 0.0]),
}


@pytest.mark.parametrize("case", sorted(PINNED))
def test_the_swiglu_models_experts_by_share_is_unchanged(case):
    """How ``mimo_v2.py`` (sigmoid scores and a bias), ``axk1.py``
    (group-limited) and ``laguna.py`` (softmax) call the layer, on a
    share of 4 of 8 experts with one row masked: pinned to what the
    parent commit gave (rounding to another order of sums on another
    CPU: 2e-6)."""
    kw, seed, corner, total, counters = PINNED[case]
    layer = _held_layer(seed, bias=case != "softmax")
    x = jax.random.normal(jax.random.key(seed + 10), (6, 16), jnp.float32)
    y, c = moe.experts_by_share(x, layer, experts_held=(2, 4), top_k=2,
                                scale=2.5, valid=jnp.arange(6) < 5, **kw)
    assert y.shape == (6, 16) and y.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(y)[:, :4], corner, rtol=2e-6,
                               atol=2e-7)
    np.testing.assert_allclose(float(y.sum()), total, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(c), counters, rtol=1e-6)


@pytest.mark.parametrize("held", [(0, 8), (4, 4)], ids=["whole", "share"])
def test_relu2_experts_on_a_latent_input_against_a_loop(held):
    """The ungated two-matrix expert (no ``we_gate`` stored) fed another
    array than the router reads: ``y = sum_k w_k relu(l U_e) ** 2 D_e``
    over the experts held, in the latent width, against a loop over
    tokens and experts in numpy."""
    first, count = held
    E, h, latent, m, T, k = 8, 16, 12, 6, 9, 3
    ks = jax.random.split(jax.random.key(60), 6)
    n = lambda k_, s, std: jax.random.normal(k_, s, jnp.float32) * std  # noqa: E731
    router, bias = n(ks[0], (h, E), h ** -0.5), n(ks[1], (E,), 0.05)
    up, down = n(ks[2], (E, latent, m), latent ** -0.5), \
        n(ks[3], (E, m, latent), m ** -0.5)
    x, lat = n(ks[4], (T, h), 1.0), n(ks[5], (T, latent), 1.0)
    layer = {"router": router, "router_bias": bias,
             "we_up": up[first:first + count],
             "we_down": down[first:first + count]}
    y, c = moe.experts_by_share(x, layer, experts_held=held, top_k=k,
                                scale=5.0, x_experts=lat)
    assert y.shape == (T, latent)
    s = 1.0 / (1.0 + np.exp(-np.asarray(x, np.float64) @ np.asarray(router)))
    want, pairs = np.zeros((T, latent)), 0
    for t in range(T):
        chosen = np.argsort(-(s[t] + np.asarray(bias)))[:k]
        for e in chosen:
            if first <= e < first + count:
                a = np.maximum(np.asarray(lat[t], np.float64)
                               @ np.asarray(up[e]), 0.0) ** 2
                want[t] += 5.0 * s[t, e] / s[t, chosen].sum() \
                    * (a @ np.asarray(down[e]))
                pairs += 1
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)
    assert float(c[1]) == pairs and float(c[4]) == 0
    # the shared expert without a gate matrix is the same two-matrix MLP
    ws = {"ws_up": up[0], "ws_down": down[0]}
    got = moe.shared_expert(lat, ws)
    np.testing.assert_allclose(
        np.asarray(got), np.maximum(np.asarray(lat) @ np.asarray(up[0]),
                                    0.0) ** 2 @ np.asarray(down[0]),
        rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------
# The row tile of the grouped products is a rule over the call's static
# shapes (PR 45): 16 where an expert gets a decode step's few rows, up to
# 128 where a prefill's bucket sends it a hundred.

# (tokens of the call, top_k, the router's width, experts held) -> the
# tile. The decode steps of the four routed cells, then every prefill
# bucket they run (and the check's own 256-token prompt).
ROW_TILE_AT = {
    "axk1-decode-192": ((192, 8, 192, 12), 16),
    "mimo_v2-decode-128": ((128, 8, 256, 16), 16),
    "laguna-decode-128": ((128, 8, 256, 256), 16),
    "nemotron_h-decode-192": ((192, 22, 512, 128), 16),
    "axk1-2048": ((2048, 8, 192, 12), 128),         # 85 rows an expert
    "axk1-1024": ((1024, 8, 192, 12), 64),          # 43
    "axk1-256": ((256, 8, 192, 12), 16),            # 11
    "mimo_v2-1024": ((1024, 8, 256, 16), 64),       # 32
    "mimo_v2-512": ((512, 8, 256, 16), 32),         # 16
    "mimo_v2-256": ((256, 8, 256, 16), 16),         # 8
    # the whole set held: 256 x (tm - 1) rows of padding against the pairs
    "laguna-2048": ((2048, 8, 256, 256), 64),       # 64, not 128
    "laguna-1024": ((1024, 8, 256, 256), 32),       # 32, not 64
    "laguna-512": ((512, 8, 256, 256), 16),         # 16, not 32
    "laguna-256": ((256, 8, 256, 256), 16),
    "nemotron_h-64": ((64, 22, 512, 128), 16),      # 3
    "nemotron_h-128": ((128, 22, 512, 128), 16),    # 6
    "nemotron_h-256": ((256, 22, 512, 128), 16),    # 11
    "nemotron_h-512": ((512, 22, 512, 128), 32),    # 22
    "nemotron_h-1024": ((1024, 22, 512, 128), 64),  # 44
    "one-token": ((1, 8, 256, 16), 16),
    "past-the-largest": ((8192, 8, 64, 4), 128),
}


@pytest.mark.parametrize("call", ROW_TILE_AT)
def test_row_tile_follows_the_rows_an_expert_expects(call):
    shapes, tile = ROW_TILE_AT[call]
    assert moe.row_tile(*shapes) == tile
    T, top_k, _, held = shapes
    if tile > moe.ROW_TILES[0]:
        # the padding it can add to the worst case is no more than the pairs
        assert held * (tile - 1) <= T * top_k


# ---------------------------------------------------------------------
# The rows of one pass and where the bound engages are rules over the
# call's static shapes (PR 58). The shapes come from the benchmark's own
# files: every routed configuration's step (the slots of its cell; the
# train cell's tokens), its widths and the experts it holds.

def _routed_calls():
    """{cell: (T, top_k, router's width, held, the experts' input and
    inner widths)} of the step of every cell whose configuration routes."""
    import json
    import os

    root = os.path.join(os.path.dirname(__file__), "..")

    def load(*path):
        with open(os.path.join(root, *path)) as f:
            return json.load(f)

    calls = {}
    for cell in load("BENCHMARK.json")["workloads"]:
        config = load("benchmark", "configs", cell["config"] + ".json")
        held = config.get("n_routed_experts", config.get("num_experts"))
        if held is None:
            continue
        run = load("benchmark", "cells", cell["name"] + ".json")
        if "deployment" in run:     # a decode step, or a step over blocks
            T = run["deployment"]["num_slots"] * config.get("block_length", 1)
        else:
            T = run["job"]["batch"] * load(
                "benchmark", "traffic", cell["traffic"] + ".json")["seq"]
        calls[cell["name"]] = (
            T, config["num_experts_per_tok"],
            config.get("router_width", held), held,
            config.get("moe_latent_size", config["hidden_size"]),
            config["moe_intermediate_size"])
    return calls


def _buffer(T, top_k, E, held, h, m):
    """(the worst case's rows, the rows of a pass, whether the bound
    engages) as ``experts_by_share`` works them out, bfloat16 rows."""
    tm = moe.row_tile(T, top_k, E, held)
    worst = -(-(T * top_k + held * (tm - 1)) // tm) * tm
    rows = moe.pass_rows(T, top_k, E, held, tm)
    return worst, rows, moe.bound_serves(worst - rows, (h + m) * 2)


def test_the_train_cells_buffer_is_twice_the_share_it_expects():
    calls = _routed_calls()
    assert calls["train-moe-conv-8k"] == (49152, 4, 64, 16, 2048, 1536)
    assert _buffer(*calls["train-moe-conv-8k"]) == (198656, 100352, True)


@pytest.mark.parametrize("cell", [
    "serve-moe-window-decode", "serve-mla-moe-decode",
    "serve-ssm-latent-moe-chat", "serve-moe-whole-mixed-decode",
    "serve-blockdiff-moe-decode"])
def test_no_decode_step_bounds_its_buffer(cell):
    """A buffer of 1-8k rows has nothing to give, and a skewed step must
    not pay a second pass: a share-held step's bound is under its worst
    case and does not engage; a set held whole has no bound."""
    T, top_k, E, held, h, m = _routed_calls()[cell]
    worst, rows, engaged = _buffer(T, top_k, E, held, h, m)
    assert not engaged and worst <= 8192
    assert (rows == worst) == (held == E)
    assert held == E or rows < 0.7 * worst


# (tokens, top_k, the router's width, held, input and inner widths) -> the
# rows of a pass, whether it engages: the share-held configurations'
# prefill buckets, measured both ways on the chip (PERF.md section 6, PR 58)
BOUND_AT = {
    "axk1-2048": ((2048, 8, 192, 12, 7168, 2048), 3584, True),
    "axk1-1024": ((1024, 8, 192, 12, 7168, 2048), 1792, True),
    "axk1-512": ((512, 8, 192, 12, 7168, 2048), 896, True),
    "axk1-256": ((256, 8, 192, 12, 7168, 2048), 448, False),
    "mimo_v2-1024": ((1024, 8, 256, 16, 4096, 2048), 2048, True),
    "mimo_v2-512": ((512, 8, 256, 16, 4096, 2048), 1024, True),
    "mimo_v2-256": ((256, 8, 256, 16, 4096, 2048), 496, False),
    "nemotron_h-1024": ((1024, 22, 512, 128, 1024, 2688), 19328, True),
    "nemotron_h-512": ((512, 22, 512, 128, 1024, 2688), 9600, True),
    "nemotron_h-256": ((256, 22, 512, 128, 1024, 2688), 4736, False),
    "laguna-2048": ((2048, 8, 256, 256, 2048, 512), 32512, False),
    "sdar-1024": ((1024, 8, 128, 128, 2048, 768), 16256, False),
    "one-token": ((1, 8, 256, 16, 4096, 2048), 256, False),
    "every-pair-is-fewer-than-twice-the-share": (
        (49152, 4, 6, 4, 2048, 1536), 197120, False),
}


@pytest.mark.parametrize("call", BOUND_AT)
def test_the_bound_follows_the_share_held_and_the_dead_rows_bytes(call):
    shapes, rows, engaged = BOUND_AT[call]
    worst, got, on = _buffer(*shapes)
    assert (got, on) == (rows, engaged)
    T, top_k, E, held = shapes[:4]
    assert got % moe.row_tile(T, top_k, E, held) == 0 and got <= worst
    if held == E or 2 * held >= E:
        assert got == worst         # all pairs provided for: one pass


def test_a_layer_whose_bound_does_not_engage_counts_no_extra_pass():
    """At the tiny shapes of every other test here the buffer is the
    worst case's and the sixth counter the constant 0."""
    layer = _held_layer(90)
    x = jax.random.normal(jax.random.key(91), (96, 16), jnp.float32)
    _, c = moe.experts_by_share(x, layer, experts_held=(2, 4), top_k=2)
    assert c.shape == (len(moe.COUNTERS),) and float(c[5]) == 0.0
    assert moe.COUNTERS[5] == "expert_extra_passes"


def _dense_experts(x, layer, idx, w, first, count, valid):
    """sum_k w_k SwiGLU_e(x) over the held experts, a loop in float64."""
    x64 = np.asarray(x, np.float64)
    want = np.zeros((x.shape[0], layer["we_down"].shape[2]))
    for t in range(x.shape[0]):
        if not valid[t]:
            continue
        for e, weight in zip(np.asarray(idx[t]), np.asarray(w[t])):
            if first <= e < first + count:
                g = x64[t] @ np.asarray(layer["we_gate"][e - first])
                u = x64[t] @ np.asarray(layer["we_up"][e - first])
                want[t] += weight * ((g / (1 + np.exp(-g)) * u)
                                     @ np.asarray(layer["we_down"][e - first]))
    return want


@pytest.mark.parametrize("tile", moe.ROW_TILES)
def test_every_tile_computes_the_worst_case_whole(tile):
    """At a call whose shapes give ``tile``, every token's ONE choice is
    the same held expert (a huge bias): that expert gets ``T * top_k``
    rows, the worst case the row buffer is sized for; the rows past
    ``T - 5`` are padding masked by ``valid``. Against a dense loop over
    tokens: nothing is dropped, a masked row gives zeros."""
    E, h, m, first, count = 4, 16, 8, 1, 3
    T = tile * E - 2                # top_k 1: just under ``tile`` rows each
    assert moe.row_tile(T, 1, E, count) == tile
    layer = _held_layer(70 + tile, E=E, h=h, m=m, first=first, count=count)
    layer["router_bias"] = jnp.zeros((E,)).at[2].set(50.0)
    x = jax.random.normal(jax.random.key(71), (T, h), jnp.float32)
    valid = jnp.arange(T) < T - 5
    y, c = moe.experts_by_share(x, layer, experts_held=(first, count),
                                top_k=1, scale=2.5, valid=valid)
    idx, w = moe.route_sigmoid_topk(x, layer["router"],
                                    layer["router_bias"], 1, 2.5)
    assert (np.asarray(idx) == 2).all()
    want = _dense_experts(x, layer, idx, w, first, count, np.asarray(valid))
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)
    assert not np.asarray(y)[T - 5:].any()
    calls, pairs, hit, ratio, dropped, _ = np.asarray(c)
    assert (calls, pairs, hit, dropped) == (1, T - 5, 1, 0)
    assert ratio == pytest.approx(count)          # all on one of three


@pytest.mark.parametrize("tile", moe.ROW_TILES)
def test_every_tile_gives_what_the_smallest_gives(tile, monkeypatch):
    """A row's product does not depend on which tile holds it: a routed
    call over a share (top_k 2, pairs spread over held and absent
    experts) under each tile against the same call at 16 rows."""
    T = 96
    layer = _held_layer(80)
    x = jax.random.normal(jax.random.key(81), (T, 16), jnp.float32)
    kw = dict(experts_held=(2, 4), top_k=2, scale=2.5,
              valid=jnp.arange(T) < T - 7)
    monkeypatch.setattr(moe, "row_tile", lambda *a: moe.ROW_TILES[0])
    want, c0 = moe.experts_by_share(x, layer, **kw)
    monkeypatch.setattr(moe, "row_tile", lambda *a: tile)
    y, c = moe.experts_by_share(x, layer, **kw)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c0))
    assert float(c[4]) == 0 and 0 < float(c[1]) < 2 * (T - 7)


@pytest.mark.parametrize("tm", [64, 128])
def test_grouped_matmul_kernel_interpret_at_a_prefills_tiles(tm):
    """The kernel in interpret mode against its oracle at the tiles a
    prefill's bucket takes (the case at 16 is
    ``tests/test_mimo_v2_serving.py``'s): six tiles of which four hold
    rows, two of them of one group, a group without a tile; the rows of
    the tiles past ``n_active`` are not compared (never written)."""
    from ray_tpu.ops.pallas import grouped_matmul as gm

    ks = jax.random.split(jax.random.key(13), 2)
    lhs = jax.random.normal(ks[0], (6 * tm, 128), jnp.bfloat16)
    rhs = jax.random.normal(ks[1], (5, 128, 256), jnp.bfloat16)
    groups = jnp.array([0, 0, 2, 4, 4, 4], jnp.int32)
    got = gm.grouped_matmul(lhs, rhs, groups, 4, tm=tm,
                            out_dtype=jnp.float32, interpret=True)
    want = gm.grouped_matmul_reference(lhs, rhs, groups, 4, tm=tm,
                                       out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got[:4 * tm]),
                               np.asarray(want[:4 * tm]), rtol=1e-5,
                               atol=1e-5)


# ---------------------------------------------------------------------
# The combine follows the pairs the chip holds (PR 50): a kernel copies
# only the rows of placed pairs; the XLA form, which gathers every
# pair's row, is its oracle.

def _combine_case(T, k, h, M, placed, seed):
    """Every placed pair a row of its own somewhere in (M, h), as the
    dispatch lays them; every OTHER row of the buffer poisoned with NaN
    and inf (the rows of tiles past ``n_active`` and the padding inside a
    tile, which nothing may read); the row indices of pairs that are not
    placed point anywhere, poisoned rows and ``M`` itself included."""
    rng = np.random.default_rng(seed)
    placed = np.asarray(placed, bool)
    n = int(placed.sum())
    assert placed.shape == (T, k) and n <= M
    row_pair = rng.integers(0, M + 1, (T, k)).astype(np.int32)
    rows = rng.permutation(M)[:n]
    row_pair[placed] = rows
    y_rows = np.where(rng.random((M, 1)) < 0.5, np.nan, np.inf) * np.ones(
        (1, h))
    y_rows[1::3] *= -1
    y_rows[rows] = rng.standard_normal((n, h))
    w = rng.random((T, k)).astype(np.float32) + 0.1
    return (jnp.asarray(y_rows, jnp.float32), jnp.asarray(row_pair),
            jnp.asarray(placed), jnp.asarray(w))


def _bernoulli(T, k, p, seed):
    return np.random.default_rng(seed).random((T, k)) < p


# case -> (T, k, h, M, placed (T, k))
COMBINE_CASES = {
    "a-sixteenth-placed": (64, 8, 1024, 96, _bernoulli(64, 8, 1 / 16, 1)),
    "the-set-held-whole": (32, 8, 1024, 272, np.ones((32, 8), bool)),
    "tokens-with-no-placed-pair": (
        48, 8, 1024, 96,
        _bernoulli(48, 8, 0.5, 2) & (np.arange(48) % 3 == 0)[:, None]),
    "a-padded-tail-masked-by-valid": (
        64, 8, 1024, 160,
        _bernoulli(64, 8, 0.25, 3) & (np.arange(64) < 41)[:, None]),
    "n_active-0": (32, 8, 1024, 64, np.zeros((32, 8), bool)),
    "top-22-a-quarter-placed": (32, 22, 1024, 208,
                                _bernoulli(32, 22, 0.25, 4)),
    "56-lane-tiles-wide": (32, 8, 7168, 48, _bernoulli(32, 8, 1 / 8, 5)),
    "tokens-no-tile-divides": (21, 8, 1024, 64, _bernoulli(21, 8, 0.3, 6)),
    "more-pairs-than-the-ring": (
        16, 22, 1024, 360, np.ones((16, 22), bool)),
}


@pytest.mark.parametrize("case", COMBINE_CASES)
def test_expert_combine_kernel_interpret_against_the_xla_form(
        case, monkeypatch):
    """The kernel in interpret mode: what the XLA form gives to float32
    rounding (the kernel sums in the order j = 0 .. k - 1), zeros for a
    token with no placed pair, and nothing of a poisoned row in any
    output (select, never multiply)."""
    from ray_tpu.ops.pallas import expert_combine as ec

    T, k, h, M, placed = COMBINE_CASES[case]
    if case == "more-pairs-than-the-ring":
        # the smallest ring, two tokens' pairs: the copies wrap it
        monkeypatch.setattr(ec, "_RING_BYTES", 0)
        assert 2 * k <= ec._depth(k, h) == 64 < placed.sum() // 5
    args = _combine_case(T, k, h, M, placed, seed=len(case))
    got = np.asarray(ec.expert_combine(*args, interpret=True))
    want = np.asarray(ec.expert_combine_reference(*args))
    assert got.shape == (T, h) and got.dtype == np.float32
    assert np.isfinite(got).all() and np.isfinite(want).all()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert not got[~placed.any(axis=1)].any()
    assert (np.abs(got[placed.any(axis=1)]).max(axis=1) > 0).all()


# how each routed model calls the layer, at its tiny default widths with
# an eighth of its experts held (where the front takes the kernel at any
# size): (hidden, experts' width, E, top_k, held, gated, further arguments)
SHARE_CALLS = {
    "mimo_v2": (64, 64, 16, 4, (4, 2), True, dict(score="sigmoid")),
    "axk1": (64, 64, 32, 8, (8, 4), True,
             dict(score="sigmoid", scale=2.5, n_group=8, topk_group=4)),
    "laguna": (64, 64, 16, 4, (8, 2), True,
               dict(score="softmax", scale=2.5)),
    "nemotron_h": (64, 32, 16, 4, (0, 2), False,
                   dict(score="sigmoid", scale=5.0)),
}


@pytest.mark.parametrize("model", SHARE_CALLS)
def test_experts_by_share_with_the_combine_kernel_is_the_oracles(
        model, kernel_on_cpu, monkeypatch):
    """``experts_by_share`` where a share is held takes the kernel on a
    TPU (here: interpreted, the grouped products too): against itself
    with the XLA form in the kernel's place, equal to 1e-6 of the
    largest value (the k-sum's order at most), counters bit for bit."""
    from ray_tpu.ops.pallas import expert_combine as ec

    hidden, width, E, k, held, gated, kw = SHARE_CALLS[model]
    T, m = 40, 32
    ks = jax.random.split(jax.random.key(90), 7)
    n = lambda k_, s, std: jax.random.normal(k_, s, jnp.float32) * std  # noqa: E731
    layer = {"router": n(ks[0], (hidden, E), hidden ** -0.5),
             "we_up": n(ks[1], (held[1], width, m), width ** -0.5),
             "we_down": n(ks[2], (held[1], m, width), m ** -0.5)}
    if gated:
        layer["we_gate"] = n(ks[3], (held[1], width, m), width ** -0.5)
    if kw["score"] == "sigmoid":
        layer["router_bias"] = n(ks[4], (E,), 0.05)
    x = n(ks[5], (T, hidden), 1.0)
    xe = None if width == hidden else n(ks[6], (T, width), 1.0)
    call = lambda: moe.experts_by_share(  # noqa: E731
        x, layer, experts_held=held, top_k=k, valid=jnp.arange(T) < T - 6,
        x_experts=xe, **kw)
    calls = []
    kernel = ec.expert_combine
    monkeypatch.setattr(ec, "expert_combine", lambda *a, **kws: (
        calls.append(a[1].shape), kernel(*a, **kws))[1])
    y, c = call()
    assert calls == [(T, k)]
    monkeypatch.setattr(ec, "expert_combine", lambda *a, **kws: (
        ec.expert_combine_reference(*a)))
    want, c0 = call()
    assert 0 < float(c[1]) < (T - 6) * k and float(c[4]) == 0
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c0))
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(want), rtol=1e-6,
        atol=1e-6 * float(np.abs(np.asarray(want)).max()))
    assert not np.asarray(y)[T - 6:].any()


# (T, top_k, the rows' width, experts held, the router's) -> the kernel
# or the XLA form: every program of the five routed cells
KERNEL_SERVES = {
    "axk1-decode-192": ((192, 8, 7168, 12, 192), True),
    "axk1-2048": ((2048, 8, 7168, 12, 192), True),
    "axk1-256": ((256, 8, 7168, 12, 192), True),
    "mimo_v2-decode-128": ((128, 8, 4096, 16, 256), True),
    "mimo_v2-1024": ((1024, 8, 4096, 16, 256), True),
    "mimo_v2-256": ((256, 8, 4096, 16, 256), True),
    # a quarter held: from a gather of 32 MiB on
    "nemotron_h-decode-192": ((192, 22, 1024, 128, 512), False),   # 17 MB
    "nemotron_h-64": ((64, 22, 1024, 128, 512), False),
    "nemotron_h-256": ((256, 22, 1024, 128, 512), False),          # 23 MB
    "nemotron_h-512": ((512, 22, 1024, 128, 512), True),           # 46 MB
    "nemotron_h-1024": ((1024, 22, 1024, 128, 512), True),         # 92 MB
    # the set held whole: never
    "laguna-decode-128": ((128, 8, 2048, 256, 256), False),
    "laguna-2048": ((2048, 8, 2048, 256, 256), False),
    "sdar-block-step-512": ((512, 8, 2048, 128, 128), False),
    "sdar-1024": ((1024, 8, 2048, 128, 128), False),
}


@pytest.mark.parametrize("call", KERNEL_SERVES)
def test_the_combines_form_follows_the_share_held(call):
    """The one rule under ``ops/``, from the call's static shapes (my
    chip runs, PR 50: the layer alone with either form at each of these
    shapes)."""
    from ray_tpu.ops.pallas import expert_combine as ec

    shapes, kernel = KERNEL_SERVES[call]
    assert ec.kernel_serves(*shapes) is kernel


def test_the_whole_held_set_combines_in_xla(kernel_on_cpu, monkeypatch):
    """Every pair of a set held whole is placed: the dense gather moves
    no row in vain, and the front keeps the XLA form on a TPU too."""
    from ray_tpu.ops.pallas import expert_combine as ec

    monkeypatch.setattr(ec, "expert_combine", lambda *a, **kw: 1 / 0)
    layer = _held_layer(91, first=0, count=8)
    x = jax.random.normal(jax.random.key(92), (24, 16), jnp.float32)
    y, c = moe.experts_by_share(x, layer, experts_held=(0, 8), top_k=2)
    assert float(c[1]) == 48 and np.isfinite(np.asarray(y)).all()
