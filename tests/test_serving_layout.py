"""Where a served model's weights lie on the device
(``ray_tpu.models.serving``: ``place``; ``llama.serving_layout``): the
dense block's ``wq`` / ``wk`` / ``wv`` in the order their products read,
shapes and values untouched, and an engine that serves the same tokens
from either tree. What the chip's compiler makes of the layout is
``tests/test_chip_compile.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, mimo_v2, ouro
from ray_tpu.models.serving import serving_model

MODELS = {"dense": llama.CONFIGS["debug"],
          "dense-bfloat16": llama.CONFIGS["tiny"],
          "ouro": ouro.OuroConfig()}


def _order(a):
    return a.format.layout.major_to_minor


def _host(tree):
    # of a copy: numpy's view of a CPU array would hold its buffer, and a
    # held buffer cannot be donated
    return jax.tree.map(
        lambda a: np.asarray(jnp.array(a, jnp.float32, copy=True)), tree)


@pytest.mark.parametrize("model", list(MODELS))
def test_serving_layout_lays_three_leaves_and_changes_nothing_else(model):
    """Same tree, shapes, dtypes and values; ``wq`` / ``wk`` / ``wv``
    heads-outside on the device they were on, committed; every other
    leaf the very array that was given; the three given are donated."""
    params = serving_model(MODELS[model]).init_params(jax.random.key(3))
    given = dict(jax.tree.leaves_with_path(params))
    want = _host(params)
    placed = llama.serving_layout(params)
    assert jax.tree.structure(placed) == jax.tree.structure(params)
    relaid = set()
    for path, new in jax.tree.leaves_with_path(placed):
        old, name = given[path], path[-1].key
        assert (new.shape, new.dtype, new.sharding) == (
            old.shape, old.dtype, old.sharding), name
        if new is old:
            assert _order(new) == tuple(range(new.ndim)), name
        else:
            relaid.add(name)
            assert _order(new) == llama.SERVING_LAYOUT[name] != (0, 1, 2, 3)
            assert new.committed and old.is_deleted(), name
    assert relaid == set(llama.SERVING_LAYOUT) == {"wq", "wk", "wv"}
    jax.tree.map(np.testing.assert_array_equal, _host(placed), want)


def _answers(cfg, params, **kwargs):
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(config=cfg, params=params, num_slots=3, max_seq=128,
                    **kwargs)
    try:
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
                   for n in (9, 21, 14)]
        return ([eng.generate(p, max_tokens=12) for p in prompts],
                eng.stats(), eng.params)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("model, cache", [
    ("dense", "paged"), ("dense", "slot"), ("dense-bfloat16", "paged"),
    ("ouro", "paged")])
def test_an_engine_answers_the_same_from_a_placed_tree(model, cache):
    """Greedy tokens of three prompts from the plain tree and from the
    placed one: the programs are compiled for the layout their weights
    arrive in, and the products are the same numbers. Off the TPU the
    engine itself places nothing: the plain tree stays the caller's."""
    cfg = MODELS[model]
    kwargs = (dict(kv_cache="slot") if cache == "slot" else
              dict(kv_block_size=8, kv_pool_tokens=3 * 128))
    plain = serving_model(cfg).init_params(jax.random.key(7))
    placed = llama.serving_layout(jax.tree.map(jnp.copy, plain))
    want, stats, served = _answers(cfg, plain, **kwargs)
    assert stats["weights_relaid_bytes"] == 0
    assert served is plain and _order(plain["layers"]["wq"]) == (0, 1, 2, 3)
    got, _, served = _answers(cfg, placed, **kwargs)
    assert _order(served["layers"]["wv"]) == llama.SERVING_LAYOUT["wv"]
    assert got == want and all(len(a) == 12 for a in got)


# --------------------------------------------- the engine's one call of it
@pytest.fixture
def as_tpu(monkeypatch):
    """The engine places on a TPU only, and asks ``jax.default_backend()``
    as the kernel dispatchers do. Steered here for the constructor alone:
    no program is run under it (its kernels are the chip's)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("model", ["dense", "ouro"])
def test_on_a_tpu_the_engine_places_what_it_is_given_and_owns_it(as_tpu,
                                                                 model):
    from ray_tpu.serve.llm import LLMEngine

    cfg = MODELS[model]
    params = serving_model(cfg).init_params(jax.random.key(1))
    three = [params["layers"][n] for n in llama.SERVING_LAYOUT]
    eng = LLMEngine(config=cfg, params=params, num_slots=2, max_seq=64,
                    kv_block_size=8)
    try:
        assert eng.stats()["weights_relaid_bytes"] == sum(
            a.nbytes for a in three)
        assert all(a.is_deleted() for a in three)       # donated
        for name, order in llama.SERVING_LAYOUT.items():
            assert _order(eng.params["layers"][name]) == order
        assert eng.params["embed"] is params["embed"]
    finally:
        eng.shutdown()


@pytest.mark.parametrize("model", ["dense", "ouro"])
def test_a_placed_engine_compiles_a_prefill_bucket_once(monkeypatch, model):
    """Re-laid leaves are committed, so what a program returns is; the
    engine commits its cache with them, or a bucket's first prefill (on a
    fresh, uncommitted cache) and its second would be two compiled
    programs, the second wherever a prompt first meets the bucket again:
    inside a measured window. (The constructor alone is steered: the
    programs trace afterwards, with the CPU's kernels.)"""
    from ray_tpu.serve.llm import LLMEngine

    cfg = MODELS[model]
    params = serving_model(cfg).init_params(jax.random.key(4))
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        eng = LLMEngine(config=cfg, params=params, num_slots=2, max_seq=64,
                        kv_block_size=8)
    try:
        assert eng.stats()["weights_relaid_bytes"] > 0
        assert all(a.committed for a in jax.tree.leaves(eng._cache))
        rng = np.random.default_rng(6)
        variants = []
        for _ in range(3):
            eng.generate(rng.integers(1, cfg.vocab_size, 8).tolist(),
                         max_tokens=4)
            variants.append((eng._prefill.jitted._cache_size(),
                             eng._decode.jitted._cache_size()))
        assert variants[0][0] == 1 and len(set(variants)) == 1, variants
    finally:
        eng.shutdown()


def test_a_model_without_place_is_served_the_arrays_it_was_given(as_tpu):
    """A routed model has no ``place``: on a TPU too its weights are the
    caller's own arrays, and nothing was re-laid."""
    from ray_tpu.serve.llm import LLMEngine

    cfg = mimo_v2.MimoV2Config()
    model = serving_model(cfg)
    assert not hasattr(model, "place")
    params = model.init_params(jax.random.key(2))
    eng = LLMEngine(config=cfg, params=params, num_slots=2, max_seq=64,
                    kv_block_size=8)
    try:
        assert eng.stats()["weights_relaid_bytes"] == 0
        assert eng.params is params
        assert not any(a.is_deleted() for a in jax.tree.leaves(params))
        assert not any(a.committed                      # as it was made
                       for a in jax.tree.leaves(eng._cache))
    finally:
        eng.shutdown()
