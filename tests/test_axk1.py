"""The latent-attention decoder with a shared expert and group-limited
routing (``ray_tpu.models.axk1``), at a small size on the CPU with every
ratio of the published model kept (nope / rope / value widths 2 : 1 : 2,
a latent narrower than heads x value, 8 groups of experts of which 4 are
kept, 8 experts a token), against the benchmark's plain reference
(``benchmark/reference/axk1.py``) on seeded random weights."""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import model_spec  # noqa: E402
from ray_tpu.models import axk1, moe  # noqa: E402
from ray_tpu.ops.pallas import paged_mla_decode_attention as mla  # noqa: E402
from ray_tpu.ops.rope import (YarnScaling, apply_rope,  # noqa: E402
                              rope_frequencies)

YARN = dict(type="yarn", factor=4, original_max_position_embeddings=64,
            beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
SPEC = dict(
    name="tiny-axk1", architecture="axk1",
    reference="benchmark/reference/axk1.py",
    vocab_size=256, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    rope_theta=10000, rope_scaling=YARN, first_k_dense_replace=1,
    moe_layer_freq=1, intermediate_size=128, moe_intermediate_size=32,
    n_routed_experts=8, router_width=32, experts_first=8,
    n_shared_experts=1, n_group=8, topk_group=4, num_experts_per_tok=8,
    routed_scaling_factor=2.5, norm_topk_prob=True, scoring_func="sigmoid",
    topk_method="none", rms_norm_eps=1e-6, max_position_embeddings=512,
    tie_word_embeddings=False, torch_dtype="bfloat16")
ARCH = model_spec.adapter(SPEC)
REF = model_spec.reference(SPEC)
DEPLOYMENT = dict(num_slots=3, max_seq=128, kv_block_size=8,
                  kv_pool_tokens=3 * 128)


def make_params(spec, seed, dtype=jnp.bfloat16):
    from benchmark import weights

    return jax.tree.map(lambda a: a.astype(dtype), weights.make(spec, seed))


def config(dtype=jnp.bfloat16):
    return dataclasses.replace(ARCH.program_config(SPEC), dtype=dtype)


# ------------------------------------------- the program and the reference
@pytest.mark.parametrize("prompt", [29, 32], ids=["mid-block", "fills-bucket"])
@pytest.mark.parametrize("dtype, limit", [(jnp.float32, 2e-4),
                                          (jnp.bfloat16, 0.15)],
                         ids=["float32", "bfloat16"])
def test_prefill_then_paged_decode_match_the_reference(dtype, limit, prompt,
                                                       monkeypatch):
    """A prompt (29 tokens: ends inside a block; 32: fills its padded
    bucket to the last row) through the EXPANDED prefill, then 20 decode
    steps through the ABSORBED form over the latent pool, across block
    boundaries (block 8): every step's logits are the reference's full
    expanded forward pass. (bfloat16 at this size: a router choice near
    a tie flips and moves a row; float32 is the arithmetic's test.)"""
    monkeypatch.setattr(ARCH, "program_config",
                        lambda spec, f=ARCH.program_config:
                        dataclasses.replace(f(spec), dtype=dtype))
    params = make_params(SPEC, 11, dtype)
    n = prompt + 20
    tokens = np.asarray(jax.random.randint(jax.random.key(5), (n,), 0, 256))
    got = ARCH.serve_program_logits(params, SPEC, tokens, DEPLOYMENT,
                                    prefill=prompt)
    want = np.asarray(REF.logits(params, jnp.asarray(tokens), SPEC,
                                 list(range(prompt - 1, n))))
    assert got.shape == want.shape == (21, 256)
    assert REF.rel_err(got[0], want[0]) < limit
    assert REF.rel_err(got[1:], want[1:]) < limit
    if dtype == jnp.float32:
        for i in (1, 4, 12, 20):
            assert REF.rel_err(got[i], want[i]) < 2 * limit


def test_the_decode_step_with_the_kernel_is_the_reference_too(kernel_on_cpu):
    """The same, float32, with the decode step on the chip's path: the
    work list built once for all layers and the kernel (interpreted)."""
    cfg = config(jnp.float32)
    params = make_params(SPEC, 11, jnp.float32)
    page = axk1.page_of(max_seq=128, block_size=8, pool_tokens=10 * 8)
    alloc = axk1.make_manager(page, 3)
    cache = axk1.init_cache(cfg, page, 3)
    assert cache["latent"].shape == (4, 11, 8, 128)     # 16 + 8 -> 128
    prefill = axk1.make_prefill(params, cfg, page)
    decode = axk1.make_decode_step(params, cfg, page)
    seqs = {s: list(np.asarray(jax.random.randint(
        jax.random.key(s), (n,), 0, 256))) for s, n in ((0, 21), (1, 30),
                                                        (2, 6))}
    plen = {s: len(t) for s, t in seqs.items()}
    for s in (1, 0, 2):
        assert alloc.ensure(s, plen[s] + 1)
        padded = np.zeros((1, -(-plen[s] // 8) * 8), np.int32)
        padded[0, :plen[s]] = seqs[s]
        cache, lg = prefill(cache, alloc.table_rows(s), jnp.asarray(padded),
                            plen[s], s)
        seqs[s].append(int(np.asarray(lg).argmax()))
    alloc.release(1)       # its stale length stays between two running
    active = np.array([True, False, True])
    step_logits = {0: [], 2: []}
    for _ in range(12):
        last = np.zeros(3, np.int32)
        for s in (0, 2):
            assert alloc.ensure(s, len(seqs[s]))
            last[s] = seqs[s][-1]
        cache, lg = decode(cache, alloc.device_tables(), jnp.asarray(last),
                           jnp.asarray(active))
        for s in (0, 2):
            step_logits[s].append(np.asarray(lg)[s])
            seqs[s].append(int(step_logits[s][-1].argmax()))
    assert np.asarray(cache["length"]).tolist() == [21 + 12, 30, 6 + 12]
    assert alloc.pools()["latent"]["blocks_free"] == 10 - 5 - 3
    for s in (0, 2):
        want = np.asarray(REF.logits(
            params, jnp.asarray(seqs[s][:-1]), SPEC,
            list(range(plen[s], len(seqs[s]) - 1))))
        got = np.stack(step_logits[s])
        assert REF.rel_err(got, want) < 2e-4
        assert got.argmax(-1).tolist() == want.argmax(-1).tolist()


def test_absorbed_attention_equals_expanded_on_the_same_latents():
    """The last query of a sequence, attended both ways over the same
    latents: keys and values expanded from them, and ``W_kvb``'s halves
    folded into the query and applied to the output over the paged rows."""
    cfg = config(jnp.float32)
    layer = make_params(SPEC, 3, jnp.float32)["layers"][1]
    S = 21
    x = jax.random.normal(jax.random.key(1), (1, S, 64), jnp.float32)
    cos, sin = axk1._rope_tables(cfg, 64)
    q_nope, q_rope, c_kv, k_rope = axk1._latents(x, layer, cfg, cos, sin,
                                                 None)
    want = axk1.attend_expanded(q_nope, q_rope, c_kv, k_rope, layer, cfg)
    rows = axk1._rows(c_kv[0], k_rope[0], cfg)
    pool = jnp.zeros((1, 5, 8, cfg.row_width)).at[0, 1:4].set(
        jnp.pad(rows, ((0, 3), (0, 0))).reshape(3, 8, -1))
    got = axk1.attend_absorbed(
        q_nope[:, -1], q_rope[:, -1], pool, 0, jnp.asarray([[1, 2, 3, 0]]),
        jnp.asarray([S]), layer, cfg)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0, -1]),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------- the kernel
BS, MBS = 8, 6
KERNEL_LENGTHS = {
    "empty-slot-between-running": [29, 0, 41],
    "every-table-full": [MBS * BS] * 3,
    "every-slot-empty": [0, 0, 0],
    "on-a-block-boundary-and-one-past": [8, 9, 16, 17, 32, 33, 40, 41],
    "one-token": [1, 1],
}


@pytest.mark.parametrize("blocks_per_step", [1, 4])
@pytest.mark.parametrize("case", KERNEL_LENGTHS)
def test_the_kernel_reads_the_live_blocks_and_no_other(case, blocks_per_step,
                                                       monkeypatch):
    """The kernel (interpreted) on a pool whose every block that the call
    has no business reading is NaN, the null block among them, against
    the oracle on the clean pool."""
    monkeypatch.setattr(mla, "BLOCKS_PER_STEP", blocks_per_step)
    lengths = KERNEL_LENGTHS[case]
    B, H, W, R = len(lengths), 4, 128, 16
    ks = jax.random.split(jax.random.key(2), 2)
    q = jax.random.normal(ks[0], (B, H, W)).at[..., 24:].set(0)
    NB = 1 + B * MBS
    pool = jax.random.normal(ks[1], (2, NB, BS, W)).at[..., 24:].set(0)
    tables = np.zeros((B, MBS), np.int32)
    for s, n in enumerate(lengths):
        for j in range(-(-n // BS)):
            tables[s, j] = 1 + s * MBS + j
    dead = np.ones(NB, bool)
    dead[tables[tables > 0]] = False
    lens = jnp.asarray(lengths, jnp.int32)
    got = np.asarray(mla.paged_mla_decode_kernel(
        q, pool.at[:, dead].set(jnp.nan), 1, jnp.asarray(tables), lens,
        scale=0.3, rank=R, interpret=True))
    want = np.asarray(mla.paged_mla_attention_reference(
        q, pool, 1, jnp.asarray(tables), lens, scale=0.3, rank=R))
    live = np.asarray(lengths) > 0
    assert got.shape == (B, H, R) and np.isfinite(got).all()
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    assert not got[~live].any()                  # zeros, not garbage


# ------------------------------------------------------------------- YaRN
def test_yarn_tables_and_scale_against_the_plain_formula():
    """The published keys: 64 rotated dimensions, theta 10000, factor 32
    over 4096. Pairs 0-10 keep their frequency, pairs 23-31 are divided
    by 32, the ramp runs between; the tables' own factor is 1 and the
    softmax scale carries m ** 2."""
    y = YarnScaling(factor=32.0, original_max_seq=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0)
    assert y.ramp_ends(64, 10000.0) == (10, 23)
    c = [64 * math.log(4096 / (b * 2 * math.pi)) / (2 * math.log(10000))
         for b in (32, 1)]
    assert [round(v, 2) for v in c] == [10.47, 22.51]
    f = np.asarray([10000.0 ** (2 * i / 64) for i in range(32)])
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    inv = (1 - ramp) / f + ramp / (32 * f)
    np.testing.assert_allclose(np.asarray(y.inverse_frequencies(64, 1e4)),
                               inv, rtol=1e-6)
    assert inv[10] == 1 / f[10] and inv[23] == pytest.approx(1 / (32 * f[23]))
    cos, sin = rope_frequencies(64, 264, 10000.0, yarn=y)
    pos = np.arange(264)[:, None]
    np.testing.assert_allclose(np.asarray(cos), np.cos(pos * inv), atol=2e-4)
    np.testing.assert_allclose(np.asarray(sin), np.sin(pos * inv), atol=2e-4)
    m = 0.1 * math.log(32) + 1
    assert y.table_factor == 1.0
    assert y.attention_factor == pytest.approx(m * m)
    cfg = dataclasses.replace(axk1.AxK1Config(), nope_dim=128, rope_dim=64,
                              yarn=y)
    assert cfg.scale == pytest.approx(0.13086, abs=1e-5)
    # the reference's own copy of the formula agrees
    spec = dict(SPEC, qk_nope_head_dim=128, qk_rope_head_dim=64,
                rope_scaling=dict(YARN, factor=32,
                                  original_max_position_embeddings=4096))
    inv_r, table_factor, scale = REF.yarn(spec)
    np.testing.assert_allclose(np.asarray(inv_r), inv, rtol=1e-6)
    assert table_factor == 1.0 and scale == pytest.approx(cfg.scale)
    # and it changes the angle at EVERY position past 0, so the check's
    # 264 positions see it
    plain = rope_frequencies(64, 264, 10000.0)[0]
    assert not np.allclose(np.asarray(cos[1:8]), np.asarray(plain[1:8]))


def test_rope_frequencies_without_yarn_are_what_they_were():
    for dim, theta in ((128, 500000.0), (64, 1e4), (8, 1e7)):
        inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                               / dim))
        freqs = jnp.outer(jnp.arange(96, dtype=jnp.float32), inv)
        cos, sin = rope_frequencies(dim, 96, theta)
        assert (np.asarray(cos) == np.asarray(jnp.cos(freqs))).all()
        assert (np.asarray(sin) == np.asarray(jnp.sin(freqs))).all()
    x = jax.random.normal(jax.random.key(4), (2, 9, 3, 8))
    cos, sin = rope_frequencies(8, 32, 1e4, yarn=YarnScaling(
        factor=1.0, original_max_seq=32))
    plain = rope_frequencies(8, 32, 1e4)
    np.testing.assert_allclose(np.asarray(apply_rope(x, cos, sin)),
                               np.asarray(apply_rope(x, *plain)), rtol=1e-6)


# ---------------------------------------------------------------- routing
def _parents_route_sigmoid_topk(x, router, bias, top_k, scale=1.0):
    """``route_sigmoid_topk`` as it stood before the group limit."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32)[None, :], top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * scale
    return idx.astype(jnp.int32), w


def test_one_group_of_which_one_is_kept_is_the_parents_routing_bit_for_bit():
    ks = jax.random.split(jax.random.key(6), 3)
    x = jax.random.normal(ks[0], (77, 64), jnp.bfloat16)
    router = jax.random.normal(ks[1], (64, 16), jnp.bfloat16) * 0.125
    bias = jax.random.normal(ks[2], (16,), jnp.bfloat16) * 0.1
    for fn in (moe.route_sigmoid_topk, jax.jit(
            moe.route_sigmoid_topk, static_argnums=(3, 4, 5, 6))):
        idx, w = fn(x, router, bias, 4, 1.5, 1, 1)
        want_idx, want_w = _parents_route_sigmoid_topk(x, router, bias, 4,
                                                       1.5)
        assert (np.asarray(idx) == np.asarray(want_idx)).all()
        assert (np.asarray(w) == np.asarray(want_w)).all()


@pytest.mark.parametrize("with_bias", [False, True])
def test_the_group_limited_choice_against_a_plain_loop(with_bias):
    """32 experts in 8 groups of 4; a group's score is the sum of its two
    largest; the 4 best groups stand; the 8 largest of their 16 experts
    are chosen; weights are s over their sum, times 2.5."""
    ks = jax.random.split(jax.random.key(7), 3)
    x = jax.random.normal(ks[0], (53, 64), jnp.float32)
    router = jax.random.normal(ks[1], (64, 32), jnp.float32) * 0.2
    bias = jax.random.normal(ks[2], (32,)) * 0.05 if with_bias else None
    idx, w = moe.route_sigmoid_topk(x, router, bias, 8, 2.5, 8, 4)
    s = 1 / (1 + np.exp(-np.asarray(x, np.float64) @ np.asarray(
        router, np.float64)))
    c = s + (np.asarray(bias, np.float64) if with_bias else 0.0)
    for t in range(53):
        score = [np.sort(c[t, g * 4:g * 4 + 4])[-2:].sum() for g in range(8)]
        groups = np.argsort(score)[-4:]
        allowed = [e for g in groups for e in range(g * 4, g * 4 + 4)]
        chosen = sorted(allowed, key=lambda e: -c[t, e])[:8]
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(chosen)
        want = {e: 2.5 * s[t, e] / sum(s[t, e2] for e2 in chosen)
                for e in chosen}
        for e, got in zip(np.asarray(idx[t]).tolist(), np.asarray(w[t])):
            assert got == pytest.approx(want[e], rel=1e-4)


def _routed_layer(seed, E=32, h=64, m=32, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 7)
    n = lambda k, shape, std: jax.random.normal(k, shape, dtype) * std  # noqa: E731
    return {"router": n(ks[0], (h, E), h ** -0.5),
            "ws_gate": n(ks[1], (h, m), h ** -0.5),
            "ws_up": n(ks[2], (h, m), h ** -0.5),
            "ws_down": n(ks[3], (m, h), m ** -0.5),
            "we_gate": n(ks[4], (E, h, m), h ** -0.5),
            "we_up": n(ks[5], (E, h, m), h ** -0.5),
            "we_down": n(ks[6], (E, m, h), m ** -0.5)}


def _share(layer, first, count):
    return dict(layer, **{k: layer[k][first:first + count]
                          for k in ("we_gate", "we_up", "we_down")})


@pytest.mark.parametrize("shares", [4, 16, 1])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """THE test that ties the share to the model: the routed parts that
    all the chips of the deployment compute (4 chips of 8 experts; 16 of
    2; one that holds all 32), plus the shared expert, which every chip
    computes alike, counted ONCE, add up to the uncut reference's whole
    layer ``shared(x) + routed(x)``."""
    layer = _routed_layer(7)
    x = jax.random.normal(jax.random.key(8), (37, 64), jnp.float32)
    routed, _, _ = REF.routed_mlp(x, layer, SPEC, held=(0, 32))
    want = REF.shared_mlp(x, layer) + routed
    count = 32 // shares
    total = moe.shared_expert(x, layer)            # once, not once a chip
    cfg = config(jnp.float32)
    for first in range(0, 32, count):
        y, counters = moe.experts_by_share(
            x, _share(layer, first, count), experts_held=(first, count),
            top_k=8, scale=2.5, n_group=8, topk_group=4)
        part, _, _ = REF.routed_mlp(x, _share(layer, first, count), SPEC,
                                    held=(first, count))
        assert REF.rel_err(y, part) < 1e-4 or float(
            jnp.abs(part).max()) == 0.0
        assert counters[4] == 0                      # nothing dropped
        total = total + y
        # the model's own layer on that chip is its share plus the shared
        mine, _ = axk1._mlp(x, _share(layer, first, count),
                            dataclasses.replace(cfg, experts_held=(first,
                                                                   count)),
                            None)
        assert REF.rel_err(mine, REF.shared_mlp(x, layer) + part) < 1e-4
    assert REF.rel_err(total, want) < 1e-4


def test_no_token_is_dropped_when_routing_piles_onto_one_held_expert():
    """Every token's first choice is expert 9 (a huge router column): 129
    rows for one expert, far over any capacity a balanced layer would
    give it, and all of them are computed."""
    layer = _routed_layer(9)
    x = jnp.abs(jax.random.normal(jax.random.key(10), (129, 64)))
    layer["router"] = layer["router"].at[:, 9].set(1.0)   # s ~ 1 for all
    kw = dict(experts_held=(8, 8), top_k=8, scale=2.5, n_group=8,
              topk_group=4)
    y, c = moe.experts_by_share(x, _share(layer, 8, 8), **kw)
    want, _, _ = REF.routed_mlp(x, _share(layer, 8, 8), SPEC, held=(8, 8))
    calls, pairs, hit, ratio, dropped, _ = np.asarray(c)
    assert dropped == 0 and pairs >= 129 and calls == 1
    assert ratio >= 8 * 129 / pairs - 1e-3           # largest over mean
    assert REF.rel_err(y, want) < 1e-4
    # rows that are no token (an idle slot) are routed nowhere
    _, c = moe.experts_by_share(x, _share(layer, 8, 8),
                                valid=jnp.arange(129) < 3, **kw)
    assert 3 <= float(c[1]) <= 24


# ------------------------------------------------------------- the engine
@pytest.fixture(scope="module")
def engine_parts():
    return ARCH.program_config(SPEC), make_params(SPEC, 21)


def test_the_engine_serves_it_and_counts_its_experts(engine_parts):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = engine_parts
    eng = LLMEngine(config=cfg, params=params, num_slots=3, max_seq=128,
                    kv_block_size=8, kv_pool_tokens=3 * 128)
    try:
        prompt = list(range(1, 41))
        out = eng.generate(prompt, max_tokens=40)
        # greedy through the engine = greedy through the bare programs
        toks = np.asarray(prompt + out)
        lg = ARCH.serve_program_logits(params, SPEC, toks[:-1], DEPLOYMENT,
                                       prefill=40)
        assert out == [int(t) for t in lg.argmax(-1)]
        st = eng.stats()
        assert st["preemptions"] == 0 and st["window_blocks_freed"] == 0
        c = st["model_counters"]
        assert c["expert_pairs_dropped"] == 0 and c["expert_pairs"] > 0
        assert c["expert_layer_calls"] == 3 * 39        # 3 routed layers
        assert st["model_counters_prefill"]["expert_layer_calls"] == 3
        assert st["kv_pools"]["latent"] == {
            "blocks_total": 48, "blocks_free": 48, "block_size": 8,
            "live_tokens": 0}
        assert st["kv_blocks_total"] == 48
        eng._alloc.check_invariants()
    finally:
        eng.shutdown()


def test_preemption_and_release_return_the_latent_blocks(engine_parts):
    """A pool too small for three growing answers: the youngest is
    preempted, recomputed and finishes; afterwards the pool is whole."""
    import threading

    from ray_tpu.serve.llm import LLMEngine

    cfg, params = engine_parts
    eng = LLMEngine(config=cfg, params=params, num_slots=3, max_seq=128,
                    kv_block_size=8, kv_pool_tokens=20 * 8)
    try:
        outs = {}

        def run(i):
            outs[i] = eng.generate(list(range(1 + i, 31 + i)),
                                   max_tokens=40)

        threads = [threading.Thread(target=run, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert all(len(outs[i]) == 40 for i in range(3))
        st = eng.stats()
        assert st["preemptions"] >= 1
        assert st["kv_pools"]["latent"]["blocks_free"] == 20
        eng._alloc.check_invariants()
    finally:
        eng.shutdown()


@pytest.mark.parametrize("kwargs, names", [
    (dict(kv_cache="slot"), "kv_cache='slot'"),
    (dict(speculation="ngram"), "speculation"),
    (dict(prefix_cache="radix"), "prefix cache"),
    (dict(prefix_cache_bytes=1 << 20), "prefix cache"),
    (dict(prefill_chunk=16), "chunked prefill")],
    ids=["slot", "speculation", "radix", "budget", "chunked"])
def test_what_the_model_lacks_raises_at_construction(engine_parts, kwargs,
                                                     names):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = engine_parts
    with pytest.raises(ValueError, match=names):
        LLMEngine(config=cfg, params=params, num_slots=2, max_seq=64,
                  **kwargs)


def test_kv_transfer_is_refused_by_name(engine_parts):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = engine_parts
    eng = LLMEngine(config=cfg, params=params, num_slots=2, max_seq=64,
                    kv_block_size=8)
    try:
        with pytest.raises(ValueError, match="KV inject"):
            eng.submit_prefilled([1, 2], np.zeros(1), np.zeros(1),
                                 np.zeros(1))
    finally:
        eng.shutdown()


def test_flash_on_a_mesh_keeps_an_axis_the_mesh_does_not_divide_whole():
    """One sequence on fsdp=2 x tp=2 (the benchmark's correctness check
    of the four-chip train cell): the batch axis cannot be divided, so
    every device of the fsdp axis attends the whole batch for its heads,
    still under ``shard_map`` (the chip's compiler refuses a bare Pallas
    kernel on a mesh of more than one device)."""
    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.parallel.sharding import FSDP_TP_RULES

    cfg = llama.CONFIGS["debug"]
    mesh = make_mesh(MeshConfig(dp=1, fsdp=2, tp=2),
                     devices=jax.devices()[:4])
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 16))
    k = jax.random.normal(ks[1], (1, 64, 2, 16))
    v = jax.random.normal(ks[2], (1, 64, 2, 16))
    want = llama._flash_on_mesh(q, k, v, cfg, FSDP_TP_RULES)
    with jax.sharding.set_mesh(mesh):
        jaxpr = str(jax.make_jaxpr(lambda *a: llama._flash_on_mesh(
            *a, cfg, FSDP_TP_RULES))(q, k, v))
        got = jax.jit(lambda *a: llama._flash_on_mesh(
            *a, cfg, FSDP_TP_RULES))(q, k, v)
    assert "shard_map" in jaxpr
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
