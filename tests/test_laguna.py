"""The decoder whose query heads differ by layer kind over one count of
KV heads, with a gate on every head's attention output, a window beside
full layers under YaRN and a shared expert beside softmax-routed experts
(``ray_tpu.models.laguna``), at a small size on the CPU with every ratio
of the published model kept (6 / 8 query heads over 2 KV heads, a window
of 3 blocks, rotary on half a head on full layers only, experts narrower
than hidden, layer 0 dense and full), against the benchmark's plain
reference (``benchmark/reference/laguna.py``) on seeded random weights."""

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
from ray_tpu.models import laguna, mimo_v2, moe  # noqa: E402
from ray_tpu.models import paged_cache as pc  # noqa: E402
from ray_tpu.ops.pallas import paged_hybrid_decode_attention as pha  # noqa: E402
from ray_tpu.ops.rope import YarnScaling, rope_frequencies  # noqa: E402

ROPE = {
    "full_attention": dict(rope_theta=500000, rope_type="yarn", factor=4,
                           original_max_position_embeddings=32, beta_slow=1,
                           beta_fast=4,
                           attention_factor=0.1 * math.log(4) + 1,
                           partial_rotary_factor=0.5),
    "sliding_attention": dict(rope_type="default", rope_theta=10000,
                              partial_rotary_factor=1)}
FULL, SWA = "full_attention", "sliding_attention"
SPEC = dict(
    name="tiny-laguna", architecture="laguna",
    reference="benchmark/reference/laguna.py",
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=5, num_attention_heads=6, num_key_value_heads=2,
    head_dim=16, max_position_embeddings=512, rms_norm_eps=1e-6,
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, tie_word_embeddings=False,
    gating=True, sliding_window=24, rope_parameters=ROPE,
    layer_types=[FULL, SWA, SWA, SWA, FULL],
    mlp_layer_types=["dense", "sparse", "sparse", "sparse", "sparse"],
    num_attention_heads_per_layer=[6, 8, 8, 8, 6],
    moe_apply_router_weight_on_input=False, moe_routed_scaling_factor=2.5,
    torch_dtype="bfloat16")
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
    bucket to the last row; both longer than the window of 24) and 40
    decode steps through both pools, across block boundaries (block 8)
    and past the window twice over: the window layers' blocks behind the
    window are given back on the way, and every step's logits are the
    reference's full forward pass. (bfloat16 at this size: a router
    choice near a tie flips and moves a row; float32 is the arithmetic's
    test.)"""
    monkeypatch.setattr(ARCH, "program_config",
                        lambda spec, f=ARCH.program_config:
                        dataclasses.replace(f(spec), dtype=dtype))
    params = make_params(SPEC, 11, dtype)
    n = prompt + 40
    tokens = np.asarray(jax.random.randint(jax.random.key(5), (n,), 0, 256))
    got = ARCH.serve_program_logits(params, SPEC, tokens, DEPLOYMENT,
                                    prefill=prompt)
    want = np.asarray(REF.logits(params, jnp.asarray(tokens), SPEC,
                                 list(range(prompt - 1, n))))
    assert got.shape == want.shape == (41, 256)
    assert REF.rel_err(got[0], want[0]) < limit
    assert REF.rel_err(got[1:], want[1:]) < limit
    if dtype == jnp.float32:
        for i in (1, 4, 12, 27, 40):   # single steps, past freed blocks
            assert REF.rel_err(got[i], want[i]) < 2 * limit


def test_window_blocks_are_given_back_and_the_pools_hold_whole_rows():
    """One cache assembly (``paged_cache``) under this model and
    ``mimo_v2``: a window of 24 at block 8 is 4 blocks a slot; a slot
    that never leaves the window gives nothing back while another runs
    far past it."""
    cfg = config()
    page = laguna.pages(cfg, num_slots=2, max_seq=128, block_size=8,
                        pool_tokens=256)
    assert page["window"].num_blocks == 1 + 2 * 4
    assert page == pc.hybrid_pages(24, num_slots=2, max_seq=128,
                                   block_size=8, pool_tokens=256)
    cache = laguna.init_cache(cfg, page, 2)
    assert cache["full"]["k"].shape == (2, 33, 8, 2 * 16)     # 2 full layers
    assert cache["window"]["v"].shape == (3, 9, 8, 2 * 16)    # 3 window
    # the other model's cache is the same assembly with its own rows
    m = mimo_v2.MimoV2Config()
    mc = mimo_v2.init_cache(m, mimo_v2.pages(
        m, num_slots=2, max_seq=128, block_size=8, pool_tokens=256), 2)
    assert set(mc) == set(cache) and mc["window"]["k"].shape[-1] == 4 * 24
    alloc = laguna.make_manager(cfg, page, 2)
    assert alloc.ensure(0, 41) and alloc.ensure(1, 11)
    short = alloc.kinds["window"].tables[1].copy()
    freed = 0
    for n in range(42, 100):
        freed += alloc.trim(0, n)
        assert alloc.trim(1, 11 + (n - 42) % 10) == 0   # inside the window
        assert alloc.ensure(0, n)
        assert np.count_nonzero(alloc.kinds["window"].tables[0]) <= 4
        alloc.check_invariants()
    assert freed == (99 - 24) // 8 - (41 - 24) // 8
    assert (alloc.kinds["window"].tables[1] == short).all()
    assert alloc.pools([99, 11])["window"]["live_tokens"] == 24 + 11


def test_the_decode_step_with_the_kernel_is_the_reference_too(kernel_on_cpu):
    """float32, with the decode step on the chip's path: the work lists
    built once for both kinds and the kernel (interpreted) at 6 and 8
    query rows over 2 KV heads, a stale slot between two running ones."""
    cfg = config(jnp.float32)
    params = make_params(SPEC, 11, jnp.float32)
    page = laguna.pages(cfg, num_slots=3, max_seq=128, block_size=8,
                        pool_tokens=14 * 8)
    alloc = laguna.make_manager(cfg, page, 3)
    cache = laguna.init_cache(cfg, page, 3)
    prefill = laguna.make_prefill(params, cfg, page)
    decode = laguna.make_decode_step(params, cfg, page)
    seqs = {s: list(np.asarray(jax.random.randint(
        jax.random.key(s), (n,), 0, 256))) for s, n in ((0, 21), (1, 37),
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
    for _ in range(22):    # slot 0 passes the window, slot 2 reaches it
        last = np.zeros(3, np.int32)
        for s in (0, 2):
            alloc.trim(s, len(seqs[s]))
            assert alloc.ensure(s, len(seqs[s]))
            last[s] = seqs[s][-1]
        cache, lg = decode(cache, alloc.device_tables(), jnp.asarray(last),
                           jnp.asarray(active))
        for s in (0, 2):
            step_logits[s].append(np.asarray(lg)[s])
            seqs[s].append(int(step_logits[s][-1].argmax()))
    assert np.asarray(cache["length"]).tolist() == [21 + 22, 37, 6 + 22]
    assert alloc.kinds["window"]._base[0] > 0           # blocks given back
    for s in (0, 2):
        want = np.asarray(REF.logits(
            params, jnp.asarray(seqs[s][:-1]), SPEC,
            list(range(plen[s], len(seqs[s]) - 1))))
        got = np.stack(step_logits[s])
        assert REF.rel_err(got, want) < 2e-4
        assert got.argmax(-1).tolist() == want.argmax(-1).tolist()


@pytest.mark.parametrize("kind, heads", [("full", 6), ("window", 8)])
def test_the_kernel_at_these_rows_matches_its_oracle(kind, heads):
    """Keys as wide as values, one chunk a KV head, no sink, 6 or 8 query
    rows over 2 KV heads; the window layer's 4 blocks in one step; blocks
    behind the window are the null block, which holds NaN."""
    cfg = config(jnp.float32)
    ks = jax.random.split(jax.random.key(3), 3)
    B, bs, mbs, D, KV = 3, 8, 8, 16, 2
    lengths = jnp.asarray([1, 29, 61], jnp.int32)
    kp = jax.random.normal(ks[0], (2, 1 + B * mbs, bs, KV * D))
    vp = jax.random.normal(ks[1], (2, 1 + B * mbs, bs, KV * D))
    kp, vp = kp.at[:, 0].set(jnp.nan), vp.at[:, 0].set(jnp.nan)
    tables = np.arange(1, 1 + B * mbs, dtype=np.int32).reshape(B, mbs)
    window = cfg.window_of(kind)
    if window:
        for b, n in enumerate(np.asarray(lengths)):
            tables[b, :max(n - window, 0) // bs] = 0       # given back
    q = jax.random.normal(ks[2], (B, heads, D))
    kw = dict(scale=cfg.scale(kind), k_slices=laguna.key_slices(cfg), dv=D,
              window=window)
    assert pha.blocks_per_step(window, bs, mbs) == 4
    got = pha.paged_hybrid_decode_attention(
        q, kp, vp, 1, jnp.asarray(tables), lengths, interpret=True, **kw)
    clean = (kp.at[:, 0].set(0.0), vp.at[:, 0].set(0.0))
    want = pha.paged_hybrid_attention_reference(
        q, *clean, 1, jnp.asarray(tables), lengths, **kw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


# --------------------------------------------------------------- the gate
def test_the_gate_against_a_plain_loop_over_heads():
    ks = jax.random.split(jax.random.key(12), 3)
    out = jax.random.normal(ks[0], (5, 8, 16), jnp.float32)
    h = jax.random.normal(ks[1], (5, 64), jnp.float32)
    layer = {"w_out_gate": jax.random.normal(ks[2], (64, 8)) * 0.125}
    got = np.asarray(laguna.gate_heads(out, h, layer))
    z = np.asarray(h, np.float64) @ np.asarray(layer["w_out_gate"],
                                               np.float64)
    for t in range(5):
        for head in range(8):
            g = 1.0 / (1.0 + math.exp(-z[t, head]))
            np.testing.assert_allclose(got[t, head],
                                       g * np.asarray(out[t, head]),
                                       rtol=1e-5, atol=1e-6)
    # and it is in the program: with layer 1's gate projection zeroed
    # (sigmoid(0) = 1/2) and its output projection doubled the layer is
    # an ungated one, and the logits are not what the gated layer gives
    cfg = config(jnp.float32)
    params = make_params(SPEC, 3, jnp.float32)
    tokens = jnp.asarray(np.arange(1, 17)[None])
    page = laguna.pages(cfg, num_slots=1, max_seq=64, block_size=8,
                        pool_tokens=64)

    def last_logits(p):
        alloc = laguna.make_manager(cfg, page, 1)
        assert alloc.ensure(0, 17)
        _, lg = laguna.make_prefill(p, cfg, page)(
            laguna.init_cache(cfg, page, 1), alloc.table_rows(0), tokens,
            16, 0)
        return np.asarray(lg)

    ungated = dict(params, layers=[
        dict(layer, w_out_gate=jnp.zeros_like(layer["w_out_gate"]),
             wo=layer["wo"] * 2.0) if l == 1 else layer
        for l, layer in enumerate(params["layers"])])
    assert not np.allclose(last_logits(ungated), last_logits(params),
                           atol=1e-3)


# ------------------------------------------------------------------- YaRN
def test_yarn_on_half_a_head_at_the_published_keys():
    """64 of 128 dimensions, theta 500,000, factor 64 over 4096,
    beta_fast 64: the ramp runs between pairs 5 and 16 of 32, the tables
    carry the published attention_factor to its digits, the softmax
    scale none."""
    published = 1.4158883083359672
    y = YarnScaling(factor=64.0, original_max_seq=4096, beta_fast=64.0,
                    beta_slow=1.0, mscale=1.0, mscale_all_dim=0.0)
    assert y.table_factor == pytest.approx(published, abs=1e-15)
    assert y.attention_factor == 1.0
    assert y.ramp_ends(64, 500000.0) == (5, 16)
    c = [64 * math.log(4096 / (b * 2 * math.pi)) / (2 * math.log(500000))
         for b in (64, 1)]
    assert [round(v, 2) for v in c] == [5.66, 15.80]
    f = np.asarray([500000.0 ** (2 * i / 64) for i in range(32)])
    ramp = np.clip((np.arange(32) - 5) / 11, 0, 1)
    inv = (1 - ramp) / f + ramp / (64 * f)
    np.testing.assert_allclose(np.asarray(y.inverse_frequencies(64, 5e5)),
                               inv, rtol=1e-6)
    cos, sin = rope_frequencies(64, 264, 500000.0, yarn=y)
    pos = np.arange(264)[:, None]
    np.testing.assert_allclose(np.asarray(cos), published * np.cos(pos * inv),
                               atol=3e-4)
    np.testing.assert_allclose(np.asarray(sin), published * np.sin(pos * inv),
                               atol=3e-4)
    # a rotated q . k is scaled by its square, the unrotated half not
    assert published ** 2 == pytest.approx(2.0047, abs=1e-4)
    # the adapter builds exactly this from the published keys, and the
    # reference's own copy of the formula agrees
    row = dict(rope_theta=500000, rope_type="yarn", factor=64,
               original_max_position_embeddings=4096, beta_slow=1,
               beta_fast=64, attention_factor=published,
               partial_rotary_factor=0.5)
    spec = dict(SPEC, head_dim=128, rope_parameters=dict(
        ROPE, full_attention=row))
    cfg = ARCH.program_config(spec)
    assert cfg.yarn == y and cfg.rotary_dim == 64
    assert cfg.swa_rotary_dim == 128 and cfg.scale("full") == 128 ** -0.5
    assert ARCH.yarn_table_factor(row) == pytest.approx(published, abs=1e-15)
    ARCH.check_config(spec)
    with pytest.raises(SystemExit, match="attention_factor"):
        ARCH.check_config(dict(spec, rope_parameters=dict(
            ROPE, full_attention=dict(row, attention_factor=1.4))))
    inv_r, factor_r = REF.rope_of(spec, "full_attention")
    np.testing.assert_allclose(np.asarray(inv_r), inv, rtol=1e-6)
    assert factor_r == published
    inv_w, factor_w = REF.rope_of(spec, "sliding_attention")
    assert inv_w.shape == (64,) and factor_w == 1.0


# ---------------------------------------------------------------- routing
def _parents_route_sigmoid_topk(x, router, bias, top_k, scale=1.0,
                                n_group=1, topk_group=1):
    """``route_sigmoid_topk`` as it stood before the softmax router."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    c = s if bias is None else s + bias.astype(jnp.float32)[None, :]
    if n_group > 1:
        T, E = c.shape
        grouped = c.reshape(T, n_group, E // n_group)
        score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, best = jax.lax.top_k(score, topk_group)
        kept = jnp.zeros((T, n_group), bool).at[
            jnp.arange(T)[:, None], best].set(True)
        c = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(T, E)
    _, idx = jax.lax.top_k(c, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * scale
    return idx.astype(jnp.int32), w


@pytest.mark.parametrize("groups", [(1, 1), (8, 4)], ids=["all", "grouped"])
def test_the_sigmoid_router_is_the_parents_bit_for_bit(groups):
    ks = jax.random.split(jax.random.key(6), 3)
    x = jax.random.normal(ks[0], (77, 64), jnp.bfloat16)
    router = jax.random.normal(ks[1], (64, 32), jnp.bfloat16) * 0.125
    bias = jax.random.normal(ks[2], (32,), jnp.bfloat16) * 0.1
    idx, w = moe.route_sigmoid_topk(x, router, bias, 4, 1.5, *groups)
    want_idx, want_w = _parents_route_sigmoid_topk(x, router, bias, 4, 1.5,
                                                   *groups)
    assert (np.asarray(idx) == np.asarray(want_idx)).all()
    assert (np.asarray(w) == np.asarray(want_w)).all()


def test_the_softmax_router_against_a_plain_loop():
    """p = softmax over all 16; the 4 largest; w = p over their sum,
    times 2.5."""
    ks = jax.random.split(jax.random.key(7), 2)
    x = jax.random.normal(ks[0], (53, 64), jnp.float32)
    router = jax.random.normal(ks[1], (64, 16), jnp.float32) * 0.2
    idx, w = moe.route_softmax_topk(x, router, 4, 2.5)
    z = np.asarray(x, np.float64) @ np.asarray(router, np.float64)
    for t in range(53):
        p = np.exp(z[t] - z[t].max())
        p /= p.sum()
        chosen = sorted(range(16), key=lambda e: -p[e])[:4]
        assert sorted(np.asarray(idx[t]).tolist()) == sorted(chosen)
        for e, got in zip(np.asarray(idx[t]).tolist(), np.asarray(w[t])):
            assert got == pytest.approx(
                2.5 * p[e] / sum(p[e2] for e2 in chosen), rel=1e-4)
    assert np.asarray(w).sum(-1) == pytest.approx(2.5, rel=1e-5)


def _routed_layer(seed, E=16, h=64, m=16, dtype=jnp.float32):
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


@pytest.mark.parametrize("shares", [1, 4])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """THE test that ties the share to the model, for the softmax
    router: ``experts_held=(0, E)`` IS the uncut reference's whole layer
    ``shared(x) + 2.5 routed(x)`` (the configuration the benchmark runs),
    and the routed parts of 4 shares of ``E / 4`` plus the shared expert,
    which every chip computes alike, counted ONCE, add up to the same."""
    layer = _routed_layer(7)
    x = jax.random.normal(jax.random.key(8), (37, 64), jnp.float32)
    routed, _ = REF.routed_mlp(x, layer, SPEC, held=(0, 16))
    want = REF.shared_mlp(x, layer) + routed
    count = 16 // shares
    total = moe.shared_expert(x, layer)            # once, not once a chip
    cfg = config(jnp.float32)
    for first in range(0, 16, count):
        y, counters = moe.experts_by_share(
            x, _share(layer, first, count), experts_held=(first, count),
            top_k=4, scale=2.5, score="softmax")
        part, _ = REF.routed_mlp(x, _share(layer, first, count), SPEC,
                                 held=(first, count))
        assert REF.rel_err(y, part) < 1e-4
        assert counters[4] == 0                      # nothing dropped
        total = total + y
        # the model's own layer on that chip is its share plus the shared
        mine, _ = laguna._mlp(x, _share(layer, first, count),
                              dataclasses.replace(
                                  cfg, experts_held=(first, count)), None)
        assert REF.rel_err(mine, REF.shared_mlp(x, layer) + part) < 1e-4
        if shares == 1:
            assert counters[1] == 37 * 4             # every pair is here
            assert REF.rel_err(mine, want) < 1e-4    # the whole layer
    assert REF.rel_err(total, want) < 1e-4


def test_no_pair_is_dropped_when_routing_piles_onto_one_expert():
    """Every token's first choice is expert 9 (a huge router column): 129
    rows for one of 16 experts, all computed; 4 pairs a token whatever
    the skew, since every expert is held."""
    layer = _routed_layer(9)
    x = jnp.abs(jax.random.normal(jax.random.key(10), (129, 64)))
    layer["router"] = layer["router"].at[:, 9].set(1.0)
    kw = dict(experts_held=(0, 16), top_k=4, scale=2.5, score="softmax")
    y, c = moe.experts_by_share(x, layer, **kw)
    want, _ = REF.routed_mlp(x, layer, SPEC, held=(0, 16))
    calls, pairs, hit, ratio, dropped, _ = np.asarray(c)
    assert dropped == 0 and pairs == 4 * 129 and calls == 1 and hit <= 16
    assert ratio >= 16 * 129 / pairs - 1e-3          # largest over mean
    assert REF.rel_err(y, want) < 1e-4
    # rows that are no token (an idle slot) are routed nowhere
    _, c = moe.experts_by_share(x, layer, valid=jnp.arange(129) < 3, **kw)
    assert float(c[1]) == 12 and float(c[2]) <= 12


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
        assert st["preemptions"] == 0
        assert st["window_blocks_freed"] == (79 - 24) // 8 - (41 - 24) // 8
        c = st["model_counters"]
        assert c["expert_pairs_dropped"] == 0
        assert c["expert_layer_calls"] == 4 * 39        # 4 routed layers
        assert c["expert_pairs"] == 4 * 39 * 4          # whole: 4 a token
        assert 4 * 39 <= c["experts_hit"] <= 4 * 39 * 4
        assert st["model_counters_prefill"]["expert_layer_calls"] == 4
        assert st["model_counters_prefill"]["expert_pairs"] == 4 * 40 * 4
        assert st["kv_pools"]["full"] == {
            "blocks_total": 48, "blocks_free": 48, "block_size": 8,
            "live_tokens": 0}
        assert st["kv_pools"]["window"]["blocks_total"] == 3 * 4
        assert st["kv_pools"]["window"]["blocks_free"] == 3 * 4
        eng._alloc.check_invariants()
    finally:
        eng.shutdown()


def test_preemption_returns_both_kinds_of_blocks(engine_parts):
    """A full pool too small for three growing answers: the youngest is
    preempted, recomputed and finishes; afterwards both pools are
    whole."""
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
        assert st["kv_pools"]["full"]["blocks_free"] == 20
        assert st["kv_pools"]["window"]["blocks_free"] == 3 * 4
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
