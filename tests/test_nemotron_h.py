"""The decoder of one mixer a layer (``ray_tpu.models.nemotron_h``): a
Mamba-2 mixer whose recurrent state lives per slot, an attention without
a position term, latent experts of two matrices and a squared ReLU
beside an ungated shared expert; at a small size on the CPU with every
ratio of the published model kept (heads in groups that share B and C,
experts narrower than hidden on a latent narrower still, the pattern's
first eleven layers), against the benchmark's plain reference
(``benchmark/reference/nemotron_h.py``, the recurrence step by step) on
seeded random weights."""

import dataclasses
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import model_spec  # noqa: E402
from ray_tpu.models import moe, nemotron_h  # noqa: E402
from ray_tpu.models.paged_cache import PagedConfig  # noqa: E402
from ray_tpu.ops.norms import rmsnorm  # noqa: E402
from ray_tpu.ops.pallas import ssm_decode_update as ssm_kernel  # noqa: E402

SPEC = dict(
    name="tiny-nemotron", architecture="nemotron_h",
    reference="benchmark/reference/nemotron_h.py",
    vocab_size=256, hidden_size=64, num_hidden_layers=11,
    hybrid_override_pattern="MEMEMEM*EMEMEMEM*", mamba_num_heads=8,
    mamba_head_dim=16, n_groups=2, ssm_state_size=16, conv_kernel=4,
    chunk_size=16, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, n_routed_experts=16, num_experts_per_tok=4,
    moe_latent_size=32, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, n_shared_experts=1,
    routed_scaling_factor=5, norm_topk_prob=True, n_group=1, topk_group=1,
    norm_eps=1e-5, mlp_hidden_act="relu2", mamba_hidden_act="silu",
    use_conv_bias=True, tie_word_embeddings=False,
    max_position_embeddings=512, torch_dtype="bfloat16")
ARCH = model_spec.adapter(SPEC)
REF = model_spec.reference(SPEC)
DEPLOYMENT = dict(num_slots=3, max_seq=128, kv_block_size=8,
                  kv_pool_tokens=3 * 128)
F32 = jnp.float32


def make_params(spec, seed, dtype=jnp.bfloat16):
    from benchmark import weights

    return jax.tree.map(lambda a: a.astype(dtype), weights.make(spec, seed))


def config(dtype=jnp.bfloat16):
    return dataclasses.replace(ARCH.program_config(SPEC), dtype=dtype)


def programs(params, cfg, slots=3, pool_tokens=3 * 128):
    page = PagedConfig(num_blocks=1 + pool_tokens // 8, block_size=8,
                       max_seq=128)
    return (page, nemotron_h.make_manager(cfg, page, slots),
            nemotron_h.init_cache(cfg, page, slots),
            nemotron_h.make_prefill(params, cfg, page),
            nemotron_h.make_decode_step(params, cfg, page))


def padded(tokens, P):
    out = np.zeros((1, P), np.int32)
    out[0, :len(tokens)] = tokens
    return jnp.asarray(out)


# --------------------------------------------------------- the recurrence
@pytest.mark.parametrize("true_len, T", [(64, 64), (48, 48), (37, 48),
                                         (5, 16), (7, 8)],
                         ids=["4-chunks", "3-chunks", "mid-chunk",
                              "inside-one", "bucket-under-a-chunk"])
def test_the_chunked_recurrence_is_the_step_by_step_one(true_len, T):
    """``ssd_chunked`` (chunks of 16, the duality's products, a scan over
    the closing states) against the reference's one-position-at-a-time
    scan, at lengths that are and are not multiples of the chunk: a
    position past ``true_len`` has ``dt = 0`` and must leave y's live
    rows and the closing state as they are."""
    H, P, G, N = 8, 16, 2, 16
    ks = jax.random.split(jax.random.key(3), 6)
    x = jax.random.normal(ks[0], (T, H, P), F32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (T, H), F32))
    A = -jnp.exp(jax.random.normal(ks[2], (H,), F32))
    B = jax.random.normal(ks[3], (T, G, N), F32)
    C = jax.random.normal(ks[4], (T, G, N), F32)
    dt = jnp.where(jnp.arange(T)[:, None] < true_len, dt, 0.0)
    with jax.default_matmul_precision("highest"):
        y, S = nemotron_h.ssd_chunked(x.reshape(T, G, -1), dt, dt * A, B, C,
                                      16)
    want_y, want_S = REF.recurrence(x[:true_len], dt[:true_len], A,
                                    B[:true_len], C[:true_len],
                                    jnp.zeros((H,), F32))
    assert REF.rel_err(y.reshape(T, H, P)[:true_len], want_y) < 2e-5
    # the cache's layout: (G, N, heads a group x P)
    S = S.reshape(G, N, H // G, P).transpose(0, 2, 3, 1).reshape(H, P, N)
    assert REF.rel_err(S, want_S) < 2e-5


# ------------------------------------------------------- the in-projection
@pytest.mark.parametrize("cfg", [
    nemotron_h.NemotronHConfig(),
    nemotron_h.NemotronHConfig(hidden=40, ssm_heads=6, ssm_head_dim=12,
                               ssm_groups=3, ssm_state=5)],
    ids=["tiny-defaults", "widths-no-multiples-of-each-other"])
def test_the_in_projection_is_the_single_product_split(cfg):
    """``_in_proj``'s three products against column runs of the stored
    matrix are ``u W_in`` split at ``[d_inner, d_inner + conv_dim]`` (64
    | 128 | 8 of 200 columns, then 72 | 102 | 6 of 180), column for
    column: a column's sum does not know which product it was made in.
    ``z`` and ``xBC`` come in the model's dtype, ``dt_raw`` in float32."""
    width = cfg.d_inner + cfg.conv_dim + cfg.ssm_heads
    assert nemotron_h.param_shapes(cfg)["layers"][0]["w_in"] == (
        cfg.hidden, width)
    kx, kn, kw = jax.random.split(jax.random.key(5), 3)
    x = jax.random.normal(kx, (7, cfg.hidden), cfg.dtype)
    layer = {"norm": 0.1 * jax.random.normal(kn, (cfg.hidden,), cfg.dtype),
             "w_in": jax.random.normal(kw, (cfg.hidden, width), cfg.dtype)
             * cfg.hidden ** -0.5}
    u = rmsnorm(x, layer["norm"], cfg.norm_eps)
    want = jnp.split(jnp.dot(u, layer["w_in"], preferred_element_type=F32),
                     [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1)
    # a sum in another order is an ulp of float32, a cast's tie one of
    # bfloat16 (2 ** -8); a split one column off is of order one
    for got, part, dtype, rtol in zip(
            nemotron_h._in_proj(x, layer, cfg), want,
            (cfg.dtype, cfg.dtype, F32), (2 ** -7, 2 ** -7, 1e-5)):
        assert (got.dtype, got.shape) == (dtype, part.shape)
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(part.astype(dtype), np.float32), rtol=rtol, atol=1e-5)


# ------------------------------------------- the program and the reference
@pytest.mark.parametrize("prompt", [29, 32, 64],
                         ids=["mid-block", "fills-a-chunk", "fills-bucket"])
@pytest.mark.parametrize("dtype, limit", [(jnp.float32, 5e-5),
                                          (jnp.bfloat16, 0.25)],
                         ids=["float32", "bfloat16"])
def test_prefill_then_decode_through_both_caches_match_the_reference(
        dtype, limit, prompt, monkeypatch):
    """A prompt padded to its bucket of 64 and 24 decode steps through
    the KV pool AND the recurrent state, across block boundaries (block
    8): every step's logits are the reference's full forward pass.
    float32 is the arithmetic's test: 5e-5 is what float32 sums in
    another order leave over eleven layers (read: 2e-6). bfloat16 at
    hidden 64: the Mamba-2 layers read 0.006 each, and ONE flipped
    router choice is a quarter of a token's routed sum here (4 of 16
    experts; 22 of 512 at the published size) and moves a row by 0.07:
    0.25 only says the path runs in bfloat16."""
    monkeypatch.setattr(ARCH, "program_config",
                        lambda spec, f=ARCH.program_config:
                        dataclasses.replace(f(spec), dtype=dtype))
    params = make_params(SPEC, 11, dtype)
    n = prompt + 24
    tokens = np.asarray(jax.random.randint(jax.random.key(5), (n,), 0, 256))
    got = ARCH.serve_program_logits(params, SPEC, tokens, DEPLOYMENT,
                                    prefill=prompt)
    want = np.asarray(REF.logits(params, jnp.asarray(tokens), SPEC,
                                 list(range(prompt - 1, n))))
    assert got.shape == want.shape == (25, 256)
    assert REF.rel_err(got[0], want[0]) < limit
    assert REF.rel_err(got[1:], want[1:]) < limit
    if dtype == jnp.float32:
        for i in (1, 7, 24):
            assert REF.rel_err(got[i], want[i]) < 2 * limit


def test_a_padded_prompt_leaves_the_state_of_its_true_length():
    """29 tokens in the bucket of 64 (35 pad positions, other ids) and
    the same 29 in the bucket of 32 leave the same recurrent and
    convolution state, the same KV rows and the same logits; and that
    state is the one 28 tokens and one decode step leave."""
    cfg = config(F32)
    params = make_params(SPEC, 12, F32)
    page, alloc, cache0, prefill, decode = programs(params, cfg)
    toks = np.asarray(jax.random.randint(jax.random.key(6), (29,), 1, 256))
    alloc.ensure(1, 30)
    wide = np.full((1, 64), 77, np.int32)
    wide[0, :29] = toks
    a, la = prefill(cache0, alloc.table_rows(1), jnp.asarray(wide), 29, 1)
    b, lb = prefill(nemotron_h.init_cache(cfg, page, 3), alloc.table_rows(1),
                    padded(toks, 32), 29, 1)
    for name in ("ssm_state", "conv_state"):
        assert REF.rel_err(a[name][:, 1], b[name][:, 1]) < 1e-5, name
        assert not np.any(np.asarray(a[name][:, 0]))     # no other slot
        assert not np.any(np.asarray(a[name][:, 2]))
    assert REF.rel_err(la, lb) < 1e-5
    assert int(a["length"][1]) == 29
    # the convolution's columns are the RAW xBC of positions 26, 27, 28
    assert np.any(np.asarray(a["conv_state"][:, 1]))
    c, _ = prefill(nemotron_h.init_cache(cfg, page, 3), alloc.table_rows(1),
                   padded(toks[:28], 32), 28, 1)
    last = np.zeros(3, np.int32)
    last[1] = toks[28]
    c, lc = decode(c, alloc.device_tables(), jnp.asarray(last),
                   jnp.asarray([False, True, False]))
    for name in ("ssm_state", "conv_state"):
        assert REF.rel_err(c[name][:, 1], a[name][:, 1]) < 2e-5, name
    assert REF.rel_err(lc[1], la) < 2e-5


def test_a_slot_taken_again_starts_from_zero_whatever_it_held():
    cfg = config(F32)
    params = make_params(SPEC, 13, F32)
    page, alloc, clean, prefill, _ = programs(params, cfg)
    toks = np.asarray(jax.random.randint(jax.random.key(7), (21,), 0, 256))
    alloc.ensure(2, 22)
    dirty = dict(nemotron_h.init_cache(cfg, page, 3))
    dirty["ssm_state"] = jnp.full_like(dirty["ssm_state"], 123.0)
    dirty["conv_state"] = jnp.full_like(dirty["conv_state"], -7.0)
    a, la = prefill(clean, alloc.table_rows(2), padded(toks, 32), 21, 2)
    b, lb = prefill(dirty, alloc.table_rows(2), padded(toks, 32), 21, 2)
    assert np.array_equal(np.asarray(la), np.asarray(lb))
    for name, held in (("ssm_state", 123.0), ("conv_state", -7.0)):
        assert np.array_equal(np.asarray(a[name][:, 2]),
                              np.asarray(b[name][:, 2])), name
        # and the other slots' rows were not touched
        assert np.all(np.asarray(b[name][:, :2]) == held)


def test_the_decode_step_makes_its_window_before_it_writes_the_columns():
    """A decode step shifts a layer's convolution columns inside the
    donated state. Left to fuse, the chip's compiler made of that, at
    the cell's 192 slots, a copy that read columns it had already
    overwritten (the first layer's two carried columns were noise every
    step: PERF.md, PR 39; no CPU and no 8-slot program shows it), so the
    window is materialised behind a barrier first: one a state-space
    layer in the program the compiler is given."""
    cfg = config()
    params = make_params(SPEC, 13)
    page, alloc, cache, _, decode = programs(params, cfg)
    text = decode.jitted.lower(
        params, cache, alloc.device_tables(), jnp.zeros((3,), jnp.int32),
        jnp.ones((3,), bool)).as_text()
    assert text.count("optimization_barrier") == cfg.pattern.count("M") == 5


@pytest.mark.parametrize("kernel", [False, True], ids=["oracle", "kernel"])
def test_an_idle_slots_state_is_bit_identical_after_a_step(kernel,
                                                           request):
    """Three slots prefilled, slot 1 stops running: three more steps
    leave its recurrent and convolution state bit for bit, move the
    others', and no running slot reads another's state (each equals the
    sequence run alone)."""
    if kernel:
        request.getfixturevalue("kernel_on_cpu")
    cfg = config(F32)
    params = make_params(SPEC, 14, F32)
    page, alloc, cache, prefill, decode = programs(params, cfg)
    seqs = [np.asarray(jax.random.randint(jax.random.key(20 + s), (n,), 0,
                                          256))
            for s, n in enumerate((19, 26, 33))]
    for slot, toks in enumerate(seqs):
        alloc.ensure(slot, len(toks) + 4)
        cache, _ = prefill(cache, alloc.table_rows(slot),
                           padded(toks[:-3], 32), len(toks) - 3, slot)
    before = {n: np.asarray(cache[n]) for n in ("ssm_state", "conv_state")}
    active = jnp.asarray([True, False, True])
    rows = []
    for i in range(3):
        last = np.asarray([s[len(s) - 3 + i] for s in seqs], np.int32)
        cache, lg = decode(cache, alloc.device_tables(), jnp.asarray(last),
                           active)
        rows.append(np.asarray(lg))
    for name, was in before.items():
        now = np.asarray(cache[name])
        assert np.array_equal(now[:, 1], was[:, 1]), name
        assert not np.array_equal(now[:, 0], was[:, 0])
    assert int(cache["length"][1]) == len(seqs[1]) - 3
    for slot in (0, 2):
        want = np.asarray(REF.logits(params, jnp.asarray(seqs[slot]), SPEC,
                                     [len(seqs[slot]) - 1]))
        assert REF.rel_err(rows[2][slot], want[0]) < 5e-5
    # the decode step's counters: five state-space layers, two slots live
    assert [float(c) for c in cache["counters"][-2:]] == [10.0, 5.0]


@pytest.mark.parametrize("G", [8, 6, 1], ids=["two_steps_of_4", "three_steps_of_2",
                                              "one_group"])
@pytest.mark.parametrize("active", [[1, 0, 1, 1, 0], [0, 0, 0, 0, 0],
                                    [1, 1, 1, 1, 1], [0, 0, 0, 0, 1]],
                         ids=["some", "none", "all", "last"])
def test_the_state_updates_kernel_matches_its_oracle(active, G):
    """The kernel (interpreted) for every way of compacting the running
    slots to the front of its grid, at counts of groups that its four a
    step divide, do not divide and exceed; the other layer of the state
    and the idle slots' rows are untouched bit for bit."""
    L, S, N, W = 2, 5, 128, 256
    ks = jax.random.split(jax.random.key(0), 5)
    state = jax.random.normal(ks[0], (L, S, G, N, W), F32)
    xdt = jax.random.normal(ks[1], (S, G, W), F32)
    decay = jax.random.uniform(ks[2], (S, G, W), F32)
    b = jax.random.normal(ks[3], (S, G, N), F32)
    c = jax.random.normal(ks[4], (S, G, N), F32)
    act = jnp.asarray(active, bool)
    want_s, want_y = ssm_kernel.ssm_decode_update_reference(
        state, 1, xdt, decay, b, c, act)
    got_s, got_y = ssm_kernel.ssm_decode_update(
        state, 1, xdt, decay, b, c, act, interpret=True)
    assert float(jnp.max(jnp.abs(got_s - want_s))) < 1e-5
    assert float(jnp.max(jnp.abs(got_y - want_y))) < 1e-4
    assert np.array_equal(np.asarray(got_s[0]), np.asarray(state[0]))
    idle = ~np.asarray(act)
    assert np.array_equal(np.asarray(got_s[1])[idle],
                          np.asarray(state[1])[idle])
    assert not np.any(np.asarray(got_y)[idle])


# ------------------------------------------------------ the expert layer
def _expert_layer(seed, E=16, h=64, latent=32, m=24, ms=48, dtype=F32):
    ks = jax.random.split(jax.random.key(seed), 9)
    n = lambda k, shape, std: jax.random.normal(k, shape, dtype) * std  # noqa: E731
    return {"norm": n(ks[0], (h,), 0.1), "router": n(ks[1], (h, E), h ** -0.5),
            "router_bias": n(ks[2], (E,), 0.01),
            "w_fc1": n(ks[3], (h, latent), h ** -0.5),
            "w_fc2": n(ks[4], (latent, h), latent ** -0.5),
            "ws_up": n(ks[5], (h, ms), h ** -0.5),
            "ws_down": n(ks[6], (ms, h), ms ** -0.5),
            "we_up": n(ks[7], (E, latent, m), latent ** -0.5),
            "we_down": n(ks[8], (E, m, latent), m ** -0.5)}


def _share(layer, first, count):
    return dict(layer, **{k: layer[k][first:first + count]
                          for k in ("we_up", "we_down")})


@pytest.mark.parametrize("shares", [1, 4])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """THE test that ties the share to the model: what the four chips of
    an expert-parallel layer compute (experts 0-3, 4-7, 8-11, 12-15; the
    partial LATENT sum of each through ``w_fc2``), with the shared
    expert, which every chip computes alike, counted ONCE, adds up to
    the uncut reference's whole layer; one share of all sixteen is it."""
    layer = _expert_layer(7)
    x = jax.random.normal(jax.random.key(8), (37, 64), F32)
    want, _ = REF.experts(x, layer, SPEC)
    with jax.default_matmul_precision("highest"):
        u = REF._rmsnorm(x, layer["norm"], 1e-5)
    count = 16 // shares
    cfg = config(F32)
    total = x + REF.shared_mlp(u, layer)           # once, not once a chip
    for first in range(0, 16, count):
        mine, counters = nemotron_h._experts(
            x, _share(layer, first, count),
            dataclasses.replace(cfg, experts_held=(first, count)), None)
        part, _ = REF.routed_mlp(u, _share(layer, first, count), SPEC,
                                 held=(first, count))
        # the model's own layer on that chip: x, its share, the shared
        assert REF.rel_err(mine, x + REF.shared_mlp(u, layer) + part) < 1e-4
        assert counters[4] == 0                      # nothing dropped
        total = total + part
        if shares == 1:
            assert counters[1] == 37 * 4             # every pair is here
            assert REF.rel_err(mine, want) < 1e-4    # the whole layer
    assert REF.rel_err(total, want) < 1e-4


# ------------------------------------------------------------- the engine
@pytest.fixture(scope="module")
def engine_parts():
    return config(F32), make_params(SPEC, 21, F32)


def _alone(cfg, params, prompt, n):
    """Greedy tokens of one request through the bare programs."""
    page, alloc, cache, prefill, decode = programs(params, cfg, slots=1,
                                                   pool_tokens=128)
    alloc.ensure(0, len(prompt) + n + 1)
    P = 64 if len(prompt) <= 64 else 128
    cache, lg = prefill(cache, alloc.table_rows(0), padded(prompt, P),
                        len(prompt), 0)
    out = [int(jnp.argmax(lg))]
    for _ in range(n - 1):
        cache, lg = decode(cache, alloc.device_tables(),
                           jnp.asarray([out[-1]], jnp.int32),
                           jnp.asarray([True]))
        out.append(int(jnp.argmax(lg[0])))
    return out


def test_the_engine_serves_it_and_reports_both_kinds_of_state(engine_parts):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = engine_parts
    eng = LLMEngine(config=cfg, params=params, num_slots=3, max_seq=128,
                    kv_block_size=8, kv_pool_tokens=3 * 128)
    try:
        prompt = list(range(1, 41))
        out = eng.generate(prompt, max_tokens=30)
        assert out == _alone(cfg, params, prompt, 30)
        st = eng.stats()
        assert st["preemptions"] == 0
        c = st["model_counters"]
        assert c["expert_pairs_dropped"] == 0
        assert c["expert_layer_calls"] == 5 * 29        # 5 expert layers
        assert c["expert_pairs"] == 5 * 29 * 4          # every expert held
        assert c["ssm_layer_calls"] == 5 * 29           # 5 Mamba-2 layers
        assert c["ssm_slots_live"] == 5 * 29            # one slot running
        assert st["model_counters_prefill"]["ssm_layer_calls"] == 5
        assert st["kv_pools"]["full"] == {
            "blocks_total": 48, "blocks_free": 48, "block_size": 8,
            "live_tokens": 0}
        assert st["kv_pools"]["ssm_state"] == {
            "slots_total": 3, "slots_live": 0,
            "bytes_per_slot": cfg.state_bytes_per_slot}
        assert cfg.state_bytes_per_slot == 5 * (4 * 128 * 16 + 4 * 3 * 192)
        eng._alloc.check_invariants()
    finally:
        eng.shutdown()


def test_the_check_borrows_the_engine_that_serves_the_weights(
        engine_parts, monkeypatch):
    """The benchmark's comparison runs the engine's OWN programs on the
    engine's own cache where it is handed the engine that serves the
    weights, as ``worker_serve.check`` hands it (a second recurrent
    state of the cell's size fits no chip beside the first): the
    compared sequence in the last slot beside neighbours that run, the
    reference's logits, and an engine that afterwards holds nothing of
    it and answers as before. Without an engine the same builders over
    scratch caches give the same numbers."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = engine_parts
    monkeypatch.setattr(ARCH, "program_config", lambda spec: cfg)
    tokens = np.asarray(jax.random.randint(jax.random.key(8), (29 + 12,),
                                           0, 256))
    want = np.asarray(REF.logits(params, jnp.asarray(tokens), SPEC,
                                 list(range(28, 41))))
    assert ARCH._neighbours(192) == [0, 96, 190]
    assert ARCH._neighbours(3) == [0, 1] and ARCH._neighbours(1) == []
    scratch = ARCH.serve_program_logits(params, SPEC, tokens, DEPLOYMENT,
                                        prefill=29)
    assert REF.rel_err(scratch, want) < 5e-5
    eng = LLMEngine(params=params, **ARCH.engine_kwargs(SPEC, DEPLOYMENT))
    try:
        with pytest.raises(RuntimeError, match="other weights"):
            ARCH.serve_program_logits(dict(params), SPEC, tokens,
                                      DEPLOYMENT, prefill=29, engine=eng)
        prompt = list(range(1, 41))
        before = eng.generate(prompt, max_tokens=12)
        with monkeypatch.context() as m:     # no program but the engine's
            m.setattr(nemotron_h, "make_decode_step", None)
            m.setattr(nemotron_h, "make_prefill", None)
            got = ARCH.serve_program_logits(params, SPEC, tokens, DEPLOYMENT,
                                            prefill=29, engine=eng)
        assert np.array_equal(got, scratch)
        st = eng.stats()
        assert st["kv_pools"]["ssm_state"]["slots_live"] == 0
        assert st["kv_pools"]["full"]["blocks_free"] == \
            st["kv_pools"]["full"]["blocks_total"]
        eng._alloc.check_invariants()
        assert eng.generate(prompt, max_tokens=12) == before
        with pytest.raises(RuntimeError, match="idle"):   # other slots
            ARCH.serve_program_logits(params, SPEC, tokens,
                                      dict(DEPLOYMENT, num_slots=2),
                                      prefill=29, engine=eng)
    finally:
        eng.shutdown()


def test_staggered_requests_and_a_preemption_give_each_its_own_tokens(
        engine_parts):
    """Five requests of different lengths on three slots, started at
    different times, in a pool too small for three growing answers: the
    youngest is preempted and recomputed (its prefill overwrites
    whatever its new slot held), slots are released and taken again,
    and every request gets the tokens it gets alone."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = engine_parts
    eng = LLMEngine(config=cfg, params=params, num_slots=3, max_seq=128,
                    kv_block_size=8, kv_pool_tokens=20 * 8)
    jobs = [(list(range(1 + i, 1 + i + n)), m)
            for i, (n, m) in enumerate([(30, 40), (21, 12), (37, 33),
                                        (9, 25), (30, 40)])]
    try:
        outs, seen = {}, []

        def run(i):
            outs[i] = eng.generate(jobs[i][0], max_tokens=jobs[i][1])
            seen.append(eng.stats()["kv_pools"]["ssm_state"]["slots_live"])

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(jobs))]
        for i, t in enumerate(threads):
            t.start()
            if i % 2:
                threading.Event().wait(0.3)
        for t in threads:
            t.join(timeout=600)
        st = eng.stats()
        assert st["preemptions"] >= 1
        for i, (prompt, n) in enumerate(jobs):
            assert outs[i] == _alone(cfg, params, prompt, n), i
        assert max(seen) <= 3
        assert st["kv_pools"]["full"]["blocks_free"] == 20
        assert st["kv_pools"]["ssm_state"]["slots_live"] == 0
        assert st["model_counters"]["expert_pairs_dropped"] == 0
        eng._alloc.check_invariants()
    finally:
        eng.shutdown()


def test_the_manager_holds_slot_state_with_the_blocks():
    """The recurrent state is a kind that is no block: a slot holds its
    row from ``ensure`` to ``release``, a refused ``ensure`` takes
    nothing, and a manager without such a kind reports none."""
    cfg = config()
    page = PagedConfig(num_blocks=1 + 6, block_size=8, max_seq=64)
    m = nemotron_h.make_manager(cfg, page, 3)
    assert m.ensure(0, 20) and m.ensure(2, 20)
    assert m.pools()["ssm_state"]["slots_live"] == 2
    assert not m.ensure(1, 30)                     # the pool is short
    assert m.pools()["ssm_state"]["slots_live"] == 2
    m.check_invariants()
    m.release(0)
    assert m.pools([20])["ssm_state"] == {
        "slots_total": 3, "slots_live": 1,
        "bytes_per_slot": cfg.state_bytes_per_slot}
    m.check_invariants()
    assert m.ensure(0, 24)                         # taken again: one row
    assert m.pools()["ssm_state"]["slots_live"] == 2
    from ray_tpu.models import laguna

    lcfg = laguna.LagunaConfig()
    lm = laguna.make_manager(lcfg, laguna.pages(
        lcfg, num_slots=2, max_seq=64, block_size=8, pool_tokens=128), 2)
    assert set(lm.pools()) == {"full", "window"}


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


def test_the_tiny_defaults_are_a_model_of_their_own():
    """``NemotronHConfig()`` with its own seeded weights serves through
    ``serving_model()`` (what a caller without the benchmark gets)."""
    from ray_tpu.models.serving import serving_model
    from ray_tpu.serve.llm import LLMEngine

    cfg = nemotron_h.NemotronHConfig()
    model = serving_model(cfg)
    # a model has what it has builders for: this one none of the
    # mechanisms beside the paged path, the dense decoder all of them
    # but the block step that stands in place of a decode step
    dense = serving_model(preset="debug")
    for builder in LLMEngine._MECHANISMS:
        assert not hasattr(model, builder), builder
        assert callable(getattr(dense, builder, None)) == (
            builder != "block_denoise"), builder
    assert len(LLMEngine._MECHANISMS) == 6
    eng = LLMEngine(config=cfg, num_slots=2, max_seq=64, kv_block_size=8)
    try:
        assert len(eng.generate([3, 4, 5], max_tokens=5)) == 5
        assert moe.COUNTERS + ("ssm_slots_live", "ssm_layer_calls") == \
            tuple(eng.stats()["model_counters"])
    finally:
        eng.shutdown()
