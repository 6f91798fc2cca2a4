"""The mixed full / sliding-window decoder with a routed expert MLP held
by share (``ray_tpu.models.mimo_v2``), at a small size on the CPU,
against the benchmark's plain reference
(``benchmark/reference/mimo_v2.py``) on seeded random weights."""

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
from ray_tpu.models import mimo_v2, moe  # noqa: E402
from ray_tpu.models.paged_cache import KVStateManager, PagedConfig  # noqa: E402
from ray_tpu.ops.attention import hybrid_attention_reference  # noqa: E402
from ray_tpu.ops.pallas import grouped_matmul as gm  # noqa: E402
from ray_tpu.ops.pallas import paged_hybrid_decode_attention as pha  # noqa: E402
from ray_tpu.ops.rope import apply_rope, rope_frequencies  # noqa: E402

# five layers: dense + full, then window, window, full, window, all routed
SPEC = dict(
    name="tiny-mimo", architecture="mimo_v2",
    reference="benchmark/reference/mimo_v2.py",
    vocab_size=256, hidden_size=64, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=2, swa_num_key_value_heads=4,
    head_dim=24, v_head_dim=16, partial_rotary_factor=0.334,
    rope_theta=1e7, swa_rope_theta=1e4, sliding_window=16,
    attention_value_scale=0.707, hybrid_layer_pattern=[0, 1, 1, 0, 1, 1],
    moe_layer_freq=[0, 1, 1, 1, 1, 1], intermediate_size=128,
    moe_intermediate_size=32, n_routed_experts=4, router_width=16,
    experts_first=4, num_experts_per_tok=4, layernorm_epsilon=1e-5,
    max_position_embeddings=512, tie_word_embeddings=False,
    routed_scaling_factor=None, torch_dtype="bfloat16")
ARCH = model_spec.adapter(SPEC)
REF = model_spec.reference(SPEC)
DEPLOYMENT = dict(num_slots=3, max_seq=128, kv_block_size=8,
                  kv_pool_tokens=3 * 128)


def make_params(spec, seed, dtype=jnp.bfloat16):
    from benchmark import weights

    return jax.tree.map(lambda a: a.astype(dtype), weights.make(spec, seed))


@pytest.mark.parametrize("dtype, limit", [(jnp.float32, 2e-4),
                                          (jnp.bfloat16, 6e-2)],
                         ids=["float32", "bfloat16"])
def test_prefill_then_paged_decode_match_the_reference(dtype, limit,
                                                       monkeypatch):
    """A prompt of 40 tokens (two and a half windows of 16), then 30
    decode steps through both pools: the window layers' blocks behind
    the window are given back on the way (block size 8), and every
    step's logits are the reference's full forward pass."""
    monkeypatch.setattr(
        ARCH, "program_config", lambda spec: mimo_v2.MimoV2Config(
            **dict(ARCH.program_kwargs(spec), dtype=dtype)))
    params = make_params(SPEC, 11, dtype)
    tokens = np.asarray(jax.random.randint(jax.random.key(5), (70,), 0, 256))
    got = ARCH.serve_program_logits(params, SPEC, tokens, DEPLOYMENT,
                                    prefill=40)
    want = np.asarray(REF.logits(params, jnp.asarray(tokens), SPEC,
                                 list(range(39, 70))))
    assert got.shape == want.shape == (31, 256)
    assert REF.rel_err(got[0], want[0]) < limit
    assert REF.rel_err(got[1:], want[1:]) < limit
    for i in (1, 9, 17, 30):           # single steps, past freed blocks
        assert REF.rel_err(got[i], want[i]) < 2 * limit


def test_window_blocks_are_given_back_and_reused():
    cfg = ARCH.program_config(SPEC)
    page = mimo_v2.pages(cfg, num_slots=2, max_seq=128, block_size=8,
                         pool_tokens=256)
    assert page["window"].num_blocks == 1 + 2 * 3     # three a slot
    alloc = mimo_v2.make_manager(cfg, page, 2)
    assert alloc.ensure(0, 41)                        # prompt of 40
    held = [int(b) for b in alloc.kinds["window"].tables[0] if b]
    assert len(held) == 3 and alloc.kinds["window"]._base[0] == 3
    assert np.count_nonzero(alloc.kinds["full"].tables[0]) == 6
    freed = 0
    for n in range(42, 100):
        freed += alloc.trim(0, n)
        assert alloc.ensure(0, n)
        assert np.count_nonzero(alloc.kinds["window"].tables[0]) <= 3
        alloc.check_invariants()
    assert freed == 99 // 8 - 40 // 8 and alloc.ensure(1, 41)
    alloc.release(0)
    alloc.release(1)
    assert alloc.pools()["window"]["blocks_free"] == 6
    assert alloc.pools()["full"]["blocks_free"] == 32


@pytest.mark.parametrize("kind", ["full", "window"])
def test_paged_kernel_interpret_matches_its_oracle(kind):
    """Keys wider than values, packed rows, the sink, the window and a
    table whose blocks behind the window are the null block."""
    got, want, _ = _run_kernel_case(kind, [1, 29, 48])
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# ------------------------------------- the kernel walks live blocks only
BS, MBS, WINDOW = 8, 6, 16          # MimoV2Config()'s window


def _blocks_read(kind, n):
    """Logical blocks of a slot of length n that a call must read."""
    first = max(n - WINDOW, 0) // BS if kind == "window" else 0
    last = min(-(-n // BS), MBS)
    if kind == "window":
        last = min(last, first + pha.blocks_in_window(WINDOW, BS))
    return list(range(first, last))


def _run_kernel_case(kind, lengths):
    """The kernel (interpreted) on pools whose every block that the call
    has no business reading is NaN, the null block among them, against
    the oracle on the clean pools. A table names only the blocks
    :func:`_blocks_read` lists: the rest are the null block, as after
    the allocator gave back what the window passed.
    -> (got, want, live rows)."""
    cfg = mimo_v2.MimoV2Config(n_heads=8)
    assert cfg.window == WINDOW
    KV, B = cfg.kv_heads(kind), len(lengths)
    ks = jax.random.split(jax.random.key(2), 5)
    q = jax.random.normal(ks[0], (B, 8, cfg.head_dim), jnp.float32)
    NB = 1 + B * MBS
    kp = jax.random.normal(ks[1], (2, NB, BS, KV * cfg.head_dim))
    vp = jax.random.normal(ks[2], (2, NB, BS, KV * cfg.v_head_dim))
    sink = jax.random.normal(ks[3], (8,)) if kind == "window" else None
    tables = np.zeros((B, MBS), np.int32)
    for s, n in enumerate(lengths):
        for j in _blocks_read(kind, n):
            tables[s, j] = 1 + s * MBS + j
    dead = np.ones(NB, bool)
    dead[tables[tables > 0]] = False
    poison = lambda pool: pool.at[:, dead].set(jnp.nan)  # noqa: E731
    kw = dict(scale=cfg.head_dim ** -0.5, dv=cfg.v_head_dim, sink=sink,
              k_slices=mimo_v2.key_slices(cfg, kind),
              window=WINDOW if kind == "window" else None)
    qp = mimo_v2.pack_queries(q, cfg, kind)
    lens = jnp.asarray(lengths, jnp.int32)
    got = pha.paged_hybrid_decode_attention(
        qp, poison(kp), poison(vp), 1, jnp.asarray(tables), lens,
        interpret=True, **kw)
    want = pha.paged_hybrid_attention_reference(
        qp, kp, vp, 1, jnp.asarray(tables), lens, **kw)
    return np.asarray(got), np.asarray(want), np.asarray(lengths) > 0


KERNEL_LENGTHS = {
    "empty-slot-between-running": [29, 0, 41],
    "every-table-full": [MBS * BS] * 3,
    "every-slot-empty": [0, 0, 0],
    "on-a-block-boundary-and-one-past": [8, 9, 16, 17, 40, 41],
    "shorter-than-the-window": [1, 5, 15, 16],
    # keys (n - 16, n]: 24 -> blocks 1-2, 25 -> 1-3, 32 -> 2-3, 47 -> 3-5
    "window-touches-two-and-three-blocks": [24, 25, 32, 33, 47],
}


@pytest.mark.parametrize("kind", ["full", "window"])
@pytest.mark.parametrize("case", KERNEL_LENGTHS)
def test_paged_kernel_reads_the_live_blocks_and_no_other(case, kind):
    got, want, live = _run_kernel_case(kind, KERNEL_LENGTHS[case])
    assert np.isfinite(got).all()                # no dead block was read
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    assert not got[~live].any()                  # zeros, not garbage


@pytest.mark.parametrize("kind", ["full", "window"])
@pytest.mark.parametrize("lengths, bs, mbs", [
    ([29, 0, 41], 8, 6), ([0, 0, 0], 8, 6), ([48, 48], 8, 6),
    ([8, 9, 16, 17], 8, 6), ([1, 15, 16, 24, 25, 47], 8, 6),
    ([700, 64, 129], 64, 4), ([5, 100], 64, 2), ([300, 257, 600], 64, 10)])
def test_work_list_is_the_steps_a_call_takes_in_slot_order(lengths, bs,
                                                           mbs, kind):
    """Against a plain enumeration: every G-th of the blocks a call
    reads of each slot, their order, the bound, and the one entry more
    that the pipeline's lookahead lands on."""
    window = WINDOW if kind == "window" else None
    n_work, slot, block = jax.jit(
        pha.hybrid_work_list, static_argnums=(1, 2, 3))(
            jnp.asarray(lengths, jnp.int32), bs, mbs, window)
    most = min(pha.blocks_in_window(WINDOW, bs), mbs) if window else mbs
    G = pha.blocks_per_step(window, bs, mbs)
    assert G == (most if window else min(4, mbs))
    want = []
    for s, n in enumerate(lengths):
        first = max(n - WINDOW, 0) // bs if window else 0
        want += [(s, j) for j in range(
            first, min(-(-n // bs), mbs, first + most), G)]
    assert int(n_work) == len(want)
    assert slot.shape == block.shape == (len(lengths) * -(-most // G) + 1,)
    got = list(zip(np.asarray(slot).tolist(), np.asarray(block).tolist()))
    assert got[:len(want)] == want
    assert set(got[len(want):]) <= {want[-1] if want
                                    else (len(lengths) - 1, 0)}


def test_a_stale_slot_between_two_running_ones_is_not_attended(
        kernel_on_cpu):
    """Nothing resets ``cache["length"]`` when a slot is released: slot 1
    keeps its 37 between two running slots, its blocks go to them as
    they grow, and their greedy tokens are the reference's."""
    cfg = mimo_v2.MimoV2Config(**dict(ARCH.program_kwargs(SPEC),
                                      dtype=jnp.float32))
    params = make_params(SPEC, 11, jnp.float32)
    page = mimo_v2.pages(cfg, num_slots=3, max_seq=128, block_size=8,
                         pool_tokens=12 * 8)
    alloc = mimo_v2.make_manager(cfg, page, 3)
    cache = mimo_v2.init_cache(cfg, page, 3)
    prefill = mimo_v2.make_prefill(params, cfg, page)
    decode = mimo_v2.make_decode_step(params, cfg, page)
    seqs = {s: list(np.asarray(jax.random.randint(
        jax.random.key(s), (n,), 0, 256))) for s, n in ((0, 21), (1, 37),
                                                        (2, 6))}
    prompt_len = {s: len(t) for s, t in seqs.items()}
    for s in (1, 0, 2):
        assert alloc.ensure(s, prompt_len[s] + 1)
        padded = np.zeros((1, -(-prompt_len[s] // 8) * 8), np.int32)
        padded[0, :prompt_len[s]] = seqs[s]
        cache, lg = prefill(cache, alloc.table_rows(s), jnp.asarray(padded),
                            prompt_len[s], s)
        seqs[s].append(int(np.asarray(lg).argmax()))
    alloc.release(1)
    active = np.array([True, False, True])
    step_logits = {0: [], 2: []}
    for _ in range(14):
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
    assert np.asarray(cache["length"]).tolist() == [21 + 14, 37, 6 + 14]
    # 5 + 3 blocks of 12, where 9 were taken before slot 1 left: its
    # blocks were taken again by its neighbours
    assert alloc.pools()["full"]["blocks_free"] == 12 - 5 - 3
    for s in (0, 2):
        want = np.asarray(REF.logits(
            params, jnp.asarray(seqs[s][:-1]), SPEC,
            list(range(prompt_len[s], len(seqs[s]) - 1))))
        got = np.stack(step_logits[s])
        assert REF.rel_err(got, want) < 2e-4
        assert got.argmax(-1).tolist() == want.argmax(-1).tolist()


def _plain_attention(q, k, v, scale, window, sink):
    """One sequence, the formula: scores, mask, the sink as one more
    column of the softmax whose probability is dropped."""
    H, KV = q.shape[1], k.shape[1]
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    S = q.shape[0]
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    ok = j <= i
    if window:
        ok &= i - j < window
    s = jnp.where(ok[None], s, -jnp.inf)
    if sink is not None:
        s = jnp.concatenate(
            [s, jnp.broadcast_to(sink[:, None, None], (H, S, 1))], -1)
    p = jax.nn.softmax(s, -1)[..., :S]
    return jnp.einsum("hqk,khd->qhd", p, v)


@pytest.mark.parametrize("window, with_sink", [
    (None, False), (None, True), (16, True), (16, False), (64, True)],
    ids=["full", "full-sink", "window-sink", "window", "window-over-seq"])
def test_prefill_attention_against_the_plain_formula(window, with_sink):
    ks = jax.random.split(jax.random.key(3), 4)
    S, H, KV, Dk, Dv = 50, 4, 2, 24, 16
    q = jax.random.normal(ks[0], (1, S, H, Dk))
    k = jax.random.normal(ks[1], (1, S, KV, Dk))
    v = jax.random.normal(ks[2], (1, S, KV, Dv))
    sink = jax.random.normal(ks[3], (H,)) if with_sink else None
    got = hybrid_attention_reference(q, k, v, scale=0.2, window=window,
                                     sink=sink)
    assert got.shape == (1, S, H, Dv)               # Dv != Dk
    want = _plain_attention(q[0], k[0], v[0], 0.2, window, sink)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_partial_rotary_against_the_plain_formula():
    """The first 8 of 24 dimensions rotate in pairs (i, i + 4) by
    p * theta ** (-2 i / 8); the other 16 pass through. A table as wide
    as the head is the full rotation the dense models use."""
    x = jax.random.normal(jax.random.key(4), (2, 9, 3, 24))
    pos = jnp.arange(9) + 5
    cos, sin = rope_frequencies(8, 32, 1e4)
    got = apply_rope(x, cos, sin, jnp.broadcast_to(pos, (2, 9)))
    inv = 1e4 ** (-jnp.arange(0, 8, 2) / 8)
    ang = pos[:, None] * inv[None]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :4], x[..., 4:8]
    want = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, x[..., 8:]], -1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    cos, sin = rope_frequencies(24, 32, 1e4)
    full = apply_rope(x, cos, sin)
    assert not np.allclose(np.asarray(full[..., 8:]), np.asarray(x[..., 8:]))


def _routed_layer(seed, E=16, h=64, m=32, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 5)
    return {"router": jax.random.normal(ks[0], (h, E), dtype) * h ** -0.5,
            "router_bias": jax.random.normal(ks[1], (E,), dtype) * 0.1,
            "we_gate": jax.random.normal(ks[2], (E, h, m), dtype) * h ** -0.5,
            "we_up": jax.random.normal(ks[3], (E, h, m), dtype) * h ** -0.5,
            "we_down": jax.random.normal(ks[4], (E, m, h), dtype) * m ** -0.5}


def _share(layer, first, count):
    return dict(layer, **{k: layer[k][first:first + count]
                          for k in ("we_gate", "we_up", "we_down")})


@pytest.mark.parametrize("shares", [4, 16, 1])
def test_the_shares_add_up_to_the_uncut_routed_layer(shares):
    """THE test that ties the share to the model: the parts that all the
    chips of the deployment compute (4 chips of 4 experts; 16 of 1; one
    that holds all) add up to the uncut reference's whole routed layer."""
    layer = _routed_layer(7)
    x = jax.random.normal(jax.random.key(8), (37, 64), jnp.float32)
    want, _ = REF.routed_mlp(x, layer, SPEC, held=(0, 16))
    count = 16 // shares
    total = 0.0
    for first in range(0, 16, count):
        y, counters = moe.experts_by_share(
            x, _share(layer, first, count), experts_held=(first, count),
            top_k=4)
        ref_part, _ = REF.routed_mlp(x, _share(layer, first, count), SPEC,
                                     held=(first, count))
        assert REF.rel_err(y, ref_part) < 1e-4 or float(
            jnp.abs(ref_part).max()) == 0.0
        assert counters[4] == 0                      # nothing dropped
        total = total + y
    assert REF.rel_err(total, want) < 1e-4


def test_no_token_is_dropped_when_routing_piles_onto_one_held_expert():
    """Every token's first choice is expert 5 (a huge router column):
    129 rows for one expert, far over any capacity a balanced layer
    would give it, and all of them are computed."""
    layer = _routed_layer(9)
    layer["router"] = layer["router"].at[:, 5].set(0.0)
    layer["router_bias"] = layer["router_bias"].at[5].set(10.0)
    x = jax.random.normal(jax.random.key(10), (129, 64), jnp.float32)
    y, c = moe.experts_by_share(x, _share(layer, 4, 4),
                                experts_held=(4, 4), top_k=4)
    want, _ = REF.routed_mlp(x, _share(layer, 4, 4), SPEC, held=(4, 4))
    calls, pairs, hit, ratio, dropped, _ = np.asarray(c)
    assert dropped == 0 and pairs >= 129 and calls == 1
    assert ratio >= 4 * 129 / pairs - 1e-3           # largest over mean
    assert REF.rel_err(y, want) < 1e-4
    # rows that are no token (an idle slot) are routed nowhere
    valid = jnp.arange(129) < 3
    _, c = moe.experts_by_share(x, _share(layer, 4, 4), experts_held=(4, 4),
                                top_k=4, valid=valid)
    assert 3 <= float(c[1]) <= 12


def test_grouped_matmul_kernel_interpret_matches_its_oracle():
    ks = jax.random.split(jax.random.key(12), 2)
    lhs = jax.random.normal(ks[0], (96, 64), jnp.bfloat16)
    rhs = jax.random.normal(ks[1], (5, 64, 256), jnp.bfloat16)
    groups = jnp.array([0, 0, 2, 4, 4, 4], jnp.int32)
    got = gm.grouped_matmul(lhs, rhs, groups, 4, tm=16,
                            out_dtype=jnp.float32, interpret=True)
    want = gm.grouped_matmul_reference(lhs, rhs, groups, 4, tm=16,
                                       out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got[:64]), np.asarray(want[:64]),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- the engine
@pytest.fixture(scope="module")
def engine_parts():
    cfg = ARCH.program_config(SPEC)
    return cfg, make_params(SPEC, 21)


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
        assert st["window_blocks_freed"] >= 4 and st["preemptions"] == 0
        assert "window_free" in st["phases"]
        c = st["model_counters"]
        assert c["expert_pairs_dropped"] == 0 and c["expert_pairs"] > 0
        assert c["expert_layer_calls"] == 4 * 39        # 4 routed layers
        assert st["model_counters_prefill"]["expert_layer_calls"] == 4
        assert st["kv_pools"]["window"]["blocks_free"] == 9
        assert st["kv_pools"]["full"]["blocks_free"] == 48
        eng._alloc.check_invariants()
    finally:
        eng.shutdown()


def test_the_counters_of_a_run_are_those_of_the_host_fed_steps(engine_parts):
    """The engine reads a step's counters after the next program has
    taken the cache (the step after it, or a prefill): each step's must
    still be its own, and none read twice or lost."""
    from ray_tpu.serve.llm import LLMEngine
    from test_llm import _drain_polls, _wait_for_tokens, host_fed

    cfg, params = engine_parts
    geometry = dict(num_slots=3, max_seq=128, block=8)
    requests = [(list(range(1, 41)), 24, 0.0, None),
                (list(range(3, 20)), 30, 0.0, None),
                (list(range(7, 30)), 12, 0.0, None)]
    eng = LLMEngine(config=cfg, params=params, num_slots=3, max_seq=128,
                    kv_block_size=8, kv_pool_tokens=3 * 128)
    try:
        # alone, the steps are the host-fed ones: all five counters
        want, counters = host_fed(cfg, params, requests[:1], **geometry)
        assert eng.generate(*requests[0][:2]) == want[0]
        alone = eng.stats()
        assert list(alone["model_counters"].values()) == pytest.approx(
            list(counters))
        assert alone["turns"] == {"overlapped": 22, "drained": 1,
                                  "surplus_dropped": 0}
        # together, two of them admitted while a step is in flight: a
        # step's rows are other requests' too, so only what adds up
        # token by token can be compared (the pairs), and the calls
        want, counters = host_fed(cfg, params, requests, **geometry)
        rids = [eng.submit(*requests[0][:2])]
        _wait_for_tokens(eng, rids[0], 3)
        rids += [eng.submit(*r[:2]) for r in requests[1:]]
        assert _drain_polls(eng, rids) == want
    finally:
        eng.shutdown()
    st = eng.stats()
    names = list(st["model_counters"])
    got = {n: st["model_counters"][n] - alone["model_counters"][n]
           for n in names}
    assert got["expert_pairs"] == counters[names.index("expert_pairs")]
    assert got["expert_layer_calls"] == 4 * (st["steps"] - alone["steps"])
    assert got["expert_pairs_dropped"] == 0
    assert st["model_counters_prefill"]["expert_layer_calls"] == 4 * 4
    assert st["turns"]["surplus_dropped"] == 0


def test_preemption_returns_both_kinds_of_blocks(engine_parts):
    """A full pool too small for three growing answers: the youngest is
    preempted, recomputed and finishes; afterwards both pools are whole."""
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
        assert st["kv_pools"]["window"]["blocks_free"] == 9
        assert st["kv_pools"]["full"]["blocks_free"] == 20
        eng._alloc.check_invariants()
    finally:
        eng.shutdown()


@pytest.mark.parametrize("kwargs, names", [
    (dict(kv_cache="slot"), "kv_cache='slot'"),
    (dict(speculation="ngram", kv_cache="slot"), "kv_cache='slot'"),
    (dict(speculation="ngram"), "speculation"),
    (dict(prefix_cache="radix"), "prefix cache"),
    (dict(prefix_cache_bytes=1 << 20), "prefix cache"),
    (dict(prefill_chunk=16), "chunked prefill")],
    ids=["slot", "speculation-slot", "speculation", "radix", "budget",
         "chunked"])
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


def test_kv_state_manager_is_all_or_nothing():
    """A slot that one kind cannot cover takes blocks of neither."""
    small = PagedConfig(num_blocks=1 + 2, block_size=8, max_seq=64)
    big = PagedConfig(num_blocks=1 + 16, block_size=8, max_seq=64)
    alloc = KVStateManager({"full": (small, None), "window": (big, 16)}, 2)
    assert not alloc.ensure(0, 30)          # four full blocks, two there
    assert alloc.pools()["window"]["blocks_free"] == 16
    assert alloc.lacking(30) == 2 and not alloc.fits(30) and alloc.fits(16)
    assert alloc.ensure(0, 16)
    alloc.check_invariants()
