"""The decoder that generates by diffusion over blocks
(``ray_tpu.models.sdar``: the Qwen3-MoE layer under a block-causal mask)
and the engine's block turn, at a small size on the CPU (hidden 64, 4
layers, 8 experts top-2, blocks of 4) against the benchmark's plain
reference (``benchmark/reference/sdar.py``: one forward under the mask,
and the generation loop with no cache) on seeded random weights."""

import dataclasses
import os
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import model_spec  # noqa: E402
from ray_tpu.models import sdar  # noqa: E402
from ray_tpu.models.paged_cache import BlockAllocator  # noqa: E402
from ray_tpu.ops import attention  # noqa: E402

MASK = 250
SPEC = dict(
    name="tiny-sdar", architecture="sdar",
    reference="benchmark/reference/sdar.py",
    vocab_size=256, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    max_position_embeddings=512, rms_norm_eps=1e-6, rope_theta=1000000,
    rope_scaling=None, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, norm_topk_prob=True, decoder_sparse_step=1,
    mlp_only_layers=[], tie_word_embeddings=False, attention_bias=False,
    use_sliding_window=False, block_length=4, mask_token_id=MASK,
    denoising_steps=2, remasking="low_confidence_static",
    torch_dtype="bfloat16")
ARCH = model_spec.adapter(SPEC)
REF = model_spec.reference(SPEC)
DEPLOYMENT = dict(num_slots=3, max_seq=128, kv_block_size=8,
                  kv_pool_tokens=3 * 128)
ENGINE = dict(max_seq=128, kv_block_size=8)


def make_params(seed, dtype=jnp.float32):
    from benchmark import weights

    return jax.tree.map(lambda a: a.astype(dtype), weights.make(SPEC, seed))


PROGRAM_CONFIG = ARCH.program_config


def config(dtype=jnp.float32, **changes):
    return dataclasses.replace(PROGRAM_CONFIG(SPEC), dtype=dtype, **changes)


@pytest.fixture(scope="module")
def parts():
    """(config, weights), float32: greedy answers are then the
    reference's token for token."""
    return config(), make_params(21)


def engine(parts, **kwargs):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = parts
    return LLMEngine(config=cfg, params=params, **{
        **ENGINE, "num_slots": 3, "kv_pool_tokens": 3 * 128, **kwargs})


# ------------------------------------------- the programs and the reference
@pytest.mark.parametrize("dtype, limit", [(jnp.float32, 2e-4),
                                          (jnp.bfloat16, 0.15)],
                         ids=["float32", "bfloat16"])
def test_prefill_then_block_steps_match_the_references_one_forward(
        dtype, limit, monkeypatch):
    """A prefill of 24 tokens (six blocks, across the pool's blocks of
    8) and five committed blocks through the paged cache on given ids,
    against ONE forward of the reference under the block-causal mask:
    row 23 from the prefill, rows 24..43 from the block step. In float32
    the limit is one that bfloat16 arithmetic fails (it reads 0.01 and
    more)."""
    monkeypatch.setattr(ARCH, "program_config",
                        lambda spec, dep=None: config(dtype))
    monkeypatch.setattr(model_spec, "limits", lambda spec: {
        "serve_decode_logits_rel_err": {"limit": limit}})
    params = make_params(7, dtype)
    tokens = jax.random.randint(jax.random.key(3), (44,), 0, 256)
    tokens = tokens.at[9].set(MASK).at[30].set(MASK)    # an id like any
    got = ARCH.serve_program_logits(params, SPEC, tokens, DEPLOYMENT,
                                    prefill=24)
    want = np.asarray(REF.logits(params, tokens, SPEC,
                                 rows=list(range(23, 44))))
    assert got.shape == want.shape == (21, 256)
    assert REF.rel_err(got[0], want[0]) < limit
    assert REF.rel_err(got[1:], want[1:]) < limit
    if dtype == jnp.float32:
        rounded = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), want)
        assert REF.rel_err(rounded, want) > limit       # the limit is tight
        assert (got.argmax(-1) == want.argmax(-1)).all()


def test_the_check_borrows_the_engines_own_programs_and_leaves_it_sound(
        parts):
    """Handed an idle engine, the check runs ITS prefill, block step,
    deciding program and seating, on its cache and allocator, beside
    two neighbours mid-block, with a denoising step before every commit
    (five blocks: ten steps); the rows are the reference's, the pool is
    given back whole, and the engine then answers as the reference's
    loop does."""
    cfg, params = parts
    eng = engine(parts)
    calls = dict.fromkeys(("_prefill", "_block_step", "_block_decide",
                           "_seat_blocks"), 0)

    def counted(name):
        fn = getattr(eng, name)

        def call(*args):
            calls[name] += 1
            if name == "_block_step":
                assert np.asarray(args[-1]).tolist() == [True] * 3
            return fn(*args)
        setattr(eng, name, call)

    programs = {name: getattr(eng, name) for name in calls}
    for name in calls:
        counted(name)
    try:
        tokens = jax.random.randint(jax.random.key(11), (44,), 0, 256)
        got = ARCH.serve_program_logits(params, SPEC, tokens, DEPLOYMENT,
                                        prefill=24, engine=eng)
        want = np.asarray(REF.logits(params, tokens, SPEC,
                                     rows=list(range(23, 44)), quiet=True))
        assert REF.rel_err(got[0], want[0]) < 2e-4
        assert REF.rel_err(got[1:], want[1:]) < 2e-4
        assert calls == {"_prefill": 3, "_block_step": 10,
                         "_block_decide": 10, "_seat_blocks": 10}
        st = eng.stats()
        assert st["kv_blocks_free"] == st["kv_blocks_total"]
        eng._alloc.check_invariants()
        for name, fn in programs.items():
            setattr(eng, name, fn)
        prompt = np.random.default_rng(2).integers(0, 256, 13).tolist()
        assert eng.generate(prompt, max_tokens=9) == REF.generate(
            params, prompt, 9, SPEC)
        with pytest.raises(RuntimeError, match="other weights"):
            ARCH.serve_program_logits(make_params(3), SPEC, tokens,
                                      DEPLOYMENT, prefill=24, engine=eng)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("fault, message", [
    ("decide", "the deciding program left slot"),
    ("mask", "denoising steps' logits")])
def test_the_check_catches_what_two_commit_steps_cannot(parts, fault,
                                                        message):
    """Faults that leave every commit step's logits sound: a deciding
    program that decides the LEAST confident positions, and a step that
    feeds another id than the mask at undecided positions."""
    cfg, params = parts
    tokens = jax.random.randint(jax.random.key(12), (32,), 0, 256)
    if fault == "mask":
        eng = engine((dataclasses.replace(cfg, mask_token_id=MASK - 1),
                      params))
    else:
        eng = engine(parts)
        decide = eng._block_decide

        def least(logits, *rest):
            return decide(-logits, *rest)
        eng._block_decide = least
    try:
        with pytest.raises(RuntimeError, match=message):
            ARCH.serve_program_logits(params, SPEC, tokens, DEPLOYMENT,
                                      prefill=24, engine=eng)
        assert eng.stats()["kv_blocks_free"] == eng.stats()["kv_blocks_total"]
    finally:
        eng.shutdown()


def test_a_block_sees_its_own_later_positions_and_no_later_block():
    """The mask itself, on the reference: changing position 6 moves the
    logits at 4..7 (its block) and after, and nothing before 4."""
    params = make_params(5)
    a = np.arange(1, 13)
    b = a.copy()
    b[6] = 99
    la = np.asarray(REF.logits(params, a, SPEC, quiet=True))
    lb = np.asarray(REF.logits(params, b, SPEC, quiet=True))
    moved = np.abs(la - lb).max(axis=-1) > 1e-6
    assert not moved[:4].any() and moved[4:].all()


def test_the_block_step_with_the_kernels_is_the_reference_too(
        kernel_on_cpu, monkeypatch):
    """The same comparison with every kernel interpreted: the flash
    forward's block-causal diagonal in the prefill, the paged decode
    kernel at (block_length x group) query rows a KV head in the step,
    the grouped products in both."""
    monkeypatch.setattr(ARCH, "program_config",
                        lambda spec, dep=None: config())
    params = make_params(7)
    tokens = jax.random.randint(jax.random.key(4), (40,), 0, 256)
    got = ARCH.serve_program_logits(params, SPEC, tokens, DEPLOYMENT,
                                    prefill=24)
    want = np.asarray(REF.logits(params, tokens, SPEC,
                                 rows=list(range(23, 40)), quiet=True))
    assert REF.rel_err(got[0], want[0]) < 2e-4
    assert REF.rel_err(got[1:], want[1:]) < 2e-4


@pytest.mark.parametrize("span", [2, 4, 16])
def test_prompt_attention_by_blocks_kernel_and_reference(span,
                                                         kernel_on_cpu):
    """``prompt_attention(span=)``: the flash forward kernel's diagonal
    tiles under ``k <= q | (span - 1)`` against a plain masked softmax,
    at a length that is no whole tile."""
    S, H, KV, D = 200, 4, 2, 16
    q, k, v = (jax.random.normal(jax.random.key(i), (1, S, h, D))
               for i, h in ((0, H), (1, KV), (2, KV)))
    got = attention.prompt_attention(q, k, v, scale=D ** -0.5, span=span)
    kr, vr = (jnp.repeat(a, H // KV, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) * D ** -0.5
    t = jnp.arange(S)
    seen = t[None, :] // span <= t[:, None] // span
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p, vr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match="no window"):
        attention.prompt_attention(q, k, v, scale=1.0, span=span, window=8)


@pytest.mark.parametrize("span", [4, 128])
def test_the_flash_forward_by_blocks_across_tiles(span):
    """The kernel itself (interpreted) over 3 x 3 tiles of 128 with a
    padded tail: the pairs that hold work and the tiles the diagonal
    crosses are the causal call's, the mask inside them is by blocks."""
    from ray_tpu.ops.pallas.flash_attention import flash_attention_fwd_pallas

    S, H, KV, D = 300, 2, 1, 32
    q, k, v = (jax.random.normal(jax.random.key(i), (1, h, S, D))
               for i, h in ((0, H), (1, KV), (2, KV)))
    got, _ = flash_attention_fwd_pallas(
        q, k, v, causal=True, scale=D ** -0.5, block_q=128, block_kv=128,
        span=span, interpret=True)
    s_ = jnp.einsum("bhqd,bkd->bhqk", q, k[:, 0]) * D ** -0.5
    t = jnp.arange(S)
    seen = t[None, :] // span <= t[:, None] // span
    p = jax.nn.softmax(jnp.where(seen, s_, -jnp.inf), axis=-1)
    want = jnp.einsum("bhqk,bkd->bhqd", p, v[:, 0])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    for bad in (3, 256):
        with pytest.raises(ValueError, match="span"):
            flash_attention_fwd_pallas(q, k, v, causal=True, scale=1.0,
                                       span=bad, interpret=True)
    with pytest.raises(ValueError, match="span"):
        flash_attention_fwd_pallas(q, k, v, causal=False, scale=1.0,
                                   span=4, interpret=True)


def test_an_idle_slots_rows_and_length_stay_as_they_were():
    """A block step leaves a slot that does not run alone: its length,
    and the rows of its blocks, bit for bit."""
    cfg, params = config(), make_params(9)
    page = sdar.make_page(cfg, max_seq=64, block_size=8, pool_tokens=128)
    alloc = BlockAllocator(page, 2)
    cache = sdar.init_cache(cfg, page, 2)
    prefill = sdar.make_prefill(params, cfg, page)
    step = sdar.make_block_step(params, cfg, page)
    toks = np.arange(1, 17, dtype=np.int32)[None]
    for slot in (0, 1):
        assert alloc.ensure(slot, 12)
        cache, _ = prefill(cache, alloc.table_rows(slot), jnp.asarray(toks),
                           8, slot)
    before = jax.tree.map(np.asarray, cache)
    ids = jnp.asarray(np.full((2, 4), 7, np.int32))
    decided = jnp.asarray(np.array([[1, 1, 1, 1], [1, 1, 1, 1]], bool))
    cache, logits = step(cache, alloc.device_tables(), ids, decided,
                         jnp.asarray([True, False]))
    assert np.asarray(cache["length"]).tolist() == [12, 8]
    mine = alloc.tables[1, :2]
    np.testing.assert_array_equal(np.asarray(cache["k"])[:, mine],
                                  before["k"][:, mine])
    # slot 0 committed rows 8..11 into its second block
    block = alloc.tables[0, 1]
    assert np.abs(np.asarray(cache["k"])[:, block, :4]).sum() > 0
    assert logits.shape == (2, 4, 256)
    # a slot that is not all decided runs and does not commit
    cache, _ = step(cache, alloc.device_tables(), ids,
                    decided.at[0, 2].set(False), jnp.asarray([True, False]))
    assert np.asarray(cache["length"]).tolist() == [12, 8]


def test_the_block_programs_operations_lie_under_a_part():
    """The block step, the deciding program and the prefill, as the
    compiler is given them (``tests/test_program_parts.py``'s reading):
    every heavy operation under a part, the head norms under
    ``qk_norm``, all of the deciding under ``block_decide``, the
    vocabulary's product under ``head``, the pool's writes under
    ``kv_store``, neither program under the decode step's name."""
    import importlib.util

    found = importlib.util.spec_from_file_location(
        "program_parts", os.path.join(ROOT, "tests",
                                      "test_program_parts.py"))
    parts_of = importlib.util.module_from_spec(found)
    found.loader.exec_module(parts_of)
    cfg, params = config(), make_params(9)
    model = cfg.serving_model()
    p = model.paged(params, num_slots=3, max_seq=64, block_size=8,
                    pool_tokens=192)
    step, decide = model.block_denoise(params, p)
    ids = jnp.zeros((3, 4), jnp.int32)
    decided = jnp.zeros((3, 4), bool)
    texts = {
        "step": step.jitted.lower(params, p.cache, p.alloc.device_tables(),
                                  ids, decided, jnp.ones((3,), bool)),
        "decide": decide.lower(jnp.zeros((3, 4, 256)), ids, decided,
                               jnp.ones((3,), jnp.int32)),
        "prefill": p.prefill.jitted.lower(
            params, p.cache, jnp.asarray(p.alloc.table_rows(0)),
            jnp.zeros((1, 32), jnp.int32), jnp.int32(28), jnp.int32(0),
            pad_len=32)}
    seen = {}
    for name, lowered in texts.items():
        text = parts_of.hlo_text(lowered)
        n, bare = parts_of._named_share(text)
        assert len(bare) <= 0.05 * n, (name, bare)
        seen[name] = {parts_of.part_of(path)
                      for _, _, path in parts_of.operations(text)}
    assert seen["decide"] <= {"block_decide", None}
    assert "block_decide" not in seen["step"] | seen["prefill"]
    for name in ("step", "prefill"):
        assert {"embed", "attn_proj", "qk_norm", "kv_store", "mlp",
                "router", "expert_dispatch", "expert_combine",
                "expert_layer", "head"} <= seen[name], name
    assert "grouped_expert_matmul" in seen["step"]
    assert "grouped_expert_matmul_prefill" in seen["prefill"]
    names = {name: re.search(r"HloModule (\w+)", parts_of.hlo_text(low)
                             ).group(1) for name, low in texts.items()}
    assert names == {"step": "jit_block_step", "decide": "jit_block_decide",
                     "prefill": "jit_prefill"}


# ----------------------------------------------------------- the decide rule
def _decide(logits, ids, decided, quota, cfg=None):
    fn = sdar.make_decide(cfg or config())
    out = fn(jnp.asarray(logits, jnp.float32)[None],
             jnp.asarray(ids, jnp.int32)[None], jnp.asarray(decided)[None],
             jnp.asarray([quota], jnp.int32))
    return [np.asarray(a)[0] for a in out]


@pytest.mark.parametrize("quota", [1, 2, 3, 4])
def test_the_decide_rule_is_the_references_on_ties_too(quota):
    """Positions 0 and 2 are given THE SAME logits (equal confidences:
    the lower position goes first), position 1 is the most confident,
    position 3 is decided already."""
    rng = np.random.default_rng(5)
    z = rng.normal(size=(4, 256)).astype(np.float32)
    z[2] = z[0]
    z[1, 17] += 9.0
    decided = np.array([False, False, False, True])
    ids = np.array([5, 6, 7, 8])
    picked, now = REF.decide(z, decided, quota)
    order = [1, 0, 2][:quota]
    assert sorted(np.nonzero(now)[0]) == sorted(order)
    new_ids, new_decided, out = _decide(z, ids, decided, quota)
    assert new_decided.tolist() == (decided | now).tolist()
    assert new_ids.tolist() == np.where(now, picked, ids).tolist()
    assert out.tolist() == [0, 0, 0, 0]                 # nothing committed


def test_a_committed_block_hands_over_its_ids_and_starts_anew():
    z = np.zeros((4, 256), np.float32)
    ids, decided, out = _decide(z, [9, 8, 7, MASK], [True] * 4, 0)
    assert out.tolist() == [9, 8, 7, MASK]
    assert not decided.any()
    # a slot that did not run (quota 0, not all decided) keeps its block
    ids, decided, out = _decide(z, [9, 8, 7, 6], [True, False, True, False],
                                0)
    assert (ids.tolist(), decided.tolist(), out.tolist()) == (
        [9, 8, 7, 6], [True, False, True, False], [0, 0, 0, 0])


def test_a_draw_at_a_temperature_is_keyed_by_request_and_position():
    """The same key, request number and position draw the same token in
    whichever slot; another request number draws others; a row at
    temperature 0 beside them takes the argmax."""
    cfg = config()
    fn = sdar.make_decide(cfg)
    z = jax.random.normal(jax.random.key(1), (1, 4, 256)) * 0.3
    logits = jnp.concatenate([z, z, z], axis=0)
    ids = jnp.zeros((3, 4), jnp.int32)
    decided = jnp.zeros((3, 4), bool)
    quota = jnp.asarray([4, 4, 4], jnp.int32)
    draw = (jnp.asarray([1.0, 1.0, 0.0]), jax.random.key(11),
            jnp.asarray([5, 5, 5], jnp.int32),
            jnp.asarray([8, 8, 8], jnp.int32))
    got, done, _ = fn(logits, ids, decided, quota, draw)
    got = np.asarray(got)
    assert np.asarray(done).all()
    assert got[0].tolist() == got[1].tolist()
    assert got[2].tolist() == np.asarray(z[0]).argmax(-1).tolist()
    assert got[0].tolist() != got[2].tolist()
    other = (draw[0], draw[1], jnp.asarray([6, 5, 5], jnp.int32), draw[3])
    again = np.asarray(fn(logits, ids, decided, quota, other)[0])
    assert again[1].tolist() == got[1].tolist()
    assert again[0].tolist() != got[0].tolist()


# ------------------------------------------------------------- the engine
CASES = [(8, 8), (9, 5), (10, 3), (11, 4), (12, 24), (3, 24), (1, 6),
         (16, 3), (16, 4), (16, 5)]


def test_generate_is_the_references_loop_token_for_token(parts):
    """Greedy through the engine (one request at a time) = the
    reference's loop with no cache, for prompts of every length mod 4
    (shorter than a block among them), a prompt that holds the mask id,
    and ``max_tokens`` 3, 4, 5 and 24: the last block's surplus is
    dropped."""
    cfg, params = parts
    eng = engine(parts)
    try:
        rng = np.random.default_rng(0)
        for plen, n in CASES:
            prompt = rng.integers(0, 256, plen).tolist()
            if plen == 12:
                prompt[5] = prompt[10] = MASK
            got = eng.generate(prompt, max_tokens=n)
            assert len(got) == n
            assert got == REF.generate(params, prompt, n, SPEC), (plen, n)
        st = eng.stats()
        blocks = sum(-(-(p % 4 + n) // 4) for p, n in CASES)
        assert st["blocks_committed"] == st["commit_steps"] == blocks
        assert st["tokens_generated"] == sum(n for _, n in CASES)
        # every block decides what its prompt tail left undecided
        assert st["positions_decided"] == 4 * blocks - sum(
            p % 4 for p, _ in CASES)
        assert st["steps"] == st["block_steps"] == st["slot_steps"]
        # two denoising steps and a commit a block; one where the
        # prompt's tail left a single position
        assert st["block_steps"] == 3 * blocks - sum(
            p % 4 == 3 for p, _ in CASES)
        c = st["model_counters"]
        assert c["expert_pairs_dropped"] == 0
        assert c["expert_layer_calls"] == 4 * st["block_steps"]
        assert c["expert_pairs"] == 4 * st["block_steps"] * 4 * 2
        pre = st["model_counters_prefill"]
        assert pre["expert_layer_calls"] == 4 * len(CASES)
        assert pre["expert_pairs"] == 4 * 2 * sum(p - p % 4
                                                  for p, _ in CASES)
        assert st["turns"]["overlapped"] + st["turns"]["drained"] \
            == st["steps"]
        assert st["turns"]["drained"] == len(CASES)
        assert st["kv_blocks_free"] == st["kv_blocks_total"] == 48
        eng._alloc.check_invariants()
    finally:
        eng.shutdown()


def test_an_answer_is_the_same_alone_and_beside_seven_others(parts):
    """Eight callers on eight slots, staggered lengths, so that slots
    stand at different points of their blocks in one step; every answer
    is what the engine gives that prompt alone (and so the reference's),
    and the steps ran one ahead of the host."""
    cfg, params = parts
    rng = np.random.default_rng(1)
    work = [(rng.integers(0, 256, 5 + 3 * i).tolist(), 9 + 2 * i)
            for i in range(8)]
    eng = engine(parts, num_slots=8, kv_pool_tokens=8 * 128)
    try:
        alone = [eng.generate(p, max_tokens=n) for p, n in work]
        assert alone[3] == REF.generate(params, work[3][0], work[3][1], SPEC)
        before = eng.stats()["turns"]
        outs = {}

        def run(i):
            outs[i] = eng.generate(work[i][0], max_tokens=work[i][1])

        threads = [threading.Thread(target=run, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert [outs[i] for i in range(8)] == alone
        turns = eng.stats()["turns"]
        ran = {k: turns[k] - before[k] for k in turns}
        assert ran["overlapped"] > 4 * ran["drained"]
        assert ran["surplus_dropped"] == 0
    finally:
        eng.shutdown()


def test_a_preemption_mid_block_resumes_from_committed_tokens(parts):
    """A pool too small for three growing answers: the youngest is
    preempted with a block half decided, re-queued with its prompt and
    its COMMITTED tokens, and every answer is still the one it gets
    alone."""
    cfg, params = parts
    work = [(list(range(1 + i, 23 + i)), 40) for i in range(3)]
    eng = engine(parts, kv_pool_tokens=15 * 8)
    try:
        outs = {}

        def run(i):
            outs[i] = eng.generate(work[i][0], max_tokens=work[i][1])

        threads = [threading.Thread(target=run, args=(i,)) for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        st = eng.stats()
        assert st["preemptions"] >= 1
        assert st["kv_blocks_free"] == 15
        eng._alloc.check_invariants()
        for i, (p, n) in enumerate(work):
            assert outs[i] == eng.generate(p, max_tokens=n), i
    finally:
        eng.shutdown()


def test_an_eos_cuts_the_block_and_the_step_past_it_is_dropped(parts):
    cfg, params = parts
    prompt = list(range(3, 14))
    eng = engine(parts)
    try:
        whole = eng.generate(prompt, max_tokens=24)
        eos = whole[9]
        first = whole.index(eos)
        got = eng.generate(prompt, max_tokens=24, eos_token=eos)
        assert got == whole[:first + 1]
        assert eng.stats()["active_slots"] == 0
        assert eng.generate(prompt, max_tokens=24) == whole
    finally:
        eng.shutdown()


def test_draws_at_a_temperature_repeat_with_the_seed(parts):
    cfg, params = parts
    outs = []
    for seed in (3, 3, 4):
        eng = engine(parts, seed=seed)
        try:
            outs.append(eng.generate(list(range(9)), max_tokens=16,
                                     temperature=1.0))
            assert eng.stats()["sampling"]["sampled_tokens"] == 16 + 3
        finally:
            eng.shutdown()
    assert outs[0] == outs[1] != outs[2]
    assert all(0 <= t < 256 for t in outs[2])


@pytest.mark.parametrize("kwargs, names", [
    (dict(kv_cache="slot"), "kv_cache='slot'"),
    (dict(speculation="ngram"), "speculation"),
    (dict(prefix_cache="radix"), "prefix cache"),
    (dict(prefix_cache_bytes=1 << 20), "prefix cache"),
    (dict(prefill_chunk=16), "chunked prefill")],
    ids=["slot", "speculation", "radix", "budget", "chunked"])
def test_what_the_model_lacks_raises_at_construction(parts, kwargs, names):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = parts
    with pytest.raises(ValueError, match=names):
        LLMEngine(config=cfg, params=params, num_slots=2, max_seq=64,
                  **kwargs)


def test_kv_transfer_is_refused_by_name(parts):
    eng = engine(parts)
    try:
        with pytest.raises(ValueError, match="KV inject"):
            eng.submit_prefilled([1, 2], np.zeros(1), np.zeros(1),
                                 np.zeros(1))
    finally:
        eng.shutdown()


def test_a_model_without_a_decode_step_or_a_block_step_is_refused(parts):
    """``PagedPrograms.decode`` None asks for ``block_denoise``; a model
    that has neither is refused by the mechanism's name."""
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = parts

    class Lacking(sdar.SdarServing):
        block_denoise = None

    class Config(sdar.SdarConfig):
        def serving_model(self):
            return Lacking(self)

    with pytest.raises(ValueError, match="generation by blocks"):
        LLMEngine(config=Config(**dataclasses.asdict(cfg)), params=params,
                  num_slots=2, **ENGINE)


@pytest.mark.parametrize("kwargs, message", [
    (dict(max_seq=126, kv_block_size=8), "multiples of block_length"),
    (dict(max_seq=128, kv_block_size=2), "multiples of block_length")])
def test_a_geometry_that_cuts_a_block_is_refused(parts, kwargs, message):
    from ray_tpu.serve.llm import LLMEngine

    cfg, params = parts
    with pytest.raises(ValueError, match=message):
        LLMEngine(config=cfg, params=params, num_slots=2, **kwargs)


def test_the_block_turns_waits_are_fetch_phases(parts):
    """The block turn's account: the wait for a prefill (its counters;
    no token comes of it) is ``prefill_fetch`` inside ``prefill``, the
    wait for a block step ``block_fetch``, and a streamed answer's
    tokens are counted on their way out, a block at a time."""
    eng = engine(parts)
    try:
        rid = eng.submit([7, 8, 9, 10, 11, 12], max_tokens=8)
        out = []
        while True:
            st = eng.poll(rid)
            out.extend(st["chunks"])
            if st["done"]:
                break
            time.sleep(0.002)
        eng.generate([3, 4, 5], max_tokens=4)
        st = eng.stats()
    finally:
        eng.shutdown()
    rows, adm = st["phases"], st["admissions"]
    assert len(out) == 8
    assert adm["prefills"] == rows["prefill"][0] \
        == rows["prefill_fetch"][0] == 2
    # whole blocks only: 4 of 6 tokens, and none of 3
    assert adm["prompt_tokens"] == 4 and adm["padded_tokens"] >= 4
    assert rows["prefill"][1] >= rows["prefill_fetch"][1] > 0.0
    assert rows["block_fetch"][0] == rows["block_dispatch"][0] \
        == st["block_steps"]
    assert "logits_fetch" not in rows
    assert sum(r[2] for r in rows.values()) == pytest.approx(
        rows["turn"][1], rel=0.01)
    assert st["delivery"]["tokens_picked"] == 8
    assert 1 <= sum(st["delivery"]["pickup_wall_counts"]) <= 8


def test_a_dense_models_turn_is_what_it_was():
    """A model with a decode step runs the decode turn: no block counter
    in its stats, a token a slot a step."""
    from ray_tpu.serve.llm import LLMEngine

    eng = LLMEngine(model="tiny", num_slots=2, max_seq=64, kv_block_size=8)
    try:
        out = eng.generate([1, 2, 3], max_tokens=5)
        st = eng.stats()
        assert len(out) == 5 and "block_steps" not in st
        assert st["steps"] == 4 and st["tokens_generated"] == 4
        assert eng._block_step is None and eng._step_rows == 1
    finally:
        eng.shutdown()


def test_a_config_that_is_no_such_model_is_refused():
    for changes, message in ((dict(block_length=3), "power of two"),
                             (dict(denoising_steps=5), "denoising_steps"),
                             (dict(mask_token_id=256), "mask_token_id"),
                             (dict(remasking="low_confidence_dynamic"),
                              "remasking")):
        with pytest.raises(ValueError, match=message):
            config(**changes)
