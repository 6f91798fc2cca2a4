"""The decoder whose layers run several times a token
(``ray_tpu.models.ouro``: the dense block between four norms, ``T``
passes over the same layers, KV rows of their own for every pass, an exit
gate after each) and the engine that serves it, at a small size on the
CPU (hidden 64, 3 layers, 4 heads of 16) against the benchmark's plain
reference (``benchmark/reference/ouro.py``: every pass a full causal
forward, no cache) on seeded random weights."""

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
from ray_tpu.models import llama, ouro  # noqa: E402
from ray_tpu.models.paged_cache import (BlockAllocator,  # noqa: E402
                                        extract_kv)

L, T, KV, D = 3, 4, 4, 16
SPEC = dict(
    name="tiny-ouro", architecture="ouro",
    reference="benchmark/reference/ouro.py",
    vocab_size=256, hidden_size=64, intermediate_size=96,
    num_hidden_layers=L, num_attention_heads=4, num_key_value_heads=KV,
    head_dim=D, max_position_embeddings=512, rms_norm_eps=1e-6,
    rope_theta=1000000, rope_scaling=None, tie_word_embeddings=False,
    use_sliding_window=False, hidden_act="silu", total_ut_steps=T,
    early_exit_threshold=1, torch_dtype="bfloat16")
ARCH = model_spec.adapter(SPEC)
REF = model_spec.reference(SPEC)
DEPLOYMENT = dict(num_slots=3, max_seq=128, kv_block_size=8,
                  kv_pool_tokens=3 * 128)
F32_LIMIT = 3e-4        # bfloat16 arithmetic reads 0.01 and more
PROGRAM_CONFIG = ARCH.program_config    # tests steer the adapter's own


def spec(**changes):
    return dict(SPEC, **changes)


def make_params(seed, dtype=jnp.float32, of=SPEC):
    from benchmark import weights

    return jax.tree.map(lambda a: a.astype(dtype), weights.make(of, seed))


def config(of=SPEC, dtype=jnp.float32, **changes):
    return dataclasses.replace(PROGRAM_CONFIG(of), dtype=dtype,
                               **changes)


def programs(cfg, params, slots=3, block=8, pool=3 * 128, max_seq=128):
    """(page, allocator, cache, prefill, decode) of the builders."""
    page = ouro.make_page(max_seq=max_seq, block_size=block,
                          pool_tokens=pool)
    return (page, BlockAllocator(page, slots),
            ouro.init_cache(cfg, page, slots),
            ouro.make_prefill(params, cfg, page),
            ouro.make_decode_step(params, cfg, page))


def prefill_slot(prefill, alloc, cache, slot, tokens, page):
    n = len(tokens)
    assert alloc.ensure(slot, n + 1)
    pad = -(-n // page.block_size) * page.block_size
    padded = np.zeros((1, pad), np.int32)
    padded[0, :n] = tokens
    return prefill(cache, alloc.table_rows(slot), jnp.asarray(padded), n,
                   slot)


def engine(cfg, params, **kwargs):
    from ray_tpu.serve.llm import LLMEngine

    return LLMEngine(config=cfg, params=params, **{
        "max_seq": 128, "kv_block_size": 8, "num_slots": 3,
        "kv_pool_tokens": 3 * 128, **kwargs})


def greedy(params, prompt, n, of=SPEC, room=64):
    """``n`` greedy tokens by a loop over the reference: no cache. The
    sequence stands in ``room`` positions (the reference is causal: what
    follows a row does not reach it), so one shape is compiled."""
    seq = np.zeros(room, np.int32)
    seq[:len(prompt)] = prompt
    for at in range(len(prompt), len(prompt) + n):
        lg = REF.logits(params, jnp.asarray(seq), of, rows=[at - 1])
        seq[at] = int(np.asarray(lg)[0].argmax())
    return seq[len(prompt):len(prompt) + n].tolist()


# ----------------------------------------- 1. the programs and the reference
@pytest.mark.parametrize("passes, dtype, limit", [
    (4, jnp.float32, F32_LIMIT), (1, jnp.float32, F32_LIMIT),
    (2, jnp.float32, F32_LIMIT), (4, jnp.bfloat16, 0.15)],
    ids=["T4", "T1", "T2", "T4-bfloat16"])
def test_prefill_then_decode_through_the_pool_match_the_references_forward(
        passes, dtype, limit, monkeypatch):
    """A prefill of 21 tokens (across the pool's blocks of 8) and 12
    teacher-forced decode steps through an engine's own programs and
    pool, beside two neighbours, against ONE forward of the reference
    with no cache: row 20 from the prefill, rows 21..32 from the decode
    step. In float32 the limit is one that bfloat16 arithmetic fails."""
    of = spec(total_ut_steps=passes)
    monkeypatch.setattr(ARCH, "program_config",
                        lambda s: config(s, dtype))
    params = make_params(7, dtype, of)
    tokens = jax.random.randint(jax.random.key(3), (33,), 0, 256)
    got = ARCH.serve_program_logits(params, of, tokens, DEPLOYMENT,
                                    prefill=21)
    want = np.asarray(REF.logits(params, tokens, of,
                                 rows=list(range(20, 33))))
    assert got.shape == want.shape == (13, 256)
    assert REF.rel_err(got[0], want[0]) < limit
    assert REF.rel_err(got[1:], want[1:]) < limit
    if dtype == jnp.float32:
        rounded = want.astype(jnp.bfloat16).astype(np.float32)
        assert REF.rel_err(rounded, want) > limit       # the limit is tight
        assert (got.argmax(-1) == want.argmax(-1)).all()


def test_the_check_borrows_the_engines_own_programs_and_leaves_it_sound():
    """Handed an idle engine, the check runs ITS prefill and decode step
    on its pool and allocator (three prefills, twelve steps), gives the
    pool back whole, and the engine then answers as the reference's loop
    does; an engine of other weights is refused."""
    cfg, params = config(), make_params(21)
    eng = engine(cfg, params)
    calls = {"_prefill": 0, "_decode": 0}
    own = {name: getattr(eng, name) for name in calls}

    def counted(name):
        def call(*args):
            calls[name] += 1
            return own[name](*args)
        setattr(eng, name, call)

    for name in calls:
        counted(name)
    try:
        tokens = jax.random.randint(jax.random.key(11), (33,), 0, 256)
        got = ARCH.serve_program_logits(params, SPEC, tokens, DEPLOYMENT,
                                        prefill=21, engine=eng)
        want = np.asarray(REF.logits(params, tokens, SPEC,
                                     rows=list(range(20, 33))))
        assert REF.rel_err(got, want) < F32_LIMIT
        assert calls == {"_prefill": 3, "_decode": 12}
        st = eng.stats()
        assert st["kv_blocks_free"] == st["kv_blocks_total"]
        eng._alloc.check_invariants()
        for name, fn in own.items():
            setattr(eng, name, fn)
        prompt = np.random.default_rng(2).integers(0, 256, 13).tolist()
        assert eng.generate(prompt, max_tokens=9) == greedy(params, prompt,
                                                            9)
        with pytest.raises(RuntimeError, match="other weights"):
            ARCH.serve_program_logits(make_params(3), SPEC, tokens,
                                      DEPLOYMENT, prefill=21, engine=eng)
    finally:
        eng.shutdown()


def test_the_decode_step_with_the_kernel_is_the_reference_too(
        kernel_on_cpu, monkeypatch):
    """The same comparison with the paged decode kernel interpreted: the
    chip's grid and work list, the pool index ``t * L + l`` a traced
    scalar of two nested scans."""
    monkeypatch.setattr(ARCH, "program_config", lambda s: config(s))
    params = make_params(7)
    tokens = jax.random.randint(jax.random.key(4), (27,), 0, 256)
    got = ARCH.serve_program_logits(params, SPEC, tokens, DEPLOYMENT,
                                    prefill=21)
    want = np.asarray(REF.logits(params, tokens, SPEC,
                                 rows=list(range(20, 27))))
    assert REF.rel_err(got[0], want[0]) < F32_LIMIT
    assert REF.rel_err(got[1:], want[1:]) < F32_LIMIT


# --------------------------------- 2. the norms matter, the pool is T*L deep
def test_one_pass_with_unit_post_norms_is_still_not_the_dense_decoder():
    """T = 1 with the post-norms' stored weight 0 (scale 1): the block
    still norms what each sublayer adds, so its logits are not those of
    the dense decoder on the same matrices; the dense reference agrees
    with the dense program, so the difference is the block's."""
    of = spec(total_ut_steps=1)
    params = make_params(5, of=of)
    layers = dict(params["layers"])
    for name in ("attn_post_norm", "mlp_post_norm"):
        layers[name] = jnp.zeros_like(layers[name])
    params = dict(params, layers=layers)
    tokens = jax.random.randint(jax.random.key(1), (16,), 0, 256)
    looped = np.asarray(REF.logits(params, tokens, of))
    dense_cfg = llama.LlamaConfig(
        vocab_size=256, hidden=64, n_layers=L, n_heads=4, n_kv_heads=KV,
        head_dim=D, mlp_dim=96, max_seq=512, rope_theta=1e6, norm_eps=1e-6,
        dtype=jnp.float32, remat=False)
    dense = np.asarray(llama.forward(
        {k: v for k, v in params.items() if not k.startswith("exit")},
        tokens[None], dense_cfg))[0]
    assert REF.rel_err(looped, dense) > 0.05      # 170 times the limit
    cfg = config(of)
    page, alloc, cache, prefill, _ = programs(cfg, params)
    _, lg = prefill_slot(prefill, alloc, cache, 1, np.asarray(tokens), page)
    assert REF.rel_err(np.asarray(lg), looped[-1]) < F32_LIMIT


# ------------------------- 3. a pass's rows are its own, at index t * L + l
def test_the_pools_rows_at_t_times_l_plus_l_are_the_references_of_that_pass():
    """After a prefill of 13 tokens and 5 decode steps the pool holds
    ``T * L`` layers, and the rows of the slot at index ``t * L + l``
    are the keys (rotated) and values the reference computes in pass
    ``t``, layer ``l``: a query of pass ``t`` attends over pass ``t``'s
    rows only, for the reference has no others in that pass. The same
    layer's rows differ between passes."""
    cfg, params = config(), make_params(9)
    page, alloc, cache, prefill, decode = programs(cfg, params)
    assert cache["k"].shape == (T * L, page.num_blocks, 8, KV * D)
    tokens = np.asarray(jax.random.randint(jax.random.key(5), (18,), 0, 256))
    slot = 2
    cache, _ = prefill_slot(prefill, alloc, cache, slot, tokens[:13], page)
    active = np.arange(3) == slot
    for i in range(13, 18):
        assert alloc.ensure(slot, i + 1)
        fed = np.where(active, tokens[i], 0).astype(np.int32)
        cache, _ = decode(cache, alloc.device_tables(), jnp.asarray(fed),
                          jnp.asarray(active))
    k, v = extract_kv(cache, alloc, slot, 18)
    assert k.shape == v.shape == (T * L, 18, KV * D)
    _, _, kv = REF.passes(params, jnp.asarray(tokens), SPEC, keep_kv=True)
    assert len(kv) == T * L
    for i, (rk, rv) in enumerate(kv):
        np.testing.assert_allclose(k[i], np.asarray(rk).reshape(18, -1),
                                   atol=2e-4, rtol=2e-4)
        np.testing.assert_allclose(v[i], np.asarray(rv).reshape(18, -1),
                                   atol=2e-4, rtol=2e-4)
    for l in range(L):
        for t in range(1, T):
            assert np.abs(k[t * L + l] - k[l]).max() > 1e-2
            assert np.abs(v[t * L + l] - v[l]).max() > 1e-2


# ------------------------------------------------------- 4. the exit gate
def _closest_pass(params, tokens, of, rows, got):
    """The pass whose state's logits each row of ``got`` is."""
    states, _, _ = REF.passes(params, jnp.asarray(tokens), of)
    by_pass = np.stack([np.asarray(REF.head(s[np.asarray(rows)],
                                            params["lm_head"]))
                        for s in states])                # (T, rows, vocab)
    err = np.linalg.norm(by_pass - got[None], axis=-1) / np.linalg.norm(
        by_pass, axis=-1)
    return err.argmin(axis=0), err.min(axis=0)


@pytest.mark.parametrize("threshold", [0.5, 0.8, 1.0])
def test_the_selected_pass_is_the_references_at_every_position(threshold,
                                                               monkeypatch):
    """Three sequences in one decode step, positions that leave at
    different passes (thresholds 0.5 and 0.8): the program's logits at every
    position are the head over the state of the pass the reference
    selects, and agree with the reference's logits. At 1.0 every position
    takes the last pass."""
    of = spec(early_exit_threshold=threshold)
    cfg, params = config(of), make_params(31, of=of)
    page, alloc, cache, prefill, decode = programs(cfg, params)
    seqs = [np.asarray(jax.random.randint(jax.random.key(40 + s), (n,), 0,
                                          256))
            for s, n in enumerate((19, 12, 25))]
    steps, lens = 6, [13, 6, 19]
    got = [[] for _ in seqs]
    for slot, (seq, n) in enumerate(zip(seqs, lens)):
        cache, lg = prefill_slot(prefill, alloc, cache, slot, seq[:n], page)
        got[slot].append(np.asarray(lg))
    for i in range(steps):
        for slot, n in enumerate(lens):
            assert alloc.ensure(slot, n + i + 1)
        fed = np.array([seq[n + i] for seq, n in zip(seqs, lens)], np.int32)
        cache, lg = decode(cache, alloc.device_tables(), jnp.asarray(fed),
                           jnp.ones((3,), bool))
        for slot in range(3):
            got[slot].append(np.asarray(lg)[slot])
    chosen_all = []
    for slot, (seq, n) in enumerate(zip(seqs, lens)):
        rows = list(range(n - 1, n + steps))
        want, chosen, p = REF.logits(params, jnp.asarray(seq), of,
                                     rows=rows, detail=True)
        mine = np.stack(got[slot])
        assert REF.rel_err(mine, np.asarray(want)) < F32_LIMIT
        closest, err = _closest_pass(params, seq, of, rows, mine)
        assert (closest == np.asarray(chosen)).all()
        assert err.max() < F32_LIMIT
        np.testing.assert_allclose(np.asarray(p).sum(axis=0), 1.0, atol=1e-5)
        chosen_all += np.asarray(chosen).tolist()
    if threshold == 1.0:
        assert set(chosen_all) == {T - 1}
    else:                                       # they do differ
        assert len(set(chosen_all)) >= 2, chosen_all


def test_the_exit_distribution_and_the_choice_are_the_references():
    lams = jax.random.uniform(jax.random.key(0), (T, 50))
    p = ouro.exit_distribution(lams)
    np.testing.assert_allclose(np.asarray(p),
                               np.asarray(REF.exit_distribution(lams)),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(p).sum(axis=0), 1.0, atol=1e-5)
    for q in (0.3, 0.5, 0.9, 1.0):
        assert (np.asarray(ouro.exit_pass(p, q))
                == np.asarray(REF.exit_pass(p, q))).all()
    one = ouro.exit_distribution(lams[:1])
    assert np.asarray(one).tolist() == [[1.0] * 50]


# ---------------------------------------------------------- 5. the counters
def test_the_counters_sum_the_exit_distribution_over_the_running_rows():
    cfg, params = config(), make_params(13)
    assert ouro.counter_names(cfg) == (
        "exit_p0", "exit_p1", "exit_p2", "exit_p3", "exit_rows")
    page, alloc, cache, prefill, decode = programs(cfg, params)
    tokens = np.arange(1, 12)
    cache, _ = prefill_slot(prefill, alloc, cache, 0, tokens, page)
    c = np.asarray(cache["counters"])
    assert c.shape == (T + 1,) and c[T] == 1
    np.testing.assert_allclose(c[:T].sum(), 1.0, atol=1e-5)
    _, _, p = REF.logits(params, jnp.asarray(tokens), SPEC, rows=[10],
                         detail=True)
    np.testing.assert_allclose(c[:T], np.asarray(p)[:, 0], atol=1e-4)
    cache, _ = prefill_slot(prefill, alloc, dict(cache, counters=None), 2,
                            tokens[:7], page)
    for slot, n in ((0, 11), (2, 7)):
        assert alloc.ensure(slot, n + 1)
    cache, _ = decode(dict(cache, counters=None), alloc.device_tables(),
                      jnp.asarray([3, 0, 4], jnp.int32),
                      jnp.asarray([True, False, True]))
    c = np.asarray(cache["counters"])
    assert c[T] == 2                            # the idle slot is not a row
    np.testing.assert_allclose(c[:T].sum(), 2.0, atol=1e-5)
    assert (c[:T] > 0).all()


# --------------------------------------------- 6. slots, blocks, the allocator
def test_slots_of_other_lengths_an_idle_one_a_boundary_and_a_reused_block():
    """Slots 0 and 2 of different lengths decode together while slot 1
    stands idle (its length and rows stay); both cross a block boundary
    of 8; slot 0 is released and slot 1 takes its blocks for another
    sequence, which decodes to the reference beside slot 2."""
    cfg, params = config(), make_params(17)
    page, alloc, cache, prefill, decode = programs(cfg, params, pool=48)
    a, b, c = (np.asarray(jax.random.randint(jax.random.key(60 + i), (n,),
                                             0, 256))
               for i, n in enumerate((12, 20, 15)))
    got = {0: [], 2: [], 1: []}
    cache, lg = prefill_slot(prefill, alloc, cache, 0, a[:5], page)
    got[0].append(np.asarray(lg))
    cache, lg = prefill_slot(prefill, alloc, cache, 2, b[:13], page)
    got[2].append(np.asarray(lg))
    first = list(alloc._owned[0])
    for i in range(6):                          # 5 -> 11 and 13 -> 19
        assert alloc.ensure(0, 5 + i + 1) and alloc.ensure(2, 13 + i + 1)
        fed = np.array([a[5 + i], 99, b[13 + i]], np.int32)
        cache, lg = decode(cache, alloc.device_tables(), jnp.asarray(fed),
                           jnp.asarray([True, False, True]))
        got[0].append(np.asarray(lg)[0])
        got[2].append(np.asarray(lg)[2])
    assert np.asarray(cache["length"]).tolist() == [11, 0, 19]
    assert len(alloc._owned[0]) == 2 and len(alloc._owned[2]) == 3
    for slot, seq, n in ((0, a, 5), (2, b, 13)):
        want = REF.logits(params, jnp.asarray(seq[:n + 6]), SPEC,
                          rows=list(range(n - 1, n + 6)))
        assert REF.rel_err(np.stack(got[slot]), np.asarray(want)) < F32_LIMIT
    first += alloc._owned[0][1:]
    alloc.release(0)
    cache, lg = prefill_slot(prefill, alloc, cache, 1, c[:9], page)
    assert set(alloc._owned[1]) <= set(first)   # the released blocks
    got[1].append(np.asarray(lg))
    for i in range(4):
        assert alloc.ensure(1, 9 + i + 1) and alloc.ensure(2, 19 + i + 1)
        fed = np.array([0, c[9 + i], 7], np.int32)
        cache, lg = decode(cache, alloc.device_tables(), jnp.asarray(fed),
                           jnp.asarray([False, True, True]))
        got[1].append(np.asarray(lg)[1])
    want = REF.logits(params, jnp.asarray(c[:13]), SPEC,
                      rows=list(range(8, 13)))
    assert REF.rel_err(np.stack(got[1]), np.asarray(want)) < F32_LIMIT
    assert np.asarray(cache["length"]).tolist() == [11, 13, 23]
    alloc.check_invariants()


# ------------------------------------------------------------ 7. the engine
def test_the_engine_answers_as_a_loop_over_the_reference_under_preemption():
    """``LLMEngine`` with an ``OuroConfig`` through ``serving_model``:
    six greedy requests on three slots over a pool too small for three
    grown sequences, so that one is preempted and prefilled again; every
    answer is the reference's loop's, token for token (float32), and
    ``stats()`` carries the exit distribution."""
    from ray_tpu.models.serving import serving_model

    cfg, params = config(), make_params(21)
    assert isinstance(serving_model(cfg), ouro.OuroServing)
    eng = engine(cfg, params, kv_pool_tokens=80, max_seq=64)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 256, n).tolist()
               for n in (9, 14, 5, 11, 7, 16)]
    lengths = [30, 12, 33, 25, 28, 10]
    out = [None] * len(prompts)

    def ask(i):
        out[i] = eng.generate(prompts[i], max_tokens=lengths[i])

    try:
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(len(prompts))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
        st = eng.stats()
    finally:
        eng.shutdown()
    for i, (prompt, n) in enumerate(zip(prompts, lengths)):
        assert out[i] == greedy(params, prompt, n), i
    assert st["preemptions"] >= 1
    assert st["kv_blocks_free"] == st["kv_blocks_total"] == 10
    decode, pre = st["model_counters"], st["model_counters_prefill"]
    assert set(decode) == set(ouro.counter_names(cfg))
    # every token is a row's: a prefill's (a request's first, and the
    # first after its preemption) or a decode step's
    assert pre["exit_rows"] == len(prompts) + st["preemptions"]
    assert decode["exit_rows"] + pre["exit_rows"] >= sum(lengths)
    for c in (decode, pre):
        assert abs(sum(c[f"exit_p{t}"] for t in range(T))
                   - c["exit_rows"]) < 1e-3 * c["exit_rows"]


# ------------------------------------------------- 8. what the model lacks
@pytest.mark.parametrize("option, words", [
    (dict(kv_cache="slot"), "kv_cache='slot'"),
    (dict(speculation="ngram", kv_cache="slot"), "kv_cache='slot'"),
    (dict(prefix_cache="radix"), "a prefix cache"),
    (dict(prefill_chunk=16), "chunked prefill"),
], ids=["slot", "speculation", "prefix_cache", "prefill_chunk"])
def test_a_mechanism_the_model_lacks_is_refused_by_name(option, words):
    from ray_tpu.serve.llm import LLMEngine

    cfg = config()
    params = make_params(2)
    with pytest.raises(ValueError, match="OuroConfig is not served with "
                       + words.replace("(", r"\(")):
        LLMEngine(config=cfg, params=params, num_slots=2, max_seq=64,
                  kv_block_size=8, **option)
    model = cfg.serving_model()
    for lacking in ("slot", "chunked_prefill", "speculative_verify",
                    "block_copy", "block_bytes", "kv_shape",
                    "block_denoise"):
        assert not hasattr(model, lacking), lacking


def test_the_engine_refuses_kv_transfer_at_the_call():
    cfg, params = config(), make_params(2)
    eng = engine(cfg, params)
    try:
        with pytest.raises(ValueError, match="OuroConfig is not served with "
                           "KV inject"):
            eng.submit_prefilled([1, 2, 3], None, None, None, max_tokens=2)
    finally:
        eng.shutdown()


# ------------------------------------------------------------ the parts
def test_the_programs_operations_lie_under_a_part_and_the_loops_under_theirs():
    """The decode step and a prefill as the compiler is given them
    (``tests/test_program_parts.py``'s reading): every heavy operation
    under a part, the norms after the sublayers under ``post_norm``, the
    gate's product under ``exit_gate``, the vocabulary's product under
    ``head`` ONCE (not once a pass), the pool's writes under
    ``kv_store``."""
    import importlib.util
    import re

    found = importlib.util.spec_from_file_location(
        "program_parts", os.path.join(ROOT, "tests",
                                      "test_program_parts.py"))
    parts_of = importlib.util.module_from_spec(found)
    found.loader.exec_module(parts_of)
    cfg, params = config(), make_params(9)
    p = cfg.serving_model().paged(params, num_slots=3, max_seq=64,
                                  block_size=8, pool_tokens=192)
    texts = {
        "decode": p.decode.jitted.lower(
            params, p.cache, p.alloc.device_tables(),
            jnp.zeros((3,), jnp.int32), jnp.ones((3,), bool)),
        "prefill": p.prefill.jitted.lower(
            params, p.cache, jnp.asarray(p.alloc.table_rows(0)),
            jnp.zeros((1, 32), jnp.int32), jnp.int32(29), jnp.int32(0),
            pad_len=32)}
    for name, lowered in texts.items():
        text = parts_of.hlo_text(lowered)
        n, bare = parts_of._named_share(text)
        assert n > 10 and len(bare) <= 0.05 * n, (name, n, bare)
        ops = parts_of.operations(text)
        seen = {parts_of.part_of(path) for _, _, path in ops}
        assert {"post_norm", "exit_gate", "attn_proj", "mlp", "head",
                "kv_store", "embed"} <= seen, (name, seen)
        wide = [path for op, result, path in ops if op == "dot"
                and re.search(r"\[(\d+,)*256\]", result)]
        assert len(wide) == 1 and parts_of.part_of(wide[0]) == "head"
        writes = [path for op, result, path in ops
                  if op in ("scatter", "dynamic-update-slice")
                  and f"[{T * L}," in result]
        assert writes and {parts_of.part_of(w) for w in writes} == {
            "kv_store"}, writes
    assert re.search(r"jit\(step\)", parts_of.hlo_text(texts["decode"]))
