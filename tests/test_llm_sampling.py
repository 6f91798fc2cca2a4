"""A turn's tokens are taken on the device (``serve/llm.py``:
``sample_ids`` beside ``greedy_ids``): the draw is exact, a greedy row is
the argmax, every (request, position) has a stream of its own, the
engine's ``seed`` decides the tokens, and the host fetches ids, never a
row of the vocabulary. CPU, the function itself and a debug engine."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import llama
from ray_tpu.serve import llm
from ray_tpu.serve.llm import LLMEngine

CFG = llama.CONFIGS["debug"]
SAMPLE = jax.jit(llm.sample_ids)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0))


def _engine(params, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq", 128)
    return LLMEngine(config=CFG, params=params, kv_cache="paged", **kw)


def _together(eng, calls):
    """Submit every (prompt, n, kwargs) at once, then drain them all."""
    rids = [eng.submit(prompt, n, **kw) for prompt, n, kw in calls]
    outs = [[] for _ in rids]
    live = set(range(len(rids)))
    deadline = time.monotonic() + 120
    while live and time.monotonic() < deadline:
        for i in sorted(live):
            st = eng.poll(rids[i])
            outs[i].extend(st["chunks"])
            if st["done"]:
                live.discard(i)
        time.sleep(0.002)
    assert not live, "requests still running"
    return outs


def _softmax(z):
    p = np.exp(z - z.max())
    return p / p.sum()


@pytest.mark.parametrize("vocab", [16, 64])
@pytest.mark.parametrize("temperature", [0.7, 1.3])
def test_draws_follow_the_softmax_at_the_temperature(temperature, vocab):
    n = 8192
    row = np.random.default_rng(vocab).normal(0.0, 2.0, vocab)
    ids = np.asarray(SAMPLE(
        jnp.tile(jnp.asarray(row, jnp.float32), (n, 1)),
        jnp.full((n,), temperature, jnp.float32), jax.random.key(3),
        jnp.arange(n, dtype=jnp.int32), jnp.full((n,), 5, jnp.int32)))
    seen = np.bincount(ids, minlength=vocab) / n

    def distance(p):                    # total variation
        return 0.5 * np.abs(seen - p).sum()

    # 8,192 draws of 64 outcomes lie about 0.03 from their own law; the
    # untempered softmax is four or more times as far from this one
    assert distance(_softmax(row / temperature)) < 0.05
    assert distance(_softmax(row)) > 0.08


def test_a_greedy_row_in_a_mixed_turn_is_the_argmax():
    logits = jax.random.normal(jax.random.key(1), (6, 64), jnp.float32) * 3
    temps = jnp.asarray([0.0, 0.7, 0.0, 1.3, -1.0, 0.7], jnp.float32)
    ids = np.asarray(SAMPLE(logits, temps, jax.random.key(0),
                            jnp.arange(6, dtype=jnp.int32),
                            jnp.zeros(6, jnp.int32)))
    greedy = np.asarray(jax.jit(llm.greedy_ids)(logits))
    assert ids.dtype == np.int32 and greedy.dtype == np.int32
    assert (greedy == np.asarray(logits).argmax(-1)).all()
    assert (ids[[0, 2, 4]] == greedy[[0, 2, 4]]).all()
    # a prefill's single row, handed over without its leading axis
    one = np.asarray(SAMPLE(logits[3], temps[:1], jax.random.key(0),
                            jnp.zeros(1, jnp.int32), jnp.zeros(1, jnp.int32)))
    assert one.shape == (1,) and one[0] == greedy[3]


def test_a_greedy_request_among_sampling_ones_answers_as_alone(params):
    eng = _engine(params)
    try:
        alone = eng.generate([5, 17, 99], 12)
        mixed = _together(eng, [([8, 3], 16, {"temperature": 0.9}),
                                ([5, 17, 99], 12, {}),
                                ([4, 4, 4], 16, {"temperature": 1.2})])
    finally:
        eng.shutdown()
    assert mixed[1] == alone


def test_streams_differ_by_request_and_by_position():
    turns, row = 64, jnp.zeros((1, 64), jnp.float32)

    def sequence(request, key=jax.random.key(0)):
        return [int(SAMPLE(row, jnp.ones(1, jnp.float32), key,
                           jnp.asarray([request], jnp.int32),
                           jnp.asarray([p], jnp.int32))[0])
                for p in range(turns)]

    first = sequence(0)
    assert first == sequence(0)
    assert first != sequence(1)
    assert first != sequence(0, jax.random.key(1))
    assert len(set(first)) > 16            # a position, a draw of its own


def test_two_slots_with_the_same_logits_draw_apart(params):
    # one prompt twice, admitted together: the slots hold identical
    # logits until their tokens part. One generator a turn (the host's
    # sampling before PR 32) kept such a pair together for ever
    eng = _engine(params)
    try:
        a, b = _together(eng, [([5, 17, 99], 64, {"temperature": 1.0})] * 2)
    finally:
        eng.shutdown()
    assert len(a) == len(b) == 64 and a != b


def test_the_seed_decides_the_tokens(params):
    def answers(seed):
        eng = _engine(params, seed=seed)
        try:
            return ([eng.generate([5, 17, 99], 12, temperature=0.8)
                     for _ in range(2)]
                    + _together(eng, [([1, 2], 12, {"temperature": 0.8}),
                                      ([1, 2], 12, {"temperature": 1.1})]))
        finally:
            eng.shutdown()

    first = answers(11)
    assert first == answers(11)
    assert first[0] != first[1]             # another request, other tokens
    other = answers(12)
    assert all(a != b for a, b in zip(first, other))


def test_the_host_fetches_ids_never_a_row_of_the_vocabulary(params):
    eng = _engine(params)
    fetched, fetch = [], eng._fetch

    def spy(ids, counters=None, prefill=False):
        fetched.append((ids.ndim, ids.dtype, ids.nbytes, prefill))
        return fetch(ids, counters, prefill)

    eng._fetch = spy
    try:
        _together(eng, [([5, 17, 99], 6, {"temperature": 0.7}),
                        ([7, 7], 6, {})])
    finally:
        eng.shutdown()
    first = [f for f in fetched if f[3]]
    turns = [f for f in fetched if not f[3]]
    assert len(first) == 2 and len(turns) >= 5
    # a first token is 4 bytes, a turn 4 bytes a slot
    assert all(f[1:3] == (jnp.int32, 4) for f in first), first
    assert all(f[:3] == (1, jnp.int32, 16) for f in turns), turns


def test_stats_count_greedy_and_sampled_turns_and_tokens(params):
    eng = _engine(params)
    try:
        eng.generate([9, 9], 3)                      # 2 greedy turns
        eng.generate([5, 17, 99], 6, temperature=0.7)  # 1 + 5 draws
        st = eng.stats()
    finally:
        eng.shutdown()
    assert st["sampling"] == {"greedy_turns": 2, "sampled_turns": 5,
                              "sampled_tokens": 6}
    assert st["steps"] == 7
