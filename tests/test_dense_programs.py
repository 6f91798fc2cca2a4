"""The seven serving programs of the dense decoder against one plain
forward (debug preset, float32): each program's logits equal
``llama.forward``'s at the same positions, and the K/V rows it wrote are
the rows a plain forward computes.

The plain forward below is written out in numpy-style jnp with nothing of
``ray_tpu`` in it (its own norm, rotation, attention and MLP), so that a
piece of the shared block (``llama.qkv`` / ``mlp`` / ``logits_f32``,
``decoding.dense_block`` / ``attend_rows``) that goes wrong shows here
whichever program runs it. Caches are filled with the plain forward's own
rows, so each case tests one program and not the prefill before it.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import decoding, llama, paged_cache

CFG = llama.CONFIGS["debug"]
TOL = dict(rtol=1e-4, atol=1e-4)
SLOTS, MAX_SEQ, BS = 3, 32, 4
PAGE = paged_cache.PagedConfig(num_blocks=40, block_size=BS, max_seq=MAX_SEQ)
T = 20                                   # tokens in each plain sequence


def _plain_forward(params, tokens):
    """tokens (T,) -> logits (T, V), k and v (L, T, KV, D), float32."""
    c = CFG
    p = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    n, half = len(tokens), c.head_dim // 2

    def norm(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True)
                           + c.norm_eps) * (1.0 + w)

    angle = (np.arange(n)[:, None]
             * c.rope_theta ** (-np.arange(half) / half)[None, :])
    cos, sin = np.cos(angle)[:, None, :], np.sin(angle)[:, None, :]

    def rotate(x):                                       # (T, heads, D)
        a, b = x[..., :half], x[..., half:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    x = p["embed"][np.asarray(tokens)]
    causal = np.tril(np.ones((n, n), bool))
    ks, vs = [], []
    for l in range(c.n_layers):
        w = {name: a[l] for name, a in p["layers"].items()}
        h = norm(x, w["attn_norm"])
        q = rotate(np.einsum("te,ehd->thd", h, w["wq"]))
        k = rotate(np.einsum("te,ehd->thd", h, w["wk"]))
        v = np.einsum("te,ehd->thd", h, w["wv"])
        ks.append(k)
        vs.append(v)
        group = c.n_heads // c.n_kv_heads
        s = np.einsum("qhd,khd->hqk", q, np.repeat(k, group, 1))
        s = np.where(causal[None], s * c.head_dim ** -0.5, -np.inf)
        s = np.exp(s - s.max(-1, keepdims=True))
        out = np.einsum("hqk,khd->qhd", s / s.sum(-1, keepdims=True),
                        np.repeat(v, group, 1))
        x = x + np.einsum("qhd,hde->qe", out, w["wo"])
        h = norm(x, w["mlp_norm"])
        g = h @ w["w_gate"]
        x = x + (g / (1.0 + np.exp(-g)) * (h @ w["w_up"])) @ w["w_down"]
    return (norm(x, p["final_norm"]) @ p["lm_head"], np.stack(ks),
            np.stack(vs))


@pytest.fixture(scope="module")
def params():
    """The preset's weights with the norms drawn away from 0, so that a
    dropped ``1 + w`` shows."""
    p = llama.init_params(CFG, jax.random.key(0))
    keys = iter(jax.random.split(jax.random.key(1), 3))
    for name in ("attn_norm", "mlp_norm"):
        p["layers"][name] = 0.1 * jax.random.normal(
            next(keys), p["layers"][name].shape)
    p["final_norm"] = 0.1 * jax.random.normal(next(keys),
                                              p["final_norm"].shape)
    return p


@pytest.fixture(scope="module")
def plain(params):
    """One sequence a slot: (tokens (T,), logits, k, v) from the plain
    forward, checked against ``llama.forward`` once."""
    rng = np.random.default_rng(0)
    out = []
    for _ in range(SLOTS):
        tokens = rng.integers(0, CFG.vocab_size, T).astype(np.int32)
        logits, k, v = _plain_forward(params, tokens)
        np.testing.assert_allclose(
            logits, np.asarray(llama.forward(params, tokens[None], CFG))[0],
            **TOL)
        out.append((tokens, logits, k, v))
    return out


def _slot_cache(plain, lengths):
    """A slot cache that holds the plain rows [0, lengths[s]) of slot s."""
    cache = decoding.init_cache(CFG, SLOTS, MAX_SEQ)
    for s, n in enumerate(lengths):
        _, _, k, v = plain[s]
        cache["k"] = cache["k"].at[:, s, :n].set(k[:, :n])
        cache["v"] = cache["v"].at[:, s, :n].set(v[:, :n])
    cache["length"] = jnp.asarray(lengths, jnp.int32)
    return cache


def _paged_cache(plain, lengths, cover):
    """A pool and its allocator: slot s holds the plain rows
    [0, lengths[s]) and blocks for ``cover[s]`` tokens."""
    alloc = paged_cache.BlockAllocator(PAGE, SLOTS)
    cache = paged_cache.init_paged_cache(CFG, PAGE, SLOTS)
    inject = paged_cache.make_paged_inject(CFG, PAGE)
    for s, n in enumerate(lengths):
        assert alloc.ensure(s, cover[s])
        if n:
            _, _, k, v = plain[s]
            pad = -(-n // BS) * BS - n
            widths = ((0, 0), (0, pad), (0, 0), (0, 0))
            cache = inject(cache, alloc.tables[s], np.pad(k[:, :n], widths),
                           np.pad(v[:, :n], widths), n, s)
    return cache, alloc


def _rows(cache, s, n):
    return (np.asarray(cache["k"][:, s, :n]), np.asarray(cache["v"][:, s, :n]))


def _padded(tokens, width):
    out = np.zeros((1, width), np.int32)
    out[0, :len(tokens)] = tokens
    return jnp.asarray(out)


def _same_rows(got, plain_s, n):
    """A paged pool's rows hold a token's KV heads side by side."""
    _, _, k, v = plain_s
    np.testing.assert_allclose(got[0].reshape(k[:, :n].shape), k[:, :n],
                               **TOL)
    np.testing.assert_allclose(got[1].reshape(v[:, :n].shape), v[:, :n],
                               **TOL)


def slot_prefill(params, plain):
    tokens, logits, _, _ = plain[1]
    n = 11
    cache, got = decoding.make_prefill(params, CFG)(
        decoding.init_cache(CFG, SLOTS, MAX_SEQ), _padded(tokens[:n], 16),
        n, 1)
    np.testing.assert_allclose(np.asarray(got), logits[n - 1], **TOL)
    _same_rows(_rows(cache, 1, n), plain[1], n)
    assert not np.asarray(cache["k"][:, 1, n:16]).any()   # padding: zeros
    assert np.asarray(cache["length"]).tolist() == [0, n, 0]


def slot_decode(params, plain):
    lengths = [7, 12, 5]                  # slot 2 holds rows and is idle
    before = _slot_cache(plain, lengths)
    idle = _rows(before, 2, MAX_SEQ)
    tokens = np.array([plain[s][0][n] for s, n in enumerate(lengths)])
    cache, got = decoding.make_decode_step(params, CFG)(
        before, jnp.asarray(tokens), jnp.asarray([True, True, False]))
    for s in (0, 1):
        np.testing.assert_allclose(np.asarray(got)[s],
                                   plain[s][1][lengths[s]], **TOL)
        _same_rows(_rows(cache, s, lengths[s] + 1), plain[s], lengths[s] + 1)
    np.testing.assert_array_equal(_rows(cache, 2, MAX_SEQ), idle)
    assert np.asarray(cache["length"]).tolist() == [8, 13, 5]


def slot_chunk(params, plain):
    tokens, logits, _, _ = plain[2]
    start, n = 5, 6                       # a start that is not 0
    cache, got = decoding.make_chunked_prefill(params, CFG)(
        _slot_cache(plain, [0, 0, start]),
        _padded(tokens[start:start + n], 8), n, start, 2)
    np.testing.assert_allclose(np.asarray(got), logits[start + n - 1], **TOL)
    _same_rows(_rows(cache, 2, start + n), plain[2], start + n)
    assert np.asarray(cache["length"]).tolist() == [0, 0, start + n]


def _windows(plain, starts, true_lens, width):
    tokens = np.zeros((SLOTS, width), np.int32)
    for s, (a, n) in enumerate(zip(starts, true_lens)):
        tokens[s, :n] = plain[s][0][a:a + n]
    return jnp.asarray(tokens)


STARTS, TRUE_LENS = [4, 9, 6], [3, 1, 0]  # ragged; slot 2 is not touched


def window_forward(params, plain):
    before = _slot_cache(plain, STARTS)
    idle = _rows(before, 2, MAX_SEQ)
    cache, got = decoding.make_batched_spec_verify(params, CFG)(
        before, _windows(plain, STARTS, TRUE_LENS, 4), TRUE_LENS, STARTS)
    for s in (0, 1):
        a, n = STARTS[s], TRUE_LENS[s]
        np.testing.assert_allclose(np.asarray(got)[s, :n],
                                   plain[s][1][a:a + n], **TOL)
        _same_rows(_rows(cache, s, a + n), plain[s], a + n)
    np.testing.assert_array_equal(_rows(cache, 2, MAX_SEQ), idle)
    assert np.asarray(cache["length"]).tolist() == [7, 10, 6]


def kv_ingest(params, plain):
    """The window forward without a head writes the rows the one with a
    head writes."""
    args = (_windows(plain, STARTS, TRUE_LENS, 4), TRUE_LENS, STARTS)
    want, _ = decoding.make_batched_spec_verify(params, CFG)(
        _slot_cache(plain, STARTS), *args)
    got = decoding.make_kv_ingest(params, CFG)(
        _slot_cache(plain, STARTS), *args)
    for name in ("k", "v", "length"):
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(want[name]))
    _same_rows(_rows(got, 0, 7), plain[0], 7)


def paged_prefill(params, plain):
    tokens, logits, _, _ = plain[1]
    n = 11
    cache, alloc = _paged_cache(plain, [0, 0, 0], [0, n + 1, 0])
    cache, got = paged_cache.make_paged_prefill(params, CFG, PAGE)(
        cache, alloc.tables[1], _padded(tokens[:n], 16), n, 1)
    np.testing.assert_allclose(np.asarray(got), logits[n - 1], **TOL)
    _same_rows(paged_cache.extract_kv(cache, alloc, 1, n), plain[1], n)
    assert np.asarray(cache["length"]).tolist() == [0, n, 0]


def paged_decode(params, plain):
    lengths = [7, 12, 5]                  # slot 2 holds rows and is idle
    cache, alloc = _paged_cache(plain, lengths, [8, 13, 5])
    idle = paged_cache.extract_kv(cache, alloc, 2, 5)
    tokens = np.array([plain[s][0][n] for s, n in enumerate(lengths)])
    cache, got = paged_cache.make_paged_decode_step(params, CFG, PAGE)(
        cache, alloc.device_tables(), jnp.asarray(tokens),
        jnp.asarray([True, True, False]))
    for s in (0, 1):
        np.testing.assert_allclose(np.asarray(got)[s],
                                   plain[s][1][lengths[s]], **TOL)
        _same_rows(paged_cache.extract_kv(cache, alloc, s, lengths[s] + 1),
                   plain[s], lengths[s] + 1)
    np.testing.assert_array_equal(
        paged_cache.extract_kv(cache, alloc, 2, 5), idle)
    assert np.asarray(cache["length"]).tolist() == [8, 13, 5]


def paged_chunk(params, plain):
    tokens, logits, _, _ = plain[2]
    start, n = 6, 7                       # a start inside block 1
    cache, alloc = _paged_cache(plain, [0, 0, start], [0, 0, start + n + 1])
    cache, got = paged_cache.make_chunked_paged_prefill(params, CFG, PAGE)(
        cache, alloc.tables[2], _padded(tokens[start:start + n], 8), n,
        start, 2)
    np.testing.assert_allclose(np.asarray(got), logits[start + n - 1], **TOL)
    _same_rows(paged_cache.extract_kv(cache, alloc, 2, start + n), plain[2],
               start + n)
    assert np.asarray(cache["length"]).tolist() == [0, 0, start + n]


@pytest.mark.parametrize("program", [
    slot_prefill, slot_decode, slot_chunk, window_forward, kv_ingest,
    paged_prefill, paged_decode, paged_chunk], ids=lambda f: f.__name__)
def test_program_matches_plain_forward(program, params, plain):
    program(params, plain)
