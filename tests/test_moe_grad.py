"""The backward of the expert layer held by share (PR 57):
``grouped_product``'s VJP and its ``dw`` kernel, the combine's transpose,
the rows' gather's transpose, and ``experts_by_share`` under ``jax.grad``
against a plain loop over the experts, at held = all and at a share, with
the XLA forms and with every kernel interpreted."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.ops.pallas import expert_combine, grouped_matmul as gm

F32 = jnp.float32


def _tiles(sizes, tm, spare=2, seed=0, K=32, poison=np.nan):
    """A row buffer in tiles of ``tm``: group g gets ``sizes[g]`` rows
    (zeros pad its last tile), ``spare`` tiles past ``n_active`` hold
    ``poison``. -> lhs (M, K) f32, tile_group, n_active, live rows."""
    rng = np.random.default_rng(seed)
    rows, groups, live = [], [], []
    for g, n in enumerate(sizes):
        for t in range(-(-n // tm)):
            fill = min(tm, n - t * tm)
            tile = np.zeros((tm, K), np.float32)
            tile[:fill] = rng.standard_normal((fill, K))
            rows.append(tile)
            groups.append(g)
            live.append(np.arange(tm) < fill)
    n_active = len(groups)
    for _ in range(spare):
        rows.append(np.full((tm, K), poison, np.float32))
        groups.append(groups[-1] if groups else 0)
        live.append(np.zeros(tm, bool))
    return (jnp.asarray(np.concatenate(rows)),
            jnp.asarray(groups, jnp.int32), jnp.int32(n_active),
            np.concatenate(live))


RAGGED = {
    "an-expert-with-no-row": ([0, 37, 16, 5, 0], 16),
    "rows-no-multiple-of-the-tile": ([130, 1, 127], 128),
    "one-group": ([40], 16),
    "a-train-steps-tile": ([256, 0, 129], 128),
}


@pytest.mark.parametrize("case", RAGGED, ids=list(RAGGED))
def test_the_dw_kernel_interpreted_is_its_oracle(case):
    sizes, tm = RAGGED[case]
    lhs, tile_group, n_active, _ = _tiles(sizes, tm, K=128)
    dout = jnp.asarray(np.random.default_rng(1).standard_normal(
        (lhs.shape[0], 256)), F32)
    dout = jnp.where(jnp.isnan(lhs[:, :1]), jnp.nan, dout)   # garbage too
    kw = dict(tm=tm, groups=len(sizes), out_dtype=F32)
    want = gm.grouped_matmul_dw_reference(lhs, dout, tile_group, n_active,
                                          **kw)
    got = gm.grouped_matmul_dw(lhs, dout, tile_group, n_active,
                               interpret=True, **kw)
    named = np.asarray(sizes) > 0
    assert np.isfinite(np.asarray(want)).all()
    np.testing.assert_allclose(np.asarray(got)[named],
                               np.asarray(want)[named], rtol=1e-5, atol=1e-4)
    assert not np.asarray(want)[~named].any()       # the oracle: zeros
    # by hand, group by group
    rows = np.cumsum([0] + [-(-n // tm) * tm for n in sizes])
    for g in np.flatnonzero(named):
        a = np.asarray(lhs)[rows[g]:rows[g + 1]]
        b = np.asarray(dout)[rows[g]:rows[g + 1]]
        np.testing.assert_allclose(np.asarray(want)[g], a.T @ b, rtol=1e-4,
                                   atol=1e-4)


def _plain_product(lhs, rhs, tile_group, n_active, tm):
    """The forward's oracle with NO backward of its own."""
    M, K = lhs.shape
    out = jnp.einsum("itk,ikn->itn", lhs.reshape(M // tm, tm, K),
                     rhs[tile_group], preferred_element_type=F32)
    live = jnp.arange(M // tm) < n_active
    return jnp.where(live[:, None, None], out, 0.0).reshape(M, -1)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
@pytest.mark.parametrize("case", RAGGED, ids=list(RAGGED))
def test_grouped_products_vjp_is_the_gradient_of_its_oracle(
        case, kernels, request):
    if kernels:
        request.getfixturevalue("kernel_on_cpu")
    sizes, tm = RAGGED[case]
    # finite garbage: jax.grad of the oracle multiplies it by zero
    lhs, tile_group, n_active, live = _tiles(sizes, tm, K=128, poison=1e3)
    rng = np.random.default_rng(2)
    rhs = jnp.asarray(rng.standard_normal((len(sizes), 128, 256)), F32) / 8
    cot = jnp.asarray(rng.standard_normal((lhs.shape[0], 256)), F32)
    active = np.repeat(np.arange(len(tile_group)) < int(n_active), tm)

    def loss(product, lhs, rhs):
        return jnp.sum(product(lhs, rhs, tile_group, n_active, tm)
                       * jnp.where(active[:, None], cot, 0.0))

    want = jax.grad(functools.partial(loss, _plain_product), (0, 1))(lhs, rhs)
    got = jax.grad(functools.partial(
        loss, lambda *a: gm.grouped_product(*a, F32)), (0, 1))(lhs, rhs)
    # rows of tiles past n_active carry no gradient that is read
    np.testing.assert_allclose(np.asarray(got[0])[active],
                               np.asarray(want[0])[active], rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=1e-4, atol=1e-3)
    assert not np.asarray(got[1])[np.asarray(sizes) == 0].any()


def test_garbage_past_n_active_reaches_no_gradient(kernel_on_cpu):
    """NaN in the rows of tiles past ``n_active``, forward AND in the
    cotangent: the matrices' gradient stays finite and the live rows'."""
    sizes, tm = RAGGED["an-expert-with-no-row"]
    lhs, tile_group, n_active, _ = _tiles(sizes, tm, K=128)
    clean = jnp.nan_to_num(lhs)
    rhs = jnp.ones((len(sizes), 128, 128), F32) / 16
    cot = jnp.where(jnp.isnan(lhs[:, :1]), jnp.nan, 1.0) * jnp.ones(
        (lhs.shape[0], 128), F32)

    def drhs(lhs, cot):
        _, vjp = jax.vjp(lambda r: gm.grouped_product(
            lhs, r, tile_group, n_active, tm, F32), rhs)
        return vjp(cot)[0]

    got = drhs(lhs, cot)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(drhs(clean, jnp.nan_to_num(cot))),
                               rtol=1e-6)


def _combine_case(T=24, k=3, h=128, M=96, share=0.4, seed=0):
    rng = np.random.default_rng(seed)
    placed = rng.random((T, k)) < share
    rows = rng.permutation(M)[:T * k].reshape(T, k)
    row_pair = np.where(placed, rows, M).astype(np.int32)
    y_rows = rng.standard_normal((M, h)).astype(np.float32)
    named = np.zeros(M, bool)
    named[rows[placed]] = True
    y_rows[~named] = np.nan                  # never written: anything
    w = rng.random((T, k)).astype(np.float32)
    return (jnp.asarray(y_rows), jnp.asarray(row_pair), jnp.asarray(placed),
            jnp.asarray(w), named)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_the_down_product_and_the_combine_share_one_backward(kernels,
                                                             request):
    """``moe._down_and_combine`` against ``jax.grad`` of the two oracles
    one after the other (float32, so the rounding of ``dy`` to the
    rows' dtype is none): the rows', the matrices' and the weights'
    gradients; pairs that are not placed get zeros."""
    if kernels:
        request.getfixturevalue("kernel_on_cpu")
    T, k, G, tm, m, h = 24, 3, 3, 16, 128, 128
    rng = np.random.default_rng(8)
    held = rng.random((T, k)) < 0.5
    key = np.where(held, rng.integers(0, G, (T, k)), G).reshape(-1)
    order = np.argsort(key, kind="stable")
    sizes = np.bincount(key, minlength=G + 1)[:G]
    padded = -(-sizes // tm) * tm
    starts = np.cumsum(padded) - padded
    M = (T * k + G * (tm - 1)) // tm * tm + tm
    row_sorted, tile_group = [], []
    for g in range(G):
        row_sorted += list(starts[g] + np.arange(sizes[g]))
        tile_group += [g] * (padded[g] // tm)
    n_active = len(tile_group)
    tile_group += [tile_group[-1]] * (M // tm - n_active)
    row_sorted += [M] * (T * k - len(row_sorted))
    act = rng.standard_normal((M, m)).astype(np.float32)
    act[n_active * tm:] = 1e3                       # never read
    ints = (jnp.asarray(tile_group, jnp.int32), jnp.int32(n_active),
            jnp.asarray(order), jnp.asarray(row_sorted), jnp.asarray(held))
    down = jnp.asarray(rng.standard_normal((G, m, h)), F32) / 8
    w = jnp.asarray(rng.random((T, k)), F32)
    cot = jnp.asarray(rng.standard_normal((T, h)), F32)

    def plain(act, down, w):
        y_rows = _plain_product(act, down, ints[0], ints[1], tm)
        row_pair = jnp.zeros(T * k, jnp.int32).at[ints[2]].set(
            ints[3].astype(jnp.int32)).reshape(T, k)
        return expert_combine.expert_combine_reference(
            y_rows, row_pair, held & (row_pair < M), w)

    def fused(act, down, w):
        y, placed = moe._down_and_combine(act, down, *ints, w, tm,
                                          gm.NAME, 4 * G)
        assert placed.shape == (T, k)
        return y

    args = (jnp.asarray(act), down, w)
    np.testing.assert_allclose(np.asarray(fused(*args)),
                               np.asarray(plain(*args)), rtol=1e-5,
                               atol=1e-5)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * cot), (0, 1, 2))(*args)
    got = jax.grad(lambda *a: jnp.sum(fused(*a) * cot), (0, 1, 2))(*args)
    live = np.repeat(np.arange(M // tm) < n_active, tm)
    np.testing.assert_allclose(np.asarray(got[0])[live],
                               np.asarray(want[0])[live], rtol=1e-4,
                               atol=1e-4)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-4)
    assert not np.asarray(got[2])[~held].any()


def test_a_long_call_of_the_combine_kernel_runs_in_runs_of_tokens(
        monkeypatch):
    """More pairs than a call prefetches into SMEM (a train step's
    tokens): the same rows, run after run."""
    monkeypatch.setattr(expert_combine, "_CALL_PAIRS", 48)   # 16 tokens of 3
    y_rows, row_pair, placed, w, _ = _combine_case(T=40, M=128)
    want = expert_combine.expert_combine_reference(
        jnp.nan_to_num(y_rows), row_pair, placed, w)
    got = expert_combine.expert_combine(y_rows, row_pair, placed, w,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


def test_the_rows_gathers_transpose_is_a_gather():
    """The dispatch's own integers, by hand: 10 tokens, top-3, the pairs
    of experts 0-1 held (sorted first), rows in tiles of 8."""
    T, k, M, h = 10, 3, 48, 8
    rng = np.random.default_rng(3)
    held = rng.random((T, k)) < 0.5
    key = np.where(held, rng.integers(0, 2, (T, k)), 2).reshape(-1)
    order = np.argsort(key, kind="stable")
    n0, n1 = (key == 0).sum(), (key == 1).sum()
    pad0 = -(-n0 // 8) * 8
    row_sorted = np.concatenate([
        np.arange(n0), pad0 + np.arange(n1), np.full(T * k - n0 - n1, M)])
    token_of_row = np.full(M, T, np.int32)
    token_of_row[row_sorted[:n0 + n1]] = order[:n0 + n1] // k
    x = jnp.asarray(rng.standard_normal((T, h)), F32)
    cot = jnp.asarray(rng.standard_normal((M, h)), F32)
    tor = jnp.asarray(token_of_row)
    ints = (tor, jnp.asarray(order), jnp.asarray(row_sorted),
            jnp.asarray(held))

    def plain(x):
        return jnp.concatenate([x, jnp.zeros((1, h), F32)])[tor]

    want = jax.grad(lambda x: jnp.sum(plain(x) * cot))(x)
    got = jax.grad(lambda x: jnp.sum(
        moe._rows_of_tokens(x, *ints) * cot))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    assert np.array_equal(moe._rows_of_tokens(x, *ints), plain(x))


# ------------------------------------------------------------ the whole layer
def _layer(seed=0, E=8, h=32, m=48, held=(0, 8), bias=0.01):
    ks = jax.random.split(jax.random.key(seed), 5)
    G = held[1]
    return {"router": jax.random.normal(ks[0], (h, E), F32) * h ** -0.5,
            "router_bias": jax.random.normal(ks[1], (E,), F32) * bias,
            "we_gate": jax.random.normal(ks[2], (E, h, m), F32)[
                held[0]:held[0] + G] * h ** -0.5,
            "we_up": jax.random.normal(ks[3], (E, h, m), F32)[
                held[0]:held[0] + G] * h ** -0.5,
            "we_down": jax.random.normal(ks[4], (E, m, h), F32)[
                held[0]:held[0] + G] * m ** -0.5}


def _loop(x, layer, held, top_k, eps=1e-6):
    """The layer as a plain loop over the experts held, the weights a
    mask: what ``benchmark/reference/lfm2.py`` computes."""
    s = jax.nn.sigmoid(x @ layer["router"])
    _, idx = jax.lax.top_k(s + layer["router_bias"][None], top_k)
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(x.shape[0])[:, None], idx].set(True)
    w = jnp.where(chosen, s, 0.0)
    w = w / (jnp.sum(w, -1, keepdims=True) + eps)
    out = jnp.zeros_like(x)
    for e in range(held[1]):
        y = (jax.nn.silu(x @ layer["we_gate"][e]) * (x @ layer["we_up"][e])
             ) @ layer["we_down"][e]
        out = out + w[:, held[0] + e, None] * y
    return out


def _share(x, layer, held, top_k):
    return moe.experts_by_share(x, layer, experts_held=held, top_k=top_k,
                                norm_eps=1e-6)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
@pytest.mark.parametrize("held", [(0, 8), (2, 2), (6, 2)],
                         ids=["all", "share-2-3", "share-6-7"])
def test_experts_by_share_under_grad_is_the_loops(held, kernels, request):
    """Every leaf's gradient, and the input's, against ``jax.grad`` of
    the loop: float32, so what differs is the order of sums."""
    if kernels:
        request.getfixturevalue("kernel_on_cpu")
    layer = _layer(held=held)
    x = jax.random.normal(jax.random.key(9), (40, 32), F32)
    cot = jax.random.normal(jax.random.key(10), (40, 32), F32)

    def loss(f, x, layer):
        out = f(x, layer, held, 2)
        return jnp.sum((out[0] if isinstance(out, tuple) else out) * cot)

    with jax.default_matmul_precision("highest"):
        want = jax.grad(functools.partial(loss, _loop), (0, 1))(x, layer)
        got = jax.grad(functools.partial(loss, _share), (0, 1))(x, layer)
        y, counters = _share(x, layer, held, 2)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(_loop(x, layer, held, 2)),
                                   rtol=1e-4, atol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=jax.tree_util.keystr(
                                       path))
    assert float(counters[4]) == 0.0                # pairs_dropped
    assert not np.asarray(got[1]["router_bias"]).any()
    assert np.asarray(got[1]["router"]).any()


def test_the_four_shares_add_up_forward_and_backward():
    """8 experts, top-2, four chips of 2: the shares' outputs sum to the
    uncut layer's, and so do the shares' gradients of the router and of
    the input (each expert's matrices lie in one share: equal there)."""
    whole = _layer()
    x = jax.random.normal(jax.random.key(4), (48, 32), F32)
    cot = jax.random.normal(jax.random.key(5), (48, 32), F32)

    def run(held):
        layer = {k: (v[held[0]:held[0] + held[1]] if k.startswith("we_")
                     else v) for k, v in whole.items()}
        f = lambda x, layer: jnp.sum(_share(x, layer, held, 2)[0] * cot)
        y = _share(x, layer, held, 2)[0]
        return y, jax.grad(f, (0, 1))(x, layer)

    with jax.default_matmul_precision("highest"):
        y_all, (dx_all, g_all) = run((0, 8))
        parts = [run((first, 2)) for first in (0, 2, 4, 6)]
    np.testing.assert_allclose(
        np.asarray(sum(y for y, _ in parts)), np.asarray(y_all), rtol=1e-4,
        atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(sum(g["router"] for _, (_, g) in parts)),
        np.asarray(g_all["router"]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(sum(dx for _, (dx, _) in parts)), np.asarray(dx_all),
        rtol=1e-4, atol=1e-5)
    for i, (_, (_, g)) in enumerate(parts):
        np.testing.assert_allclose(
            np.asarray(g["we_down"]),
            np.asarray(g_all["we_down"])[2 * i:2 * i + 2], rtol=1e-4,
            atol=1e-5)


def test_nothing_is_dropped_when_every_token_chooses_one_expert():
    """A bias that puts expert 3 into every token's choice: 64 rows for
    one expert of 8 where 16 are expected. No pair is dropped, the
    output is the loop's, the bias moved the choice and got no gradient,
    the router got one through the weights."""
    layer = _layer()
    x = jax.random.normal(jax.random.key(6), (64, 32), F32)
    tilted = dict(layer, router_bias=layer["router_bias"].at[3].set(10.0))
    with jax.default_matmul_precision("highest"):
        y, counters = _share(x, tilted, (0, 8), 2)
        y_flat, flat = _share(x, layer, (0, 8), 2)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(_loop(x, tilted, (0, 8), 2)),
            rtol=1e-4, atol=1e-5)
        g = jax.grad(lambda l: jnp.sum(_share(x, l, (0, 8), 2)[0] ** 2))(
            tilted)
    named = dict(zip(moe.COUNTERS, np.asarray(counters)))
    assert named["expert_pairs_dropped"] == 0.0
    assert named["expert_pairs"] == 128.0
    assert named["expert_load_max_over_mean"] >= 4.0     # 64 of 128 on one
    assert float(flat[3]) < named["expert_load_max_over_mean"]
    assert not np.allclose(np.asarray(y), np.asarray(y_flat))
    assert not np.asarray(g["router_bias"]).any()
    assert np.asarray(g["router"]).any()


def test_the_routers_eps_is_an_argument_that_defaults_to_nothing():
    layer = _layer()
    x = jax.random.normal(jax.random.key(7), (16, 32), F32)
    idx0, w0 = moe.route_sigmoid_topk(x, layer["router"],
                                      layer["router_bias"], 2)
    idx1, w1 = moe.route_sigmoid_topk(x, layer["router"],
                                      layer["router_bias"], 2, norm_eps=0.5)
    assert np.array_equal(idx0, idx1)
    np.testing.assert_allclose(np.asarray(w0).sum(-1), 1.0, rtol=1e-6)
    s = np.take_along_axis(np.asarray(jax.nn.sigmoid(x @ layer["router"])),
                           np.asarray(idx0), -1)
    np.testing.assert_allclose(np.asarray(w1), s / (s.sum(-1, keepdims=True)
                                                    + 0.5), rtol=1e-5)
    text = jax.jit(lambda x: moe.route_sigmoid_topk(
        x, layer["router"], layer["router_bias"], 2)).lower(x).as_text()
    assert "5.000000e-01" not in text and text == jax.jit(
        lambda x: moe.route_sigmoid_topk(
            x, layer["router"], layer["router_bias"], 2, 1.0, 1, 1, 0.0)
    ).lower(x).as_text()


# ------------------------------------------- the bounded row buffer (PR 58)
# A share-held layer's buffer holds twice the rows the chip expects
# (``moe.pass_rows``) where ``moe.bound_serves``; at these tiny shapes the
# threshold is steered, 0 engaging the bound wherever a share is held.

def _bounded(monkeypatch, on: bool):
    monkeypatch.setattr(moe, "_DEAD_BYTES", 0 if on else 1 << 62)


def _value_and_grads(x, layer, held, cot, checkpoint=True):
    """(y, counters, (dx, every leaf's gradient)) as a train step takes
    them: under ``jax.checkpoint(nothing_saveable)`` and ``jax.grad``."""
    def loss(x, layer):
        y, counters = _share(x, layer, held, 2)
        return jnp.sum(y * cot), (y, counters)

    if checkpoint:
        loss = jax.checkpoint(
            loss, policy=jax.checkpoint_policies.nothing_saveable)
    with jax.default_matmul_precision("highest"):
        (_, (y, counters)), grads = jax.jit(jax.value_and_grad(
            loss, (0, 1), has_aux=True))(x, layer)
    return y, dict(zip(moe.COUNTERS, np.asarray(counters))), grads


def _every_pair_held(layer, held):
    """A bias that puts the two held experts into every token's top-2."""
    return dict(layer, router_bias=layer["router_bias"].at[
        held[0]:held[0] + held[1]].set(10.0))


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
def test_at_the_expected_load_the_bounded_layer_is_the_unbounded_bit_for_bit(
        kernels, request, monkeypatch):
    """2 of 16 experts held, 250 tokens, top-2: 75 pairs land here where
    63 are expected and 188 (+ padding: 192 rows) are provided for. One
    pass; ``y``, the counters and every gradient EQUAL what the buffer of
    576 rows gives."""
    if kernels:
        request.getfixturevalue("kernel_on_cpu")
    held, T = (2, 2), 250
    assert moe.pass_rows(T, 2, 16, 2, moe.row_tile(T, 2, 16, 2)) == 192
    layer = _layer(held=held, E=16)
    x = jax.random.normal(jax.random.key(9), (T, 32), F32)
    cot = jax.random.normal(jax.random.key(10), (T, 32), F32)
    _bounded(monkeypatch, False)
    y0, c0, g0 = _value_and_grads(x, layer, held, cot)
    _bounded(monkeypatch, True)
    y1, c1, g1 = _value_and_grads(x, layer, held, cot)
    assert c1 == c0 and c1["expert_extra_passes"] == 0
    assert 0 < c1["expert_pairs"] <= 192 - 2 * 31
    assert np.array_equal(y0, y1)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g0),
                            jax.tree.leaves(g1)):
        assert np.array_equal(a, b), jax.tree_util.keystr(path)


# (router's width, tokens) -> passes: every token's two pairs on the two
# held experts, each expert's rows padded to its tile
WORST = {"two-passes": (8, 250, 2), "three-passes": (16, 250, 3),
         "a-last-pass-partly-live": (16, 100, 3)}


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "kernels"])
@pytest.mark.parametrize("case", WORST, ids=list(WORST))
def test_rows_past_the_bound_take_further_passes_and_none_is_dropped(
        case, kernels, request, monkeypatch):
    """The worst case: every pair is placed here. The passes number
    ``ceil(pend[-1] / M_b)``, nothing is dropped, and ``y`` and the
    gradients of ``x``, the router and the three expert matrices are the
    unbounded layer's and the loop's to float32 summation order."""
    if kernels:
        request.getfixturevalue("kernel_on_cpu")
    E, T, passes = WORST[case]
    held = (2, 2)
    layer = _every_pair_held(_layer(held=held, E=E), held)
    x = jax.random.normal(jax.random.key(11), (T, 32), F32)
    cot = jax.random.normal(jax.random.key(12), (T, 32), F32)
    tm = moe.row_tile(T, 2, E, 2)
    rows = moe.pass_rows(T, 2, E, 2, tm)
    pend = 2 * -(-T // tm) * tm
    assert -(-pend // rows) == passes
    _bounded(monkeypatch, False)
    y0, c0, g0 = _value_and_grads(x, layer, held, cot)
    _bounded(monkeypatch, True)
    y1, c1, g1 = _value_and_grads(x, layer, held, cot)
    assert c0["expert_extra_passes"] == 0
    assert c1["expert_extra_passes"] == passes - 1
    assert c1["expert_pairs"] == 2 * T and c1["expert_pairs_dropped"] == 0
    assert {**c1, "expert_extra_passes": 0.0} == c0
    with jax.default_matmul_precision("highest"):
        want = _loop(x, layer, held, 2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=1e-5,
                               atol=1e-6)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g1),
                            jax.tree.leaves(g0)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5,
            err_msg=jax.tree_util.keystr(path))
    assert np.asarray(g1[1]["router"]).any() and np.asarray(g1[0]).any()
    assert not np.asarray(g1[1]["router_bias"]).any()


def test_the_further_passes_gradient_is_jax_grads_without_a_checkpoint(
        monkeypatch):
    """The same step with no ``jax.checkpoint`` around the layer: the
    further passes' own backward does not lean on the recomputation."""
    held, (E, T, _) = (2, 2), WORST["three-passes"]
    layer = _every_pair_held(_layer(held=held, E=E), held)
    x = jax.random.normal(jax.random.key(11), (T, 32), F32)
    cot = jax.random.normal(jax.random.key(12), (T, 32), F32)
    _bounded(monkeypatch, True)
    _, c1, g1 = _value_and_grads(x, layer, held, cot)
    _, c2, g2 = _value_and_grads(x, layer, held, cot, checkpoint=False)
    assert c1 == c2 and c1["expert_extra_passes"] == 2
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)


@pytest.mark.parametrize("case", ["one-pass", "three-passes"])
def test_garbage_past_a_passes_n_active_reaches_neither_y_nor_a_gradient(
        case, monkeypatch):
    """A product that leaves NaN in the rows of every tile at or past
    ``n_active`` (the kernel never writes them: anything may lie there),
    forward and in both transposes: the bounded layer's output and
    gradients stay what the clean product gives, in the first pass (most
    of whose buffer is past ``n_active`` at the expected load) and in a
    last pass of which four tiles of five are live."""
    held = (2, 2)
    E, T, _ = WORST["a-last-pass-partly-live"]
    layer = _layer(held=held, E=E)
    if case == "three-passes":
        layer = _every_pair_held(layer, held)
    x = jax.random.normal(jax.random.key(13), (T, 32), F32)
    cot = jax.random.normal(jax.random.key(14), (T, 32), F32)
    _bounded(monkeypatch, True)
    y0, c0, g0 = _value_and_grads(x, layer, held, cot)
    clean, traced = gm.grouped_matmul_reference, []

    def poisoned(lhs, rhs, tile_group, n_active, *, tm, **kw):
        traced.append(lhs.shape)
        out = clean(lhs, rhs, tile_group, n_active, tm=tm, **kw)
        dead = jnp.repeat(jnp.arange(lhs.shape[0] // tm) >= n_active, tm)
        return jnp.where(dead[:, None], jnp.nan, out)

    monkeypatch.setattr(gm, "grouped_matmul_reference", poisoned)
    y1, c1, g1 = _value_and_grads(x, layer, held, cot)
    assert c1 == c0 and len(traced) >= 6 and {s[0] for s in traced} == {80}
    assert c1["expert_extra_passes"] == (2 if case == "three-passes" else 0)
    assert np.isfinite(np.asarray(y1)).all() and np.array_equal(y0, y1)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g1),
                            jax.tree.leaves(g0)):
        assert np.array_equal(a, b), jax.tree_util.keystr(path)
