"""Kernel correctness vs the naive oracle, on the 8-device CPU mesh."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import (apply_rope, flash_attention, layernorm,
                         mha_reference, ring_attention, rmsnorm,
                         rope_frequencies)
from ray_tpu.parallel import MeshConfig, make_mesh


def _qkv(key, b=2, s=128, hq=4, hkv=2, d=32, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, hq, d), dtype)
    k = jax.random.normal(kk, (b, s, hkv, d), dtype)
    v = jax.random.normal(kv, (b, s, hkv, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hkv", [4, 2])
def test_flash_matches_reference(causal, hkv):
    q, k, v = _qkv(jax.random.PRNGKey(0), hkv=hkv)
    out = flash_attention(q, k, v, causal=causal, block=64)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_grads_match_reference():
    q, k, v = _qkv(jax.random.PRNGKey(1), s=96, hkv=2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, block=32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("mode", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(mode, causal):
    mesh = make_mesh(MeshConfig(fsdp=2, sp=4))
    q, k, v = _qkv(jax.random.PRNGKey(2), b=2, s=64, hq=4, hkv=4, d=16)

    @jax.jit
    def f(q, k, v):
        return ring_attention(q, k, v, mesh, causal=causal, mode=mode,
                              block=16)

    out = f(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_ring_attention_grad():
    mesh = make_mesh(MeshConfig(fsdp=1, dp=1, sp=4, tp=2))
    q, k, v = _qkv(jax.random.PRNGKey(3), b=1, s=32, hq=4, hkv=4, d=16)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True,
                                      block=8) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    g1 = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def _bhsd(key, sq, skv, hq, hkv, d=32):
    kq, kk, kv = jax.random.split(key, 3)
    return (jax.random.normal(kq, (1, hq, sq, d)),
            jax.random.normal(kk, (1, hkv, skv, d)),
            jax.random.normal(kv, (1, hkv, skv, d)))


# (sq, skv, block_q, block_kv): the first is the case the kernel tests began as
FLASH_SHAPES = {
    "tail_q_and_kv": (80, 80, 32, 32),
    "tail_kv_only": (64, 72, 32, 32),
    "one_block": (32, 32, 32, 32),
    "one_block_of_lanes": (80, 80, 512, 512),
    "several_blocks": (128, 128, 32, 32),
    "wide_q": (128, 128, 64, 32),
    "wide_kv": (128, 128, 32, 64),
    "more_keys_than_queries": (32, 96, 32, 32),
}


@pytest.mark.parametrize("shape", FLASH_SHAPES)
@pytest.mark.parametrize("hkv", [1, 2, 4])
@pytest.mark.parametrize("causal", [True, False])
def test_pallas_kernel_interpret_mode(causal, hkv, shape):
    """Validate the TPU kernel logic itself via the pallas interpreter."""
    from ray_tpu.ops.attention import _fwd_xla
    from ray_tpu.ops.pallas.flash_attention import flash_attention_fwd_pallas

    sq, skv, block_q, block_kv = FLASH_SHAPES[shape]
    qt, kt, vt = _bhsd(jax.random.PRNGKey(4), sq, skv, 4, hkv)
    out, lse = flash_attention_fwd_pallas(
        qt, kt, vt, causal=causal, scale=32 ** -0.5, block_q=block_q,
        block_kv=block_kv, interpret=True)
    ref = mha_reference(*(x.transpose(0, 2, 1, 3) for x in (qt, kt, vt)),
                        causal=causal).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    assert lse.shape == (1, 4, sq)
    _, lse_ref = _fwd_xla(qt, kt, vt, causal, 32 ** -0.5)
    np.testing.assert_allclose(lse, lse_ref, atol=1e-5, rtol=1e-5)


def _inner_grid(fn, *args):
    """Inner grid steps a head of the one pallas_call ``fn`` traces."""
    eqns = [e for e in jax.make_jaxpr(fn)(*args).eqns
            if e.primitive.name == "pallas_call"]
    assert len(eqns) == 1
    heads, *inner = eqns[0].params["grid_mapping"].grid
    return int(np.prod(inner))


@pytest.mark.parametrize("causal,steps", [(True, 36), (False, 64)])
def test_pallas_fwd_grid_is_the_live_blocks(causal, steps):
    """8 x 8 blocks: a causal call takes a grid step for the 36 pairs on or
    below the diagonal only, a non-causal one for the whole rectangle."""
    from ray_tpu.ops.pallas.flash_attention import flash_attention_fwd_pallas

    qt, kt, vt = _bhsd(jax.random.PRNGKey(8), 256, 256, 2, 1)
    assert _inner_grid(lambda q, k, v: flash_attention_fwd_pallas(
        q, k, v, causal=causal, scale=1.0, block_q=32, block_kv=32,
        interpret=True), qt, kt, vt) == steps


# (key width, value width, query heads, kv heads): the latent model's
# widths, the window model's under grouped heads, the whole model's grouping
SERVED_WIDTHS = {
    "keys_wider": (192, 128, 4, 4),
    "keys_wider_grouped": (192, 128, 8, 2),
    "equal_grouped": (128, 128, 6, 2),
}
# (prompt length, block)
SERVED_LENGTHS = {
    "one_block": (64, 64),
    "several_blocks": (192, 64),
    "padded_last_block": (168, 64),
}


@pytest.mark.parametrize("length", SERVED_LENGTHS)
@pytest.mark.parametrize("widths", SERVED_WIDTHS)
def test_pallas_fwd_takes_values_narrower_than_keys(widths, length):
    """The forward kernel at a served prompt's shapes against the XLA
    form the prefills ran before: keys ``dk`` and values ``dv`` wide."""
    from ray_tpu.ops.attention import _fwd_xla, hybrid_attention_reference
    from ray_tpu.ops.pallas.flash_attention import flash_attention_fwd_pallas

    dk, dv, h, kv = SERVED_WIDTHS[widths]
    s, block = SERVED_LENGTHS[length]
    kq, kk, kvv = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(kq, (1, s, h, dk))
    k = jax.random.normal(kk, (1, s, kv, dk))
    v = jax.random.normal(kvv, (1, s, kv, dv))
    scale = 1.3 * dk ** -0.5
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out, lse = flash_attention_fwd_pallas(
        qt, kt, vt, causal=True, scale=scale, block_q=block, block_kv=block,
        interpret=True)
    assert out.shape == (1, h, s, dv) and lse.shape == (1, h, s)
    ref = hybrid_attention_reference(q, k, v, scale=scale)
    np.testing.assert_allclose(out.transpose(0, 2, 1, 3), ref, atol=2e-5,
                               rtol=2e-5)
    _, lse_ref = _fwd_xla(qt, kt, vt, True, scale)
    np.testing.assert_allclose(lse, lse_ref, atol=1e-5, rtol=1e-5)


# what a prefill hands the front -> whether the kernel is what is traced
PROMPT_CALLS = {
    "full": (dict(), True),
    "window_not_shorter": (dict(window=64), True),
    "window": (dict(window=16), False),
    "sink": (dict(sink=np.zeros((4,), np.float32)), False),
}


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
@pytest.mark.parametrize("call", PROMPT_CALLS)
def test_prompt_attention_chooses_by_its_arguments(call, backend,
                                                   monkeypatch):
    """A full layer without a sink traces the flash forward on a TPU
    backend; a window, a sink or another backend the XLA reference."""
    from ray_tpu.ops.attention import prompt_attention

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    kwargs, full = PROMPT_CALLS[call]
    q, k, v = _qkv(jax.random.PRNGKey(3), b=1, s=64, hq=4, hkv=2, d=32)
    jaxpr = jax.make_jaxpr(functools.partial(
        prompt_attention, scale=0.2, **kwargs))(q, k, v[..., :16])
    assert jaxpr.out_avals[0].shape == (1, 64, 4, 16)
    kernels = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(kernels) == (1 if full and backend == "tpu" else 0)
    assert all(e.params["name"] == "flash_attention_fwd" for e in kernels)


@pytest.mark.parametrize("shape", FLASH_SHAPES)
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hkv", [2, 1])
def test_pallas_bwd_kernel_interpret_mode(causal, hkv, shape):
    """Backward kernels (dq + fused-GQA dkv) vs autodiff of the oracle."""
    from ray_tpu.ops.attention import _fwd_xla
    from ray_tpu.ops.pallas.flash_attention import flash_attention_bwd_pallas

    sq, skv, block_q, block_kv = FLASH_SHAPES[shape]
    qt, kt, vt = _bhsd(jax.random.PRNGKey(7), sq, skv, 2, hkv)
    scale = 32 ** -0.5

    def loss_ref(qt, kt, vt):
        q, k, v = (x.transpose(0, 2, 1, 3) for x in (qt, kt, vt))
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    refs = jax.grad(loss_ref, argnums=(0, 1, 2))(qt, kt, vt)

    # Oracle forward in (B,H,S,D) layout for out/lse/dout residuals.
    out, lse = _fwd_xla(qt, kt, vt, causal, scale)
    dout = 2.0 * out  # d/dx of sum(out²)
    delta = jnp.sum(dout * out, axis=-1)
    grads = flash_attention_bwd_pallas(
        qt, kt, vt, lse, delta, dout, causal=causal, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=True)
    for got, ref in zip(grads, refs):
        np.testing.assert_allclose(got, ref, atol=3e-4, rtol=3e-4)


def test_rmsnorm_layernorm():
    x = jax.random.normal(jax.random.PRNGKey(5), (4, 16), jnp.bfloat16)
    w = jnp.ones(16) * 0.5
    y = rmsnorm(x, w - 1.0 + 0.5)  # weight centered at 0 (llama style)
    assert y.dtype == jnp.bfloat16
    y32 = rmsnorm(x.astype(jnp.float32), jnp.zeros(16))
    np.testing.assert_allclose(
        np.mean(np.square(np.asarray(y32)), -1), 1.0, rtol=1e-4)
    ln = layernorm(x.astype(jnp.float32), jnp.ones(16), jnp.zeros(16))
    np.testing.assert_allclose(np.mean(np.asarray(ln), -1), 0.0, atol=1e-5)


def test_rope_rotation_preserves_norm_and_relative_phase():
    cos, sin = rope_frequencies(32, 64, theta=10000.0)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 64, 2, 32))
    y = apply_rope(x, cos, sin)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(y), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    # positions arg matches implicit arange
    pos = jnp.arange(64)[None, :]
    y2 = apply_rope(x, cos, sin, positions=pos)
    np.testing.assert_allclose(y, y2, rtol=1e-6)


def test_mesh_and_sharding_rules():
    from ray_tpu.parallel.sharding import FSDP_TP_RULES, logical_spec

    mesh = make_mesh(MeshConfig(fsdp=4, tp=2))
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
        "pp": 1, "dp": 1, "fsdp": 4, "sp": 1, "tp": 2}
    spec = logical_spec(("batch", "seq", "embed"), FSDP_TP_RULES)
    assert spec == jax.sharding.PartitionSpec(("dp", "fsdp"), "sp", None)


class TestDecodeAttentionKernel:
    @pytest.mark.parametrize("group", [1, 2])
    def test_pallas_decode_matches_dense(self, group):
        """Flash-decoding kernel (interpret mode) vs the masked dense
        oracle, including per-slot length masking and GQA groups."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.pallas.decode_attention import decode_attention

        B, S, KV, D = 3, 96, 2, 32
        H = KV * group
        key = jax.random.key(0)
        ks = jax.random.split(key, 4)
        q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
        kc = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
        vc = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
        lengths = jnp.array([1, 40, 96], jnp.int32)
        scale = D ** -0.5

        got = decode_attention(q, kc, vc, lengths, scale=scale,
                               block_s=32, interpret=True)

        qg = q.reshape(B, KV, group, D)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, kc) * scale
        mask = jnp.arange(S)[None, :] < lengths[:, None]
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        want = jnp.einsum("bkgs,bskd->bkgd", p, vc).reshape(B, 1, H, D)
        import numpy as np

        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
