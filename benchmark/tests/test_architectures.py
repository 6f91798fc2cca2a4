"""The adapter of a block (``architectures/<architecture>.py``): every one
that is there keeps the contract of ``benchmark/README.md``, loading one
imports no jax, its counts are the hand-worked ones, and the weights of a
seed are the bits the harness made before the block moved behind it."""

import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from benchmark import model_spec, weights

CONTRACT = (
    "check_config", "num_params", "matrix_params", "train_flops_per_token",
    "kv_bytes_per_token", "kernel_counts", "program_config",
    "weight_shapes", "weight_stds", "engine_kwargs", "serve_program_logits",
    "train_program_loss_and_grads", "lower_serve_programs", "train_setup")
ADAPTERS = sorted(
    os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(model_spec.HERE, "architectures", "*.py")))
MISTRAL = model_spec.load_config("mistral-7b-l16")
DEEPSEEK = model_spec.load_config("deepseek-coder-1.3b")


@pytest.mark.parametrize("architecture", ADAPTERS)
def test_every_adapter_keeps_the_contract(architecture):
    arch = model_spec.adapter({"architecture": architecture})
    missing = [n for n in CONTRACT if not callable(getattr(arch, n, None))]
    assert not missing


def test_reading_a_configuration_and_its_adapter_imports_no_jax():
    code = ("import sys; from benchmark import model_spec; "
            "spec = model_spec.load_config('mistral-7b-l16'); "
            "arch = model_spec.adapter(spec); "
            "arch.engine_kwargs and model_spec.num_params(spec); "
            "assert 'jax' not in sys.modules, 'jax was imported'")
    proc = subprocess.run([sys.executable, "-c", code],
                          cwd=os.path.dirname(model_spec.HERE),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_a_configuration_without_a_key_of_its_block_is_refused():
    spec = {k: v for k, v in MISTRAL.items() if k != "head_dim"}
    with pytest.raises(SystemExit, match="head_dim"):
        model_spec.adapter(spec).check_config(spec)


def test_kernel_counts_by_the_kernels_instruction_names():
    tri = 6 * 16 * 4096 * 4096 * 128
    sizes = dict(batch=6, seq=4096)
    assert [model_spec.kernel_counts(DEEPSEEK, k, **sizes) for k in (
        "flash_attention_fwd", "flash_attention_dq", "flash_attention_dkv")
    ] == [{"flops": 2 * tri}, {"flops": 3 * tri}, {"flops": 4 * tri}]
    assert model_spec.kernel_counts(
        MISTRAL, "paged_decode_attention", live_tokens=10_000, slots=32) == {
        "bytes": 10_000 * 4096 + 2 * 32 * 4096 * 2}
    with pytest.raises(KeyError, match="no kernel named"):
        model_spec.kernel_counts(MISTRAL, "ragged_prefill")


TINY = dict(architecture="dense_decoder", hidden_size=64,
            intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=256, tie_word_embeddings=False)


@pytest.mark.parametrize("seed", (1, 3_000_000_000))
def test_the_dense_weights_of_a_seed_are_the_bits_they_were(seed):
    """The tree and the table of standard deviations as
    ``weights.init_fn`` held them before they moved into the adapter:
    the key is split by the flattened order of the tree, so a leaf
    renamed, added or moved would change every later leaf's draw."""
    L, h, m, H, KV, D, V = 2, 64, 128, 4, 2, 16, 256
    tree = {"embed": (V, h), "final_norm": (h,), "lm_head": (h, V),
            "layers": {
                "attn_norm": (L, h), "wq": (L, h, H, D), "wk": (L, h, KV, D),
                "wv": (L, h, KV, D), "wo": (L, H, D, h), "mlp_norm": (L, h),
                "w_gate": (L, h, m), "w_up": (L, h, m), "w_down": (L, m, h)}}
    std = h ** -0.5
    stds = {"attn_norm": 0.1, "mlp_norm": 0.1, "final_norm": 0.1,
            "wo": std / (2 * L) ** 0.5, "w_down": std / (2 * L) ** 0.5}
    leaves, treedef = jax.tree.flatten_with_path(
        tree, is_leaf=lambda t: isinstance(t, tuple))
    keys = jax.random.split(weights.seed_key(seed), len(leaves))
    want = jax.tree.unflatten(treedef, [
        jax.random.normal(k, shape, jnp.bfloat16)
        * jnp.bfloat16(stds.get(path[-1].key, std))
        for k, (path, shape) in zip(keys, leaves)])
    got = weights.make(TINY, seed)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == jnp.bfloat16 and bool(jnp.array_equal(a, b))
