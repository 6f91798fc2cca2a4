"""Parameter, operation and byte counts against hand-worked figures."""

from benchmark import model_spec

MISTRAL = model_spec.load_config("mistral-7b-l16")
DEEPSEEK = model_spec.load_config("deepseek-coder-1.3b")


def test_published_widths_are_unchanged():
    for spec in (MISTRAL, DEEPSEEK):
        changed = {k for k, v in spec["published"].items()
                   if k in spec and spec[k] != v}
        assert changed == set(spec["reduced"])
    assert MISTRAL["num_hidden_layers"] == 16
    assert MISTRAL["published"]["num_hidden_layers"] == 32


def test_mistral_parameters_by_hand():
    # per layer: q 4096*4096, k and v 4096*1024 each, o 4096*4096,
    # three 4096*14336 MLP matrices, two norms of 4096
    per_layer = 16_777_216 + 2 * 4_194_304 + 16_777_216 + 176_160_768 + 8192
    assert per_layer == 218_112_000
    full = 2 * 32768 * 4096 + 32 * per_layer + 4096
    assert full == 7_248_023_552                       # "7.25 B"
    assert model_spec.num_params(MISTRAL, layers=32) == full
    assert model_spec.num_params(MISTRAL) == (
        2 * 32768 * 4096 + 16 * per_layer + 4096)      # 3.76 B as run
    assert round(model_spec.num_params(MISTRAL) / 1e9, 2) == 3.76


def test_deepseek_parameters_by_hand():
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5504 + 4096
    assert per_layer == 50_597_888
    full = 2 * 32256 * 2048 + 24 * per_layer + 2048
    assert full == 1_346_471_936                       # "1.35 B"
    assert model_spec.num_params(DEEPSEEK) == full


def test_train_operations_per_token_by_hand():
    # 6 x matrix parameters (no embedding gather, no recomputation)
    # + 6 x layers x sequence x (heads x head size) of causal attention
    assert model_spec.train_flops_per_token(DEEPSEEK, 4096) == (
        6 * (24 * 50_593_792 + 66_060_288) + 6 * 24 * 4096 * 2048)
    assert round(model_spec.train_flops_per_token(DEEPSEEK, 4096) / 1e9,
                 2) == 8.89
    assert round(model_spec.train_flops_per_token(MISTRAL, 4096) / 1e9,
                 2) == 23.35


def test_kernel_operations_and_bytes_by_hand():
    # one causal matmul over half the 4096 x 4096 square, 16 heads of 128
    tri = 6 * 16 * 4096 * 4096 * 128
    assert model_spec.flash_flops(DEEPSEEK, 6, 4096) == {
        "fwd": 2 * tri, "bwd_dq": 3 * tri, "bwd_dkv": 4 * tri}
    # a cached token: keys and values, 8 heads of 128, bf16, one layer
    assert model_spec.kv_bytes_per_token(MISTRAL) == 4096
    assert model_spec.paged_decode_bytes(MISTRAL, 10_000, 32) == (
        10_000 * 4096 + 2 * 32 * 4096 * 2)


def test_program_fields_are_the_published_keys():
    kw = model_spec.program_kwargs(MISTRAL)
    assert kw == {"vocab_size": 32768, "hidden": 4096, "n_layers": 16,
                  "n_heads": 32, "n_kv_heads": 8, "head_dim": 128,
                  "mlp_dim": 14336, "max_seq": 32768,
                  "rope_theta": 1000000.0, "norm_eps": 1e-05,
                  "tie_embeddings": False}
