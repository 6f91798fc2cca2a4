"""The system against the plain reference at a tiny size on the CPU, and
the control (the reference computed in int8) that must NOT pass. The
same comparison runs on the chip at the published widths in every
benchmark run; ``benchmark/control.py`` runs the control there."""

import pytest

from benchmark import checks, model_spec, weights

TINY = dict(architecture="dense_decoder",
            reference="benchmark/reference/dense_decoder.py",
            hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=256, max_position_embeddings=1024, rope_theta=1e4,
            rms_norm_eps=1e-5, tie_word_embeddings=False)
# at this size, on the CPU: the bf16 program reads 0.008-0.014, the int8
# control 0.027-0.032 (logits) and 0.041 (gradients)
LIMIT = 0.02
SEEDS = (1, 3_000_000_000)
DEPLOYMENT = dict(num_slots=3, max_seq=512, kv_block_size=64)
ref = model_spec.reference(TINY)


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_programs_agree_with_the_reference(seed):
    params = weights.make(TINY, seed)
    got = checks.serve_check(params, TINY, seed, DEPLOYMENT)
    assert all(v["value"] < LIMIT for v in got.values()), got


@pytest.mark.parametrize("seed", SEEDS)
def test_int8_control_fails_the_serve_comparison(seed):
    params = weights.make(TINY, seed)
    toks = checks.sample_tokens(TINY, seed, 264)
    want = checks.serve_reference_logits(params, TINY, toks)
    lower = checks.serve_reference_logits(params, TINY, toks, quant="int8")
    assert ref.rel_err(lower[0], want[0]) > LIMIT
    assert ref.rel_err(lower[1:], want[1:]) > LIMIT


@pytest.mark.parametrize("seed", SEEDS)
def test_train_gradients_agree_and_the_control_does_not(seed):
    params = weights.make(TINY, seed)
    got = checks.train_check(params, TINY, seed, 128)
    assert got["train_tail_grad_rel_err"]["value"] < LIMIT, got
    lower = checks.train_check(params, TINY, seed, 128, quant="int8")
    assert lower["train_tail_grad_rel_err"]["value"] > LIMIT, lower


def test_a_dropped_norm_gain_shows():
    """The reference scales by ``1 + w``: weights drawn at 0.1 make a
    program that forgot the gain miss by far more than rounding."""
    params = weights.make(TINY, 5)
    toks = checks.sample_tokens(TINY, 5, 264)
    want = checks.serve_reference_logits(params, TINY, toks)
    flat = dict(params, final_norm=params["final_norm"] * 0)
    assert ref.rel_err(checks.serve_reference_logits(flat, TINY, toks),
                       want) > 0.05
