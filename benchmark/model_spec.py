"""Sizes, parameter counts and required operations of a configuration.

Pure Python (no jax): the driver process reads it, the worker that holds
the chip reads it, the tests read it. Everything is computed from the
published keys of ``benchmark/configs/<config>.json``; nothing is asked
of the program (its ``LlamaConfig.flops_per_token`` counts the embedding
gather as a matrix multiply, which it is not).
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

# published key -> field of the program's LlamaConfig. Only these are set;
# every other field keeps the program's default, so a later PR that
# changes a default is measured.
PROGRAM_FIELDS = {
    "vocab_size": "vocab_size",
    "hidden_size": "hidden",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "mlp_dim",
    "max_position_embeddings": "max_seq",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def load_config(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "configs", f"{name}.json")) as f:
        spec = json.load(f)
    if spec.get("architecture") != "dense_decoder":
        raise SystemExit(f"config {name!r}: architecture "
                         f"{spec.get('architecture')!r} has no reference "
                         "under benchmark/reference/")
    if spec.get("torch_dtype") != "bfloat16":
        raise SystemExit(f"config {name!r}: only bfloat16 is served")
    return spec


def program_kwargs(spec: dict) -> dict:
    """Keyword arguments for the program's config class."""
    return {field: spec[key] for key, field in PROGRAM_FIELDS.items()}


def matrix_params(spec: dict, layers: int | None = None) -> dict:
    """Parameters that take part in a matrix multiply, by group."""
    h, m = spec["hidden_size"], spec["intermediate_size"]
    q = spec["num_attention_heads"] * spec["head_dim"]
    kv = spec["num_key_value_heads"] * spec["head_dim"]
    n = spec["num_hidden_layers"] if layers is None else layers
    per_layer = h * q + 2 * h * kv + q * h + 3 * h * m
    return {"per_layer": per_layer, "layers": n * per_layer,
            "head": h * spec["vocab_size"]}


def num_params(spec: dict, layers: int | None = None) -> int:
    """All stored parameters: embedding table, blocks with their two
    norms, final norm, and the head where it is not tied."""
    h, v = spec["hidden_size"], spec["vocab_size"]
    n = spec["num_hidden_layers"] if layers is None else layers
    mp = matrix_params(spec, layers)
    total = v * h + mp["layers"] + n * 2 * h + h
    if not spec["tie_word_embeddings"]:
        total += mp["head"]
    return total


def train_flops_per_token(spec: dict, seq: int) -> float:
    """Operations the forward and backward passes REQUIRE for one trained
    token: 6 for every parameter in a matrix multiply (2 forward, 4
    backward), none for the embedding gather, none for recomputation,
    plus causal attention: forward QK^T and PV are 4*S*d over the full
    square, halved by causality, and the backward costs twice the
    forward: 3 * 2*S*d = 6*S*d a layer (d = heads * head size)."""
    mp = matrix_params(spec)
    q = spec["num_attention_heads"] * spec["head_dim"]
    return (6.0 * (mp["layers"] + mp["head"])
            + 6.0 * spec["num_hidden_layers"] * seq * q)


def flash_flops(spec: dict, batch: int, seq: int) -> dict:
    """Operations of ONE call of each causal flash kernel (one layer, one
    step), counted over the lower triangle. Forward: QK^T and PV. The
    backward is split in two kernels that each recompute what they need
    (flash attention stores no scores): dq = scores, dP, dQ; dkv =
    scores, dP, dV, dK."""
    h, d = spec["num_attention_heads"], spec["head_dim"]
    tri = batch * h * seq * seq * d      # one matmul over half the square
    return {"fwd": 2 * tri, "bwd_dq": 3 * tri, "bwd_dkv": 4 * tri}


def kv_bytes_per_token(spec: dict) -> int:
    """Bytes of keys and values one cached token takes in ONE layer."""
    return 2 * spec["num_key_value_heads"] * spec["head_dim"] * 2


def paged_decode_bytes(spec: dict, live_tokens: int, slots: int) -> int:
    """Bytes the paged decode-attention kernel has to move for ONE layer
    and one step: the live keys and values once, the queries in and the
    outputs out (bf16)."""
    q = spec["num_attention_heads"] * spec["head_dim"]
    return live_tokens * kv_bytes_per_token(spec) + 2 * slots * q * 2
