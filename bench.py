"""Headline benchmark: Llama training-step MFU on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The reference has no in-tree tokens/sec or MFU numbers (BASELINE.md); the
north-star target from BASELINE.json is >=40% MFU for Llama-family training
on v5e, so ``vs_baseline`` = achieved_MFU / 0.40.
"""

import dataclasses
import json
import sys
import time

# bf16 peak FLOP/s by TPU generation (public spec sheets).
PEAK_FLOPS = {
    "v6": 918e12,   # Trillium
    "v5p": 459e12,
    "v5": 197e12,   # v5e ("TPU v5 lite")
    "v4": 275e12,
    "v3": 123e12,
    "v2": 46e12,
}


def peak_flops(device) -> float:
    """Published bf16 peak of the device. A device that is not in the
    table is an error, not a default."""
    kind = device.device_kind.lower().replace(" ", "")
    if device.platform == "tpu":
        for key in ("v6", "v5p", "v4", "v3", "v2", "v5"):
            if key in kind:
                return PEAK_FLOPS[key]
    raise SystemExit(f"bench.py: no peak FLOP/s known for {device.platform} "
                     f"device {device.device_kind!r}")


def require_tpu():
    """A bench asked to run where there is no chip says so and exits
    non-zero: a CPU timing is never written under a device metric's name."""
    import jax

    from ray_tpu.common.compile_cache import use_compile_cache

    use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py: no chip — jax reports platform "
                         f"{dev.platform!r} ({dev.device_kind}); nothing "
                         "was measured")
    return dev


def run(config_name: str, batch: int, seq: int, steps: int = 10):
    import jax
    import jax.numpy as jnp

    dev = require_tpu()

    from ray_tpu.models import llama
    from ray_tpu.models.training import (
        OptimizerConfig, init_train_state, make_train_step)
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.parallel.sharding import ShardingRules

    cfg = llama.CONFIGS[config_name]
    mesh = make_mesh(MeshConfig(dp=1, fsdp=-1), devices=jax.devices()[:1])
    rules = ShardingRules()
    opt = OptimizerConfig(warmup_steps=1, decay_steps=1000).make()

    with jax.sharding.set_mesh(mesh):
        state, _ = init_train_state(
            lambda key: llama.init_params(cfg, key),
            llama.param_logical_axes(cfg), opt, mesh, rules,
            jax.random.key(0))
        step_fn = make_train_step(
            lambda p, b: llama.loss_fn(p, b, cfg, rules), opt, mesh, rules)
        tokens = jax.random.randint(
            jax.random.key(1), (batch, seq), 0, cfg.vocab_size,
            dtype=jnp.int32)
        b = {"tokens": tokens}

        # Sync via host fetch of the loss: the final step's loss depends
        # on the whole chain, so the transfer is a barrier.
        state, m = step_fn(state, b)           # compile + warmup
        float(m["loss"])
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = step_fn(state, b)
        final_loss = float(m["loss"])
        dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * steps / dt
    mfu = cfg.flops_per_token(seq) * tokens_per_sec / peak_flops(dev)
    return {
        "metric": f"llama_{config_name}_train_mfu_1chip",
        "value": round(mfu * 100, 2),
        "unit": "percent_mfu",
        "vs_baseline": round(mfu / 0.40, 3),
        "tokens_per_sec": round(tokens_per_sec, 1),
        "loss": round(final_loss, 4),
        "batch": batch,
        "seq": seq,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }


def run_kernels():
    """``--kernels`` mode: flash-attention fwd/bwd + paged-decode
    microbenches — seconds, not minutes."""
    import jax
    import jax.numpy as jnp

    dev = require_tpu()
    peak = peak_flops(dev)

    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.ops.pallas.paged_decode_attention import \
        paged_decode_attention

    B, S, H, D = 4, 2048, 16, 128      # 1B-class attention shape
    PB, PLEN, PBS, PKV = 64, 1024, 16, 16
    steps = 20
    key = jax.random.key(0)
    dt = jnp.bfloat16
    q = jax.random.normal(key, (B, S, H, D), dt)
    k = jax.random.normal(key, (B, S, H, D), dt)
    v = jax.random.normal(key, (B, S, H, D), dt)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    fwdbwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    def _time(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / steps

    t_fwd = _time(fwd, q, k, v)
    t_bwd = _time(fwdbwd, q, k, v)
    # causal flash: fwd = 2 matmuls over the lower triangle
    flops_fwd = 4 * B * H * S * S * D * 0.5
    flops_bwd = flops_fwd * 2.5  # dq, dk, dv recompute (standard 2.5x)
    fwd_tflops = flops_fwd / t_fwd / 1e12
    bwd_tflops = flops_bwd / t_bwd / 1e12

    # paged decode: one token per sequence against a block-table KV pool
    MBS = PLEN // PBS
    NBLK = PB * MBS
    qd = jax.random.normal(key, (PB, 1, H, D), dt)
    kp = jax.random.normal(key, (1, NBLK, PBS, PKV * D), dt)  # one layer
    vp = jax.random.normal(key, (1, NBLK, PBS, PKV * D), dt)
    tables = jnp.arange(NBLK, dtype=jnp.int32).reshape(PB, MBS)
    lengths = jnp.full((PB,), PLEN, jnp.int32)
    paged = jax.jit(lambda *a: paged_decode_attention(
        *a, scale=D ** -0.5))
    t_dec = _time(paged, qd, kp, vp, jnp.int32(0), tables, lengths)
    # HBM traffic is the decode bottleneck: bytes of KV streamed per step
    kv_bytes = 2 * NBLK * PBS * PKV * D * jnp.dtype(dt).itemsize
    dec_gbps = kv_bytes / t_dec / 1e9

    result = {
        "metric": "kernels_flash_fwd_tflops",
        "value": round(fwd_tflops, 2),
        "unit": "TFLOP/s",
        # kernel-level bar: fraction of chip peak the fwd kernel sustains
        "vs_baseline": round(fwd_tflops * 1e12 / peak, 3),
        "rows": {
            "flash_fwd": {"tflops": round(fwd_tflops, 2),
                          "us": round(t_fwd * 1e6, 1),
                          "shape": [B, S, H, D]},
            "flash_fwd_bwd": {"tflops": round(bwd_tflops, 2),
                              "us": round(t_bwd * 1e6, 1)},
            "paged_decode": {"kv_read_gbps": round(dec_gbps, 1),
                             "us": round(t_dec * 1e6, 1),
                             "batch": PB, "ctx": PLEN},
        },
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }
    return result


def main():
    if "--kernels" in sys.argv:
        print(json.dumps(run_kernels()))
        return 0
    from ray_tpu.models import llama
    # the bench variant: tied 32k vocabulary, attn_block=1024
    llama.CONFIGS.setdefault(
        "1b_bench",
        dataclasses.replace(llama.CONFIGS["1b"], vocab_size=32000,
                            tie_embeddings=True, max_seq=2048,
                            attn_block=1024))
    print(json.dumps(run("1b_bench", 16, 2048)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
