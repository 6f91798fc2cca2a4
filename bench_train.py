"""Train-plane benchmarks: MPMD pipeline-parallel stepping vs per-call
actor submission, and the quantized collective wire vs the exact path.

Same conventions as ``bench_core.py``: one JSON line per metric, full set
written to ``BENCH_train.json``.  All rows run on the CPU host mesh with
same-box shm channels — they measure ORCHESTRATION cost (driver RPCs,
channel hops, schedule overlap), not TPU math; captions in the JSON say
so (PERF_PLAN convention: every number carries its device context).

Rows:
  percall_steps_per_second        driver-orchestrated per-microbatch RPC
  pipeline_steps_per_second       PipelineRunner 1F1B over shm channels
  pipeline_microbatches_per_second  derived: steps/s x num_microbatches
  allreduce_{exact,quantized}_calls_per_second   KV-backend allreduce
  allreduce_bytes_on_wire_{exact,quantized}      measured serialized bytes

Run: python bench_train.py [filter_substring] [--out PATH]
"""

import json
import statistics
import sys
import time

import numpy as np

import ray_tpu

BASELINES = {}  # no reference publishes comparable numbers for these rows

CAPTIONS = {
    "percall_steps_per_second":
        "CPU host mesh, 2-stage MLP, 4 microbatches, driver-mediated RPC "
        "per hop (get between stages) — the dynamic-dispatch baseline",
    "pipeline_steps_per_second":
        "CPU host mesh, same model/schedule, 1F1B over same-box shm "
        "channels, zero per-microbatch driver involvement — "
        "orchestration-bound, not TPU math",
    "pipeline_microbatches_per_second":
        "derived: pipeline_steps_per_second x num_microbatches (4)",
    "allreduce_exact_calls_per_second":
        "KV backend, 2 members (actor processes), 1 MiB float32, exact "
        "wire — same-box GCS KV, not ICI",
    "allreduce_quantized_calls_per_second":
        "KV backend, 2 members, 1 MiB float32, block-wise int8 wire "
        "(RT_quantized_collectives) — same-box GCS KV, not ICI",
    "allreduce_bytes_on_wire_exact":
        "measured serialized put bytes per allreduce per member, exact",
    "allreduce_bytes_on_wire_quantized":
        "measured serialized put bytes per allreduce per member, "
        "block-256 int8 codes + per-block scale/offset",
}

RESULTS = []
OUT_PATH = "BENCH_train.json"
if "--out" in sys.argv:
    _i = sys.argv.index("--out")
    OUT_PATH = sys.argv[_i + 1]
    del sys.argv[_i:_i + 2]
FILTER = sys.argv[1] if len(sys.argv) > 1 else ""


def _want(name):
    return not FILTER or FILTER in name


def timeit(name, fn, multiplier=1, trials=3, trial_s=2.0, unit="steps/s"):
    if not _want(name):
        return None
    start = time.perf_counter()
    count = 0
    while time.perf_counter() - start < 1.0:
        fn()
        count += 1
    step = count // 10 + 1
    stats = []
    for _ in range(trials):
        start = time.perf_counter()
        count = 0
        while time.perf_counter() - start < trial_s:
            for _ in range(step):
                fn()
            count += step
        stats.append(multiplier * count / (time.perf_counter() - start))
    return emit(name, statistics.mean(stats), unit,
                stddev=statistics.pstdev(stats))


def emit(name, value, unit, stddev=0.0):
    rec = {"metric": name, "value": round(value, 1),
           "stddev": round(stddev, 1), "unit": unit,
           "baseline": None, "vs_baseline": None}
    RESULTS.append(rec)
    print(json.dumps(rec), flush=True)
    return rec


# ------------------------------------------------------------- the model
# Closure factories: cloudpickle serializes closures BY VALUE, so stage
# actors never need this script importable (same constraint as tests).
D_IN, D_H, D_OUT, BATCH, MICRO = 16, 32, 4, 8, 4


def _make_stage_fns(d_in, d_out):
    import jax
    import jax.numpy as jnp

    def init(rng):
        kw, kb = jax.random.split(rng)
        return {"w": jax.random.normal(kw, (d_in, d_out)) * 0.1,
                "b": jax.random.normal(kb, (d_out,)) * 0.01}

    def apply(params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    return init, apply


def _make_loss():
    import jax.numpy as jnp

    def loss(y_pred, y):
        return jnp.mean((y_pred - y) ** 2)

    return loss


def _data(seed=0):
    rng = np.random.RandomState(seed)
    xs = [rng.randn(BATCH, D_IN).astype(np.float32) for _ in range(MICRO)]
    ys = [rng.randn(BATCH, D_OUT).astype(np.float32) for _ in range(MICRO)]
    return xs, ys


# --------------------------------------------- per-call dispatch baseline
def _make_percall_stage():
    """Stage actor for the baseline: same jit'd compute as the pipeline
    stage loop, but every microbatch hop is a driver-mediated RPC."""

    class PerCallStage:
        def __init__(self, fns_blob, index, n_stages, seed, lr):
            import cloudpickle
            import jax

            fns = cloudpickle.loads(fns_blob)
            init_fn, self._apply = fns["init"], fns["apply"]
            loss_fn = fns.get("loss")
            self._jax, self._lr = jax, lr
            self.params = jax.device_get(
                init_fn(jax.random.PRNGKey(seed + index)))
            self._fwd = jax.jit(self._apply)
            if loss_fn is not None:
                self._fused = jax.jit(jax.value_and_grad(
                    lambda p, x, y: loss_fn(self._apply(p, x), y),
                    argnums=(0, 1)))
            self._bwd = jax.jit(
                lambda p, x, g: jax.vjp(self._apply, p, x)[1](g))
            self._acc, self._stash = None, []

        def _add(self, gp):
            tm = self._jax.tree_util.tree_map
            self._acc = gp if self._acc is None else tm(
                lambda a, b: a + b, self._acc, gp)

        def forward(self, x):
            self._stash.append(x)
            return np.asarray(self._fwd(self.params, x))

        def fused_acc(self, x, y):
            loss, (gp, gx) = self._fused(self.params, x, y)
            self._add(gp)
            return np.asarray(gx), float(loss)

        def backward_acc(self, g):
            gp, gx = self._bwd(self.params, self._stash.pop(0), g)
            self._add(gp)
            return np.asarray(gx)

        def step(self, num_micro):
            tm = self._jax.tree_util.tree_map
            self.params = self._jax.device_get(tm(
                lambda p, a: p - self._lr * (a / num_micro),
                self.params, self._acc))
            self._acc = None
            return True

    return PerCallStage


def bench_percall(xs, ys):
    import cloudpickle

    fns = []
    dims = [(D_IN, D_H), (D_H, D_OUT)]
    for i, (di, do) in enumerate(dims):
        init, apply = _make_stage_fns(di, do)
        fns.append({"init": init, "apply": apply,
                    "loss": _make_loss() if i == len(dims) - 1 else None})
    cls = ray_tpu.remote(_make_percall_stage())
    actors = [cls.options(num_cpus=0).remote(
        cloudpickle.dumps(f), i, len(fns), 0, 0.001)
        for i, f in enumerate(fns)]

    def one_step():
        for m in range(MICRO):
            act = ray_tpu.get(actors[0].forward.remote(xs[m]))
            gx, _loss = ray_tpu.get(
                actors[1].fused_acc.remote(act, ys[m]))
            ray_tpu.get(actors[0].backward_acc.remote(gx))
        ray_tpu.get([a.step.remote(MICRO) for a in actors])

    one_step()  # warm the jit caches before the timed region
    rec = timeit("percall_steps_per_second", one_step, trials=2)
    for a in actors:
        ray_tpu.kill(a)
    return rec


# ----------------------------------------------------- pipelined stepping
def bench_pipeline(xs, ys):
    from ray_tpu.train import PipelineRunner, PipelineSpec, StageSpec

    stages = []
    for i, (di, do) in enumerate([(D_IN, D_H), (D_H, D_OUT)]):
        init, apply = _make_stage_fns(di, do)
        stages.append(StageSpec(init=init, apply=apply, name=f"s{i}"))
    spec = PipelineSpec(stages=stages, loss=_make_loss(),
                        num_microbatches=MICRO, optimizer="sgd",
                        learning_rate=0.001)
    runner = PipelineRunner(spec)
    try:
        runner.step(xs, ys)  # warm the jit caches + channel path
        rec = timeit("pipeline_steps_per_second",
                     lambda: runner.step(xs, ys), trials=2)
    finally:
        runner.shutdown()
    if rec is not None and _want("pipeline_microbatches_per_second"):
        emit("pipeline_microbatches_per_second", rec["value"] * MICRO,
             "microbatches/s")
    return rec


# ------------------------------------------------- quantized wire rows
def _make_member():
    class Member:
        def __init__(self, rank, world, group, quantized):
            import numpy as np  # noqa: F811 — actor process import

            from ray_tpu import collective as col

            col.init_collective_group(world, rank, backend="kv",
                                      group_name=group, quantized=quantized)
            self._g = col.get_group_handle(group)
            self._payload = (np.random.RandomState(rank)
                             .randn(1 << 18).astype(np.float32))
            self._calls = 0

        def do_allreduce(self, n=1):
            for _ in range(n):
                self._g.allreduce(self._payload.copy())
            self._calls += n
            return self._calls

        def wire_stats(self):
            return self._g.wire_put_bytes, self._calls

    return Member


def bench_allreduce(quantized):
    mode = "quantized" if quantized else "exact"
    rate_row = f"allreduce_{mode}_calls_per_second"
    bytes_row = f"allreduce_bytes_on_wire_{mode}"
    if not (_want(rate_row) or _want(bytes_row)):
        return
    cls = ray_tpu.remote(_make_member())
    members = [cls.options(num_cpus=0).remote(r, 2, f"bench_{mode}",
                                              quantized)
               for r in range(2)]
    ray_tpu.get([m.do_allreduce.remote() for m in members])  # rendezvous

    def one_round():
        ray_tpu.get([m.do_allreduce.remote() for m in members])

    if _want(rate_row):
        timeit(rate_row, one_round, trials=2, unit="allreduces/s")
    if _want(bytes_row):
        put_bytes, calls = ray_tpu.get(members[0].wire_stats.remote())
        emit(bytes_row, put_bytes / calls, "bytes/allreduce")
    for m in members:
        ray_tpu.kill(m)


def main():
    ray_tpu.init(num_cpus=8, num_tpus=0)
    xs, ys = _data()

    percall = pipeline = None
    if _want("percall_steps_per_second"):
        percall = bench_percall(xs, ys)
    if _want("pipeline_steps_per_second"):
        pipeline = bench_pipeline(xs, ys)
    if percall and pipeline:
        print(json.dumps({
            "note": "pipeline_vs_percall_speedup",
            "value": round(pipeline["value"] / percall["value"], 2)}),
            flush=True)

    bench_allreduce(quantized=False)
    bench_allreduce(quantized=True)

    ray_tpu.shutdown()
    with open(OUT_PATH, "w") as f:
        json.dump({"results": RESULTS,
                   "captions": {k: v for k, v in CAPTIONS.items()
                                if any(r["metric"] == k for r in RESULTS)},
                   "source": "bench_train.py (pipeline + quantized wire)"},
                  f, indent=2)
    print(f"# wrote {OUT_PATH} ({len(RESULTS)} metrics)")


if __name__ == "__main__":
    main()
