"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             one chip: serve phase, then train phase
    python chip_smoke.py --chips 4   four chips: sharded train vs one device,
                                     then four one-chip replicas (only these)

Both phases go through the entry points a user calls — ``serve.run`` + a
handle + the HTTP proxy, and ``JaxTrainer`` — at the published widths of
``llama.CONFIGS["1b"]`` with random weights made from ``--seed``.

This process is the ray_tpu driver and NEVER imports jax: a chip belongs
to one process at a time, and here that is the replica, then the train
worker. What the device is comes back from the worker that held it.
Any failed check raises; nothing is caught and passed over. The last
line of stdout is the result object, printed only if every phase passed
on a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

APP = "llm"
TRAIN_SEQ = 2048


# --------------------------------------------------------------- the train loop
def train_loop(config):
    """Runs inside the JaxTrainer worker (the process that holds the
    chips). Builds the step from models/training.py; with several devices
    it first runs the same seeded steps on one device, then on the
    ``fsdp x tp`` mesh, and reports both."""
    import dataclasses
    import time

    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.common.compile_cache import compile_cache_counts
    from ray_tpu.models import llama
    from ray_tpu.models.training import (OptimizerConfig, init_train_state,
                                         make_train_step)
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.parallel.sharding import FSDP_TP_RULES

    cache_counts = compile_cache_counts()
    devices = jax.devices()
    dev = devices[0]
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    rules = FSDP_TP_RULES
    seq, steps, seed = config["seq"], config["steps"], config["seed"]
    opt = OptimizerConfig(learning_rate=config["lr"], warmup_steps=1,
                          decay_steps=1000).make()
    cuts = []

    def build(cfg, batch, mesh):
        t0 = time.monotonic()
        with jax.sharding.set_mesh(mesh):
            state, _ = init_train_state(
                lambda key: llama.init_params(cfg, key),
                llama.param_logical_axes(cfg), opt, mesh, rules,
                jax.random.key(seed))
            step_fn = make_train_step(
                lambda p, b: llama.loss_fn(p, b, cfg, rules), opt, mesh,
                rules)
            tokens = jax.random.randint(
                jax.random.key(seed + 1), (batch, seq), 0, cfg.vocab_size,
                dtype=jnp.int32)
            compiled = step_fn.lower(state, {"tokens": tokens}).compile()
        return state, compiled, tokens, time.monotonic() - t0

    def fit_one_device(cfg, batch, mesh1):
        """Widths are never cut. If state + logits do not fit the device,
        cut batch first, then depth, and say what was cut and why."""
        while True:
            built = build(cfg, batch, mesh1)
            mem = built[1].memory_analysis()
            need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                    + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
            if limit is None or need <= 0.92 * limit:
                return cfg, batch, need, built
            del built
            why = (f"batch {batch} x {cfg.n_layers} layers needs "
                   f"{need / 2**30:.2f} GiB of {limit / 2**30:.2f} GiB")
            if batch > config["min_batch"]:
                batch //= 2
                cuts.append(f"batch cut to {batch}: {why}")
            elif cfg.n_layers > 1:
                cfg = dataclasses.replace(cfg, n_layers=cfg.n_layers // 2)
                cuts.append(f"n_layers cut to {cfg.n_layers}: {why}")
            else:
                raise MemoryError(why)
            print(f"[train] {cuts[-1]}", flush=True)

    def run(built, mesh):
        state, compiled, tokens, build_s = built
        leaves = jax.tree.leaves(state.params)
        # code that never ran on more than one chip may put everything on
        # the first: every parameter spans the mesh, and one device holds
        # its share of the bytes, not all of them
        span = min(len(leaf.sharding.device_set) for leaf in leaves)
        share = (sum(leaf.addressable_shards[0].data.nbytes
                     for leaf in leaves)
                 / sum(leaf.nbytes for leaf in leaves))
        losses, step_s = [], []
        with jax.sharding.set_mesh(mesh):
            for _ in range(steps + 1):      # first = the warm-up step
                t0 = time.monotonic()
                state, metrics = compiled(state, {"tokens": tokens})
                losses.append(float(metrics["loss"]))
                step_s.append(time.monotonic() - t0)
        return {"losses": losses, "build_s": build_s, "step_s": step_s,
                "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
                "param_device_span": span, "param_share_per_device": share}

    cfg = llama.CONFIGS[config["model"]]
    mesh1 = make_mesh(MeshConfig(dp=1, fsdp=1), devices=devices[:1])
    cfg, batch, need, built = fit_one_device(cfg, config["batch"], mesh1)
    out = {"one_device": run(built, mesh1)}
    del built
    if len(devices) > 1:
        mesh = make_mesh(MeshConfig(dp=1, fsdp=2, tp=len(devices) // 2),
                         devices=devices)
        out["mesh"] = run(build(cfg, batch, mesh), mesh)
    out.update(
        model=config["model"], n_layers=cfg.n_layers, batch=batch, seq=seq,
        widths={"hidden": cfg.hidden, "n_heads": cfg.n_heads,
                "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
                "mlp_dim": cfg.mlp_dim, "vocab_size": cfg.vocab_size},
        cuts=cuts, step_bytes=need, compile_cache=dict(cache_counts),
        device={"platform": dev.platform, "kind": dev.device_kind,
                "count": len(devices),
                "peak_bytes_in_use": (dev.memory_stats() or {}).get(
                    "peak_bytes_in_use")})
    train.report(out)


# ------------------------------------------------------------------ the phases
def _prompts(rng, n, vocab, lo, hi):
    return [[rng.randrange(vocab) for _ in range(rng.randint(lo, hi))]
            for _ in range(n)]


def _generate_all(handle, prompts, max_tokens, timeout_s=900.0):
    """Send every prompt concurrently through one handle."""
    import ray_tpu

    out = [None] * len(prompts)
    errors = []

    def one(i):
        try:
            out[i] = ray_tpu.get(handle.remote(prompts[i],
                                               max_tokens=max_tokens),
                                 timeout=timeout_s)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return out


def _check_tokens(outputs, max_tokens, vocab):
    for toks in outputs:
        assert len(toks) == max_tokens, (len(toks), max_tokens)
        assert all(isinstance(t, int) and 0 <= t < vocab for t in toks)


def _replica_stats(app):
    """stats() of EVERY replica (a handle call reaches only one)."""
    import ray_tpu
    from ray_tpu.serve.controller import CONTROLLER_NAME

    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    _, replicas, *_ = ray_tpu.get(controller.get_replicas.remote(app))
    return ray_tpu.get([r.handle_request.remote("stats", (), {})
                        for r in replicas], timeout=120.0)


def _wait_replicas(app, n, timeout_s):
    import ray_tpu
    from ray_tpu.serve.controller import CONTROLLER_NAME

    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    deadline = time.monotonic() + timeout_s
    while True:
        _, replicas, *_ = ray_tpu.get(controller.get_replicas.remote(app))
        if len(replicas) >= n:
            return
        if time.monotonic() > deadline:
            raise TimeoutError(f"{len(replicas)}/{n} replicas of {app!r}")
        time.sleep(0.5)


def _require_tpu(device, phase):
    if device["platform"] != "tpu":
        raise SystemExit(
            f"no chip: the {phase} worker reports platform "
            f"{device['platform']!r} ({device['kind']}); nothing it did "
            "counts as a chip run")


def serve_phase(args, vocab, replicas):
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve import api as serve_api

    rng = random.Random(args.seed)
    t0 = time.monotonic()
    addr = serve.start(http_port=0, grpc_port=None)
    serve.run(serve_api.llm_app(model=args.model, name=APP,
                                num_replicas=replicas, seed=args.seed,
                                ray_actor_options={"num_tpus": 1}),
              name=APP, wait_timeout_s=900.0)
    _wait_replicas(APP, replicas, 900.0)
    handle = serve.get_deployment_handle(APP)
    ready_s = time.monotonic() - t0
    lo, hi = args.prompt_len
    if replicas == 1:
        prompts = _prompts(rng, 5, vocab, lo, hi)
        prompts.append(list(prompts[0]))      # the same greedy prompt twice
        groups = [[0, 5], [1], [2], [3], [4]]
    else:
        base = _prompts(rng, 4, vocab, lo, hi)
        prompts = [list(p) for p in base for _ in range(replicas)]
        groups = [list(range(i * replicas, (i + 1) * replicas))
                  for i in range(4)]
    t0 = time.monotonic()
    outputs = _generate_all(handle, prompts, args.max_tokens)
    first_wave_s = time.monotonic() - t0
    _check_tokens(outputs, args.max_tokens, vocab)
    for group in groups:      # greedy: same prompt -> same tokens, on any
        for i in group[1:]:   # slot and any replica
            assert outputs[i] == outputs[group[0]], (group, i)
    if replicas == 1:
        body = json.dumps({"prompt": prompts[0],
                           "max_tokens": args.max_tokens}).encode()
        req = urllib.request.Request(
            f"http://{addr['http_host']}:{addr['http_port']}/{APP}",
            data=body, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=600) as resp:
            assert resp.status == 200, resp.status
            via_http = json.loads(resp.read())
        _check_tokens([via_http], args.max_tokens, vocab)
        assert via_http == outputs[0], "HTTP and handle answers differ"
    stats = _replica_stats(APP)
    assert len(stats) == replicas, (len(stats), replicas)
    for st in stats:
        _require_tpu(st["device"], "serve")
        assert st["tokens_generated"] > 0, "a replica answered nothing"
        assert st["kv_cache"] == "paged"
    chips = [tuple(st["device"]["granted_chips"]) for st in stats]
    assert len(set(chips)) == replicas, f"replicas share a chip: {chips}"
    generated = sum(len(o) for o in outputs)
    print(f"[serve] replicas={replicas} ready_s={ready_s:.1f} "
          f"requests={len(prompts)} first_wave_s={first_wave_s:.1f} "
          f"tokens_generated={generated} chips={chips}", flush=True)
    for st in stats:
        print(f"[serve] replica chips={st['device']['granted_chips']} "
              f"device={st['device']} steps={st['steps']} "
              f"tokens={st['tokens_generated']} "
              f"compile_cache={st['compile_cache']}", flush=True)
    serve.delete(APP)
    return stats[0]["device"]


def train_phase(args, chips):
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    storage = tempfile.mkdtemp(prefix="chip_smoke_train_")
    try:
        t0 = time.monotonic()
        result = JaxTrainer(
            train_loop,
            train_loop_config={"model": args.model, "seq": args.seq,
                               "batch": args.batch,
                               "min_batch": 1 if chips == 1 else 2,
                               "steps": args.steps, "seed": args.seed,
                               "lr": 3e-5},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"TPU": float(chips)}),
            run_config=RunConfig(name="chip_smoke", storage_path=storage),
        ).fit(timeout_s=1000.0)
        wall_s = time.monotonic() - t0
    finally:
        shutil.rmtree(storage, ignore_errors=True)
    m = result.metrics
    runs = [m["one_device"]] + ([m["mesh"]] if chips > 1 else [])
    for r in runs:
        losses = r["losses"]
        assert len(losses) == args.steps + 1
        assert all(x == x and abs(x) < 1e4 for x in losses), losses
        # the first update runs at lr 0 (warm-up), the rest must descend
        assert losses[-1] < min(losses[0], losses[1]), f"no descent: {losses}"
    if chips > 1:
        one, mesh = m["one_device"]["losses"], m["mesh"]["losses"]
        assert m["mesh"]["param_device_span"] == chips, m["mesh"]
        assert m["mesh"]["param_share_per_device"] < 0.5, m["mesh"]
        rel = [abs(a - b) / max(abs(a), 1e-6) for a, b in zip(one, mesh)]
        # The first two losses come from the same weights (the first update
        # runs at lr 0): only the sharded forward pass can differ, so the
        # bound is tight. Later steps amplify bf16 rounding through the
        # updates; the learning rate was chosen so that they stay under 1%.
        assert max(rel[:2]) < 1e-4, (one, mesh)
        assert max(rel) < 0.01, (one, mesh)
    _require_tpu(m["device"], "train")
    assert m["device"]["count"] == chips, (m["device"], chips)
    for cut in m["cuts"]:
        print(f"[train] cut: {cut}", flush=True)
    for r in runs:
        print(f"[train] mesh={r['mesh'] or 'one device'} losses="
              f"{[round(x, 4) for x in r['losses']]} init_and_compile_s="
              f"{r['build_s']:.1f} step_s="
              f"{[round(s, 3) for s in r['step_s']]} "
              f"param_device_span={r['param_device_span']} "
              f"param_share_per_device={r['param_share_per_device']:.3f}",
              flush=True)
    if chips > 1:
        print(f"[train] one device vs {m['mesh']['mesh']}: relative loss "
              f"difference by step {[float(f'{x:.2e}') for x in rel]}",
              flush=True)
    print(f"[train] model={m['model']} widths={m['widths']} n_layers="
          f"{m['n_layers']} batch={m['batch']} seq={m['seq']} wall_s="
          f"{wall_s:.1f} step_bytes={m['step_bytes']} device={m['device']} "
          f"compile_cache={m['compile_cache']}", flush=True)
    return m["device"]


def _proc_state(pid):
    """(state, parent pid) of a process from /proc, None if it is gone."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
    except OSError:
        return None
    return fields[0], int(fields[1])


def _running(pid) -> bool:
    st = _proc_state(pid)       # a zombie has stopped, it awaits reaping
    return st is not None and st[0] != "Z"


def _descendants():
    """{pid: command} of every running process below this one."""
    parent = {}
    for entry in filter(str.isdigit, os.listdir("/proc")):
        st = _proc_state(entry)
        if st is not None and st[0] != "Z":
            parent[int(entry)] = st[1]
    found, frontier = {}, {os.getpid()}
    while frontier:
        frontier = {p for p, pp in parent.items() if pp in frontier}
        for pid in frontier:
            try:
                with open(f"/proc/{pid}/cmdline") as f:
                    found[pid] = f.read().replace("\0", " ")[:100]
            except OSError:
                pass            # gone meanwhile
    return found


def _stop_everything(started, grace_s=30.0):
    """The script stops every process it started: after the framework's
    own shutdown, wait for the stragglers (a process that held a chip takes
    seconds to let go of it) and kill what is still there."""
    deadline = time.monotonic() + grace_s
    while any(map(_running, started)) and time.monotonic() < deadline:
        time.sleep(0.2)
    for pid in filter(_running, started):
        print(f"[cleanup] killing straggler {pid}: {started[pid]}",
              flush=True)
        os.kill(pid, signal.SIGKILL)


def _wait_chips_answer(n, timeout_s=180.0):
    """A four-chip run ends with five processes that held chips killed;
    the chips take a while to come back. Before returning, see that a
    fresh process can open all of them (this one still never does)."""
    code = f"import jax; assert len(jax.devices()) == {n}, jax.devices()"
    deadline = time.monotonic() + timeout_s
    while True:
        probe = subprocess.run([sys.executable, "-c", code], timeout=120,
                               capture_output=True, text=True)
        if probe.returncode == 0:
            return
        if time.monotonic() > deadline:
            raise RuntimeError("the chips do not answer after the run:\n"
                               + probe.stderr[-2000:])
        time.sleep(3.0)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    args.model, args.seq, args.batch, args.steps = "1b", TRAIN_SEQ, 4, 4
    args.prompt_len, args.max_tokens = (300, 480), 32

    import ray_tpu

    ray_tpu.init()
    try:
        found = ray_tpu.cluster_resources().get("TPU", 0)
        if found < args.chips:
            raise SystemExit(
                f"no chip: this host offers TPU: {found:g}, the run needs "
                f"{args.chips}; nothing was served or trained")
        vocab = 128256   # llama.CONFIGS["1b"].vocab_size; importing it imports jax
        if args.chips == 1:
            devices = [serve_phase(args, vocab, replicas=1),
                       train_phase(args, chips=1)]
        else:
            devices = [train_phase(args, chips=4),
                       serve_phase(args, vocab, replicas=4)]
    finally:
        from ray_tpu import serve

        started = _descendants()
        serve.shutdown()
        ray_tpu.shutdown()
        _stop_everything(started)
        if args.chips > 1:
            _wait_chips_answer(args.chips)
    assert "jax" not in sys.modules, "the driver imported jax"
    kinds = {d["kind"] for d in devices}
    assert len(kinds) == 1, kinds
    print(json.dumps({"ok": True, "device": {
        "platform": "tpu", "kind": kinds.pop(), "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
