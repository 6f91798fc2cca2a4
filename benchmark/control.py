"""The control of ``correct``, on the chip at a configuration's own
size: the numbers sound runs of the program give on many seeds, beside
the numbers the control gives (the configuration's reference with its
weight matrix multiplies computed in int8, the step below bfloat16 that
this chip has units for). A limits file (``benchmark/limits.json``, or
the one a configuration names) is set from what this prints.

    python3 benchmark/control.py --config mistral-7b-l16 --kind serve \
        --cell serve-chat-steady --seeds 12 --control-seeds 3

``--kind losses`` reads ``loss_did_not_fall``'s two sides on a train
cell's own job (its batch, mesh and sequence; the steps a window of 51 s
holds): the losses of the program's steps on many seeds, and of the
control, whose optimizer does nothing (a learning rate of 0 throughout).

    python3 benchmark/control.py --config deepseek-coder-1.3b \
        --kind losses --cell train-1chip --seeds 12 --control-seeds 3

One process that holds the chip itself (no cluster): the benchmark's own
runs never run it. The same comparison at a tiny size is a test under
``benchmark/tests``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def job_losses(spec: dict, mix: dict, job: dict, seed: int, steps: int,
               dead_optimizer: bool = False) -> list:
    """The losses of ``steps`` steps of a train cell's job from ``seed``,
    as ``worker_train.train_loop`` takes them: the adapter's compiled
    step, a fresh batch each step. ``dead_optimizer``: the control, the
    same step under a schedule that is 0 at every step."""
    from unittest import mock

    import jax
    import optax
    from ray_tpu.models.training import OptimizerConfig

    from benchmark import model_spec, sizing, traffic_gen, weights

    job = dict(job, optimizer=mix["optimizer"])
    mesh = sizing.train_mesh(jax.devices(), job)
    schedule = (mock.patch.object(OptimizerConfig, "schedule",
                                  lambda self: optax.constant_schedule(0.0))
                if dead_optimizer else contextlib.nullcontext())
    with jax.sharding.set_mesh(mesh):
        with schedule:
            _, step_fn, _, init_state = model_spec.adapter(
                spec).train_setup(spec, job, mesh)
        state = init_state(weights.seed_key(seed))
        batches = traffic_gen.train_batches(mix, seed, spec["vocab_size"],
                                            job["batch"])
        losses = []
        for _ in range(steps):
            state, m = step_fn(state, {"tokens": next(batches)})
            losses.append(float(m["loss"]))
        del state
    return losses


def losses_main(args, spec) -> int:
    from benchmark import model_spec, traffic_gen
    from benchmark import run as bench_run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = next(w for w in json.load(f)["workloads"]
                    if w["name"] == args.cell)
    mix = traffic_gen.load_mix(cell["traffic"])
    with open(os.path.join(model_spec.HERE, "cells",
                           args.cell + ".json")) as f:
        job = json.load(f)["job"]
    seeds = ([int(s) for s in args.seed_list.split(",")] if args.seed_list
             else [args.first_seed + 7919 * i for i in range(args.seeds)])
    rows = []
    for i, seed in enumerate(seeds):
        for dead in ([False, True] if i < args.control_seeds else [False]):
            t0 = time.monotonic()
            losses = job_losses(spec, mix, job, seed, args.steps, dead)
            row = {"seed": seed, "cell": args.cell,
                   "control": "lr0" if dead else None, "losses": losses,
                   "loss_fall": bench_run.loss_fall(losses),
                   "seconds": time.monotonic() - t0}
            rows.append(row)
            print(json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"control_{args.cell}_losses.json"),
              "w") as f:
        json.dump(rows, f, indent=1)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--kind", choices=("serve", "train", "losses"),
                    required=True)
    ap.add_argument("--cell", help="the workload whose cells/<cell>.json "
                    "holds the deployment (kind serve) or the job (kind "
                    "losses)")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_147_483_000)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--modes", default="int8,int8w,fp8")
    ap.add_argument("--steps", type=int, default=16, help="kind losses: "
                    "steps of the job (the first, two of warm-up and what "
                    "a window of 51 s holds)")
    ap.add_argument("--seed-list", help="kind losses: these seeds, "
                    "comma-separated, in place of --seeds from --first-seed")
    args = ap.parse_args()

    from ray_tpu.common.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    from benchmark import checks, model_spec, weights

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no chip: jax reports {dev.platform!r}")
    spec = model_spec.load_config(args.config)
    if args.kind == "losses":
        if not args.cell:
            ap.error("--kind losses needs --cell")
        return losses_main(args, spec)
    ref = model_spec.reference(spec)
    if args.kind == "serve":
        if not args.cell:
            ap.error("--kind serve needs --cell")
        with open(os.path.join(model_spec.HERE, "cells",
                               args.cell + ".json")) as f:
            deployment = json.load(f)["deployment"]
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.monotonic()
        params = weights.make(spec, seed)
        row = {"seed": seed}
        if args.kind == "serve":
            got = checks.serve_check(params, spec, seed, deployment)
            row["program"] = {k: v["value"] for k, v in got.items()}
            if i < args.control_seeds:
                toks = checks.sample_tokens(
                    spec, seed, checks.SERVE_PREFILL + checks.SERVE_DECODE)
                want = checks.serve_reference_logits(params, spec, toks)
                for mode in args.modes.split(","):
                    c = checks.serve_reference_logits(params, spec, toks,
                                                      quant=mode)
                    row[mode] = {
                        "serve_prefill_logits_rel_err":
                            ref.rel_err(c[0], want[0]),
                        "serve_decode_logits_rel_err":
                            ref.rel_err(c[1:], want[1:])}
        else:
            got = checks.train_check(params, spec, seed, args.seq)
            row["program"] = {k: v["value"] for k, v in got.items()}
            if i < args.control_seeds:
                for mode in args.modes.split(","):
                    c = checks.train_check(params, spec, seed, args.seq,
                                           quant=mode)
                    row[mode] = {k: v["value"] for k, v in c.items()}
        row["seconds"] = time.monotonic() - t0
        del params
        rows.append(row)
        print(json.dumps(row), flush=True)
    names = sorted(rows[0]["program"])
    summary = {"config": args.config, "kind": args.kind,
               "device": dev.device_kind, "seeds": len(rows)}
    for name in names:
        summary[name] = {
            "program_largest": max(r["program"][name] for r in rows),
            "program_smallest": min(r["program"][name] for r in rows)}
        for mode in args.modes.split(","):
            vals = [r[mode][name] for r in rows if mode in r]
            if vals:
                summary[name][f"{mode}_smallest"] = min(vals)
    print(json.dumps(summary), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"control_{args.config}_{args.kind}.json"),
              "w") as f:
        json.dump({"rows": rows, "summary": summary}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
