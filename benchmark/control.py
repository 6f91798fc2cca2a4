"""The control of ``correct``, on the chip at a configuration's own
size: the numbers sound runs of the program give on many seeds, beside
the numbers the control gives (the configuration's reference with its
weight matrix multiplies computed in int8, the step below bfloat16 that
this chip has units for). A limits file (``benchmark/limits.json``, or
the one a configuration names) is set from what this prints.

    python3 benchmark/control.py --config mistral-7b-l16 --kind serve \
        --cell serve-chat-steady --seeds 12 --control-seeds 3

One process that holds the chip itself (no cluster): the benchmark's own
runs never run it. The same comparison at a tiny size is a test under
``benchmark/tests``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--kind", choices=("serve", "train"), required=True)
    ap.add_argument("--cell", help="kind serve: the workload whose "
                    "cells/<cell>.json holds the deployment")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_147_483_000)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--modes", default="int8,int8w,fp8")
    args = ap.parse_args()

    from ray_tpu.common.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    from benchmark import checks, model_spec, weights

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no chip: jax reports {dev.platform!r}")
    spec = model_spec.load_config(args.config)
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
