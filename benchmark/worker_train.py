"""The train job's loop: runs inside the JaxTrainer worker, the process
that holds the chips. The benchmark's own function around the program's
``init_train_state`` and ``make_train_step``.
"""

from __future__ import annotations


def train_loop(config):
    import os
    import time

    import jax
    from ray_tpu import train
    from ray_tpu.common.compile_cache import compile_cache_counts

    from benchmark import (checks, model_spec, sizing, trace_reduce,
                           traffic_gen, weights)

    t_loop = time.monotonic()
    counts = compile_cache_counts()
    spec, mix = config["spec"], config["mix"]
    job = dict(config["job"], optimizer=mix["optimizer"])
    seed, seconds = config["seed"], config["seconds"]
    devices = jax.devices()
    dev = devices[0]
    times = {"jax_s": time.monotonic() - t_loop}
    if (dev.platform != "tpu" and not config["rehearse"]) \
            or len(devices) != config["chips"]:
        raise RuntimeError(
            f"no chip: this worker's jax reports {len(devices)} x "
            f"{dev.platform!r} ({dev.device_kind}), the cell asks for "
            f"{config['chips']} tpu")
    mesh = sizing.train_mesh(devices, job)
    batch, seq = job["batch"], mix["seq"]
    with jax.sharding.set_mesh(mesh):
        state_shape, step_fn, rules, init_state = model_spec.adapter(
            spec).train_setup(spec, job, mesh)
        # 1. correctness, before the train state takes the memory: the
        # program's loss and gradients on one seeded sequence against
        # the float32 reference, on weights of this seed
        t0 = time.monotonic()
        params = weights.make(spec, seed, jax.tree.map(
            lambda s: s.sharding, state_shape.params))
        check = checks.train_check(params, spec, seed, seq, rules)
        span = min(len(leaf.sharding.device_set)
                   for leaf in jax.tree.leaves(params))
        del params
        times["check_s"] = time.monotonic() - t0
        # 2. the train state, sharded from birth, from the same seed
        t0 = time.monotonic()
        state = init_state(weights.seed_key(seed))
        batches = traffic_gen.train_batches(mix, seed, spec["vocab_size"],
                                            batch)
        first = next(batches)
        compiled = step_fn.lower(state, {"tokens": first}).compile()
        times["init_and_compile_s"] = time.monotonic() - t0
        # 3. warm-up: the first step runs at learning rate 0
        losses = []
        state, m = compiled(state, {"tokens": first})
        losses.append(float(m["loss"]))
        for _ in range(job.get("warmup_steps", 2)):
            state, m = compiled(state, {"tokens": next(batches)})
            losses.append(float(m["loss"]))
        requests_before = dict(counts)
        # 4. the window: whole steps, one kept in flight, closed by the
        # last loss reaching the host. A traced run wraps a few steps of
        # the window in the profiler.
        trace_dir = os.path.join(config["out_dir"], "trace")
        trace_steps = job.get("trace_steps", 3) if config["trace"] else 0
        traced = False
        pending, n = None, 0

        def one_step():
            nonlocal state, pending, n
            state, m = compiled(state, {"tokens": next(batches)})
            n += 1
            if pending is not None:          # waits for the step before
                losses.append(float(pending["loss"]))
            pending = m

        def drain():
            nonlocal pending
            if pending is not None:
                losses.append(float(pending["loss"]))
                pending = None

        window_open = time.monotonic()
        while True:
            one_step()
            if trace_steps and n == 1:
                drain()
                os.makedirs(trace_dir, exist_ok=True)
                jax.profiler.start_trace(
                    trace_dir,
                    profiler_options=trace_reduce.profiler_options())
                for _ in range(trace_steps):
                    one_step()
                drain()
                traced = True
                jax.profiler.stop_trace()
            if time.monotonic() - window_open >= seconds:
                break
        drain()
        window_s = time.monotonic() - window_open
    mem = dev.memory_stats() or {}
    out = {
        "check": check, "times": times, "losses": losses,
        "steps": n, "window_s": window_s, "window_open": window_open,
        "loop_start": t_loop, "tokens_per_step": batch * seq,
        "param_device_span": span,
        "compile_requests_before": requests_before,
        "compile_requests_after": dict(counts),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": mem.get("peak_bytes_in_use"),
                   "bytes_limit": mem.get("bytes_limit")},
    }
    if traced:
        path = trace_reduce.find_xplane(trace_dir)
        summary = trace_reduce.reduce_planes(trace_reduce.read_xplane(path, config["rehearse"]))
        summary["layout"] = trace_reduce.list_planes(path)
        summary["traced_steps"] = trace_steps
        out["trace"] = summary
    train.report(out)
