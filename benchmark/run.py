"""One run of one cell, in a new process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json`` and everything that belongs to it by
name: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``cells/<workload>.json`` and, in a traced run,
``layer_metrics/<metric>.py|json``. Brings the cluster up, loads, warms
up only this cell's shapes, measures for ``--seconds``, checks the
outputs, shuts everything down and prints the result object as the last
line. This process is the ray_tpu driver and NEVER imports jax: the
device is named by the worker that held it.
"""

from __future__ import annotations

import time

T_START = time.monotonic()          # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)            # `benchmark` and `ray_tpu` as packages

from benchmark import cluster, loadgen, model_spec, traffic_gen  # noqa: E402

APP = "bench"
RUN_LIMIT_S = 1150.0    # a first run compiles and may take 1200 s; none may hang


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_START:7.2f}s] {msg}", flush=True)


def watchdog() -> None:
    """A run that outlives its limit is a fault (a worker that never got
    its chip, a call that never returns): stop every process it started
    and exit non-zero without a result, rather than hang the machine."""
    import threading

    def abort():
        say(f"no result after {RUN_LIMIT_S:.0f}s: stopping everything")
        for name in ("raylet.log", "gcs.log"):      # why, if they say
            try:
                with open(f"/tmp/rt/session_{os.getpid()}/{name}") as f:
                    print(f"--- {name}\n" + "".join(f.readlines()[-25:]),
                          flush=True)
            except OSError:
                pass
        cluster.stop_everything(cluster.census(), grace_s=0.0)
        os._exit(3)

    timer = threading.Timer(RUN_LIMIT_S, abort)
    timer.daemon = True
    timer.start()


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def check_device(device: dict, chips: int, who: str,
                 rehearse: bool) -> dict:
    """The worker's device must be the chips the cell asks for, and of a
    kind whose peaks are known: anything else fails the run."""
    table = load_json(HERE, "peaks.json")["devices"]
    entry = table.get(device["kind"])
    if entry is None:
        raise SystemExit(f"device kind {device['kind']!r} is not in "
                         "benchmark/peaks.json: no peak, no result")
    real = device["platform"] == "tpu" and not entry.get("rehearsal_only")
    if (not real and not rehearse) or device["count"] != chips:
        raise SystemExit(f"no chip: the {who} reports {device}, the cell "
                         f"asks for {chips} tpu")
    return entry


# ----------------------------------------------------------------- serving
def _call(handle, method, *args, timeout=1200.0, **kwargs):
    import ray_tpu

    return ray_tpu.get(handle.options(method_name=method).remote(
        *args, **kwargs), timeout=timeout)


def serve_cell(args, cell, spec, mix, cellfile, out_dir) -> dict:
    """Kinds ``open_loop_sse`` and ``closed_loop_handle``: one replica of
    BenchServer behind serve.run, load from this process."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.deployment import make_deployment

    from benchmark.worker_serve import BenchServer

    dep = cellfile["deployment"]
    vocab = spec["vocab_size"]
    t_run = time.monotonic()
    addr = serve.start(http_port=0, grpc_port=None)
    app = make_deployment(
        BenchServer, name=APP,
        max_ongoing_requests=dep["max_ongoing_requests"],
        ray_actor_options={"num_tpus": 1}).bind(spec, dep, args.seed,
                                                out_dir, args.rehearse)
    serve.run(app, name=APP, wait_timeout_s=900.0)
    handle = serve.get_deployment_handle(APP)
    info = _call(handle, "bench_info")
    ready_s = time.monotonic() - t_run
    device = info["device"]
    peaks = check_device(device, cell["chips"], "replica", args.rehearse)
    say(f"replica ready in {ready_s:.1f}s on {device}")
    cluster.census()        # controller, proxy and replica are up
    check = _call(handle, "check")
    say(f"check {check}")

    # warm up this mix's prefill buckets and the decode step, and nothing
    # else; then the HTTP route and its stream protocol
    temperature = mix["temperature"]
    rng = traffic_gen.seeded_rng(args.seed, 9)
    for b in traffic_gen.prompt_buckets(mix):
        t0 = time.monotonic()
        out = ray_tpu.get(handle.remote(
            rng.integers(0, vocab, b).tolist(), max_tokens=3,
            temperature=temperature), timeout=1200.0)
        if len(out) != 3:
            raise SystemExit(f"warm-up of bucket {b} answered {out!r}")
        say(f"warm bucket {b}: {time.monotonic() - t0:.1f}s")
    probe_prompt = rng.integers(0, vocab, 200).tolist()
    probe = dict(max_tokens=24, temperature=0.0)
    probe_before = ray_tpu.get(handle.remote(probe_prompt, **probe),
                               timeout=600.0)

    sse = dict(host=addr["http_host"], port=addr["http_port"],
               path=f"/{APP}", temperature=temperature)
    if mix["kind"] == "open_loop_sse":
        now = time.monotonic()
        warm = loadgen.open_loop_sse(
            iter([{"i": -1, "gap_s": 0.0, "prompt": probe_prompt,
                   "max_tokens": 4},
                  {"i": -2, "gap_s": 1e9, "prompt": [], "max_tokens": 0}]),
            start_at=now, stop_sending_at=now + 5.0, drain_s=120.0, **sse)
        if not warm[0].ok:
            raise SystemExit(f"SSE through the proxy does not work: "
                             f"{warm[0].error} status {warm[0].status}")

    marks, refs = {}, {}
    start_at = time.monotonic() + 0.2
    t_open = start_at + mix["lead_s"]
    t_close = t_open + args.seconds
    t_trace = t_open + mix["trace_offset_s"]
    say(f"load starts; the window of {args.seconds:g}s opens in "
        f"{t_open - time.monotonic():.1f}s")

    def on_tick(now):
        def fire(name, method, *args):
            marks[name] = now
            refs[name] = handle.options(method_name=method).remote(*args)
        if "open" not in marks and now >= t_open:
            fire("open", "bench_info")
        if "close" not in marks and now >= t_close:
            fire("close", "bench_info")
        if args.trace:
            if "trace_start" not in marks and now >= t_trace:
                fire("trace_start", "trace_start")
            if "trace_stop" not in marks and now >= t_trace + mix["trace_s"]:
                fire("trace_stop", "trace_stop", mix["trace_s"])

    requests = traffic_gen.request_stream(mix, args.seed, vocab,
                                          args.seconds)
    if mix["kind"] == "open_loop_sse":
        work = loadgen.open_loop_sse(
            requests, start_at=start_at, stop_sending_at=t_close,
            drain_s=mix["drain_s"], on_tick=on_tick, **sse)
    else:
        work = loadgen.closed_loop_handle(
            requests, handle, clients=mix["clients"],
            temperature=temperature, stop_sending_at=t_close,
            drain_s=mix["drain_s"], on_tick=on_tick)
    t_end = time.monotonic()
    on_tick(t_end)      # a window shorter than the trace still closes it
    got = {k: ray_tpu.get(r, timeout=600.0) for k, r in refs.items()}
    probe_after = ray_tpu.get(handle.remote(probe_prompt, **probe),
                              timeout=600.0)
    final = _call(handle, "bench_info")
    # let the engine run dry and the replica leave by the front door: a
    # process killed in the middle of a device step can leave the chip
    # unusable for the next run
    deadline = time.monotonic() + 90.0
    while time.monotonic() < deadline:
        st = _call(handle, "stats")
        if st["active_slots"] == 0 and st["queued"] == 0:
            break
        time.sleep(0.5)
    serve.delete(APP)
    deadline = time.monotonic() + 60.0
    while cluster.running(final["pid"]) and time.monotonic() < deadline:
        time.sleep(0.2)
    return {"kind": mix["kind"], "work": work, "t_open": t_open,
            "t_close": t_close, "t_end": t_end, "ready_s": ready_s,
            "check": check, "probe_same": probe_before == probe_after,
            "open": got["open"], "close": got["close"], "final": final,
            "trace": got.get("trace_stop"), "device": final["device"],
            "peaks": peaks}


def serve_results(args, cell, spec, mix, r) -> dict:
    """End-to-end numbers of a serve cell, from this host's clock."""
    vocab = spec["vocab_size"]
    t_open, t_close = r["t_open"], r["t_close"]
    e2e, notes = {}, {}
    if r["kind"] == "open_loop_sse":
        inwin = [s for s in r["work"] if t_open <= s.due < t_close]
        ok = [s for s in inwin if s.ok]
        shape_ok = all(0 <= t < vocab for s in ok for t in s.tokens)
        ttft = [(s.token_times[0] if s.ok else r["t_end"]) - s.due
                for s in inwin]
        gaps = [b - a for s in ok
                for a, b in zip(s.token_times, s.token_times[1:])]
        lag = [s.sent - s.due for s in inwin if s.sent is not None]
        client = {"ttft_p95_ms": 1e3 * loadgen.percentile(ttft, 95),
                  "itl_p95_ms": 1e3 * loadgen.percentile(gaps, 95),
                  "ttft_p50_ms": 1e3 * statistics.median(ttft),
                  "itl_p50_ms": 1e3 * statistics.median(gaps)}
        e2e.update(client)      # main() keeps what BENCHMARK.json lists
        r["client"] = client
        notes = dict(client, ttft_samples=len(ttft), itl_samples=len(gaps),
                     loadgen_lag_p95_ms=1e3 * loadgen.percentile(lag, 95),
                     errors=sorted({s.error for s in inwin if s.error})[:5])
        r["lag_s"] = lag
    else:
        inwin = [c for c in r["work"]
                 if c.sent < t_close and (c.finished or t_close) > t_open]
        ok = [c for c in inwin if c.error is None]
        shape_ok = all(len(c.tokens) == c.max_tokens
                       and all(0 <= t < vocab for t in c.tokens) for c in ok)
        # tokens of answers, each counted by the share of its life (sent
        # to answered) that fell inside the window
        tokens = sum(
            len(c.tokens) * (min(c.finished, t_close) - max(c.sent, t_open))
            / (c.finished - c.sent) for c in ok)
        whole = sum(len(c.tokens) for c in ok
                    if t_open <= c.finished < t_close)
        e2e["output_tokens_per_s"] = tokens / (t_close - t_open)
        notes = {"answers_completed_in_window": sum(
                     1 for c in ok if t_open <= c.finished < t_close),
                 "tokens_of_answers_completed_in_window_per_s":
                     whole / (t_close - t_open),
                 "errors": sorted({c.error for c in inwin if c.error})[:5]}
    e2e["setup_s"] = t_open - T_START
    so, sc = r["open"]["stats"], r["close"]["stats"]
    notes.update(sent=len(inwin), succeeded=len(ok),
                 failed=len(inwin) - len(ok),
                 backlog_at_open=so["queued"] + so["active_slots"],
                 backlog_at_close=sc["queued"] + sc["active_slots"],
                 preemptions=sc["preemptions"] - so["preemptions"],
                 replica_times=r["final"]["times"])
    say(f"notes {json.dumps(notes)}")
    numbers = dict(r["check"])
    numbers["greedy_probe_differs"] = {
        "value": 0 if r["probe_same"] else 1, "limit": 0}
    numbers["answers_of_wrong_shape"] = {
        "value": 0 if shape_ok else 1, "limit": 0}
    return {"e2e": e2e, "attempted": len(inwin),
            "failed": len(inwin) - len(ok), "numbers": numbers,
            "window_s": t_close - t_open}


# ---------------------------------------------------------------- training
def train_cell(args, cell, spec, mix, cellfile, out_dir) -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    from benchmark.worker_train import train_loop

    t_fit = time.monotonic()
    cluster.census_in(10.0)     # the worker is up, its set-up under way
    result = JaxTrainer(
        train_loop,
        train_loop_config={"spec": spec, "mix": mix, "job": cellfile["job"],
                           "seed": args.seed, "seconds": args.seconds,
                           "trace": args.trace, "chips": cell["chips"],
                           "out_dir": out_dir, "rehearse": args.rehearse},
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True,
            resources_per_worker={"TPU": float(cell["chips"])}),
        run_config=RunConfig(name="job",
                             storage_path=os.path.join(out_dir, "train")),
    ).fit(timeout_s=1500.0)
    m = result.metrics
    device = m["device"]
    m["peaks"] = check_device(device, cell["chips"], "train worker",
                              args.rehearse)
    m["t_fit"] = t_fit
    return m


LOSS_ENDS = 3   # steps at each end of a job whose mean loss is compared


def loss_fall(losses: list) -> float:
    """How far the job's loss fell: the mean of its first ``LOSS_ENDS``
    steps less the mean of its last. One step's batch moves a loss by a
    few hundredths, which a single first loss against a single last one
    (how this was read until PR 41) cannot tell from an optimizer that
    does nothing; the means over three steps can."""
    k = min(LOSS_ENDS, len(losses) // 2)
    if not k:
        return float("nan")
    return sum(losses[:k]) / k - sum(losses[-k:]) / k


def train_results(args, cell, spec, mix, m) -> dict:
    losses = m["losses"]
    say(f"steps {m['steps']} window {m['window_s']:.2f}s times {m['times']} "
        f"losses {json.dumps(losses)}")
    numbers = dict(m["check"])
    numbers["losses_not_finite"] = {
        "value": sum(1 for x in losses if not (x == x and abs(x) < 1e4)),
        "limit": 0}
    fall = loss_fall(losses)
    numbers["loss_fall"] = {"value": fall, "limit": None}
    # the margin of the configuration's own limits file where it has one
    least = (model_spec.limits(spec).get("loss_fall_min")
             or load_json(HERE, "limits.json")["limits"]["loss_fall_min"])
    numbers["loss_did_not_fall"] = {
        "value": 0 if fall > least["limit"] else 1, "limit": 0}
    if cell["chips"] > 1:
        numbers["parameters_not_spanning_all_chips"] = {
            "value": 0 if m["param_device_span"] == cell["chips"] else 1,
            "limit": 0}
    e2e = {"train_tokens_per_s":
           m["steps"] * m["tokens_per_step"] / m["window_s"],
           "setup_s": m["window_open"] - T_START}
    return {"e2e": e2e, "attempted": m["steps"], "failed": 0,
            "numbers": numbers, "window_s": m["window_s"]}


KINDS = {"open_loop_sse": (serve_cell, serve_results, True),
         "closed_loop_handle": (serve_cell, serve_results, True),
         "train_job": (train_cell, train_results, False)}


# ------------------------------------------------------- per-layer metrics
def load_reader(name: str):
    """``layer_metrics/<name>.py`` with ``read(run)``, or
    ``layer_metrics/<name>.json`` naming another reader and arguments."""
    folder = os.path.join(HERE, "layer_metrics")
    if folder not in sys.path:
        sys.path.insert(0, folder)      # the readers' shared `_lib`
    base = os.path.join(folder, name)
    if os.path.exists(base + ".json"):
        alias = load_json(base + ".json")
        inner = load_reader(alias["reader"])
        return lambda run: inner(run, **alias.get("args", {}))
    if not os.path.exists(base + ".py"):
        raise SystemExit(f"per-layer metric {name!r} has no reader under "
                         "benchmark/layer_metrics/")
    return model_spec.load_module(base + ".py").read


def reports(metric: dict, workload: str, e2e_of_cell: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_of_cell


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="find faults on a CPU at a tiny size: pretend "
                    "chips, the line names the cpu, nothing it prints is "
                    "a measurement")
    args = ap.parse_args()
    watchdog()
    cluster.exit_on_signals()

    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json")
    cell = cells[args.workload]
    spec = model_spec.load_config(cell["config"])
    mix = traffic_gen.load_mix(cell["traffic"])
    cellfile = load_json(HERE, "cells", cell["name"] + ".json")
    if mix["kind"] not in KINDS:
        raise SystemExit(f"traffic kind {mix['kind']!r}: one of "
                         f"{sorted(KINDS)}")
    measure, results, serving = KINDS[mix["kind"]]
    out_dir = os.path.join(HERE, ".out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    e2e_names = {m["name"] for m in bench["end_to_end"]
                 if cell["name"] in m.get("workloads", [cell["name"]])}

    with cluster.Cluster(cell["chips"], serve=serving, pretend=args.rehearse):
        raw = measure(args, cell, spec, mix, cellfile, out_dir)
    if "jax" in sys.modules:
        raise SystemExit("the driver imported jax")
    res = results(args, cell, spec, mix, raw)

    correct = True
    for name, n in res["numbers"].items():
        if n["limit"] is None:
            say(f"read {name}: {n['value']!r} (not judged)")
            continue
        ok = n["value"] == n["value"] and n["value"] <= n["limit"]
        correct &= ok
        say(f"compared {name}: {n['value']!r} limit {n['limit']!r} "
            f"{'ok' if ok else 'NOT CORRECT'}")
    device = raw["device"]
    out_device = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"],
                  "memory_peak_bytes": device["memory_peak_bytes"]}
    e2e = {k: v for k, v in res["e2e"].items() if k in e2e_names}
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    say(f"end to end {json.dumps(res['e2e'])}")
    line = {"correct": bool(correct), "attempted": res["attempted"],
            "failed": res["failed"]}
    if not args.trace:
        metrics = e2e
    else:
        trace = raw.get("trace")
        if not trace or trace["busy_s"] <= 0:
            raise SystemExit("traced run: no operation ran on the device "
                             "inside the traced window")
        from benchmark import trace_reduce

        out_device["busy_s"] = trace["busy_s"]
        out_device["window_s"] = trace["window_s"]
        run = {"cell": cell, "spec": spec, "mix": mix, "cellfile": cellfile,
               "raw": raw, "res": res, "trace": trace, "T_START": T_START,
               "peaks": raw["peaks"], "seconds": args.seconds}
        metrics = {}
        for m in bench["per_layer"]:
            if not reports(m, cell["name"], set(e2e)):
                continue
            value = load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = value
        line["breakdown"] = trace_reduce.breakdown(trace)
        say(f"trace layout {json.dumps(trace.get('layout'))[:3000]}")
        say("programs " + json.dumps(trace["programs"])[:3000])
    line["metrics"] = {k: {"value": v, "unit": units[k]}
                       for k, v in metrics.items()}
    line["device"] = out_device
    # every number that was compared beside its limit: last in the line,
    # and as the last lines of standard error (what a record of a run
    # that is not correct keeps)
    line["compared"] = {
        name: dict(n, value=n["value"] if abs(n["value"]) < float("inf")
                   else repr(n["value"]))       # a NaN is no JSON number
        for name, n in res["numbers"].items() if n["limit"] is not None}
    for name, n in line["compared"].items():
        print(f"compared {name}: {n['value']!r} limit {n['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
