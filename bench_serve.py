"""Serve/LLM throughput benchmark (BASELINE target #5 discipline).

Drives the continuous-batching engine (``ray_tpu/serve/llm.py``) directly —
the replica hot path, without HTTP overhead — with a closed-loop client
pool, and reports decode throughput (tokens/s), time-to-first-token, and
slot occupancy as ONE JSON line per config, plus a summary line in the
driver's ``{"metric": ...}`` shape.

The engine rows run the 1b config and need a TPU: where jax finds none the
bench says so and exits non-zero (bench.py's contract — a CPU timing is
never written under a device metric's name). ``--proxy``, ``--prefix`` and
``--overload`` measure host-side orchestration and run anywhere.
"""

from __future__ import annotations

import json
import sys
import threading
import time


def run_engine_bench(model: str, num_slots: int, n_requests: int,
                     prompt_len: int, max_tokens: int,
                     max_seq: int = 2048) -> dict:
    import numpy as np

    from ray_tpu.serve.llm import LLMEngine

    # bound max_seq: the 1b config's native 8192 would size the KV pool
    # (and the old slot cache alike) past one v5e's HBM at 8 slots
    engine = LLMEngine(model=model, num_slots=num_slots, max_seq=max_seq)
    rng = np.random.default_rng(0)
    vocab = engine.config.vocab_size

    # warmup: compile prefill + decode
    engine.generate(list(rng.integers(1, vocab, size=prompt_len)),
                    max_tokens=4)

    ttfts: list = []
    done_tokens = [0]
    lock = threading.Lock()
    occupancy_samples: list = []

    def client(i):
        prompt = list(rng.integers(1, vocab, size=prompt_len))
        t0 = time.perf_counter()
        rid = engine.submit(prompt, max_tokens=max_tokens)
        first = None
        collected = 0
        while True:
            st = engine.poll(rid)
            collected += len(st["chunks"])
            if first is None and collected:
                first = time.perf_counter() - t0
            if st["done"]:
                break
            time.sleep(0.005)
        with lock:
            ttfts.append(first if first is not None
                         else time.perf_counter() - t0)
            done_tokens[0] += collected

    def sampler(stop):
        while not stop.is_set():
            occupancy_samples.append(
                engine.stats()["active_slots"] / num_slots)
            time.sleep(0.05)

    stop = threading.Event()
    threading.Thread(target=sampler, args=(stop,), daemon=True).start()
    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    stop.set()
    stats = engine.stats()
    engine.shutdown()
    import numpy as np

    return {
        "model": model,
        "num_slots": num_slots,
        "n_requests": n_requests,
        "prompt_len": prompt_len,
        "max_tokens": max_tokens,
        "wall_s": round(dt, 2),
        "decode_tokens_per_s": round(done_tokens[0] / dt, 1),
        "ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1000, 1),
        "ttft_p95_ms": round(float(np.percentile(ttfts, 95)) * 1000, 1),
        "slot_occupancy_mean": round(float(np.mean(occupancy_samples)), 3)
        if occupancy_samples else None,
        "engine_steps": stats["steps"],
        "kv_cache": stats.get("kv_cache"),
        "kv_preemptions": stats.get("preemptions"),
    }


def run_chunked_prefill_bench(model: str, long_len: int = 48,
                              chunk: int = 8) -> dict:
    """TTFT interference: p95 TTFT of SHORT requests arriving while LONG
    prompts keep prefilling — chunked vs monolithic prefill. Chunking
    bounds the decode-stall a long prompt inflicts on everyone else."""
    import numpy as np

    from ray_tpu.serve.llm import LLMEngine

    out = {}
    for label, kwargs in (("monolithic", {}),
                          ("chunked", {"prefill_chunk": chunk})):
        engine = LLMEngine(model=model, num_slots=4, kv_cache="slot",
                           **kwargs)
        rng = np.random.default_rng(0)
        vocab = engine.config.vocab_size
        engine.generate(list(rng.integers(1, vocab, size=long_len)),
                        max_tokens=2)  # compile both programs
        engine.generate([1, 2, 3], max_tokens=2)
        ttfts = []
        stop = threading.Event()

        def long_feeder():
            while not stop.is_set():
                engine.generate(
                    list(rng.integers(1, vocab, size=long_len)),
                    max_tokens=2)

        t = threading.Thread(target=long_feeder, daemon=True)
        t.start()
        for _ in range(20):
            t0 = time.perf_counter()
            rid = engine.submit([7, 8, 9], max_tokens=2)
            while not engine.poll(rid)["chunks"]:
                time.sleep(0.001)
            ttfts.append(time.perf_counter() - t0)
        stop.set()
        t.join(timeout=30)
        engine.shutdown()
        out[label] = {
            "short_ttft_p50_ms": round(
                float(np.percentile(ttfts, 50)) * 1000, 1),
            "short_ttft_p95_ms": round(
                float(np.percentile(ttfts, 95)) * 1000, 1),
        }
    out["long_len"] = long_len
    out["prefill_chunk"] = chunk
    return out


def run_speculation_bench(model: str, n_requests: int = 8,
                          prompt_len: int = 24, max_tokens: int = 48,
                          num_slots: int = 4, spec_k: int = 4) -> dict:
    """Spec-vs-baseline decode throughput + acceptance rate, batched
    under continuous batching (same workload, same weights, slot cache
    for all three engines). The draft row shares the target weights —
    an acceptance-rate CEILING with random init; a trained smaller
    draft trades acceptance for cheaper proposal steps."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMEngine

    cfg = llama.CONFIGS[model]
    params = llama.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    vocab = cfg.vocab_size
    # half repetitive prompts (prompt-lookup hits), half structureless
    prompts = []
    for i in range(n_requests):
        if i % 2 == 0:
            unit = [int(t) for t in rng.integers(1, vocab, size=4)]
            prompts.append((unit * (prompt_len // 4 + 1))[:prompt_len])
        else:
            prompts.append(
                [int(t) for t in rng.integers(1, vocab, size=prompt_len)])
    configs = (
        ("baseline", {}),
        ("ngram", {"speculation": {"method": "ngram", "k": spec_k}}),
        ("draft", {"speculation": {"method": "draft", "k": spec_k,
                                   "draft_config": cfg,
                                   "draft_params": params}}),
    )
    rows = []
    for label, kw in configs:
        # a copy: the engine owns the tree it is given (on a TPU it
        # re-lays wq / wk / wv and donates them), and the next engine and
        # the draft row read ``params`` itself
        engine = LLMEngine(config=cfg, params=jax.tree.map(jnp.copy, params),
                           num_slots=num_slots, kv_cache="slot", seed=0,
                           **kw)
        # warmup compiles prefill bucket + decode/verify (+ draft)
        # paths: a repetitive prompt guarantees ngram proposals (verify
        # program), a structureless one the no-proposal plain-decode
        # fallback
        unit = [int(t) for t in rng.integers(1, vocab, size=3)]
        engine.generate((unit * prompt_len)[:prompt_len], max_tokens=4)
        engine.generate(
            [int(t) for t in rng.integers(1, vocab, size=prompt_len)],
            max_tokens=4)
        warm = engine.stats()
        t0 = time.perf_counter()
        rids = [engine.submit(p, max_tokens=max_tokens) for p in prompts]
        done = set()
        total = 0
        while len(done) < len(rids):
            for rid in rids:
                if rid in done:
                    continue
                st = engine.poll(rid)
                total += len(st["chunks"])
                if st["done"]:
                    done.add(rid)
            time.sleep(0.002)
        dt = time.perf_counter() - t0
        stats = engine.stats()
        engine.shutdown()
        # deltas over the timed window only — the warmup's repetitive
        # prompt guarantees proposals and would inflate the rate
        proposed = stats["spec_proposed"] - warm["spec_proposed"]
        accepted = stats["spec_accepted"] - warm["spec_accepted"]
        rows.append({
            "speculation": label,
            "decode_tokens_per_s": round(total / dt, 1),
            "acceptance_rate": (round(accepted / proposed, 4)
                                if proposed else None),
            "spec_proposed": proposed,
            "engine_steps": stats["steps"] - warm["steps"],
            "device": jax.default_backend(),
        })
    base = rows[0]["decode_tokens_per_s"]
    for row in rows[1:]:
        row["vs_baseline"] = round(row["decode_tokens_per_s"] / base, 2) \
            if base else None
    return {"model": model, "num_slots": num_slots,
            "n_requests": n_requests, "prompt_len": prompt_len,
            "max_tokens": max_tokens, "spec_k": spec_k, "rows": rows,
            "draft_note": ("draft shares the target weights: acceptance "
                           "ceiling, not a trained-draft speedup claim")}


# --------------------------------------------------------------- proxy/RPS
def _http_keepalive_worker(host: str, port: int, path: str, body: bytes,
                           n_requests: int, latencies: list, errors: list):
    """Closed-loop client on ONE keep-alive connection: send a request,
    read the full response, repeat.  Raw sockets (not urllib) so the
    connection is reused and per-request latency excludes connect cost."""
    import socket

    req = (f"POST {path} HTTP/1.1\r\n"
           f"host: {host}\r\n"
           f"content-length: {len(body)}\r\n"
           f"\r\n").encode() + body
    sock = socket.create_connection((host, port), timeout=60)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    try:
        buf = b""
        for _ in range(n_requests):
            t0 = time.perf_counter()
            sock.sendall(req)
            # read one response: headers, then content-length bytes
            while b"\r\n\r\n" not in buf:
                chunk = sock.recv(65536)
                if not chunk:
                    raise ConnectionError("server closed mid-response")
                buf += chunk
            head, _, buf = buf.partition(b"\r\n\r\n")
            clen = 0
            for line in head.split(b"\r\n")[1:]:
                name, _, value = line.partition(b":")
                if name.strip().lower() == b"content-length":
                    clen = int(value.strip())
            while len(buf) < clen:
                chunk = sock.recv(65536)
                if not chunk:
                    raise ConnectionError("server closed mid-body")
                buf += chunk
            buf = buf[clen:]
            if not head.startswith(b"HTTP/1.1 200"):
                raise RuntimeError(head.split(b"\r\n", 1)[0].decode())
            latencies.append(time.perf_counter() - t0)
    except Exception as e:  # noqa: BLE001 — one row, not a crash
        errors.append(repr(e))
    finally:
        sock.close()


def _sse_stream_worker(host: str, port: int, path: str, body: bytes,
                       token_counts: list, errors: list):
    """One SSE stream: POST with Accept: text/event-stream, count data
    events until [DONE]."""
    import socket

    req = (f"POST {path} HTTP/1.1\r\n"
           f"host: {host}\r\n"
           f"accept: text/event-stream\r\n"
           f"content-length: {len(body)}\r\n"
           f"\r\n").encode() + body
    sock = socket.create_connection((host, port), timeout=120)
    try:
        sock.sendall(req)
        buf, tokens, done = b"", 0, False
        while not done:
            chunk = sock.recv(65536)
            if not chunk:
                break
            buf += chunk
            while b"\n" in buf:
                line, _, buf = buf.partition(b"\n")
                line = line.strip()
                if line == b"data: [DONE]":
                    done = True
                elif line.startswith(b"data: "):
                    tokens += 1
        token_counts.append(tokens)
    except Exception as e:  # noqa: BLE001
        errors.append(repr(e))
    finally:
        sock.close()


def run_proxy_bench(conns: int = 8, requests_per_conn: int = 250,
                    handle_clients: int = 4, handle_calls: int = 250,
                    sse_streams: int = 4, sse_rounds: int = 2,
                    sse_tokens: int = 48) -> dict:
    """End-to-end Serve data-plane rows (PERF_PLAN round-11): proxy RPS +
    latency percentiles over keep-alive HTTP against a plain echo
    deployment, a handle-only row (routing cost without HTTP), and SSE
    streaming tokens/s through the LLM debug deployment.

    These are CPU orchestration rows by design: they measure the
    proxy→handle→replica→response path, not model math (the same caption
    discipline as the speculation rows)."""
    import numpy as np

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4, num_tpus=0)
    addr = serve.start(http_port=0, grpc_port=None)
    host, port = addr["http_host"], addr["http_port"]
    rows = []
    try:
        @serve.deployment(name="bench_echo")
        class Echo:
            def __call__(self, request):
                return {"n": len(request.body)}

        serve.run(Echo.bind())
        body = b"x" * 64
        # warmup: route resolution + replica spin-up off the timed path
        warm_lat: list = []
        _http_keepalive_worker(host, port, "/bench_echo", body, 20,
                               warm_lat, [])

        latencies: list = []
        errors: list = []
        threads = [threading.Thread(
            target=_http_keepalive_worker,
            args=(host, port, "/bench_echo", body, requests_per_conn,
                  latencies, errors)) for _ in range(conns)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        if errors:
            raise RuntimeError(f"proxy bench client errors: {errors[:3]}")
        rows.append({
            "metric": "proxy_rps_plain",
            "value": round(len(latencies) / dt, 1),
            "unit": "requests/s",
            "p50_ms": round(float(np.percentile(latencies, 50)) * 1000, 2),
            "p99_ms": round(float(np.percentile(latencies, 99)) * 1000, 2),
            "conns": conns,
            "requests": len(latencies),
        })

        # handle-only: same replica set, no HTTP — separates routing cost
        # from HTTP parse/render cost
        from ray_tpu.serve.proxy import Request

        handle = serve.get_deployment_handle("bench_echo")
        req = Request(method="POST", path="/bench_echo", query={},
                      headers={}, body=body)
        hl_lat: list = []
        hl_errors: list = []

        def handle_client():
            try:
                for _ in range(handle_calls):
                    t0 = time.perf_counter()
                    ray_tpu.get(handle.remote(req), timeout=60.0)
                    hl_lat.append(time.perf_counter() - t0)
            except Exception as e:  # noqa: BLE001
                hl_errors.append(repr(e))

        ray_tpu.get(handle.remote(req), timeout=60.0)  # warm
        threads = [threading.Thread(target=handle_client)
                   for _ in range(handle_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        if hl_errors:
            raise RuntimeError(f"handle bench errors: {hl_errors[:3]}")
        rows.append({
            "metric": "handle_calls_per_second",
            "value": round(len(hl_lat) / dt, 1),
            "unit": "calls/s",
            "p50_ms": round(float(np.percentile(hl_lat, 50)) * 1000, 2),
            "p99_ms": round(float(np.percentile(hl_lat, 99)) * 1000, 2),
            "clients": handle_clients,
        })
        serve.delete("bench_echo")

        # SSE streaming: LLM debug deployment, concurrent streams
        from ray_tpu.serve.llm import LLMServer

        dep = serve.deployment(LLMServer, name="bench_llm",
                               max_ongoing_requests=max(4, sse_streams))
        serve.run(dep.bind("debug"), name="bench_llm")
        sse_body = json.dumps({"prompt": [1, 2, 3],
                               "max_tokens": sse_tokens}).encode()
        # warmup compiles prefill/decode
        _sse_stream_worker(host, port, "/bench_llm", sse_body, [], [])
        counts: list = []
        sse_errors: list = []
        t0 = time.perf_counter()
        for _ in range(sse_rounds):
            threads = [threading.Thread(
                target=_sse_stream_worker,
                args=(host, port, "/bench_llm", sse_body, counts,
                      sse_errors)) for _ in range(sse_streams)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        dt = time.perf_counter() - t0
        if sse_errors:
            raise RuntimeError(f"sse bench errors: {sse_errors[:3]}")
        rows.append({
            "metric": "sse_tokens_per_second",
            "value": round(sum(counts) / dt, 1),
            "unit": "tokens/s",
            "streams": sse_streams,
            "rounds": sse_rounds,
            "tokens_per_stream": sse_tokens,
        })
        serve.delete("bench_llm")

        # per-stage accounting from the proxy, when it exports it
        try:
            proxy = ray_tpu.get_actor("SERVE_PROXY")
            dbg = ray_tpu.get([proxy.debug_state.remote()], timeout=10.0)[0]
        except Exception:  # noqa: BLE001 — pre-round-11 proxy
            dbg = None
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return {"results": rows, "proxy_debug_state": dbg}


# ---------------------------------------------------------- overload/chaos
def _typed_fire(url: str, out: list, lock) -> None:
    """One request on its own connection; append (status, latency_s).
    Typed HTTP errors (429/503) are answers; anything untyped records
    status 0 — the caller fails the bench on those."""
    import urllib.error
    import urllib.request

    t0 = time.perf_counter()
    try:
        with urllib.request.urlopen(
                urllib.request.Request(url, data=b"x"), timeout=60) as resp:
            resp.read()
            status = resp.status
    except urllib.error.HTTPError as e:
        status = e.code
    except Exception:  # noqa: BLE001 — untyped answer: counted, then fatal
        status = 0
    with lock:
        out.append((status, time.perf_counter() - t0))


def run_overload_bench(burst_factor: float = 3.0, burst_s: float = 3.0,
                       service_s: float = 0.3,
                       failover_window_s: float = 8.0) -> dict:
    """Overload + failover rows (ISSUE 18): the robustness claims as
    guarded numbers.

    - ``proxy_overload_accepted_rps``: open-loop burst at ~burst_factor×
      replica capacity against a fixed-service-time app.  Admission
      control must answer EVERY request — 200 for the capacity's worth,
      typed 503/429 before dispatch for the excess — and accepted
      requests keep their latency profile (p99_accepted vs p99_unloaded).
    - ``proxy_failover_rps_recovered``: steady closed-loop load over two
      replicas, one SIGKILLed mid-window with ``serve.replica.call``
      armed (nth:40) in the replica workers, so the row is measured
      THROUGH an injected transport fault, not just a clean kill.  Pins
      post-recovery RPS plus the typed error window and respawn time.

    An unanswered or untyped (non-200/429/503) response raises — these
    rows exist so 'never hang, never an untyped 5xx' is a regression the
    guard can catch."""
    import os
    import signal

    import numpy as np

    import ray_tpu
    from ray_tpu import serve

    os.environ.setdefault("RT_FAULTS", "serve.replica.call=nth:40")
    ray_tpu.init(num_cpus=4, num_tpus=0)
    addr = serve.start(http_port=0, grpc_port=None)
    host, port = addr["http_host"], addr["http_port"]
    rows = []
    lock = threading.Lock()
    try:
        @serve.deployment(name="bench_overload", num_replicas=2,
                          max_ongoing_requests=4)
        class Work:
            def __call__(self, request):
                time.sleep(service_s)
                return "ok"

        serve.run(Work.bind())
        url = f"http://{host}:{port}/bench_overload"

        # unloaded profile: sequential requests, zero contention
        unloaded: list = []
        for _ in range(12):
            _typed_fire(url, unloaded, lock)
        bad = [s for s, _ in unloaded if s != 200]
        if bad:
            raise RuntimeError(f"unloaded warmup saw non-200s: {bad}")
        p99_unloaded = float(np.percentile([l for _, l in unloaded], 99))

        # open-loop burst at ~burst_factor × capacity: fire on the
        # schedule, never wait for responses — overload by construction
        capacity_rps = (2 * 4) / service_s  # replicas × slots / service
        offered_rps = burst_factor * capacity_rps
        n_total = int(offered_rps * burst_s)
        results: list = []
        threads = []
        t0 = time.perf_counter()
        for i in range(n_total):
            delay = (t0 + i / offered_rps) - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            t = threading.Thread(target=_typed_fire,
                                 args=(url, results, lock))
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=120)
        wall = time.perf_counter() - t0
        if len(results) != n_total:
            raise RuntimeError(
                f"overload burst: {n_total - len(results)} of {n_total} "
                "requests never answered — the proxy hung under overload")
        untyped = [s for s, _ in results if s not in (200, 429, 503)]
        if untyped:
            raise RuntimeError(
                f"overload burst: untyped responses {untyped[:5]} — "
                "every shed must be a typed 429/503")
        accepted = [l for s, l in results if s == 200]
        if not accepted:
            raise RuntimeError("overload burst: nothing accepted")
        rows.append({
            "metric": "proxy_overload_accepted_rps",
            "value": round(len(accepted) / wall, 1),
            "unit": "requests/s",
            "offered_rps": round(offered_rps, 1),
            "burst_s": round(wall, 2),
            "requests": n_total,
            "shed_pct": round(
                100.0 * (n_total - len(accepted)) / n_total, 1),
            "p99_accepted_ms": round(
                float(np.percentile(accepted, 99)) * 1000, 1),
            "p99_unloaded_ms": round(p99_unloaded * 1000, 1),
            "service_time_ms": service_s * 1000,
        })
        serve.delete("bench_overload")

        # failover: SIGKILL one of two replicas under steady load
        @serve.deployment(name="bench_failover", num_replicas=2,
                          max_ongoing_requests=8)
        class Fast:
            def __call__(self, request):
                return "ok"

        serve.run(Fast.bind())
        furl = f"http://{host}:{port}/bench_failover"
        warm: list = []
        for _ in range(10):
            _typed_fire(furl, warm, lock)
        samples: list = []  # (t_rel, status)
        stop = threading.Event()
        slock = threading.Lock()
        bench_t0 = time.perf_counter()

        def steady_client():
            while not stop.is_set():
                one: list = []
                olock = threading.Lock()
                t_sent = time.perf_counter() - bench_t0
                _typed_fire(furl, one, olock)
                with slock:
                    samples.append((t_sent, one[0][0]))

        clients = [threading.Thread(target=steady_client)
                   for _ in range(4)]
        for c in clients:
            c.start()
        time.sleep(failover_window_s * 0.3)
        ctrl = ray_tpu.get_actor("SERVE_CONTROLLER")
        _, replicas, _, _ = ray_tpu.get(
            [ctrl.get_replicas.remote("bench_failover")], timeout=10)[0]
        victim_pid = ray_tpu.get([replicas[0].pid.remote()], timeout=10)[0]
        if victim_pid in (os.getpid(), os.getppid()):
            raise RuntimeError("refusing to SIGKILL the driver")
        from ray_tpu.common.status import ActorDiedError

        t_kill = time.perf_counter() - bench_t0
        os.kill(victim_pid, signal.SIGKILL)
        recovery_s = None
        deadline = time.perf_counter() + 60
        try:
            while time.perf_counter() < deadline:
                # the controller's view holds the corpse until its next
                # probe cycle: pinging it raises — keep polling
                try:
                    _, reps, _, _ = ray_tpu.get(
                        [ctrl.get_replicas.remote("bench_failover")],
                        timeout=10)[0]
                    pids = (ray_tpu.get([r.pid.remote() for r in reps],
                                        timeout=5)
                            if len(reps) == 2 else [])
                except (ActorDiedError, ConnectionError, TimeoutError):
                    pids = []
                if pids and victim_pid not in pids:
                    recovery_s = time.perf_counter() - bench_t0 - t_kill
                    break
                time.sleep(0.1)
            remaining = failover_window_s - (time.perf_counter() - bench_t0)
            if remaining > 0:
                time.sleep(remaining)
        finally:
            stop.set()  # clients must stop even when the poll raises
        for c in clients:
            c.join(timeout=120)
        if recovery_s is None:
            raise RuntimeError("failover: replica never respawned")
        with slock:
            data = list(samples)
        untyped = [(t, s) for t, s in data if s not in (200, 429, 503)]
        if untyped:
            raise RuntimeError(f"failover: untyped responses "
                               f"{untyped[:5]} — replica death must "
                               "surface as retry-to-200 or typed shed")
        errs = [t for t, s in data if s != 200]
        pre = [t for t, s in data if s == 200 and t < t_kill]
        post_start = t_kill + recovery_s
        post = [t for t, s in data if s == 200 and t >= post_start]
        post_span = (time.perf_counter() - bench_t0) - post_start
        rows.append({
            "metric": "proxy_failover_rps_recovered",
            "value": round(len(post) / post_span, 1)
            if post_span > 0 else 0.0,
            "unit": "requests/s",
            "pre_kill_rps": round(len(pre) / t_kill, 1),
            "error_window_s": round(max(errs) - min(errs), 3)
            if errs else 0.0,
            "recovery_s": round(recovery_s, 2),
            "typed_errors": len(errs),
            "untyped_errors": 0,
            "clients": 4,
            "rt_faults": os.environ.get("RT_FAULTS"),
        })
        serve.delete("bench_failover")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    return {"results": rows}


def run_prefix_bench(model: str = "tiny", num_slots: int = 4,
                     n_requests: int = 20, shared_frac: float = 0.8,
                     prefix_len: int = 448, tail_len: int = 16,
                     max_tokens: int = 8, kv_block_size: int = 64,
                     max_seq: int = 1024) -> dict:
    """Shared-prefix traffic (ISSUE 19 acceptance shape): 80% of the
    requests agree on a ``prefix_len``-token system prompt and diverge
    only in a ``tail_len``-token tail; the other 20% are unrelated.
    The same sequential closed loop runs twice — ``prefix_cache="off"``
    (every request pays the full monolithic prefill) vs
    ``prefix_cache="radix"`` (a hit adopts the cached blocks and
    prefills ONLY the suffix) — and the rows report the TTFT ratio and
    decode throughput. Sequential on purpose: one request in flight
    isolates the prefill term of TTFT, which is the thing radix reuse
    changes; under concurrency TTFT is queueing-dominated and the same
    compute saving hides in scheduling noise. Greedy parity is asserted
    in-bench: the radix engine must emit byte-identical token streams,
    or the bench raises instead of reporting a number."""
    import numpy as np

    import jax

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMEngine

    rng = np.random.default_rng(7)
    vocab = llama.CONFIGS[model].vocab_size
    prefix = [int(t) for t in rng.integers(1, vocab, size=prefix_len)]
    n_shared = int(n_requests * shared_frac)
    prompts = []
    for i in range(n_requests):
        tail = [int(t) for t in rng.integers(1, vocab, size=tail_len)]
        if i < n_shared:
            prompts.append(prefix + tail)
        else:
            prompts.append([int(t) for t in rng.integers(
                1, vocab, size=prefix_len)] + tail)
    order = [int(i) for i in rng.permutation(n_requests)]
    # fixed warmup tails (drawn outside the per-engine loop so both
    # engines see identical token streams): wt1 compiles the monolithic
    # prefill + decode programs, wt2 hits the radix tree wt1 populated
    # and compiles the suffix-chunk kernel — all compile cost off the
    # clock, and the timed radix hits measure steady state
    wt1 = [max(1, vocab - 2)] * tail_len
    wt2 = [max(1, vocab - 3)] * tail_len

    out = {}
    for label, kw in (("cold", {"prefix_cache": "off"}),
                      ("radix", {"prefix_cache": "radix"})):
        eng = LLMEngine(model=model, num_slots=num_slots, max_seq=max_seq,
                        kv_block_size=kv_block_size, seed=0, **kw)
        for wt in (wt1, wt2):
            eng.generate(prefix + wt, max_tokens=2)
        ttfts: list = [None] * n_requests
        outs: list = [None] * n_requests

        t0 = time.perf_counter()
        for i in order:
            tr = time.perf_counter()
            rid = eng.submit(prompts[i], max_tokens=max_tokens)
            first, chunks = None, []
            while True:
                st = eng.poll(rid)
                chunks.extend(st["chunks"])
                if first is None and chunks:
                    first = time.perf_counter() - tr
                if st["done"]:
                    break
                time.sleep(0.0005)
            ttfts[i] = (first if first is not None
                        else time.perf_counter() - tr)
            outs[i] = chunks
        wall = time.perf_counter() - t0
        stats = eng.stats()
        eng.shutdown()
        pc = stats.get("prefix_cache", {})
        out[label] = {
            "ttft_p50_ms": round(float(np.percentile(ttfts, 50)) * 1000,
                                 1),
            "ttft_p95_ms": round(float(np.percentile(ttfts, 95)) * 1000,
                                 1),
            "tokens_per_s": round(sum(len(o) for o in outs) / wall, 1),
            "wall_s": round(wall, 2),
            "outputs": outs,
            "prefix_hits": stats.get("prefix_hits", 0),
            "hit_tokens": pc.get("hit_tokens", 0),
            "cow_hits": pc.get("cow_hits", 0),
        }

    bad = [i for i in range(n_requests)
           if out["radix"]["outputs"][i] != out["cold"]["outputs"][i]]
    if bad:
        raise RuntimeError(
            f"greedy parity violated on requests {bad[:5]}: radix reuse "
            "must be bit-identical to cold prefill")
    cold, radix = out["cold"], out["radix"]
    speedup = (round(cold["ttft_p50_ms"] / radix["ttft_p50_ms"], 2)
               if radix["ttft_p50_ms"] > 0 else float("inf"))
    if speedup < 2.0:
        raise RuntimeError(
            f"prefix-cache TTFT speedup {speedup}x < 2x acceptance "
            f"(cold p50 {cold['ttft_p50_ms']}ms, radix p50 "
            f"{radix['ttft_p50_ms']}ms)")
    common = {
        "model": model, "num_slots": num_slots, "n_requests": n_requests,
        "shared_frac": shared_frac, "prefix_len": prefix_len,
        "tail_len": tail_len, "max_tokens": max_tokens,
        "greedy_parity": True,
        "device": jax.devices()[0].platform,
    }
    rows = [
        dict(common,
             metric="llm_prefix_ttft_speedup", value=speedup, unit="x",
             ttft_p50_cold_ms=cold["ttft_p50_ms"],
             ttft_p50_radix_ms=radix["ttft_p50_ms"],
             ttft_p95_cold_ms=cold["ttft_p95_ms"],
             ttft_p95_radix_ms=radix["ttft_p95_ms"],
             prefix_hits=radix["prefix_hits"],
             hit_tokens=radix["hit_tokens"],
             cow_hits=radix["cow_hits"]),
        dict(common,
             metric="llm_prefix_decode_tokens_per_s",
             value=radix["tokens_per_s"], unit="tokens/s",
             cold_tokens_per_s=cold["tokens_per_s"],
             wall_radix_s=radix["wall_s"], wall_cold_s=cold["wall_s"]),
    ]
    return {"results": rows}


PROXY_CAPTION = (
    "proxy rows are CPU orchestration cost by design (PERF_PLAN round-11): "
    "they measure the proxy→handle→replica→response path end to end — "
    "RPS/latency of the HTTP data plane, not model math. "
    "handle_calls_per_second is the same replica set without HTTP, "
    "separating routing cost from parse/render cost. before_round11 = "
    "same-box numbers at the pre-async-data-plane commit (threadpool "
    "dispatch, blocking gets, poll-based SSE); the round-11 values ride "
    "the async-native path (get_async + micro-batched dispatch + "
    "push-based SSE). sse_tokens_per_second is engine-rate-bound on this "
    "1-core CPU box — the round-11 win there is protocol shape (push, "
    "no poll RPCs), not throughput. "
    "proxy_overload_accepted_rps (round-18, --overload) drives an "
    "open-loop burst at ~3x replica capacity: value is the RPS of "
    "ACCEPTED (200) requests, shed_pct the fraction answered with a "
    "typed 503/429 BEFORE dispatch, p99_accepted_ms vs p99_unloaded_ms "
    "the latency-protection claim. proxy_failover_rps_recovered "
    "SIGKILLs one of two replicas under steady load with "
    "serve.replica.call armed (nth:40) in the replica workers: value is "
    "post-recovery RPS; error_window_s / recovery_s bound the typed "
    "error window and respawn. both chaos rows raise on any unanswered "
    "or untyped (non-200/429/503) response. "
    "llm_prefix_ttft_speedup / llm_prefix_decode_tokens_per_s "
    "(round-19, --prefix) drive 80%-shared-prefix traffic at the engine "
    "twice — prefix_cache=off vs radix block reuse — on the same "
    "sequential closed loop (one request in flight isolates the prefill "
    "term of TTFT, the thing radix reuse changes): value is cold/radix "
    "TTFT p50 (acceptance >= 2x, asserted in-bench) and radix tokens/s; "
    "greedy parity (radix streams bit-identical to cold) is asserted "
    "before any row is written.")


def _merge_proxy_section(proxy: dict) -> None:
    """Write the proxy rows into BENCH_serve.json, preserving the other
    sections and any per-row history fields (before_round11) the fresh
    rows don't carry.  The row-merge rule is bench_guard's — imported,
    not re-implemented, so --capture and --proxy can never diverge."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "rt_bench_guard", os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "scripts", "bench_guard.py"))
    bench_guard = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_guard)

    doc = {}
    if os.path.exists("BENCH_serve.json"):
        with open("BENCH_serve.json") as f:
            doc = json.load(f)
    old_proxy = doc.get("proxy", {})
    old_rows = {r.get("metric"): r for r in old_proxy.get("results", [])}
    proxy = dict(proxy)
    fresh_rows = proxy.get("results", [])
    fresh_metrics = {r.get("metric") for r in fresh_rows}
    merged = bench_guard._merge_rows(fresh_rows, old_rows)
    # --proxy and --overload write DISJOINT row sets into one section:
    # rows this invocation never measures must survive the merge
    merged += [row for m, row in old_rows.items() if m not in fresh_metrics]
    proxy["results"] = merged
    for k, v in old_proxy.items():  # section keys this run lacks
        proxy.setdefault(k, v)
    proxy["caption"] = PROXY_CAPTION
    doc["proxy"] = proxy
    with open("BENCH_serve.json", "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")


def main():
    if "--proxy" in sys.argv:
        # proxy/data-plane rows only: CPU orchestration cost, valid on any
        # box (the captioned contract above)
        proxy = run_proxy_bench()
        _merge_proxy_section(proxy)
        print(json.dumps(proxy["results"], indent=1))
        return 0

    if "--prefix" in sys.argv:
        # shared-prefix radix-reuse rows: engine-level (no HTTP), greedy
        # parity + the >=2x TTFT acceptance asserted inside; merged into
        # the proxy section so bench_guard's --fresh-serve diff sees them
        import os

        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        section = run_prefix_bench()
        _merge_proxy_section(section)
        print(json.dumps(section["results"], indent=1))
        return 0

    if "--overload" in sys.argv:
        # overload shed + SIGKILL failover chaos rows: answered-typed is
        # asserted inside; merged into the proxy section next to the
        # plain RPS rows
        section = run_overload_bench()
        _merge_proxy_section(section)
        print(json.dumps(section["results"], indent=1))
        return 0

    # the engine rows are device numbers: no chip, no run (bench.py's
    # contract — a CPU timing is never written under a device's name)
    from bench import require_tpu

    dev = require_tpu()
    model, slots, n_req, plen, mtok = "1b", 8, 24, 128, 128

    result = run_engine_bench(model, slots, n_req, plen, mtok)
    result["chunked_prefill_interference"] = run_chunked_prefill_bench(
        model, long_len=max(48, plen), chunk=max(8, plen // 4))
    result["speculation"] = run_speculation_bench(
        model, prompt_len=min(24, plen), max_tokens=mtok)
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind}
    print(json.dumps(result))
    headline = {
        "metric": f"llm_serve_{result['model']}_decode_tokens_per_s",
        "value": result["decode_tokens_per_s"],
        "unit": "tokens/s",
        "vs_baseline": None,  # no reference serve-throughput number in-tree
        "ttft_p50_ms": result["ttft_p50_ms"],
        "slot_occupancy_mean": result["slot_occupancy_mean"],
    }
    print(json.dumps(headline))
    import os as _os

    if _os.path.exists("BENCH_serve.json"):
        # keep the proxy/data-plane section (written by --proxy runs):
        # the engine rows and the proxy rows are separate measurements
        with open("BENCH_serve.json") as f:
            prev = json.load(f)
        if "proxy" in prev:
            result["proxy"] = prev["proxy"]
    with open("BENCH_serve.json", "w") as f:
        json.dump(result, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
