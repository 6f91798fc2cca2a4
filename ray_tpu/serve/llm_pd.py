"""Prefill/decode disaggregation for LLM serving.

Reference: ``python/ray/llm/_internal/serve/deployments/
prefill_decode_disagg/`` — prefill and decode run as separate Serve
deployments so the bursty, compute-bound prefill fleet scales
independently of the steady, memory-bound decode fleet; there the KV
moves between vLLM instances via NIXL/NCCL. TPU-native version: the
prefill replica computes the prompt KV with the jitted prefill program,
ships it as plain arrays over the serve transport (shm object plane
same-node, chunked RPC across nodes), and the decode replica injects it
into a slot with one fused ``dynamic_update_slice`` per cache array
(:func:`ray_tpu.models.decoding.make_inject`) — no re-prefill on the
decode side.

Deploy with :func:`build_pd_app`::

    handles = build_pd_app(model="tiny", prefill_replicas=1,
                           decode_replicas=1)
    out = ray_tpu.get(handles.remote([1, 2, 3], max_tokens=8))
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np


class PrefillServer:
    """Prefill-only replica: one-slot cache, returns the prompt KV.

    Scale this deployment with prompt traffic; it holds the same params
    as the decode fleet (same model + seed) but only ever runs the
    prefill program.
    """

    def __init__(self, model: str = "tiny", seed: int = 0,
                 max_seq: Optional[int] = None):
        import threading

        import jax

        from ray_tpu.models import llama
        from ray_tpu.models.decoding import init_cache, make_prefill

        self.config = llama.CONFIGS[model]
        self.params = llama.init_params(self.config, jax.random.key(seed))
        self.max_seq = max_seq or self.config.max_seq
        self._cache = init_cache(self.config, 1, self.max_seq)
        self._prefill = make_prefill(self.params, self.config)
        # replica actors run handle_request with max_concurrency > 1 and
        # prefill donates the cache buffer: calls must serialize
        self._lock = threading.Lock()

    def __call__(self, prompt: List[int]) -> Dict[str, Any]:
        with self._lock:
            return self._prefill_one(prompt)

    def _prefill_one(self, prompt: List[int]) -> Dict[str, Any]:
        import jax
        import jax.numpy as jnp

        from ray_tpu.models.decoding import pad_to_bucket

        plen = len(prompt)
        if plen == 0:
            raise ValueError("empty prompt")
        P = min(pad_to_bucket(plen), self.max_seq)
        tokens = np.zeros((1, P), np.int32)
        tokens[0, :plen] = prompt
        self._cache, logits = self._prefill(
            self._cache, jnp.asarray(tokens), plen, 0)
        k, v, lg = jax.device_get((self._cache["k"][:, 0, :plen],
                                   self._cache["v"][:, 0, :plen], logits))
        return {"k": np.asarray(k), "v": np.asarray(v),
                "logits": np.asarray(lg), "len": plen}


class DecodeServer:
    """Decode-only replica: full slot engine, admits prefilled KV."""

    def __init__(self, model: str = "tiny", num_slots: int = 8,
                 seed: int = 0, max_seq: Optional[int] = None):
        from ray_tpu.serve.llm import LLMEngine

        self.engine = LLMEngine(model=model, num_slots=num_slots, seed=seed,
                                max_seq=max_seq)

    def submit_prefilled(self, prompt: List[int], kv: Any,
                         max_tokens: int = 64, temperature: float = 0.0,
                         eos_token: Optional[int] = None) -> str:
        from ray_tpu.core_worker.reference import ObjectRef

        if isinstance(kv, ObjectRef):
            # KV shipped by reference: resolve from the object plane HERE
            # (the payload goes prefill replica -> object store -> this
            # process, skipping the orchestrator entirely)
            import ray_tpu

            kv = ray_tpu.get(kv, timeout=120.0)
        return self.engine.submit_prefilled(
            prompt, kv["k"], kv["v"], kv["logits"], max_tokens=max_tokens,
            temperature=temperature, eos_token=eos_token)

    def poll(self, request_id: str) -> Dict[str, Any]:
        return self.engine.poll(request_id)

    def stats(self) -> Dict[str, Any]:
        return self.engine.stats()

    def __del__(self):
        try:
            self.engine.shutdown()
        except Exception:  # noqa: BLE001
            pass


class PDOrchestrator:
    """Ingress deployment gluing the two fleets: route the prompt to a
    prefill replica, hand the KV to a decode replica, stream tokens.

    The KV crosses replica boundaries as a value through the object
    plane — the orchestrator never copies it into its own process twice
    (it passes the prefill reply straight through).
    """

    def __init__(self, prefill_handle, decode_handle,
                 poll_interval_s: float = 0.01):
        import ray_tpu

        self._rt = ray_tpu
        self.prefill = prefill_handle
        self.decode = decode_handle
        self._poll_interval = poll_interval_s

    def __call__(self, prompt: List[int], max_tokens: int = 64,
                 temperature: float = 0.0,
                 eos_token: Optional[int] = None,
                 timeout_s: float = 300.0) -> List[int]:
        import time

        # the KV ObjectRef passes through UNTOUCHED: the decode replica
        # resolves it from the object plane, so the payload never lands
        # in the orchestrator process
        kv_ref = self.prefill.remote(list(prompt))
        # Sticky routing: submit and every poll must hit the SAME decode
        # replica (the request id lives in that replica's engine state) —
        # same idiom as the proxy's SSE path (proxy.py _dispatch_stream).
        self.decode._state.refresh()
        acquired = self.decode._state.acquire_replica()
        if acquired is None:
            raise RuntimeError("no running decode replicas")
        replica, ridx = acquired
        try:
            rid = self._rt.get(
                replica.handle_request.remote(
                    "submit_prefilled", (list(prompt), kv_ref),
                    {"max_tokens": max_tokens, "temperature": temperature,
                     "eos_token": eos_token}),
                timeout=timeout_s)
            out: List[int] = []
            deadline = time.monotonic() + timeout_s
            while True:
                r = self._rt.get(
                    replica.handle_request.remote("poll", (rid,), {}),
                    timeout=timeout_s)
                out.extend(r["chunks"])
                if r["done"]:
                    return out
                if time.monotonic() > deadline:
                    raise TimeoutError("PD generation timed out")
                time.sleep(self._poll_interval)
        finally:
            self.decode._state.release(ridx)

    def stats(self) -> Dict[str, Any]:
        """Aggregate engine stats over every decode replica."""
        self.decode._state.refresh()
        replicas = list(self.decode._state.replicas)
        per = self._rt.get(
            [r.handle_request.remote("stats", (), {}) for r in replicas])
        out: Dict[str, Any] = {}
        for s in per:
            for key, val in s.items():
                out[key] = out.get(key, 0) + val
        return out


def build_pd_app(model: str = "tiny", *, prefill_replicas: int = 1,
                 decode_replicas: int = 1, num_slots: int = 8,
                 seed: int = 0, max_seq: Optional[int] = None,
                 name: str = "llm-pd"):
    """Deploy prefill fleet + decode fleet + orchestrator; returns the
    orchestrator's DeploymentHandle."""
    from ray_tpu import serve

    prefill_dep = serve.deployment(
        PrefillServer, name=f"{name}-prefill",
        num_replicas=prefill_replicas)
    decode_dep = serve.deployment(
        DecodeServer, name=f"{name}-decode", num_replicas=decode_replicas)
    serve.run(prefill_dep.bind(model=model, seed=seed, max_seq=max_seq))
    serve.run(decode_dep.bind(model=model, num_slots=num_slots, seed=seed,
                              max_seq=max_seq))
    pf = serve.get_deployment_handle(f"{name}-prefill")
    dc = serve.get_deployment_handle(f"{name}-decode")
    orch_dep = serve.deployment(PDOrchestrator, name=name)
    serve.run(orch_dep.bind(pf, dc))
    return serve.get_deployment_handle(name)
