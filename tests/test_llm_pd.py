"""Prefill/decode disaggregation + engine prefix cache (CPU mesh).

Reference parity: python/ray/llm/_internal/serve/deployments/
prefill_decode_disagg/ (PD split) and the prefix-cache-backed routing
stack. Correctness bar: disaggregated greedy decode must equal the
single-engine greedy oracle token-for-token.
"""

import numpy as np

import jax
import jax.numpy as jnp

from ray_tpu.models import llama

CFG = llama.CONFIGS["debug"]


def _greedy_reference(params, prompt, n_tokens):
    toks = list(prompt)
    for _ in range(n_tokens):
        logits = llama.forward(params, jnp.asarray([toks]), CFG)
        toks.append(int(np.asarray(logits)[0, -1].argmax()))
    return toks[len(prompt):]


class TestInject:
    def test_inject_matches_prefill(self):
        """KV written by inject must reproduce prefill's decode stream."""
        from ray_tpu.models.decoding import (
            init_cache, make_decode_step, make_inject, make_prefill)

        params = llama.init_params(CFG, jax.random.key(0))
        prompt = [5, 17, 99, 3]
        prefill = make_prefill(params, CFG)
        decode = make_decode_step(params, CFG)
        inject = make_inject(CFG)

        # source cache: normal prefill in slot 0
        src = init_cache(CFG, num_slots=1, max_seq=64)
        tokens = np.zeros((1, 32), np.int32)
        tokens[0, :len(prompt)] = prompt
        src, logits = prefill(src, jnp.asarray(tokens), len(prompt), 0)
        k = np.asarray(src["k"][:, 0, :len(prompt)])
        v = np.asarray(src["v"][:, 0, :len(prompt)])

        # destination cache: inject into slot 1 of a fresh 2-slot cache
        dst = init_cache(CFG, num_slots=2, max_seq=64)
        pad = ((0, 0), (0, 32 - len(prompt)), (0, 0), (0, 0))
        dst = inject(dst, jnp.asarray(np.pad(k, pad)),
                     jnp.asarray(np.pad(v, pad)), len(prompt), 1)
        assert int(dst["length"][1]) == len(prompt)

        want = _greedy_reference(params, prompt, 5)
        got = [int(np.asarray(logits).argmax())]
        last = np.array([0, got[0]], np.int32)
        active = np.array([False, True])
        for _ in range(4):
            dst, lg = decode(dst, jnp.asarray(last), jnp.asarray(active))
            tok = int(np.asarray(lg)[1].argmax())
            got.append(tok)
            last[1] = tok
        assert got == want


class TestPrefixCache:
    def test_repeat_prompt_hits_and_matches(self):
        """A prompt sent again adopts its own cached block (4 of its 5
        tokens at block size 4) and decodes the same greedy tokens."""
        from ray_tpu.serve.llm import LLMEngine

        eng = LLMEngine(model="debug", num_slots=2, max_seq=64,
                        prefix_cache="radix", kv_block_size=4)
        try:
            prompt = [5, 17, 99, 3, 42]
            first = eng.generate(prompt, max_tokens=6)
            second = eng.generate(prompt, max_tokens=6)
            assert first == second == _greedy_reference(
                llama.init_params(CFG, jax.random.key(0)), prompt, 6)
            s = eng.stats()
            assert s["prefix_hits"] >= 1
            assert s["prefix_cache"]["hit_tokens"] >= 4
        finally:
            eng.shutdown()


class TestPDEngineLevel:
    def test_disaggregated_matches_oracle(self):
        """PrefillServer KV handed to a separate engine's
        submit_prefilled must reproduce the greedy oracle."""
        from ray_tpu.serve.llm import LLMEngine
        from ray_tpu.serve.llm_pd import PrefillServer

        prompt = [7, 3, 88, 11]
        n_new = 6
        params = llama.init_params(CFG, jax.random.key(0))
        want = _greedy_reference(params, prompt, n_new)

        pf = PrefillServer(model="debug", max_seq=64)
        kv = pf(prompt)
        assert kv["k"].shape[1] == len(prompt)

        eng = LLMEngine(model="debug", num_slots=2, max_seq=64)
        try:
            rid = eng.submit_prefilled(prompt, kv["k"], kv["v"],
                                       kv["logits"], max_tokens=n_new)
            import time

            out, deadline = [], time.monotonic() + 60
            while True:
                r = eng.poll(rid)
                out.extend(r["chunks"])
                if r["done"]:
                    break
                assert time.monotonic() < deadline
                time.sleep(0.01)
            assert out == want
        finally:
            eng.shutdown()


class TestPDServe:
    def test_pd_app_end_to_end(self):
        """Full serve topology: orchestrator -> prefill fleet -> decode
        fleet, greedy output equals oracle."""
        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.serve.llm_pd import build_pd_app

        ray_tpu.init(num_cpus=6)
        try:
            # 2 decode replicas: exercises the sticky submit/poll routing
            handle = build_pd_app(model="debug", num_slots=2, max_seq=64,
                                  decode_replicas=2)
            params = llama.init_params(CFG, jax.random.key(0))
            for prompt in ([9, 2, 55], [4, 4, 8, 1]):
                want = _greedy_reference(params, prompt, 5)
                out = ray_tpu.get(handle.remote(prompt, max_tokens=5),
                                  timeout=120)
                assert out == want, prompt
        finally:
            serve.shutdown()
            ray_tpu.shutdown()


class TestPrefixAwareRouter:
    def test_affinity_and_fallback(self):
        """Same-prefix requests stick to one replica; saturation falls
        back to the less-loaded pick."""
        from ray_tpu.serve.handle import _RouterState

        st = _RouterState("d", controller=None)
        st.replicas = ["r0", "r1", "r2"]
        st.outstanding = {0: 0, 1: 0, 2: 0}
        st.max_ongoing = 4
        st.router = "prefix_aware"
        st.last_refresh = float("inf")  # never refresh (no controller)

        prompt = list(range(40))
        _, first = st.acquire_replica(prompt)
        for _ in range(3):
            _, idx = st.acquire_replica(prompt)
            assert idx == first  # sticks while capacity remains
        # owner saturated at max_ongoing=4 -> falls back elsewhere
        _, other = st.acquire_replica(prompt)
        assert other != first
        # distinct prompt is unconstrained
        st2 = _RouterState("d", controller=None)
        st2.replicas = ["r0", "r1"]
        st2.outstanding = {0: 0, 1: 0}
        st2.router = "prefix_aware"
        st2.last_refresh = float("inf")
        a = st2.acquire_replica("a" * 64)[1]
        assert st2.acquire_replica("a" * 64)[1] == a

    def test_shared_prefix_routes_together(self):
        from ray_tpu.serve.handle import _RouterState

        st = _RouterState("d", controller=None)
        st.replicas = ["r0", "r1", "r2", "r3"]
        st.outstanding = {i: 0 for i in range(4)}
        st.max_ongoing = 100
        st.router = "prefix_aware"
        st.last_refresh = float("inf")
        system = list(range(32))          # shared "system prompt"
        _, owner = st.acquire_replica(system + [900])
        for q in range(5):
            _, idx = st.acquire_replica(system + [1000 + q])
            assert idx == owner  # 32-token shared prefix wins affinity
