"""Tests for the RPC layer, pubsub, and chaos injection."""

import threading
import time

import pytest

from ray_tpu.common.config import GLOBAL_CONFIG
from ray_tpu.rpc import chaos
from ray_tpu.rpc.pubsub import Publisher, Subscriber
from ray_tpu.rpc.rpc import (
    RemoteMethodError,
    RetryableRpcClient,
    RpcClient,
    RpcError,
    RpcServer,
)


@pytest.fixture
def server():
    s = RpcServer()

    async def echo(x):
        return x

    async def boom():
        raise ValueError("kapow")

    async def add(a, b):
        return a + b

    s.register("echo", echo)
    s.register("boom", boom)
    s.register("add", add)
    s.start()
    yield s
    s.stop()


class TestRpc:
    def test_roundtrip(self, server):
        c = RpcClient(server.address)
        assert c.call("echo", x={"k": [1, 2, 3]}) == {"k": [1, 2, 3]}
        assert c.call("add", a=2, b=3) == 5
        c.close()

    def test_remote_exception_propagates(self, server):
        c = RpcClient(server.address)
        with pytest.raises(RemoteMethodError) as ei:
            c.call("boom")
        assert isinstance(ei.value.cause, ValueError)
        c.close()

    def test_unknown_method(self, server):
        c = RpcClient(server.address)
        with pytest.raises(RpcError):
            c.call("nope")
        c.close()

    def test_concurrent_calls_multiplexed(self, server):
        c = RpcClient(server.address)
        results = []
        errs = []

        def worker(i):
            try:
                results.append(c.call("add", a=i, b=i))
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(i,)) for i in range(20)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert sorted(results) == [2 * i for i in range(20)]
        c.close()

    def test_connect_refused(self):
        c = RpcClient(("127.0.0.1", 1))  # nothing listens on port 1
        with pytest.raises(RpcError):
            c.call("echo", x=1)

    def test_stop_does_not_wait_for_a_peer_that_never_reads(self):
        """Replies pile up in the server's transport for a peer that is
        alive and does not read (a worker mid-teardown). A graceful close
        flushes them first, so stop() used to wait for ever; now it drops
        what is left after a moment."""
        import pickle
        import socket

        from ray_tpu.rpc import rpc

        s = RpcServer()

        async def blob(n):
            return b"x" * n

        s.register("blob", blob)
        s.start()
        sock = socket.create_connection(s.address)
        try:
            for i in range(8):
                body = pickle.dumps(
                    {"id": i, "method": "blob", "kwargs": {"n": 4 << 20}})
                sock.sendall(rpc._HEADER.pack(len(body), rpc._FRAME_REQ)
                             + body)
            time.sleep(0.5)              # 32 MB of replies, nobody reads
            stopper = threading.Thread(target=s.stop, daemon=True)
            stopper.start()
            stopper.join(rpc._STOP_FLUSH_S + 10)
            assert not stopper.is_alive()
        finally:
            sock.close()

    def test_retryable_client_survives_server_restart(self):
        s = RpcServer()

        async def echo(x):
            return x

        s.register("echo", echo)
        s.start()
        addr = s.address
        c = RetryableRpcClient(addr)
        assert c.call("echo", x=1) == 1
        s.stop()
        # restart on the same port while a call retries in the background
        result = {}

        def late_call():
            result["v"] = c.call("echo", x=42)

        t = threading.Thread(target=late_call)
        t.start()
        time.sleep(0.3)
        s2 = RpcServer(port=addr[1])
        s2.register("echo", echo)
        s2.start()
        t.join(timeout=10)
        assert result.get("v") == 42
        s2.stop()


class TestChaos:
    def test_injected_failures(self, server):
        GLOBAL_CONFIG.initialize({"testing_rpc_failure": "echo=1.0", "testing_rpc_failure_seed": 42})
        GLOBAL_CONFIG.reset_cache()
        chaos.reset()
        try:
            c = RpcClient(server.address)
            with pytest.raises(chaos.RpcChaosError):
                c.call("echo", x=1)
            # other methods unaffected
            assert c.call("add", a=1, b=1) == 2
            c.close()
        finally:
            GLOBAL_CONFIG.initialize({})
            GLOBAL_CONFIG.reset_cache()
            chaos.reset()

    def test_retryable_client_rides_through_chaos(self, server):
        GLOBAL_CONFIG.initialize({"testing_rpc_failure": "add=0.5", "testing_rpc_failure_seed": 7})
        GLOBAL_CONFIG.reset_cache()
        chaos.reset()
        try:
            c = RetryableRpcClient(server.address, max_attempts=50)
            for i in range(10):
                assert c.call("add", a=i, b=1) == i + 1
            c.close()
        finally:
            GLOBAL_CONFIG.initialize({})
            GLOBAL_CONFIG.reset_cache()
            chaos.reset()


class TestPubsub:
    def test_publish_and_longpoll(self):
        s = RpcServer()
        pub = Publisher()
        pub.attach(s)
        s.start()
        got = []
        sub = Subscriber("sub1", s.address)
        sub.subscribe("actors", lambda key, msg: got.append((key, msg)))
        time.sleep(0.2)
        pub.publish("actors", "a1", {"state": "ALIVE"})
        pub.publish("other", "x", "ignored")
        deadline = time.time() + 5
        while not got and time.time() < deadline:
            time.sleep(0.05)
        assert got == [("a1", {"state": "ALIVE"})]
        sub.close()
        s.stop()

    def test_key_filter(self):
        s = RpcServer()
        pub = Publisher()
        pub.attach(s)
        s.start()
        got = []
        sub = Subscriber("sub2", s.address)
        sub.subscribe("objects", lambda key, msg: got.append(key), key="obj-A")
        time.sleep(0.2)
        pub.publish("objects", "obj-B", 1)
        pub.publish("objects", "obj-A", 2)
        deadline = time.time() + 5
        while not got and time.time() < deadline:
            time.sleep(0.05)
        assert got == ["obj-A"]
        sub.close()
        s.stop()


class TestFastspec:
    """Native submit-record codec (rpc/native/fastspec.c)."""

    FIELDS = (b"T" * 16, b"J" * 4, b"A" * 12, b"W" * 16, b"10.0.0.7",
              b"step", b"\x80\x05payload", 2**40 + 7, 300, 50051)

    def test_roundtrip_and_wide_num_returns(self):
        from ray_tpu.rpc.native import load_fastspec

        fs = load_fastspec()
        assert fs is not None, "C toolchain present in this image"
        buf = fs.pack(*self.FIELDS)
        assert buf[:4] == b"RTFS"
        out = fs.unpack(buf)
        assert out == self.FIELDS  # num_returns=300 must not truncate mod 256

    def test_python_fallback_agrees(self, monkeypatch):
        import ray_tpu.rpc.native as native

        buf = native.load_fastspec().pack(*self.FIELDS)
        monkeypatch.setattr(native, "load_fastspec", lambda: None)
        assert native.unpack_fastspec(buf) == self.FIELDS

    def test_from_fast_rebuilds_actor_task(self):
        import pickle

        from ray_tpu.common.task_spec import TaskSpec, TaskType, _FastArgs
        from ray_tpu.rpc.native import load_fastspec

        payload = pickle.dumps(_FastArgs((1, 2), {"k": 3}))
        buf = load_fastspec().pack(b"T" * 24, b"J" * 4, b"A" * 16, b"W" * 16,
                                   b"10.0.0.7", b"step", payload, 9, 2, 50051)
        spec = TaskSpec.from_fast(buf)
        assert spec.task_type == TaskType.ACTOR_TASK
        assert spec.actor_method_name == "step"
        assert spec.sequence_number == 9
        assert spec.num_returns == 2
        assert spec.caller_address == ("10.0.0.7", 50051)
        assert pickle.loads(spec.args[0].value).args == (1, 2)
