"""Client server — head-side half of the ``ray://`` protocol.

Reference: ``python/ray/util/client/server/proxier.py`` (one server-side
driver PROCESS per client session, so sessions get their own job, clean
teardown, and no shared interpreter state). Here:

- :class:`ClientServer` listens on the advertised client port; a
  ``new_session`` RPC forks a session driver subprocess
  (``session_main.py``) which runs ``ray_tpu.init(address=gcs)`` as a real
  driver and serves the session API on its own port.
- The client then talks to its session driver directly (same host as the
  head — the only address a NAT'd client can reach is the head anyway, and
  per-session ports keep the proxy out of the data path).
- Sessions die with their connection: the driver subprocess exits when the
  client stops pinging (heartbeat timeout), releasing its job and refs.
"""

from __future__ import annotations

import logging
import os
import subprocess
import sys
import time
from typing import Dict, Tuple

from ray_tpu.rpc.rpc import IoContext, RpcServer

logger = logging.getLogger(__name__)

DEFAULT_CLIENT_PORT = 10001


class ClientServer:
    def __init__(self, gcs_address: Tuple[str, int],
                 host: str = "127.0.0.1", port: int = 0):
        self._gcs_address = tuple(gcs_address)
        self._host = host  # session drivers bind here too: the client must
        # be able to reach their per-session ports directly
        self.server = RpcServer(host, port)
        self.server.register("new_session", self.h_new_session)
        self.server.register("end_session", self.h_end_session)
        self.server.register("ping", self.h_ping)
        self._sessions: Dict[str, subprocess.Popen] = {}
        self._io = IoContext.current()

    def start(self):
        self.server.start()
        logger.info("client server at %s", self.server.address)

    @property
    def address(self) -> Tuple[str, int]:
        return self.server.address

    async def h_ping(self):
        return True

    def _reap(self):
        """Collect exited session drivers (heartbeat-timeout exits would
        otherwise sit as zombies for the server's lifetime)."""
        for sid in list(self._sessions):
            if self._sessions[sid].poll() is not None:
                del self._sessions[sid]

    async def h_new_session(self, session_id: str,
                            runtime_env: dict = None):
        import asyncio

        self._reap()
        env = dict(os.environ)
        env["RT_ADDRESS"] = f"{self._gcs_address[0]}:{self._gcs_address[1]}"
        env["RT_CLIENT_SESSION_ID"] = session_id
        env["RT_CLIENT_SESSION_HOST"] = self._host
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        if pkg_root not in env.get("PYTHONPATH", "").split(os.pathsep):
            env["PYTHONPATH"] = (pkg_root + os.pathsep + env["PYTHONPATH"]
                                 if env.get("PYTHONPATH") else pkg_root)
        if runtime_env:
            import json

            env["RT_JOB_RUNTIME_ENV"] = json.dumps(runtime_env)
        from ray_tpu.common.tpu_detect import leaseless_env

        env = leaseless_env(env)  # a session driver holds no TPU lease
        proc = await asyncio.to_thread(
            subprocess.Popen,
            [sys.executable, "-m", "ray_tpu.client.session_main"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        )
        self._sessions[session_id] = proc
        # the session driver prints its serving address on the first line
        line = await asyncio.to_thread(proc.stdout.readline)
        try:
            tag, host, port = line.decode().split()
            assert tag == "SESSION_READY"
        except Exception:  # noqa: BLE001
            proc.kill()
            return {"ok": False, "error": f"session driver failed: {line!r}"}
        return {"ok": True, "address": (host, int(port))}

    async def h_end_session(self, session_id: str):
        import asyncio

        proc = self._sessions.pop(session_id, None)
        if proc is not None:
            if proc.poll() is None:
                proc.terminate()
            await asyncio.to_thread(self._wait_reap, proc)
        self._reap()
        return True

    @staticmethod
    def _wait_reap(proc, timeout: float = 10.0):
        try:
            proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=5.0)

    def stop(self):
        for proc in self._sessions.values():
            if proc.poll() is None:
                proc.terminate()
        for proc in self._sessions.values():
            self._wait_reap(proc)
        self._sessions.clear()
        self.server.stop()
