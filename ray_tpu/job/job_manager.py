"""JobManager — submit, supervise, and stop driver entrypoints.

Reference: ``python/ray/dashboard/modules/job/job_manager.py:60``
(submit_job → JobSupervisor actor → entrypoint subprocess) and
``job_supervisor.py`` (polling the child, status transitions, log capture).
Here the supervisor is an asyncio task in the manager's process — the
entrypoint is still a REAL subprocess with the cluster address exported, so
the driver it runs is a full ray_tpu client; only the babysitting moved
in-process (this image has no need to survive a head restart mid-job, and
job state IS durable: it lives in the GCS KV, which is table-log-persisted).

Runtime envs apply to the DRIVER process here (env_vars, staged
working_dir as cwd, PYTHONPATH) — the driver's tasks then inherit it as
their job-level default via ``RT_JOB_RUNTIME_ENV``.
"""

from __future__ import annotations

import asyncio
import json
import logging
import os
import signal
import subprocess
import threading
import time
import uuid
from typing import AsyncIterator, Dict, List, Optional

from ray_tpu.gcs.client import GcsClient
from ray_tpu.rpc.rpc import IoContext

from .common import JOB_KV_NAMESPACE, JobInfo, JobStatus

logger = logging.getLogger(__name__)


class JobManager:
    def __init__(self, gcs_address, session_dir: str):
        self._gcs_address = tuple(gcs_address)
        self._gcs = GcsClient(self._gcs_address, client_id="job-manager")
        self._log_dir = os.path.join(session_dir, "job-logs")
        os.makedirs(self._log_dir, exist_ok=True)
        from ray_tpu.runtime_env.agent import RuntimeEnvAgent

        self._env_agent = RuntimeEnvAgent(session_dir)
        self._procs: Dict[str, subprocess.Popen] = {}
        # serializes status read-modify-write between stop_job (sync,
        # caller threads) and the supervisor (via to_thread) so a STOPPED
        # marker can never be clobbered by a racing RUNNING save
        self._status_locks: Dict[str, threading.Lock] = {}
        self._io = IoContext.current()

    def _status_lock(self, submission_id: str) -> threading.Lock:
        return self._status_locks.setdefault(submission_id,
                                             threading.Lock())

    # ----------------------------------------------------------------- state
    def _save(self, info: JobInfo):
        self._gcs.kv_put(JOB_KV_NAMESPACE, info.submission_id, info.to_json())

    async def _save_async(self, info: JobInfo):
        # supervisor coroutines run ON the shared IO loop: they must use the
        # async client (the sync one parks the loop on itself — deadlock)
        await self._gcs.call_async(
            "kv_put", namespace=JOB_KV_NAMESPACE, key=info.submission_id,
            value=info.to_json(), overwrite=True)

    async def _get_info_async(self, submission_id: str):
        raw = await self._gcs.call_async(
            "kv_get", namespace=JOB_KV_NAMESPACE, key=submission_id)
        return JobInfo.from_json(raw) if raw else None

    def get_job_info(self, submission_id: str) -> Optional[JobInfo]:
        raw = self._gcs.kv_get(JOB_KV_NAMESPACE, submission_id)
        return JobInfo.from_json(raw) if raw else None

    def list_jobs(self) -> List[JobInfo]:
        out = []
        for key in self._gcs.kv_keys(JOB_KV_NAMESPACE):
            raw = self._gcs.kv_get(JOB_KV_NAMESPACE, key)
            if raw:
                out.append(JobInfo.from_json(raw))
        return sorted(out, key=lambda j: j.start_time)

    def log_path(self, submission_id: str) -> str:
        return os.path.join(self._log_dir, f"{submission_id}.log")

    def get_job_logs(self, submission_id: str) -> str:
        path = self.log_path(submission_id)
        if not os.path.exists(path):
            return ""
        with open(path, "r", errors="replace") as f:
            return f.read()

    # ---------------------------------------------------------------- submit
    def submit_job(self, *, entrypoint: str,
                   submission_id: Optional[str] = None,
                   runtime_env: Optional[dict] = None,
                   metadata: Optional[Dict[str, str]] = None) -> str:
        submission_id = submission_id or f"raysubmit_{uuid.uuid4().hex[:16]}"
        if self.get_job_info(submission_id) is not None:
            raise ValueError(f"job {submission_id!r} already exists")
        info = JobInfo(submission_id=submission_id, entrypoint=entrypoint,
                       runtime_env=runtime_env, metadata=metadata or {})
        self._save(info)
        self._io.spawn_threadsafe(self._run_supervisor(info))
        return submission_id

    async def _run_supervisor(self, info: JobInfo):
        """One supervisor per job: materialize env, spawn, babysit."""
        try:
            ctx = await asyncio.to_thread(
                self._env_agent.get_or_create, info.runtime_env)
        except Exception as e:  # noqa: BLE001
            info.status = JobStatus.FAILED
            info.message = f"runtime env setup failed: {e}"
            info.end_time = time.time()
            await self._save_async(info)
            return
        self._env_agent.acquire(ctx.env_key)
        from ray_tpu.common.tpu_detect import leaseless_env

        # a job driver holds no TPU lease: its jax stays on the CPU, the
        # chips belong to the workers its tasks and actors are leased
        env = ctx.apply(leaseless_env(dict(os.environ)))
        pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        if pkg_root not in env.get("PYTHONPATH", "").split(os.pathsep):
            env["PYTHONPATH"] = (pkg_root + os.pathsep + env["PYTHONPATH"]
                                 if env.get("PYTHONPATH") else pkg_root)
        env["RT_ADDRESS"] = f"{self._gcs_address[0]}:{self._gcs_address[1]}"
        env["RT_JOB_SUBMISSION_ID"] = info.submission_id
        if info.runtime_env:
            env["RT_JOB_RUNTIME_ENV"] = json.dumps(info.runtime_env)
        def _spawn():
            # open+fork off-loop (rt-analyze loop-blocker): the log file
            # open and the fork both block; the child inherits the fd so
            # the parent copy closes immediately after spawn
            logfile = open(self.log_path(info.submission_id), "ab")
            try:
                return subprocess.Popen(
                    ["bash", "-c", info.entrypoint], env=env,
                    cwd=ctx.cwd or os.getcwd(),
                    stdout=logfile, stderr=subprocess.STDOUT,
                    start_new_session=True,  # stop_job kills the group
                )
            finally:
                logfile.close()

        try:
            proc = await asyncio.to_thread(_spawn)
        except Exception as e:  # noqa: BLE001
            info.status = JobStatus.FAILED
            info.message = f"failed to start entrypoint: {e}"
            info.end_time = time.time()
            await self._save_async(info)
            self._env_agent.release(ctx.env_key)
            return
        self._procs[info.submission_id] = proc

        def mark_running() -> bool:
            # atomic check-and-set under the status lock: stop_job may have
            # raced us while the env staged / process spawned (status
            # PENDING, nothing in _procs to kill) — honor the STOPPED
            # marker instead of clobbering it with RUNNING.
            with self._status_lock(info.submission_id):
                latest = self.get_job_info(info.submission_id)
                if latest is not None and \
                        latest.status == JobStatus.STOPPED:
                    return False
                info.status = JobStatus.RUNNING
                info.driver_pid = proc.pid
                self._save(info)
                return True

        if not await asyncio.to_thread(mark_running):
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                proc.kill()
            self._procs.pop(info.submission_id, None)
            self._env_agent.release(ctx.env_key)
            return
        logger.info("job %s running (pid %s): %s",
                    info.submission_id, proc.pid, info.entrypoint)
        while proc.poll() is None:
            await asyncio.sleep(0.2)
        self._procs.pop(info.submission_id, None)
        self._env_agent.release(ctx.env_key)

        def classify_exit():
            # read-classify-save under the same lock as stop_job: a
            # STOPPED marker must never be clobbered by SUCCEEDED/FAILED
            with self._status_lock(info.submission_id):
                latest = self.get_job_info(info.submission_id)
                if latest is not None and \
                        latest.status == JobStatus.STOPPED:
                    return
                info.driver_exit_code = proc.returncode
                info.end_time = time.time()
                if proc.returncode == 0:
                    info.status = JobStatus.SUCCEEDED
                else:
                    info.status = JobStatus.FAILED
                    info.message = \
                        f"driver exited with code {proc.returncode}"
                self._save(info)

        await asyncio.to_thread(classify_exit)

    # ------------------------------------------------------------------ stop
    def stop_job(self, submission_id: str) -> bool:
        with self._status_lock(submission_id):
            info = self.get_job_info(submission_id)
            if info is None or JobStatus.is_terminal(info.status):
                return False
            info.status = JobStatus.STOPPED
            info.message = "stopped via stop_job"
            info.end_time = time.time()
            self._save(info)
        proc = self._procs.get(submission_id)
        if proc is not None and proc.poll() is None:
            try:  # TERM the process group, escalate to KILL
                os.killpg(proc.pid, signal.SIGTERM)
            except (ProcessLookupError, PermissionError):
                proc.terminate()

            async def escalate(p=proc):
                for _ in range(15):
                    if p.poll() is not None:
                        return
                    await asyncio.sleep(0.2)
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    p.kill()

            self._io.spawn_threadsafe(escalate())
        return True

    def delete_job(self, submission_id: str) -> bool:
        info = self.get_job_info(submission_id)
        if info is None or not JobStatus.is_terminal(info.status):
            return False
        self._gcs.kv_del(JOB_KV_NAMESPACE, submission_id)
        try:
            os.remove(self.log_path(submission_id))
        except OSError:
            pass
        return True

    @staticmethod
    def _read_chunk(path: str, pos: int) -> bytes:
        """Blocking log read — runs via to_thread; one tailing dashboard
        client must not park the shared IO loop on disk every 300ms."""
        if not os.path.exists(path):
            return b""
        with open(path, "rb") as f:
            f.seek(pos)
            return f.read()

    async def tail_logs(self, submission_id: str) -> AsyncIterator[bytes]:
        """Yield log chunks until the job reaches a terminal state."""
        path = self.log_path(submission_id)
        pos = 0
        while True:
            chunk = await asyncio.to_thread(self._read_chunk, path, pos)
            if chunk:
                pos += len(chunk)
                yield chunk
            info = await self._get_info_async(submission_id)
            if info is None or JobStatus.is_terminal(info.status):
                # final drain
                chunk = await asyncio.to_thread(self._read_chunk, path,
                                                pos)
                if chunk:
                    yield chunk
                return
            await asyncio.sleep(0.3)

    def close(self):
        self._gcs.close()
